"""Kimi-Linear's family (HF `KimiLinearForCausalLM`, `model_type: kimi_linear`;
arXiv:2510.26692): Kimi-Delta-Attention layers among latent-attention layers
without positions, a leading dense layer and then sigmoid-routed experts
beside a shared one.

The block is `models/base.py`'s with the config's switches set: RMSNorm,
SwiGLU, no biases, an untied head, **no position anywhere**. **Which layers
attend is two LISTS of 1-indexed layer numbers** (`linear_attn_config`'s
`kda_layers` and `full_attn_layers`; the last period of the published 27 is
short, so no interval says it): here ONE list, `TransformerConfig.layer_types`,
"kda" or "attention" a layer. The **KDA** layers (`models/parts/kda.kda_mixer`, the
kinds "kda.dense" and "kda.routed"): q, k and v each through a causal depthwise
convolution of `short_conv_kernel_size` taps and SiLU, L2-normalised q and k,
the delta rule whose gate is a VECTOR over the key's channels
(`ops/linear_attention.kda_rule`) from a low-rank pair, beta from its own
projection, a head-wise RMSNorm times the SIGMOID of a second low-rank pair,
the output projection. The **attention** layers are latent attention (MLA)
with no low-rank q (`q_lora_rank: null`), q/k heads of `qk_nope_head_dim` +
`qk_rope_head_dim` beside v heads of `v_head_dim`, and no rotation
(`mla_use_nope`): the `qk_rope_head_dim` dims are one unrotated key vector
shared by the heads. The first `first_k_dense_replace` layers' MLP half is a
dense SwiGLU of `intermediate_size`; every later one `num_experts` SwiGLU
experts of `moe_intermediate_size` with `num_experts_per_token` a token by a
sigmoid router whose choice adds a bias no gradient moves, weights
renormalised over the pick (`moe_renormalize`) x `routed_scaling_factor`,
dropless (ops/moe.py), beside `num_shared_experts` ungated shared one(s). The
published top-level `head_dim` (hidden / heads) sizes no tensor and is not
read: the program's `head_dim` is the width of the ONE attention call, 256,
to which q, k (192) and v (128) are padded with zeros (the flash kernels take
heads of whole 128-lane tiles; exact, at the scale 1 / sqrt(192)). The preset carries the PUBLISHED config with its source (ROADMAP D12).

A program may hold a share of the experts (`experts_held`,
`experts_held_start`: the router still ranks all `num_experts`).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no expert form, no form of latent attention and none of the KDA layers,
and refuse such a config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

KIMI_LINEAR_SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "kimi-linear-48b-a3b": {
        "source": KIMI_LINEAR_SOURCE,
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32, "num_expert_group": 1,
        "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0, "num_shared_experts": 1,
        "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840,
    },
}
# what the published file does not carry: HF's default, and the bias update
# speed of DeepSeek-V3's pre-training (arXiv:2412.19437 4.2), whose
# auxiliary-loss-free balancing the router's bias is
INITIALIZER_RANGE = 0.02
ROUTER_BIAS_UPDATE_RATE = 0.001


def layer_types_from_lists(kda_layers, full_attn_layers):
    """The two published lists of 1-indexed layer numbers as ONE list, "kda"
    or "attention" a layer; a number in both or in neither is refused."""
    kda, full = set(kda_layers), set(full_attn_layers)
    depth = max(kda | full)
    if kda & full or (kda | full) != set(range(1, depth + 1)):
        raise ValueError(
            "kda_layers %r and full_attn_layers %r do not name each of the layers 1 to %d once"
            % (sorted(kda), sorted(full), depth))
    return ["kda" if i in kda else "attention" for i in range(1, depth + 1)]


def kimi_linear_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `KimiLinearConfig` (or anything with its attributes). What
    the program does not model is refused, not dropped. The layer pattern is
    handed on whole: a model cut in depth (`num_layers` overridden) runs the
    pattern's first so many layers."""
    for key, modelled in (("rope_scaling", None), ("num_expert_group", 1), ("topk_group", 1),
                          ("q_lora_rank", None), ("mla_use_nope", True),
                          ("num_nextn_predict_layers", 0), ("moe_layer_freq", 1),
                          ("moe_router_activation_func", "sigmoid"), ("hidden_act", "silu")):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Kimi-Linear has %r)"
                             % (key, getattr(hf_config, key), modelled))
    linear = hf_config.linear_attn_config
    # the ONE attention call's width: the flash kernels take heads of whole
    # 128-lane tiles, so q, k (192) and v (128) are padded with zeros to 256
    widest = max(hf_config.qk_nope_head_dim + hf_config.qk_rope_head_dim, hf_config.v_head_dim)
    layer_types = layer_types_from_lists(linear["kda_layers"], linear["full_attn_layers"])
    if len(layer_types) != hf_config.num_hidden_layers:
        raise ValueError("kda_layers and full_attn_layers name %d layers, num_hidden_layers is %d"
                         % (len(layer_types), hf_config.num_hidden_layers))
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        head_dim=-(-widest // 128) * 128,
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        dense_ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.model_max_length,
        position_type="none",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        qkv_bias=False,
        out_bias=False,
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        layer_types=layer_types,
        linear_num_key_heads=linear["num_heads"],
        linear_num_value_heads=linear["num_heads"],
        linear_key_head_dim=linear["head_dim"],
        linear_value_head_dim=linear["head_dim"],
        linear_conv_kernel=linear["short_conv_kernel_size"],
        first_dense_layers=hf_config.first_k_dense_replace,
        num_experts=hf_config.num_experts,
        experts_per_token=hf_config.num_experts_per_token,
        num_shared_experts=hf_config.num_shared_experts,
        norm_topk_prob=hf_config.moe_renormalize,
        router_score="sigmoid",
        routed_scaling_factor=hf_config.routed_scaling_factor,
        router_bias=True,
        router_bias_update_rate=ROUTER_BIAS_UPDATE_RATE,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def kimi_linear_config(model_size: str = "kimi-linear-48b-a3b", **overrides) -> TransformerConfig:
    return kimi_linear_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="kimi_linear", config_fn=kimi_linear_config, meta_configs=META_CONFIGS,
                     default_size="kimi-linear-48b-a3b", config_from_hf=kimi_linear_config_from_hf))
