"""LFM2-MoE's family (HF `Lfm2MoeForCausalLM`, `model_type: lfm2_moe`): gated
short-convolution layers among grouped-query softmax-attention layers, leading
dense layers and then sigmoid-routed experts with no shared one.

The block is `models/base.py`'s with the config's switches set: RMSNorm,
SwiGLU, no biases, a head tied to the embedding (HF's `embedding_norm` is the
stack's final norm). **Which layers attend is a LIST** (`layer_types`, "conv"
or "full_attention" a layer; `TransformerConfig.layer_types` takes "conv" as it
is and "full_attention" as "attention"): the published 8B-A1B attends at layers
2, 6, 10, 14, 18 and 21 of 24, which no interval says. The **convolution**
layers (`models/parts/conv.conv_mixer`, the kinds "conv.dense" and
"conv.routed"): `[B | C | u]` from one projection, a causal depthwise
convolution of `conv_L_cache` taps on `B * u` with no bias and no activation,
`C *` its result, the output projection. The **attention** layers: GQA with no
bias, an RMSNorm over each head's dims of q and of k with ONE scale for all
heads (`q_layernorm`, `k_layernorm`), rope of `rope_theta` on all of a head's
dims. The first `num_dense_layers` layers' MLP half is a dense SwiGLU of
`intermediate_size`; every later one `num_experts` SwiGLU experts of
`moe_intermediate_size` with `num_experts_per_tok` a token by a sigmoid router
whose choice adds a bias no gradient moves (`use_expert_bias`), weights
renormalised over the pick (`norm_topk_prob`) x `routed_scaling_factor`,
dropless (ops/moe.py). HF renormalises by the pick's sum + 1e-6, the program by
+ 1e-20 (ops/moe.py's, DeepSeek's): the sum of four sigmoids is about 2, so the
weights differ by 5e-7 relative. The preset carries the PUBLISHED config with
its source (ROADMAP D12).

A program may hold a share of the experts (`experts_held`,
`experts_held_start`: the router still ranks all `num_experts`).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no expert form and none of the convolution layers, and refuse such a
config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, register

LFM2_8B_A1B_SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
_ATTENDS_8B_A1B = (2, 6, 10, 14, 18, 21)

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "lfm2-8b-a1b": {
        "source": LFM2_8B_A1B_SOURCE,
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
        "layer_types": ["full_attention" if i in _ATTENDS_8B_A1B else "conv" for i in range(24)],
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    },
}
# what the published file does not carry: HF's defaults (`Lfm2MoeConfig`: the
# head is tied, which the 8.3 B total only adds up with), and the bias update
# speed of DeepSeek-V3's pre-training (arXiv:2412.19437 4.2), whose
# auxiliary-loss-free balancing the router's bias is (HF holds it as a buffer)
INITIALIZER_RANGE = 0.02
TIE_WORD_EMBEDDINGS = True
ROUTER_BIAS_UPDATE_RATE = 0.001
_MIXER_OF = {"conv": "conv", "full_attention": "attention"}


def lfm2_moe_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `Lfm2MoeConfig` (or anything with its attributes). What the
    program does not model is refused, not dropped. `layer_types` is handed on
    whole: a model cut in depth (`num_layers` overridden) runs the pattern's
    first so many layers."""
    for key, modelled in (("conv_bias", False), ("rope_scaling", None)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published LFM2-8B-A1B has %r)"
                             % (key, getattr(hf_config, key), modelled))
    unknown = sorted(set(hf_config.layer_types) - set(_MIXER_OF))
    if unknown or len(hf_config.layer_types) != hf_config.num_hidden_layers:
        raise ValueError("layer_types names \"conv\" or \"full_attention\" for each of the %d layers; got %d "
                         "entries, unknown %r" % (hf_config.num_hidden_layers, len(hf_config.layer_types), unknown))
    scale = hf_config.routed_scaling_factor
    if isinstance(scale, bool) or not isinstance(scale, (int, float)):
        raise ValueError("routed_scaling_factor=%r is not modelled (a number x the chosen experts' weights)"
                         % (scale,))
    fields = dict(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        num_layers=hf_config.num_hidden_layers,
        vocab_size=hf_config.vocab_size,
        norm_type="rmsnorm", activation="swiglu", causal=True, pre_norm=True,
        qkv_bias=False, out_bias=False, mlp_bias=False,
        layernorm_eps=hf_config.norm_eps,
        init_std=getattr(hf_config, "initializer_range", INITIALIZER_RANGE),
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        dense_ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        position_type="rope",
        rope_theta=float(hf_config.rope_theta),
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", TIE_WORD_EMBEDDINGS),
        qk_norm="head",
        layer_types=[_MIXER_OF[t] for t in hf_config.layer_types],
        short_conv_kernel=hf_config.conv_L_cache,
        first_dense_layers=hf_config.num_dense_layers,
        num_experts=hf_config.num_experts,
        experts_per_token=hf_config.num_experts_per_tok,
        norm_topk_prob=hf_config.norm_topk_prob,
        router_score="sigmoid",
        routed_scaling_factor=float(scale),
        router_bias=hf_config.use_expert_bias,
        router_bias_update_rate=ROUTER_BIAS_UPDATE_RATE if hf_config.use_expert_bias else 0.0,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def lfm2_moe_config(model_size: str = "lfm2-8b-a1b", **overrides) -> TransformerConfig:
    return lfm2_moe_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="lfm2_moe", config_fn=lfm2_moe_config, meta_configs=META_CONFIGS,
                     default_size="lfm2-8b-a1b", config_from_hf=lfm2_moe_config_from_hf))
