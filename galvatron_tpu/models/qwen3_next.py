"""Qwen3-Next's family (HF `Qwen3NextForCausalLM`, `model_type: qwen3_next`):
gated-DeltaNet linear-attention layers among gated softmax-attention layers,
every MLP half a fine-grained mixture of experts.

The block is `models/base.py`'s with the config's switches set: RMSNorm scaled
by `(1 + w)` (`norm_zero_centered`), SwiGLU, no biases, an untied head. Of
every `full_attention_interval` layers the last attends and the others are
**linear** (`models/parts/linear.linear_mixer`, the kind "linear.routed"): q, k, v and
an output gate z from one projection, a causal depthwise convolution of
`linear_conv_kernel_dim` taps and SiLU on q, k and v, L2-normalised q and k,
the **gated delta rule** over `linear_num_value_heads` states of
`linear_key_head_dim` x `linear_value_head_dim` (ops/linear_attention.py: the
chunked form, its backward written for the carried state), a gated RMSNorm a
head, the output projection. The **attention** layers project q beside an
output gate (`attn_output_gate`), norm q and k a head (`qk_norm="head"`), turn
the leading `partial_rotary_factor` of a head's dims and multiply the
attention's output by sigmoid(gate). Every layer's MLP half: `num_experts`
SwiGLU experts of `moe_intermediate_size` with `num_experts_per_tok` a token by
a softmax router, weights renormalised over the pick, dropless (ops/moe.py),
beside one shared expert behind a sigmoid gate (`shared_expert_gate`); the
objective adds `router_aux_loss_coef` x the load-balancing loss. The preset
carries the PUBLISHED config with its source (ROADMAP D12).

A program may hold a share of the experts (`experts_held`,
`experts_held_start`: the router still ranks all `num_experts`). The
multi-token-prediction module of the release is in no key of the published
config and is not modelled.

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no expert form and no form of the linear layers, and refuse such a
config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

QWEN3_NEXT_SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "qwen3-next-80b-a3b": {
        "source": QWEN3_NEXT_SOURCE,
        "hidden_size": 2048, "intermediate_size": 5120, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "num_hidden_layers": 48,
        "num_attention_heads": 16, "num_key_value_heads": 2, "head_dim": 256,
        "full_attention_interval": 4, "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_value_head_dim": 128, "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "num_experts": 512, "num_experts_per_tok": 10, "norm_topk_prob": True,
        "decoder_sparse_step": 1, "mlp_only_layers": [], "partial_rotary_factor": 0.25,
        "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 10000000, "rope_scaling": None,
        "max_position_embeddings": 262144, "vocab_size": 151936, "use_sliding_window": False,
        "tie_word_embeddings": False,
    },
}
# HF's `Qwen3NextConfig` defaults, which the published file does not override
ROUTER_AUX_LOSS_COEF = 0.001
INITIALIZER_RANGE = 0.02


def qwen3_next_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `Qwen3NextConfig` (or anything with its attributes). What
    the program does not model is refused, not dropped."""
    for key, modelled in (("decoder_sparse_step", 1), ("mlp_only_layers", []),
                          ("rope_scaling", None), ("use_sliding_window", False),
                          ("hidden_act", "silu"), ("attention_bias", False)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Qwen3-Next has %r)"
                             % (key, getattr(hf_config, key), modelled))
    if hf_config.shared_expert_intermediate_size != hf_config.moe_intermediate_size:
        raise ValueError("a shared expert of width %d beside experts of %d is not modelled: the "
                         "shared expert is one more expert's width"
                         % (hf_config.shared_expert_intermediate_size,
                            hf_config.moe_intermediate_size))
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        head_dim=hf_config.head_dim,
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        max_seq_len=hf_config.max_position_embeddings,
        norm_zero_centered=True,
        position_type="rope",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        qkv_bias=False,
        out_bias=False,
        rope_theta=float(hf_config.rope_theta),
        partial_rotary_factor=hf_config.partial_rotary_factor,
        qk_norm="head",
        attn_output_gate=True,
        full_attention_interval=hf_config.full_attention_interval,
        linear_num_key_heads=hf_config.linear_num_key_heads,
        linear_num_value_heads=hf_config.linear_num_value_heads,
        linear_key_head_dim=hf_config.linear_key_head_dim,
        linear_value_head_dim=hf_config.linear_value_head_dim,
        linear_conv_kernel=hf_config.linear_conv_kernel_dim,
        num_experts=hf_config.num_experts,
        experts_per_token=hf_config.num_experts_per_tok,
        norm_topk_prob=hf_config.norm_topk_prob,
        router_score="softmax",
        router_aux_loss_coef=getattr(hf_config, "router_aux_loss_coef", ROUTER_AUX_LOSS_COEF),
        num_shared_experts=1,
        shared_expert_gate=True,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def qwen3_next_config(model_size: str = "qwen3-next-80b-a3b", **overrides) -> TransformerConfig:
    return qwen3_next_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="qwen3_next", config_fn=qwen3_next_config, meta_configs=META_CONFIGS,
                     default_size="qwen3-next-80b-a3b", config_from_hf=qwen3_next_config_from_hf))
