"""Granite-4.0-H's family (HF `GraniteMoeHybridForCausalLM`, `model_type:
granitemoehybrid`): Mamba-2 state-space layers among softmax-attention layers
without positions, every MLP half a dense SwiGLU, four multipliers.

The block is `models/base.py`'s with the config's switches set: RMSNorm,
SwiGLU (`shared_intermediate_size`: with `num_local_experts` 0 the "shared"
MLP is the only one and no router exists), no biases, a head tied to the
embedding. **Which layers attend is a LIST** (`layer_types`, "mamba" or
"attention" a layer; `TransformerConfig.layer_types` takes it as it is and
reads "mamba" as the mixer "ssm"): the published Micro attends at layers 5,
15, 25, 35 of 40, which no interval says. The **state-space** layers (`models/parts/ssm.ssm_mixer`, the
kind "ssm.dense"): `[z | x B C | dt]` from one projection, a causal depthwise
convolution of `mamba_d_conv` taps WITH a bias and SiLU on `[x | B | C]`,
Mamba-2's scan over `mamba_n_heads` states of `mamba_d_head` x `mamba_d_state`
with B and C shared by all heads (`mamba_n_groups` 1; ops/ssd.py: the chunked
form, its backward from the kept chunk-start states), the gate `silu(z)`
BEFORE an RMSNorm over all `mamba_expand x hidden_size` channels, the output
projection. The **attention** layers: GQA, no bias, no positions
(`position_embedding_type: nope`), the softmax's scale `attention_multiplier`
in place of 1 / sqrt(head_dim). The embedding's rows are multiplied by
`embedding_multiplier`, each half's output by `residual_multiplier` before it
joins the residual stream, and the logits divided by `logits_scaling`. The
preset carries the PUBLISHED config with its source (ROADMAP D12).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no form of the state-space layers and refuse such a config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

GRANITE_4_H_MICRO_SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
_MICRO_LAYER_TYPES = ["attention" if i % 10 == 5 else "mamba" for i in range(40)]

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "granite-4.0-h-micro": {
        "source": GRANITE_4_H_MICRO_SOURCE,
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
        "layer_types": _MICRO_LAYER_TYPES, "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
        "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "normalization_function": "rmsnorm",
        "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352,
    },
}
INITIALIZER_RANGE = 0.02  # HF's `GraniteMoeHybridConfig` default, which the published file keeps


def granite_hybrid_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `GraniteMoeHybridConfig` (or anything with its attributes).
    What the program does not model is refused, not dropped. `layer_types` is
    handed on whole: a model cut in depth (`num_layers` overridden) runs the
    pattern's first so many layers."""
    for key, modelled in (("num_local_experts", 0), ("mamba_n_groups", 1), ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("normalization_function", "rmsnorm"),
                          ("position_embedding_type", "nope"), ("rope_scaling", None)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Granite-4.0-H-Micro has %r)"
                             % (key, getattr(hf_config, key), modelled))
    if hf_config.mamba_n_heads * hf_config.mamba_d_head != hf_config.mamba_expand * hf_config.hidden_size:
        raise ValueError("mamba_n_heads %d x mamba_d_head %d is not mamba_expand %d x hidden_size %d"
                         % (hf_config.mamba_n_heads, hf_config.mamba_d_head,
                            hf_config.mamba_expand, hf_config.hidden_size))
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        ffn_hidden=hf_config.shared_intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        position_type="none",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        qkv_bias=False,
        out_bias=False,
        layer_types=list(hf_config.layer_types),
        ssm_num_heads=hf_config.mamba_n_heads,
        ssm_head_dim=hf_config.mamba_d_head,
        ssm_state_dim=hf_config.mamba_d_state,
        ssm_conv_kernel=hf_config.mamba_d_conv,
        embedding_multiplier=float(hf_config.embedding_multiplier),
        residual_multiplier=float(hf_config.residual_multiplier),
        attention_multiplier=float(hf_config.attention_multiplier),
        logits_scaling=float(hf_config.logits_scaling),
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def granite_hybrid_config(model_size: str = "granite-4.0-h-micro", **overrides) -> TransformerConfig:
    return granite_hybrid_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="granite_hybrid", config_fn=granite_hybrid_config, meta_configs=META_CONFIGS,
                     default_size="granite-4.0-h-micro", config_from_hf=granite_hybrid_config_from_hf))
