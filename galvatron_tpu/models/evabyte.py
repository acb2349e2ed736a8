"""EvaByte's family (HF `model_type: evabyte`, `attention_class: eva`; HKU NLP /
SambaNova, 2025-01): a byte-level decoder of 6.5 B parameters over a vocabulary
of 320 (the bytes and a few specials), every layer pre-norm RMSNorm (scaled by
`1 + w`: `norm_add_unit_offset`), EVA attention, and a bias-free SwiGLU.

**EVA attention** (Zheng et al., ICLR 2023; `models/parts/eva.py`, the mixer
"eva"; ops/eva_attention.py): a query attends exactly to the keys of its own
window of `window_size` positions and, in the same softmax, to ONE pooled key
and value for each `chunk_size` positions before that window; rope (theta 1e5,
rotate-half, whole heads) turns q and k first. **The head** emits
`num_pred_heads` predictions a position from one matmul, head i the byte i + 1
places on, and the loss is the mean of the heads' mean cross entropies
(`models/parts/embed_head.next_tokens_cross_entropy`).

The block is `models/base.py`'s with the config's switches set; the preset
carries the PUBLISHED config with its source (ROADMAP D12). What the published
file is silent on (the pooling's form, `phi` and `mu`'s initialisation, the
rotation's convention, the weights of the eight losses) is in `ASSUMED`, and
`fp32_skip_add` read as: the residual add is computed in float32 and rounded
once to the stream's dtype, so the stream stays in the compute dtype (what the
stack's `x + o` does on bf16 operands already).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no form of these layers and refuse such a config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, register

EVABYTE_SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the shape or the objective)
PUBLISHED = {
    "evabyte-6.5b": {
        "source": EVABYTE_SOURCE,
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
        "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096, "init_std": 0.01275,
        "intermediate_size": 11008, "max_position_embeddings": 32768, "max_seq_length": 32768,
        "model_type": "evabyte", "norm_add_unit_offset": True, "num_attention_heads": 32,
        "num_hidden_layers": 32, "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
        "window_size": 2048,
    },
}
# what the published file has no key for: EvaByte's public form as recalled
ASSUMED = {"pooling": "a = softmax_i(<phi_h, k_i>) within the chunk, unscaled; mu added to the pooled key alone",
           "phi_mu_init": "clamp(N(0, 1), -1, 1) x head_dim^-1/2", "rope": "rotate-half (HF Llama's), whole heads",
           "pred_head_weights": "equal"}


def evabyte_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF EvaByte config (or anything with its attributes). What the
    program does not model is refused, not dropped."""
    for key, modelled in (("attention_class", "eva"), ("hidden_act", "silu"), ("attention_bias", False),
                          ("rope_scaling", None), ("fp32_ln", False)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published EvaByte has %r)"
                             % (key, getattr(hf_config, key), modelled))
    if hf_config.num_key_value_heads != hf_config.num_attention_heads:
        raise ValueError("EVA attention pools a key head a query head: %d key heads on %d query heads is not modelled"
                         % (hf_config.num_key_value_heads, hf_config.num_attention_heads))
    fields = dict(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        num_layers=hf_config.num_hidden_layers,
        vocab_size=hf_config.vocab_size,
        ffn_hidden=hf_config.intermediate_size,
        max_seq_len=getattr(hf_config, "max_seq_length", hf_config.max_position_embeddings),
        norm_type="rmsnorm", activation="swiglu", causal=True, pre_norm=True,
        qkv_bias=False, out_bias=False, mlp_bias=False,
        norm_zero_centered=bool(getattr(hf_config, "norm_add_unit_offset", True)),
        layernorm_eps=hf_config.rms_norm_eps,
        init_std=hf_config.init_std,
        position_type="rope", rope_theta=float(hf_config.rope_theta),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        mixer="eva", eva_window=hf_config.window_size, eva_chunk=hf_config.chunk_size,
        pred_heads=getattr(hf_config, "num_pred_heads", 1),
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def evabyte_config(model_size: str = "evabyte-6.5b", **overrides) -> TransformerConfig:
    return evabyte_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="evabyte", config_fn=evabyte_config, meta_configs=META_CONFIGS,
                     default_size="evabyte-6.5b", config_from_hf=evabyte_config_from_hf))
