"""OLMoE family: a decoder whose every block is attention with QK-norm and a
sparse mixture of experts (arXiv:2409.02060; HF `OlmoeForCausalLM`).

The block is `models/base.py`'s with the config's switches set: RMSNorm,
rope, SwiGLU experts without biases, an RMSNorm over the whole projected q
and k (`qk_norm`), `num_experts` experts of width `ffn_hidden` of which a
token is sent to `experts_per_token`, weights not renormalised, dropless
(ops/moe.py), and two router losses in the objective. The preset carries the
PUBLISHED config with its source: a preset of sizes alone would train
something that is not the model (ROADMAP D12).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search` and `profile` have no expert
form yet and refuse such a config (GLS018); `ep` is the next step.
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

OLMOE_1B_7B_SOURCE = "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "olmoe-1b-7b": {
        "source": OLMOE_1B_7B_SOURCE,
        "hidden_size": 2048, "intermediate_size": 1024, "num_hidden_layers": 16,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8, "norm_topk_prob": False,
        "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 10000.0,
        "max_position_embeddings": 4096, "vocab_size": 50304,
        "attention_bias": False, "clip_qkv": None, "tie_word_embeddings": False,
        "router_aux_loss_coef": 0.01,
    },
}
# what the paper states and HF's config does not carry
ROUTER_Z_LOSS_COEF = 0.001  # arXiv:2409.02060 section 4.1.5
INITIALIZER_RANGE = 0.02


def olmoe_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `OlmoeConfig` (or anything with its attributes). What the
    program does not model is refused, not dropped: `clip_qkv`."""
    if getattr(hf_config, "clip_qkv", None) is not None:
        raise ValueError("clip_qkv=%r is not modelled (the published OLMoE-1B-7B has null)"
                         % hf_config.clip_qkv)
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        ffn_hidden=hf_config.intermediate_size,  # the width of ONE expert
        max_seq_len=hf_config.max_position_embeddings,
        position_type="rope",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        qkv_bias=getattr(hf_config, "attention_bias", False),
        out_bias=getattr(hf_config, "attention_bias", False),
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        qk_norm=True,
        num_experts=hf_config.num_experts,
        experts_per_token=hf_config.num_experts_per_tok,
        norm_topk_prob=getattr(hf_config, "norm_topk_prob", False),
        router_aux_loss_coef=getattr(hf_config, "router_aux_loss_coef", 0.01),
        router_z_loss_coef=ROUTER_Z_LOSS_COEF,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def olmoe_config(model_size: str = "olmoe-1b-7b", **overrides) -> TransformerConfig:
    return olmoe_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="olmoe", config_fn=olmoe_config, meta_configs=META_CONFIGS,
                     default_size="olmoe-1b-7b", config_from_hf=olmoe_config_from_hf))
