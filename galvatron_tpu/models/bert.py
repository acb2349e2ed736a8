"""BERT family (reference: galvatron/models/bert_hf/).

Post-LN bidirectional encoder with token-type embeddings, embedding
LayerNorm, and an MLM head (transform dense + gelu + LN + tied decoder).
Meta configs mirror the reference presets (models/bert_hf/meta_configs/).
`convert_hf_bert` maps a HuggingFace `BertForMaskedLM` state dict onto the
functional param tree (the analogue of tools/checkpoint_convert_h2g.py)."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

import jax.numpy as jnp

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, register

META_CONFIGS = {
    "bert-base": dict(hidden_size=768, num_heads=12, num_layers=12, max_seq_len=512),
    "bert-large": dict(hidden_size=1024, num_heads=16, num_layers=24, max_seq_len=512),
    "bert-huge-32": dict(hidden_size=1280, num_heads=16, num_layers=32, max_seq_len=512),
    "bert-huge-48": dict(hidden_size=1280, num_heads=16, num_layers=48, max_seq_len=512),
}


def bert_config(model_size: str = "bert-base", **overrides) -> TransformerConfig:
    base = dict(META_CONFIGS[model_size])
    base.update(
        vocab_size=30522,
        type_vocab_size=2,
        norm_type="layernorm",
        activation="gelu_exact",
        position_type="learned",
        causal=False,
        pre_norm=False,
        embed_norm=True,
        head_type="mlm",
        tie_embeddings=True,
        qkv_bias=True,
        mlp_bias=True,
        out_bias=True,
        layernorm_eps=1e-12,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def bert_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    return TransformerConfig(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_layers=hf_config.num_hidden_layers,
        vocab_size=hf_config.vocab_size,
        max_seq_len=hf_config.max_position_embeddings,
        ffn_hidden=hf_config.intermediate_size,
        type_vocab_size=hf_config.type_vocab_size,
        norm_type="layernorm",
        activation="gelu_exact",
        position_type="learned",
        causal=False,
        pre_norm=False,
        embed_norm=True,
        head_type="mlm",
        layernorm_eps=hf_config.layer_norm_eps,
        **overrides,
    )


from galvatron_tpu.models.hf_utils import linear as _linear, stack_qkv as _stack_qkv, to_np as _np


def convert_hf_bert(state_dict: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, Any]:
    """HF BertForMaskedLM state dict -> galvatron_tpu param tree."""
    g = lambda n: _np(state_dict[n])
    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    params: Dict[str, Any] = {
        "embed": {
            "wte": jnp.asarray(g("bert.embeddings.word_embeddings.weight")),
            "wpe": jnp.asarray(g("bert.embeddings.position_embeddings.weight")),
            "tte": jnp.asarray(g("bert.embeddings.token_type_embeddings.weight")),
            "norm": {
                "scale": jnp.asarray(g("bert.embeddings.LayerNorm.weight")),
                "bias": jnp.asarray(g("bert.embeddings.LayerNorm.bias")),
            },
        },
        "layers": [],
    }
    for i in range(cfg.num_layers):
        pre = "bert.encoder.layer.%d." % i
        qkv_k, qkv_b = _stack_qkv(state_dict, pre + "attention.self.", h, nh, hd)
        wo_k, wo_b = _linear(state_dict, pre + "attention.output.dense")
        wi_k, wi_b = _linear(state_dict, pre + "intermediate.dense")
        wom_k, wom_b = _linear(state_dict, pre + "output.dense")
        params["layers"].append(
            {
                "ln1": {
                    "scale": jnp.asarray(g(pre + "attention.output.LayerNorm.weight")),
                    "bias": jnp.asarray(g(pre + "attention.output.LayerNorm.bias")),
                },
                "ln2": {
                    "scale": jnp.asarray(g(pre + "output.LayerNorm.weight")),
                    "bias": jnp.asarray(g(pre + "output.LayerNorm.bias")),
                },
                "wqkv": {"kernel": jnp.asarray(qkv_k), "bias": jnp.asarray(qkv_b)},
                "wo": {"kernel": jnp.asarray(wo_k), "bias": jnp.asarray(wo_b)},
                "wi": {"kernel": jnp.asarray(wi_k), "bias": jnp.asarray(wi_b)},
                "wo_mlp": {"kernel": jnp.asarray(wom_k), "bias": jnp.asarray(wom_b)},
            }
        )
    tr_k, tr_b = _linear(state_dict, "cls.predictions.transform.dense")
    params["head"] = {
        "transform": {"kernel": jnp.asarray(tr_k), "bias": jnp.asarray(tr_b)},
        "norm": {
            "scale": jnp.asarray(g("cls.predictions.transform.LayerNorm.weight")),
            "bias": jnp.asarray(g("cls.predictions.transform.LayerNorm.bias")),
        },
        "bias": jnp.asarray(g("cls.predictions.bias")),
    }
    return params


def export_hf_bert(params: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """galvatron_tpu param tree -> HF BertForMaskedLM state dict arrays
    (the analogue of tools/checkpoint_convert_g2h.py)."""
    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    a = lambda x: np.asarray(x, np.float32)
    out: Dict[str, np.ndarray] = {
        "bert.embeddings.word_embeddings.weight": a(params["embed"]["wte"]),
        "bert.embeddings.position_embeddings.weight": a(params["embed"]["wpe"]),
        "bert.embeddings.token_type_embeddings.weight": a(params["embed"]["tte"]),
        "bert.embeddings.LayerNorm.weight": a(params["embed"]["norm"]["scale"]),
        "bert.embeddings.LayerNorm.bias": a(params["embed"]["norm"]["bias"]),
        "cls.predictions.transform.dense.weight": a(params["head"]["transform"]["kernel"]).T,
        "cls.predictions.transform.dense.bias": a(params["head"]["transform"]["bias"]),
        "cls.predictions.transform.LayerNorm.weight": a(params["head"]["norm"]["scale"]),
        "cls.predictions.transform.LayerNorm.bias": a(params["head"]["norm"]["bias"]),
        "cls.predictions.bias": a(params["head"]["bias"]),
        "cls.predictions.decoder.weight": a(params["embed"]["wte"]),
        "cls.predictions.decoder.bias": a(params["head"]["bias"]),
    }
    for i, lp in enumerate(params["layers"]):
        pre = "bert.encoder.layer.%d." % i
        qkv = a(lp["wqkv"]["kernel"])  # (h, 3, nh, hd)
        qkv_b = a(lp["wqkv"]["bias"])
        for j, role in enumerate(("query", "key", "value")):
            out[pre + "attention.self.%s.weight" % role] = qkv[:, j].reshape(h, nh * hd).T
            out[pre + "attention.self.%s.bias" % role] = qkv_b[j].reshape(nh * hd)
        out[pre + "attention.output.dense.weight"] = a(lp["wo"]["kernel"]).T
        out[pre + "attention.output.dense.bias"] = a(lp["wo"]["bias"])
        out[pre + "attention.output.LayerNorm.weight"] = a(lp["ln1"]["scale"])
        out[pre + "attention.output.LayerNorm.bias"] = a(lp["ln1"]["bias"])
        out[pre + "intermediate.dense.weight"] = a(lp["wi"]["kernel"]).T
        out[pre + "intermediate.dense.bias"] = a(lp["wi"]["bias"])
        out[pre + "output.dense.weight"] = a(lp["wo_mlp"]["kernel"]).T
        out[pre + "output.dense.bias"] = a(lp["wo_mlp"]["bias"])
        out[pre + "output.LayerNorm.weight"] = a(lp["ln2"]["scale"])
        out[pre + "output.LayerNorm.bias"] = a(lp["ln2"]["bias"])
    return out


register(
    ModelFamily(
        name="bert",
        config_fn=bert_config,
        meta_configs=META_CONFIGS,
        default_size="bert-base",
        convert_from_hf=convert_hf_bert,
        export_to_hf=export_hf_bert,
        config_from_hf=bert_config_from_hf,
    )
)
