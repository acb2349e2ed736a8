"""ViT family (reference: galvatron/models/vit_hf/).

Pre-LN bidirectional encoder over image patches with a cls token and a
classification head. The stride-P conv patch embedding becomes a dense on
patchified pixels (models/parts/embed_head.py `patchify`) — a single MXU matmul.
`convert_hf_vit` maps a HuggingFace `ViTForImageClassification` state dict
onto the functional param tree."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

import jax.numpy as jnp

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, register
from galvatron_tpu.models.bert import _linear, _np, _stack_qkv

META_CONFIGS = {
    "vit-base": dict(hidden_size=768, num_heads=12, num_layers=12),
    "vit-large": dict(hidden_size=1024, num_heads=16, num_layers=24),
    "vit-huge": dict(hidden_size=1280, num_heads=16, num_layers=32),
    "vit-xhuge": dict(hidden_size=2560, num_heads=32, num_layers=36),
}


def vit_config(model_size: str = "vit-base", **overrides) -> TransformerConfig:
    base = dict(META_CONFIGS[model_size])
    base.update(
        vocab_size=1,  # unused for patch input
        num_classes=1000,
        image_size=224,
        patch_size=16,
        num_channels=3,
        input_type="patches",
        use_cls_token=True,
        head_type="classification",
        pool_type="cls",
        norm_type="layernorm",
        activation="gelu_exact",
        position_type="learned",
        causal=False,
        pre_norm=True,
        tie_embeddings=False,
        qkv_bias=True,
        mlp_bias=True,
        out_bias=True,
        layernorm_eps=1e-12,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def vit_config_from_hf(hf_config, num_classes: int = 1000, **overrides) -> TransformerConfig:
    return TransformerConfig(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_layers=hf_config.num_hidden_layers,
        vocab_size=1,
        ffn_hidden=hf_config.intermediate_size,
        num_classes=num_classes,
        image_size=hf_config.image_size,
        patch_size=hf_config.patch_size,
        num_channels=hf_config.num_channels,
        input_type="patches",
        use_cls_token=True,
        head_type="classification",
        pool_type="cls",
        norm_type="layernorm",
        activation="gelu_exact",
        position_type="learned",
        causal=False,
        pre_norm=True,
        tie_embeddings=False,
        layernorm_eps=hf_config.layer_norm_eps,
        **overrides,
    )


def convert_hf_vit(state_dict: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, Any]:
    """HF ViTForImageClassification state dict -> galvatron_tpu param tree.

    The conv projection (H, C, P, P) is re-laid-out to the (P, P, C) patch
    ordering of `patchify` and flattened to a (P*P*C, H) dense kernel."""
    g = lambda n: _np(state_dict[n])
    h, nh, hd, P = cfg.hidden_size, cfg.num_heads, cfg.head_dim, cfg.patch_size
    conv = g("vit.embeddings.patch_embeddings.projection.weight")  # (h, C, P, P)
    patch_kernel = conv.transpose(2, 3, 1, 0).reshape(P * P * cfg.num_channels, h)
    params: Dict[str, Any] = {
        "embed": {
            "patch": {
                "kernel": jnp.asarray(patch_kernel),
                "bias": jnp.asarray(g("vit.embeddings.patch_embeddings.projection.bias")),
            },
            "wpe": jnp.asarray(g("vit.embeddings.position_embeddings")[0]),
            "cls_token": jnp.asarray(g("vit.embeddings.cls_token").reshape(h)),
        },
        "layers": [],
        "final_norm": {
            "scale": jnp.asarray(g("vit.layernorm.weight")),
            "bias": jnp.asarray(g("vit.layernorm.bias")),
        },
        "head": {
            "kernel": jnp.asarray(_np(state_dict["classifier.weight"]).T),
            "bias": jnp.asarray(g("classifier.bias")),
        },
    }
    for i in range(cfg.num_layers):
        pre = "vit.encoder.layer.%d." % i
        qkv_k, qkv_b = _stack_qkv(state_dict, pre + "attention.attention.", h, nh, hd)
        wo_k, wo_b = _linear(state_dict, pre + "attention.output.dense")
        wi_k, wi_b = _linear(state_dict, pre + "intermediate.dense")
        wom_k, wom_b = _linear(state_dict, pre + "output.dense")
        params["layers"].append(
            {
                "ln1": {
                    "scale": jnp.asarray(g(pre + "layernorm_before.weight")),
                    "bias": jnp.asarray(g(pre + "layernorm_before.bias")),
                },
                "ln2": {
                    "scale": jnp.asarray(g(pre + "layernorm_after.weight")),
                    "bias": jnp.asarray(g(pre + "layernorm_after.bias")),
                },
                "wqkv": {"kernel": jnp.asarray(qkv_k), "bias": jnp.asarray(qkv_b)},
                "wo": {"kernel": jnp.asarray(wo_k), "bias": jnp.asarray(wo_b)},
                "wi": {"kernel": jnp.asarray(wi_k), "bias": jnp.asarray(wi_b)},
                "wo_mlp": {"kernel": jnp.asarray(wom_k), "bias": jnp.asarray(wom_b)},
            }
        )
    return params


def export_hf_vit(params: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """galvatron_tpu param tree -> HF ViTForImageClassification state dict
    arrays — exact inverse of convert_hf_vit (reference g2h analogue)."""
    h, nh, hd, P, C = (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
                       cfg.patch_size, cfg.num_channels)
    a = lambda x: np.asarray(x, np.float32)
    out: Dict[str, np.ndarray] = {
        "vit.embeddings.patch_embeddings.projection.weight": a(
            params["embed"]["patch"]["kernel"]
        ).reshape(P, P, C, h).transpose(3, 2, 0, 1),
        "vit.embeddings.patch_embeddings.projection.bias": a(params["embed"]["patch"]["bias"]),
        "vit.embeddings.position_embeddings": a(params["embed"]["wpe"])[None],
        "vit.embeddings.cls_token": a(params["embed"]["cls_token"]).reshape(1, 1, h),
        "vit.layernorm.weight": a(params["final_norm"]["scale"]),
        "vit.layernorm.bias": a(params["final_norm"]["bias"]),
        "classifier.weight": a(params["head"]["kernel"]).T,
        "classifier.bias": a(params["head"]["bias"]),
    }
    for i, lp in enumerate(params["layers"]):
        pre = "vit.encoder.layer.%d." % i
        qkv = a(lp["wqkv"]["kernel"])  # (h, 3, nh, hd)
        qkv_b = a(lp["wqkv"]["bias"])  # (3, nh, hd)
        for j, role in enumerate(("query", "key", "value")):
            out[pre + "attention.attention.%s.weight" % role] = qkv[:, j].reshape(h, nh * hd).T
            out[pre + "attention.attention.%s.bias" % role] = qkv_b[j].reshape(nh * hd)
        out[pre + "attention.output.dense.weight"] = a(lp["wo"]["kernel"]).T
        out[pre + "attention.output.dense.bias"] = a(lp["wo"]["bias"])
        out[pre + "intermediate.dense.weight"] = a(lp["wi"]["kernel"]).T
        out[pre + "intermediate.dense.bias"] = a(lp["wi"]["bias"])
        out[pre + "output.dense.weight"] = a(lp["wo_mlp"]["kernel"]).T
        out[pre + "output.dense.bias"] = a(lp["wo_mlp"]["bias"])
        out[pre + "layernorm_before.weight"] = a(lp["ln1"]["scale"])
        out[pre + "layernorm_before.bias"] = a(lp["ln1"]["bias"])
        out[pre + "layernorm_after.weight"] = a(lp["ln2"]["scale"])
        out[pre + "layernorm_after.bias"] = a(lp["ln2"]["bias"])
    return out


register(
    ModelFamily(
        name="vit",
        config_fn=vit_config,
        meta_configs=META_CONFIGS,
        default_size="vit-base",
        data_kind="vision",
        convert_from_hf=convert_hf_vit,
        export_to_hf=export_hf_vit,
        config_from_hf=vit_config_from_hf,
    )
)
