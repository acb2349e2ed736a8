"""GPT-2 family (reference: galvatron/models/gpt_hf/).

Meta configs mirror the reference presets
(models/gpt_hf/meta_configs/config_utils.py:9-14: gpt-0.3b/1.5b/2.7b/6.7b).
`convert_hf_gpt2` maps a HuggingFace GPT2LMHeadModel state dict onto the
functional param tree (the analogue of tools/checkpoint_convert_h2g.py +
GPTModel_checkpoint.py TP-aware loading — here conversion is layout-only;
sharding is applied by device_put with the param specs)."""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

import jax.numpy as jnp

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, flash_variant, register

META_CONFIGS = {
    "gpt-0.3b": dict(hidden_size=1024, num_heads=16, num_layers=24, max_seq_len=1024),
    "gpt-1.5b": dict(hidden_size=1600, num_heads=32, num_layers=48, max_seq_len=1024, head_dim=50),
    "gpt-2.7b": dict(hidden_size=2560, num_heads=32, num_layers=32, max_seq_len=2048, head_dim=80),
    "gpt-6.7b": dict(hidden_size=4096, num_heads=32, num_layers=32, max_seq_len=2048),
}


def gpt_config(model_size: str = "gpt-0.3b", **overrides) -> TransformerConfig:
    base = dict(META_CONFIGS[model_size])
    base.update(
        vocab_size=50257,
        norm_type="layernorm",
        activation="gelu",
        position_type="learned",
        causal=True,
        pre_norm=True,
        tie_embeddings=True,
        qkv_bias=True,
        mlp_bias=True,
        out_bias=True,
        layernorm_eps=1e-5,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def gpt_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    return TransformerConfig(
        hidden_size=hf_config.n_embd,
        num_heads=hf_config.n_head,
        num_layers=hf_config.n_layer,
        vocab_size=hf_config.vocab_size,
        max_seq_len=hf_config.n_positions,
        norm_type="layernorm",
        activation="gelu",
        position_type="learned",
        layernorm_eps=hf_config.layer_norm_epsilon,
        **overrides,
    )


def convert_hf_gpt2(state_dict: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, Any]:
    """HF GPT2LMHeadModel state dict -> galvatron_tpu param tree.

    HF Conv1D stores kernels (in, out), matching our layout directly; the
    fused c_attn (h, 3*nh*hd) reshapes to our head-major (h, 3, nh, hd)."""

    def g(name):
        t = state_dict[name]
        return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t, np.float32)

    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    params: Dict[str, Any] = {
        "embed": {
            "wte": jnp.asarray(g("transformer.wte.weight")),
            "wpe": jnp.asarray(g("transformer.wpe.weight")),
        },
        "final_norm": {
            "scale": jnp.asarray(g("transformer.ln_f.weight")),
            "bias": jnp.asarray(g("transformer.ln_f.bias")),
        },
        "layers": [],
    }
    for i in range(cfg.num_layers):
        pre = "transformer.h.%d." % i
        lp = {
            "ln1": {"scale": jnp.asarray(g(pre + "ln_1.weight")), "bias": jnp.asarray(g(pre + "ln_1.bias"))},
            "ln2": {"scale": jnp.asarray(g(pre + "ln_2.weight")), "bias": jnp.asarray(g(pre + "ln_2.bias"))},
            "wqkv": {
                "kernel": jnp.asarray(g(pre + "attn.c_attn.weight").reshape(h, 3, nh, hd)),
                "bias": jnp.asarray(g(pre + "attn.c_attn.bias").reshape(3, nh, hd)),
            },
            "wo": {
                "kernel": jnp.asarray(g(pre + "attn.c_proj.weight")),
                "bias": jnp.asarray(g(pre + "attn.c_proj.bias")),
            },
            "wi": {
                "kernel": jnp.asarray(g(pre + "mlp.c_fc.weight")),
                "bias": jnp.asarray(g(pre + "mlp.c_fc.bias")),
            },
            "wo_mlp": {
                "kernel": jnp.asarray(g(pre + "mlp.c_proj.weight")),
                "bias": jnp.asarray(g(pre + "mlp.c_proj.bias")),
            },
        }
        params["layers"].append(lp)
    return params


def export_hf_gpt2(params: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """galvatron_tpu param tree -> HF GPT2 state dict arrays (the analogue of
    tools/checkpoint_convert_g2h.py)."""
    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    out: Dict[str, np.ndarray] = {
        "transformer.wte.weight": np.asarray(params["embed"]["wte"], np.float32),
        "transformer.wpe.weight": np.asarray(params["embed"]["wpe"], np.float32),
        "transformer.ln_f.weight": np.asarray(params["final_norm"]["scale"], np.float32),
        "transformer.ln_f.bias": np.asarray(params["final_norm"]["bias"], np.float32),
        "lm_head.weight": np.asarray(params["embed"]["wte"], np.float32),
    }
    for i, lp in enumerate(params["layers"]):
        pre = "transformer.h.%d." % i
        out[pre + "ln_1.weight"] = np.asarray(lp["ln1"]["scale"], np.float32)
        out[pre + "ln_1.bias"] = np.asarray(lp["ln1"]["bias"], np.float32)
        out[pre + "ln_2.weight"] = np.asarray(lp["ln2"]["scale"], np.float32)
        out[pre + "ln_2.bias"] = np.asarray(lp["ln2"]["bias"], np.float32)
        out[pre + "attn.c_attn.weight"] = np.asarray(lp["wqkv"]["kernel"], np.float32).reshape(h, 3 * nh * hd)
        out[pre + "attn.c_attn.bias"] = np.asarray(lp["wqkv"]["bias"], np.float32).reshape(3 * nh * hd)
        out[pre + "attn.c_proj.weight"] = np.asarray(lp["wo"]["kernel"], np.float32)
        out[pre + "attn.c_proj.bias"] = np.asarray(lp["wo"]["bias"], np.float32)
        out[pre + "mlp.c_fc.weight"] = np.asarray(lp["wi"]["kernel"], np.float32)
        out[pre + "mlp.c_fc.bias"] = np.asarray(lp["wi"]["bias"], np.float32)
        out[pre + "mlp.c_proj.weight"] = np.asarray(lp["wo_mlp"]["kernel"], np.float32)
        out[pre + "mlp.c_proj.bias"] = np.asarray(lp["wo_mlp"]["bias"], np.float32)
    return out


register(flash_variant(register(ModelFamily(
    name="gpt", config_fn=gpt_config, meta_configs=META_CONFIGS, default_size="gpt-0.3b",
    convert_from_hf=convert_hf_gpt2, export_to_hf=export_hf_gpt2, config_from_hf=gpt_config_from_hf))))
