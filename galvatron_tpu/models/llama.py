"""LLaMA / Qwen2.5 family (reference: galvatron/models/llama_hf/).

Meta configs mirror the reference presets (models/llama_hf/meta_configs/:
llama-0.3b/7b/13b/30b, llama2-70b, qwen2.5-*). This is the flagship family
(BASELINE.md north-star: LLaMA-7B tokens/sec/chip)."""

from __future__ import annotations

from typing import Any, Dict

import numpy as np

import jax.numpy as jnp

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, flash_variant, register

META_CONFIGS = {
    "llama-0.3b": dict(hidden_size=1024, num_heads=16, num_layers=24, max_seq_len=1024),
    "llama-7b": dict(hidden_size=4096, num_heads=32, num_layers=32, max_seq_len=2048),
    "llama-13b": dict(hidden_size=5120, num_heads=40, num_layers=40, max_seq_len=2048),
    "llama-30b": dict(hidden_size=6656, num_heads=52, num_layers=60, max_seq_len=2048),
    "llama2-70b": dict(
        hidden_size=8192, num_heads=64, num_kv_heads=8, num_layers=80,
        max_seq_len=4096, ffn_hidden=28672,
    ),
    "qwen2.5-7b": dict(
        hidden_size=3584, num_heads=28, num_kv_heads=4, num_layers=28,
        max_seq_len=8192, ffn_hidden=18944, vocab_size=152064,
    ),
}


def _default_ffn(hidden: int, multiple_of: int = 256) -> int:
    """LLaMA-1 rule: 2/3 * 4h rounded up to multiple_of."""
    ffn = int(2 * (4 * hidden) / 3)
    return multiple_of * ((ffn + multiple_of - 1) // multiple_of)


def llama_config(model_size: str = "llama-0.3b", **overrides) -> TransformerConfig:
    base = dict(META_CONFIGS[model_size])
    base.setdefault("ffn_hidden", _default_ffn(base["hidden_size"]))
    base.setdefault("vocab_size", 32000)
    base.update(
        norm_type="rmsnorm",
        activation="swiglu",
        position_type="rope",
        causal=True,
        pre_norm=True,
        tie_embeddings=False,
        qkv_bias=False,
        mlp_bias=False,
        out_bias=False,
        layernorm_eps=1e-6,
        init_std=0.02,
    )
    base.update(overrides)
    return TransformerConfig(**base)


def llama_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    return TransformerConfig(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        num_layers=hf_config.num_hidden_layers,
        ffn_hidden=hf_config.intermediate_size,
        vocab_size=hf_config.vocab_size,
        max_seq_len=hf_config.max_position_embeddings,
        norm_type="rmsnorm",
        activation="swiglu",
        position_type="rope",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        qkv_bias=False,
        mlp_bias=False,
        out_bias=False,
        layernorm_eps=hf_config.rms_norm_eps,
        rope_theta=getattr(hf_config, "rope_theta", 10000.0),
        **overrides,
    )


def convert_hf_llama(state_dict: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, Any]:
    """HF LlamaForCausalLM state dict -> param tree. HF Linear kernels are
    (out, in) and transpose to our (in, out); q/k/v reshape head-major; gate
    and up fuse into wi (h, 2, ffn)."""

    def g(name):
        t = state_dict[name]
        return np.asarray(t.detach().float().cpu().numpy() if hasattr(t, "detach") else t, np.float32)

    h, nh, nkv, hd, ffn = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ffn_hidden
    params: Dict[str, Any] = {
        "embed": {"wte": jnp.asarray(g("model.embed_tokens.weight"))},
        "final_norm": {"scale": jnp.asarray(g("model.norm.weight"))},
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": jnp.asarray(g("lm_head.weight").T)}
    for i in range(cfg.num_layers):
        pre = "model.layers.%d." % i
        q = g(pre + "self_attn.q_proj.weight").T.reshape(h, nh, hd)
        k = g(pre + "self_attn.k_proj.weight").T.reshape(h, nkv, hd)
        v = g(pre + "self_attn.v_proj.weight").T.reshape(h, nkv, hd)
        gate = g(pre + "mlp.gate_proj.weight").T
        up = g(pre + "mlp.up_proj.weight").T
        lp: Dict[str, Any] = {
            "ln1": {"scale": jnp.asarray(g(pre + "input_layernorm.weight"))},
            "ln2": {"scale": jnp.asarray(g(pre + "post_attention_layernorm.weight"))},
            "wo": {"kernel": jnp.asarray(g(pre + "self_attn.o_proj.weight").T)},
            "wi": {"kernel": jnp.asarray(np.stack([gate, up], axis=1))},
            "wo_mlp": {"kernel": jnp.asarray(g(pre + "mlp.down_proj.weight").T)},
        }
        if cfg.fused_qkv:
            lp["wqkv"] = {"kernel": jnp.asarray(np.stack([q, k, v], axis=1))}
        else:
            lp["wq"] = {"kernel": jnp.asarray(q)}
            lp["wkv"] = {"kernel": jnp.asarray(np.stack([k, v], axis=1))}
        params["layers"].append(lp)
    return params


def export_hf_llama(params: Dict[str, Any], cfg: TransformerConfig) -> Dict[str, np.ndarray]:
    """galvatron_tpu param tree -> HF LlamaForCausalLM state dict arrays —
    exact inverse of convert_hf_llama (the analogue of the reference llama
    exporter, tools/checkpoint_convert_g2h.py:11-110)."""
    h, nh, nkv, hd = cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    a = lambda x: np.asarray(x, np.float32)
    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": a(params["embed"]["wte"]),
        "model.norm.weight": a(params["final_norm"]["scale"]),
    }
    if cfg.tie_embeddings:
        out["lm_head.weight"] = a(params["embed"]["wte"])
    else:
        out["lm_head.weight"] = a(params["lm_head"]["kernel"]).T
    for i, lp in enumerate(params["layers"]):
        pre = "model.layers.%d." % i
        if cfg.fused_qkv:
            qkv = a(lp["wqkv"]["kernel"])  # (h, 3, nh, hd)
            q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        else:
            q = a(lp["wq"]["kernel"])  # (h, nh, hd)
            kv = a(lp["wkv"]["kernel"])  # (h, 2, nkv, hd)
            k, v = kv[:, 0], kv[:, 1]
        out[pre + "self_attn.q_proj.weight"] = q.reshape(h, nh * hd).T
        out[pre + "self_attn.k_proj.weight"] = k.reshape(h, nkv * hd).T
        out[pre + "self_attn.v_proj.weight"] = v.reshape(h, nkv * hd).T
        out[pre + "self_attn.o_proj.weight"] = a(lp["wo"]["kernel"]).T
        wi = a(lp["wi"]["kernel"])  # (h, 2, ffn): [gate, up]
        out[pre + "mlp.gate_proj.weight"] = wi[:, 0].T
        out[pre + "mlp.up_proj.weight"] = wi[:, 1].T
        out[pre + "mlp.down_proj.weight"] = a(lp["wo_mlp"]["kernel"]).T
        out[pre + "input_layernorm.weight"] = a(lp["ln1"]["scale"])
        out[pre + "post_attention_layernorm.weight"] = a(lp["ln2"]["scale"])
    return out


register(flash_variant(register(ModelFamily(
    name="llama", config_fn=llama_config, meta_configs=META_CONFIGS, default_size="llama-0.3b",
    convert_from_hf=convert_hf_llama, export_to_hf=export_hf_llama, config_from_hf=llama_config_from_hf))))
