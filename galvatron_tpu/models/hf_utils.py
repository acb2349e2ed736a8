"""Shared helpers for reading HF configs and converting HF state dicts (used
by every family — the analogue of the common slicing code in the reference's
tools/checkpoint_convert_h2g.py)."""

from __future__ import annotations

import numpy as np


def decoder_fields(hf_config, init_std: float) -> dict:
    """What the HF config of a pre-norm causal decoder of RMSNorms and SwiGLUs
    states as LLaMA's does; a family's reader adds what its model adds."""
    return dict(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads", hf_config.num_attention_heads),
        num_layers=hf_config.num_hidden_layers,
        vocab_size=hf_config.vocab_size,
        norm_type="rmsnorm", activation="swiglu", causal=True, pre_norm=True, mlp_bias=False,
        layernorm_eps=hf_config.rms_norm_eps,
        init_std=getattr(hf_config, "initializer_range", init_std),
    )


def to_np(t) -> np.ndarray:
    """torch tensor or array-like -> float32 numpy."""
    return np.asarray(t.detach().cpu().numpy() if hasattr(t, "detach") else t, np.float32)


def linear(state_dict, name):
    """torch Linear stores (out, in); we store (in, out). Returns (kernel, bias)."""
    return to_np(state_dict[name + ".weight"]).T, to_np(state_dict[name + ".bias"])


def stack_qkv(state_dict, prefix, h, nh, hd, roles=("query", "key", "value")):
    """Separate q/k/v Linears -> fused head-major (h, 3, nh, hd) kernel +
    (3, nh, hd) bias."""
    ks, bs = [], []
    for role in roles:
        w, b = linear(state_dict, prefix + role)
        ks.append(w.reshape(h, nh, hd))
        bs.append(b.reshape(nh, hd))
    return np.stack(ks, axis=1), np.stack(bs, axis=0)
