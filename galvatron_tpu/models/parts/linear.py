"""Gated DeltaNet, the linear-attention token mixer (Qwen3-Next's three layers in four)."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, _dense_init, _proj_std, _unit, no_form
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.kernels import KernelSharding
from galvatron_tpu.ops.linear_attention import (Heads, causal_conv, gated_delta_rule, kernel_mixer, linear_layout,
                                                mixer_form)
from galvatron_tpu.ops.norms import rms_norm
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes


def validate_delta_heads(cfg: TransformerConfig, kda: bool = False) -> None:
    """The clause of the two delta-rule mixers, whose heads and convolution
    are the `linear_*` fields (the module after the stack takes a softmax
    layer's outputs: `mtp_logits`)."""
    heads = (cfg.linear_num_key_heads, cfg.linear_num_value_heads)
    if (min(heads + (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                     cfg.linear_conv_kernel)) < 1 or heads[1] % heads[0]
            or cfg.mtp_layers or (kda and heads[0] != heads[1])):
        raise ValueError(
            "linear-attention layers (full_attention_interval=%d, or layer_types naming "
            "\"kda\") want linear_num_key_heads dividing linear_num_value_heads (equal "
            "under \"kda\"), head dims and a convolution kernel of 1 or more, and no "
            "multi-token-prediction module; got heads %r, dims (%d, %d), kernel %d" % (
                cfg.full_attention_interval, heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim, cfg.linear_conv_kernel))


# the recurrence has no tp, sp, cp or pp form, the decode engine no recurrent
# state, the cost models no row
UNSUPPORTED = no_form(
    "linear-attention layers",
    serve="no recurrent state of a linear-attention layer (serve/kv_cache.py holds keys and values)",
    autotune="a linear-attention layer as softmax attention",
    pp="stack one kind of layer a stage, not linear-attention layers among attention layers",
    tp="linear-attention layers (the delta rule's state runs along the whole sequence of all a layer's heads)",
    quant="a linear-attention layer's counters",
)


def _init_linear(ks, cfg: TransformerConfig) -> Params:
    """The gated-DeltaNet mixer's leaves, under `linear` (HF
    `Qwen3NextGatedDeltaNet`: in_proj_qkvz, in_proj_ba, conv1d, A_log,
    dt_bias, norm, out_proj). `wqkvz`'s columns lie [q | k | v | z], `wba`'s
    [b | a], heads in order within each (HF groups them a key head: on random
    weights a permutation of columns). The gate starts as the Gated DeltaNet
    reference does: A = exp(A_log) ~ U(0, 16) and dt = softplus(dt_bias)
    log-uniform in [0.001, 0.1], so that exp(g) spans 0.2 to 1 a token and
    state crosses chunks; the taps U(-1, 1) / sqrt(taps), PyTorch's default
    for a convolution of that fan-in."""
    h, taps = cfg.hidden_size, cfg.linear_conv_kernel
    nv = cfg.linear_num_value_heads
    key_dim = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    value_dim = nv * cfg.linear_value_head_dim
    kin = jax.random.split(ks[0], 2)
    kgate = jax.random.split(ks[4], 3)
    step = jnp.exp(jax.random.uniform(kgate[2], (nv,), jnp.float32, math.log(1e-3), math.log(0.1)))
    return {"linear": {
        "wqkvz": {"kernel": _dense_init(
            kin[0], (h, 2 * key_dim + 2 * value_dim), cfg.init_std, cfg.param_dtype)},
        "wba": {"kernel": _dense_init(kin[1], (h, 2 * nv), cfg.init_std, cfg.param_dtype)},
        "conv": jax.random.uniform(kgate[0], (2 * key_dim + value_dim, taps), jnp.float32,
                                   -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
        "A_log": jnp.log(jax.random.uniform(kgate[1], (nv,), jnp.float32, 1e-6, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "norm": {"scale": jnp.ones((cfg.linear_value_head_dim,), cfg.param_dtype)},
        "wout": {"kernel": _dense_init(ks[1], (value_dim, h), _proj_std(cfg), cfg.param_dtype)},
    }}


def linear_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
                 attn_sharding: Optional[KernelSharding] = None, **_):
    """Gated DeltaNet on normed activations (B, S, H) (HF
    `Qwen3NextGatedDeltaNet`; arXiv:2412.06464), p the layer's tree:

        [q, k, v, z] = y Wqkvz;  [b, a] = y Wba
        [q, k, v] = silu(conv([q, k, v]))             causal, depthwise, a channel
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   float32, <= 0
        q, k L2-normalised a head, q / sqrt(d_k); each key head serves
        value / key heads
        o = gated_delta_rule(q, k, v, g, beta)         ops/linear_attention.py
        out = (RMSNorm(o; w) silu(z)) Wout             a head; the norm BEFORE the gate

    -> out, None, and the layer's counters: the mean gate `exp(g)` (how much
    state a token keeps) and the largest magnitude in any head's final state.
    Scopes: the core under `gt.attn.delta`, all else under `gt.attn.linear`.
    No position enters: the order is the recurrence's. `attn_sharding` tells
    the core where its operands lie (on TPUs it runs as Pallas kernels)."""
    p, dtype = p["linear"], cfg.compute_dtype
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = nk * dk, nv * dv
    b, s, _ = y.shape

    with jax.named_scope(tracing.ATTN_LINEAR):
        qkvz = _dense(y, p["wqkvz"], dtype)
        ba = _dense(y, p["wba"], dtype).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :nv])
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., nv:] + p["dt_bias"].astype(jnp.float32))
    layout = linear_layout(Heads(nk, dk, nv, dv))
    if mixer_form(qkvz, p["conv"], layout, sharding=attn_sharding) == "pallas":
        # the same arithmetic as lane-aligned passes around the core's kernels
        o, state = kernel_mixer(qkvz, p["conv"], p["norm"]["scale"], g, beta, layout,
                                eps=cfg.layernorm_eps, sharding=attn_sharding)
    else:
        with jax.named_scope(tracing.ATTN_LINEAR):
            qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * key_dim + value_dim], p["conv"]))
            z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, s, nv, dv)
            q = (_unit(qkv[..., :key_dim].reshape(b, s, nk, dk)) * dk ** -0.5).astype(dtype)
            k = _unit(qkv[..., key_dim:2 * key_dim].reshape(b, s, nk, dk)).astype(dtype)
            v = qkv[..., 2 * key_dim:].reshape(b, s, nv, dv)
        with jax.named_scope(tracing.ATTN_DELTA):
            o, state = gated_delta_rule(q, k, v, g, beta, sharding=attn_sharding)
        with jax.named_scope(tracing.ATTN_LINEAR):
            o = rms_norm(o.astype(jnp.float32), p["norm"]["scale"], cfg.layernorm_eps)
            o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(dtype).reshape(b, s, value_dim)
    with jax.named_scope(tracing.ATTN_LINEAR):
        out = _dense(o, p["wout"], dtype)
        stats = {"decay_mean": jnp.mean(jnp.exp(g)), "state_abs_max": jnp.max(jnp.abs(state))}
    return out, None, stats


def _linear_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp, sp, cp are refused, GLS018): ZeRO-3 splits the
    # projections' input dim; the small leaves are whole everywhere
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    return {"linear": {
        "wqkvz": {"kernel": P(z3, None)}, "wba": {"kernel": P(z3, None)},
        "conv": P(None, None), "A_log": r1, "dt_bias": r1, "norm": {"scale": r1},
        "wout": {"kernel": P(z3, None)},
    }}


LINEAR = LayerPart(_init_linear, linear_mixer, _linear_specs, (tracing.ATTN_LINEAR, tracing.ATTN_DELTA),
                   counters=True, validate=validate_delta_heads, unsupported=lambda cfg: UNSUPPORTED)
