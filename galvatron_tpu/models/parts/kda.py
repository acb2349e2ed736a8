"""Kimi Delta Attention, the delta rule whose gate is a vector a head (Kimi-Linear's three layers in four)."""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, _dense_init, _proj_std, _unit, no_form
from galvatron_tpu.models.parts.linear import validate_delta_heads
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.kernels import KernelSharding
from galvatron_tpu.ops.linear_attention import Heads, causal_conv, kda_kernel_mixer, kda_layout, kda_rule, mixer_form
from galvatron_tpu.ops.norms import rms_norm
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes

# the per-channel delta rule is a recurrence as the scalar one is
UNSUPPORTED = no_form(
    "Kimi-Delta-Attention layers",
    serve="no recurrent state of a Kimi-Delta-Attention layer, d_k rows a head that forget separately "
          "(serve/kv_cache.py holds keys and values)",
    autotune="a Kimi-Delta-Attention layer as softmax attention",
    pp="stack one kind of layer a stage, not Kimi-Delta-Attention layers among attention layers",
    tp="Kimi-Delta-Attention layers (the per-channel delta rule's state runs along the whole sequence of "
       "all a layer's heads)",
    quant="a Kimi-Delta-Attention layer's counters",
)


def _init_kda(ks, cfg: TransformerConfig) -> Params:
    """The Kimi-Delta-Attention mixer's leaves, under `kda` (HF
    `KimiDeltaAttention`: q_proj, k_proj, v_proj, their three conv1d,
    f_a_proj / f_b_proj, b_proj, A_log, dt_bias, g_a_proj / g_b_proj, o_norm,
    o_proj). The three projections are ONE kernel `wqkv` whose columns lie
    [q | k | v], heads in order within each, and the three convolutions one
    `conv` over those columns (on random weights, HF's three of each side by
    side); the gate's and the output gate's low-rank pairs `wf_a`, `wf_b` and
    `wg_a`, `wg_b` of rank d_v, no bias. The gate starts as the linear
    mixer's does, `A_log` a head and `dt_bias` a head AND channel: exp(g)
    spans 0.2 to 1 a token, so that state crosses chunks."""
    h, taps, nh = cfg.hidden_size, cfg.linear_conv_kernel, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = nh * dk, nh * dv
    kin = jax.random.split(ks[0], 6)
    kgate = jax.random.split(ks[4], 3)
    step = jnp.exp(jax.random.uniform(kgate[2], (key_dim,), jnp.float32, math.log(1e-3), math.log(0.1)))
    dense = lambda key, shape: {"kernel": _dense_init(key, shape, cfg.init_std, cfg.param_dtype)}  # noqa: E731
    return {"kda": {
        "wqkv": dense(kin[0], (h, 2 * key_dim + value_dim)),
        "wf_a": dense(kin[1], (h, dv)), "wf_b": dense(kin[2], (dv, key_dim)),
        "wg_a": dense(kin[3], (h, dv)), "wg_b": dense(kin[4], (dv, value_dim)),
        "wb": dense(kin[5], (h, nh)),
        "conv": jax.random.uniform(kgate[0], (2 * key_dim + value_dim, taps), jnp.float32,
                                   -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
        "A_log": jnp.log(jax.random.uniform(kgate[1], (nh,), jnp.float32, 1e-6, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "norm": {"scale": jnp.ones((dv,), cfg.param_dtype)},
        "wout": {"kernel": _dense_init(ks[1], (value_dim, h), _proj_std(cfg), cfg.param_dtype)},
    }}


def kda_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
              attn_sharding: Optional[KernelSharding] = None, **_):
    """Kimi Delta Attention on normed activations (B, S, H) (HF
    `KimiDeltaAttention`; arXiv:2510.26692), p the layer's tree:

        [q, k, v] = silu(conv(y Wqkv))                causal, depthwise, a channel
        q, k L2-normalised a head, q / sqrt(d_k)
        g = -exp(A_log) softplus((y Wfa) Wfb + dt_bias)   (heads, d_k) a token, float32, <= 0
        beta = sigmoid(y Wb)
        o = kda_rule(q, k, v, g, beta)                ops/linear_attention.py
        out = (RMSNorm(o; w) sigmoid((y Wga) Wgb)) Wout   a head; the norm BEFORE the gate

    The delta rule whose gate is a vector over the key's channels, each row of
    a head's (d_k, d_v) state forgetting at its own rate. -> out, None, and
    the linear mixer's counters: the mean gate `exp(g)` and the largest
    magnitude in any head's final state. Scopes: the core under
    `gt.attn.kda_rule`, all else under `gt.attn.kda_mixer`. No position enters.
    `attn_sharding` tells the kernels where their operands lie. On TPUs the
    matmuls alone are XLA's: the core runs as two Pallas kernels (`kda_fwd`,
    `kda_bwd`) and what lies between the projections and the core as
    lane-aligned Pallas passes over the projections' (B, S, channels) results
    (`conv_norm_*`, `kda_gate_*`, `gated_norm_*`; `kda_kernel_mixer`: one
    rule with the core), no (tokens, heads, 128) view of an activation
    anywhere. The arithmetic written out below is the definition: what the
    CPU runs, and the passes' oracle."""
    p, dtype = p["kda"], cfg.compute_dtype
    nh, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim = nh * dk
    b, s, _ = y.shape

    with jax.named_scope(tracing.ATTN_KDA):
        qkv = _dense(y, p["wqkv"], dtype)
        f = _dense(_dense(y, p["wf_a"], dtype), p["wf_b"], dtype)
        beta = jax.nn.sigmoid(_dense(y, p["wb"], dtype).astype(jnp.float32))
        gate = _dense(_dense(y, p["wg_a"], dtype), p["wg_b"], dtype)
    layout = kda_layout(Heads(nh, dk, nh, dv))
    if mixer_form(qkv, p["conv"], layout, sharding=attn_sharding) == "pallas":
        o, state, decay = kda_kernel_mixer(qkv, p["conv"], p["norm"]["scale"], f, p["dt_bias"], p["A_log"], gate,
                                           beta, layout, eps=cfg.layernorm_eps, sharding=attn_sharding)
    else:
        with jax.named_scope(tracing.ATTN_KDA):
            qkv = jax.nn.silu(causal_conv(qkv, p["conv"]))
            q = (_unit(qkv[..., :key_dim].reshape(b, s, nh, dk)) * dk ** -0.5).astype(dtype)
            k = _unit(qkv[..., key_dim:2 * key_dim].reshape(b, s, nh, dk)).astype(dtype)
            v = qkv[..., 2 * key_dim:].reshape(b, s, nh, dv)
            g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
                f.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)).reshape(b, s, nh, dk)
        with jax.named_scope(tracing.ATTN_KDA_RULE):
            o, state = kda_rule(q, k, v, g, beta, sharding=attn_sharding)
        with jax.named_scope(tracing.ATTN_KDA):
            o = rms_norm(o.astype(jnp.float32), p["norm"]["scale"], cfg.layernorm_eps)
            o = (o * jax.nn.sigmoid(gate.reshape(b, s, nh, dv).astype(jnp.float32))).astype(dtype)
            o, decay = o.reshape(b, s, nh * dv), jnp.exp(g)
    with jax.named_scope(tracing.ATTN_KDA):
        out = _dense(o, p["wout"], dtype)
        stats = {"decay_mean": jnp.mean(decay), "state_abs_max": jnp.max(jnp.abs(state))}
    return out, None, stats


def _kda_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves, as the linear mixer's
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    wide = {"kernel": P(z3, None)}
    return {"kda": {
        "wqkv": wide, "wf_a": wide, "wf_b": {"kernel": P(None, None)}, "wg_a": wide,
        "wg_b": {"kernel": P(None, None)}, "wb": wide,
        "conv": P(None, None), "A_log": r1, "dt_bias": r1, "norm": {"scale": r1}, "wout": wide,
    }}


KDA = LayerPart(_init_kda, kda_mixer, _kda_specs, (tracing.ATTN_KDA, tracing.ATTN_KDA_RULE), counters=True,
                validate=partial(validate_delta_heads, kda=True), unsupported=lambda cfg: UNSUPPORTED)
