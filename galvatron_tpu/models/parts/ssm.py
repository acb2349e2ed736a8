"""Mamba-2, the state-space token mixer (Granite-4.0-H's nine layers in ten, one group of B and C;
Nemotron-H's `M` blocks, `ssm_groups` of them and a gated norm a group)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, _dense_init, _proj_std, no_form
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.linear_attention import causal_conv
from galvatron_tpu.ops.norms import rms_norm
from galvatron_tpu.ops.ssd import ssd_scan
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes


def _validate(cfg: TransformerConfig) -> None:
    if (min(cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_dim, cfg.ssm_conv_kernel, cfg.ssm_groups) < 1
            or cfg.ssm_num_heads % cfg.ssm_groups or cfg.mtp_layers):
        raise ValueError(
            "state-space layers want ssm_num_heads, ssm_head_dim, ssm_state_dim and a "
            "convolution kernel of 1 or more, ssm_groups that divide the heads and no "
            "multi-token-prediction module; got heads %d x %d in %d groups, state %d, kernel %d"
            % (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state_dim, cfg.ssm_conv_kernel))


# the scan's state runs along the whole sequence, the gated norm over all of
# a layer's channels (a group's, where B and C have groups: no layout splits them yet either)
UNSUPPORTED = no_form(
    "state-space layers",
    serve="no convolution window or scan state of a state-space layer (serve/kv_cache.py holds keys and values)",
    autotune="a state-space layer as softmax attention",
    pp="stack one kind of layer a stage, not state-space layers among attention layers",
    tp="state-space layers (the scan's state runs along the whole sequence and the gated norm over all of a "
       "layer's channels)",
    quant="a state-space layer's counter",
)


def _init_ssm(ks, cfg: TransformerConfig) -> Params:
    """The Mamba-2 mixer's leaves, under `ssm` (HF `GraniteMoeHybridMambaLayer`:
    in_proj, conv1d, dt_bias, A_log, D, norm, out_proj). `win`'s columns lie
    [z | x | B | C | dt] as HF's, B and C `ssm_groups` x d_state columns each, group by group. Initialised as the Mamba-2 reference does: A
    = exp(A_log) ~ U(1, 16), dt = softplus(dt_bias) log-uniform in [0.001,
    0.1], D = 1, so that exp(dt A) spans 0.2 to 0.999 a token and state
    crosses chunks; the taps and their bias U(-1, 1) / sqrt(taps), PyTorch's
    default for a convolution of that fan-in."""
    h, taps, nh = cfg.hidden_size, cfg.ssm_conv_kernel, cfg.ssm_num_heads
    inner = nh * cfg.ssm_head_dim
    conv_dim = inner + 2 * cfg.ssm_groups * cfg.ssm_state_dim
    kgate = jax.random.split(ks[4], 4)
    step = jnp.exp(jax.random.uniform(kgate[2], (nh,), jnp.float32, math.log(1e-3), math.log(0.1)))
    p = {
        "win": {"kernel": _dense_init(ks[0], (h, inner + conv_dim + nh), cfg.init_std, cfg.param_dtype)},
        "conv": {"kernel": jax.random.uniform(kgate[0], (conv_dim, taps), jnp.float32,
                                              -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
                 "bias": jax.random.uniform(kgate[3], (conv_dim,), jnp.float32,
                                            -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5},
        "A_log": jnp.log(jax.random.uniform(kgate[1], (nh,), jnp.float32, 1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "D": jnp.ones((nh,), jnp.float32),
        "norm": {"scale": jnp.ones((inner,), cfg.param_dtype)},
        "wout": {"kernel": _dense_init(ks[1], (inner, h), _proj_std(cfg), cfg.param_dtype)},
    }
    return {"ssm": p}


def ssm_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, attn_sharding=None, **_):
    """Mamba-2 on normed activations (B, S, H) (HF `GraniteMoeHybridMambaLayer`;
    arXiv:2405.21060), p the layer's tree:

        [z | xBC | dt] = y Win
        xBC = silu(conv(xBC) + b)                     causal, depthwise, a channel
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)   float32
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t   ops/ssd.py
        out = (RMSNorm(y silu(z); w)) Wout            the gate BEFORE the norm, the
                                                      norm over ALL the mixer's channels

    B and C are one group's: every head reads the same. With `ssm_groups` = G > 1
    (Nemotron-H) B and C are (G, d_state) a token, head n reads group n // (heads / G), and
    the norm runs over each group's inner / G channels apart (HF `MambaRMSNormGated`
    with `group_size`); G = 1 traces what it always did. -> out, None, and the
    layer's counter: the largest magnitude of any head's state at any chunk's
    end. Scopes: the scan under `gt.attn.ssd`, all else under `gt.attn.ssm`.
    No position enters: the order is the recurrence's. `attn_sharding` tells
    the scan where its operands lie: on TPUs it runs as Pallas kernels
    (`ssd_scan`). The convolution and the gated norm are XLA's (`causal_conv`;
    the Pallas passes of ops/linear_attention.py norm a head's 128 lanes and
    know no bias)."""
    p, dtype = p["ssm"], cfg.compute_dtype
    nh, hd, groups = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_groups
    ds = groups * cfg.ssm_state_dim  # B's columns, and C's
    inner = nh * hd
    b, s, _ = y.shape
    with jax.named_scope(tracing.ATTN_SSM):
        zxbcdt = _dense(y, p["win"], dtype)
        z = zxbcdt[..., :inner]
        xbc = causal_conv(zxbcdt[..., inner:2 * inner + 2 * ds], p["conv"]["kernel"])
        xbc = jax.nn.silu((xbc.astype(jnp.float32) + p["conv"]["bias"].astype(jnp.float32)).astype(dtype))
        dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * ds:].astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope(tracing.ATTN_SSD):
        x, bm, cm = xbc[..., :inner].reshape(b, s, nh, hd), xbc[..., inner:inner + ds], xbc[..., inner + ds:]
        if groups > 1:
            bm, cm = (t.reshape(b, s, groups, cfg.ssm_state_dim) for t in (bm, cm))
        o, _, peak = ssd_scan(x, dt, a, bm, cm, p["D"], sharding=attn_sharding)
    with jax.named_scope(tracing.ATTN_SSM):
        o = o.reshape(b, s, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        if groups > 1:  # a group's channels a norm
            o = rms_norm(o.reshape(b, s, groups, inner // groups), p["norm"]["scale"].reshape(groups, -1),
                         cfg.layernorm_eps).reshape(b, s, inner).astype(dtype)
        else:
            o = rms_norm(o, p["norm"]["scale"], cfg.layernorm_eps).astype(dtype)
        out = _dense(o, p["wout"], dtype)
    return out, None, {"ssm_state_abs_max": peak}


def _ssm_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp, sp, cp are refused, GLS018): ZeRO-3 splits the
    # projections' input dim; the small leaves are whole everywhere
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    return {"ssm": {
        "win": {"kernel": P(z3, None)}, "conv": {"kernel": P(None, None), "bias": r1},
        "A_log": r1, "dt_bias": r1, "D": r1,
        "norm": {"scale": r1}, "wout": {"kernel": P(z3, None)},
    }}


SSM = LayerPart(_init_ssm, ssm_mixer, _ssm_specs, (tracing.ATTN_SSM, tracing.ATTN_SSD), counters=True,
                validate=_validate, unsupported=lambda cfg: UNSUPPORTED)
