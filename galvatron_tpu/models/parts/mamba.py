"""Mamba-1, the selective-scan token mixer (Phi-4-mini-flash's nine layers in
thirty-two), and the gated memory unit that reads ONE such layer's scan output
in place of a scan of its own (SambaY's cross-decoder, arXiv:2507.06607)."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, _dense_init, _proj_std, no_form
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.linear_attention import causal_conv
from galvatron_tpu.ops.selective_scan import selective_scan
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes

MEMORY = "memory"  # what a Mamba-1 layer publishes: its scan's output with the D skip, BEFORE the gate


def d_inner(cfg: TransformerConfig) -> int:
    return cfg.mamba_expand * cfg.hidden_size


def _validate(cfg: TransformerConfig) -> None:
    if (min(cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_expand, cfg.mamba_dt_rank) < 1
            or cfg.routed or cfg.mtp_layers):
        raise ValueError(
            "Mamba-1 layers and gated memory units want mamba_d_state, mamba_d_conv, mamba_expand and "
            "mamba_dt_rank of 1 or more, a dense MLP half and no multi-token-prediction module; got state %d, "
            "taps %d, expand %d, dt_rank %d"
            % (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_expand, cfg.mamba_dt_rank))


# the scan's state runs along the whole sequence and the memory's channels are
# ONE layer's; a published tensor would have to cross pipeline stages, which
# neither engine carries; the decode engine has no convolution window, scan
# state or memory that one layer writes and seven read; the cost models no row
UNSUPPORTED = no_form(
    "Mamba-1 layers",
    serve="no convolution window or scan state of a Mamba-1 layer (serve/kv_cache.py holds keys and values)",
    autotune="a Mamba-1 layer as softmax attention",
    pp="carry no tensor a layer publishes for later layers across stages (a Mamba-1 layer's memory)",
    tp="Mamba-1 layers (the scan's state runs along the whole sequence and the memory's channels are one layer's)",
    quant="a Mamba-1 layer's counter",
)
GMU_UNSUPPORTED = no_form(
    "gated memory units",
    serve="no memory that one layer writes and later layers read (serve/kv_cache.py holds a layer's own keys "
          "and values)",
    autotune="a gated memory unit as softmax attention",
    pp="carry no tensor a layer publishes for later layers across stages (the memory a gated memory unit reads)",
    tp="gated memory units (the memory's channels are one Mamba-1 layer's, whole on its chip)",
    quant="a layer that reads another layer's tensor",
)


def _init_mamba(ks, cfg: TransformerConfig) -> Params:
    """The Mamba-1 mixer's leaves, under `mamba` (HF `Phi4FlashMambaMixer`'s /
    the Mamba reference's names: in_proj, conv1d, x_proj, dt_proj, A_log, D,
    out_proj). `win`'s columns lie [x | z]; `wx`'s [dt (R) | B (N) | C (N)].
    Initialised as the Mamba reference does: A = 1 .. N the same for every
    channel (S4D-real), D = 1, dt = softplus(dt_bias) log-uniform in [0.001,
    0.1], W_dt U(-R^-1/2, R^-1/2); the taps and their bias U(-1, 1) /
    sqrt(taps), PyTorch's default for a convolution of that fan-in."""
    h, taps, n, r = cfg.hidden_size, cfg.mamba_d_conv, cfg.mamba_d_state, cfg.mamba_dt_rank
    inner = d_inner(cfg)
    kgate = jax.random.split(ks[4], 5)
    step = jnp.exp(jax.random.uniform(kgate[2], (inner,), jnp.float32, math.log(1e-3), math.log(0.1)))
    p = {
        "win": {"kernel": _dense_init(ks[0], (h, 2 * inner), cfg.init_std, cfg.param_dtype)},
        "conv": {"kernel": jax.random.uniform(kgate[0], (inner, taps), jnp.float32,
                                              -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
                 "bias": jax.random.uniform(kgate[3], (inner,), jnp.float32,
                                            -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5},
        "wx": {"kernel": _dense_init(kgate[1], (inner, r + 2 * n), cfg.init_std, cfg.param_dtype)},
        "wdt": {"kernel": jax.random.uniform(kgate[4], (r, inner), jnp.float32,
                                             -1.0, 1.0).astype(cfg.param_dtype) / r ** 0.5,
                "bias": step + jnp.log(-jnp.expm1(-step))},  # softplus^-1, float32
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (inner, n)),
        "D": jnp.ones((inner,), jnp.float32),
        "wout": {"kernel": _dense_init(ks[1], (inner, h), _proj_std(cfg), cfg.param_dtype)},
    }
    return {"mamba": p}


def mamba_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *, publish=(),
                attn_sharding=None, **_):
    """Mamba-1 on normed activations (B, S, H) (arXiv:2312.00752; HF
    `Phi4FlashMambaMixer`), p the layer's tree:

        [x | z] = y Win;  x = silu(conv(x) + b)           causal, depthwise, a channel
        [dt_r | B | C] = x Wx;  dt = softplus(dt_r Wdt + b_dt);  A = -exp(A_log)   float32
        h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t;  m_t = h_t C_t + D x_t          ops/selective_scan.py
        out = (m silu(z)) Wout

    A is a (channels, N) matrix: the decay differs by channel AND state. -> out,
    None, the layer's counter (the largest magnitude of any state at any chunk's
    end) and, where a later layer reads it (`publish`), `m` as the layer's
    MEMORY: the scan's output with the D skip, before the gate. Scopes: the scan
    under `gt.attn.selscan`, all else under `gt.attn.mamba`. No position enters:
    the order is the recurrence's. `attn_sharding` tells the scan where its
    operands lie: on TPUs it runs as Pallas kernels (`selective_scan`)."""
    p, dtype = p["mamba"], cfg.compute_dtype
    inner, n, r = d_inner(cfg), cfg.mamba_d_state, cfg.mamba_dt_rank
    with jax.named_scope(tracing.ATTN_MAMBA):
        xz = _dense(y, p["win"], dtype)
        x = causal_conv(xz[..., :inner], p["conv"]["kernel"])
        x = jax.nn.silu((x.astype(jnp.float32) + p["conv"]["bias"].astype(jnp.float32)).astype(dtype))
        dbc = _dense(x, p["wx"], dtype)
        dt = jax.nn.softplus(jnp.einsum("bsr,rc->bsc", dbc[..., :r], p["wdt"]["kernel"].astype(dtype),
                                        preferred_element_type=jnp.float32)
                             + p["wdt"]["bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope(tracing.ATTN_SELSCAN):
        m, _, peak = selective_scan(x, dt, a, dbc[..., r:r + n], dbc[..., r + n:], p["D"],
                                    sharding=attn_sharding)
    with jax.named_scope(tracing.ATTN_MAMBA):
        out = _dense(m * jax.nn.silu(xz[..., inner:]), p["wout"], dtype)
    said = {"selscan_state_abs_max": peak}
    return (out, None, said, {MEMORY: m}) if publish else (out, None, said)


def _mamba_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp, sp, cp are refused, GLS018): ZeRO-3 splits the
    # projections' input dim; the small leaves are whole everywhere
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    return {"mamba": {
        "win": {"kernel": P(z3, None)}, "conv": {"kernel": P(None, None), "bias": r1},
        "wx": {"kernel": P(z3, None)}, "wdt": {"kernel": P(None, None), "bias": r1},
        "A_log": P(None, None), "D": r1, "wout": {"kernel": P(z3, None)},
    }}


def _init_gmu(ks, cfg: TransformerConfig) -> Params:
    h, inner = cfg.hidden_size, d_inner(cfg)
    return {"gmu": {"win": {"kernel": _dense_init(ks[0], (h, inner), cfg.init_std, cfg.param_dtype)},
                    "wout": {"kernel": _dense_init(ks[1], (inner, h), _proj_std(cfg), cfg.param_dtype)}}}


def gmu_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *, shared, **_):
    """A gated memory unit on normed activations (B, S, H): `out = (m silu(y
    W1)) W2`, m the MEMORY the last Mamba-1 layer before it published (`shared`;
    the scan's output with the D skip, before that layer's gate). No scan, no
    convolution, no state of its own. Under `gt.attn.gmu`."""
    p, dtype = p["gmu"], cfg.compute_dtype
    with jax.named_scope(tracing.ATTN_GMU):
        return _dense(shared[MEMORY].astype(dtype) * jax.nn.silu(_dense(y, p["win"], dtype)), p["wout"], dtype), None, None


def _gmu_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    z3 = S._ax(axes.dp) if axes.zero3 else None
    return {"gmu": {"win": {"kernel": P(z3, None)}, "wout": {"kernel": P(z3, None)}}}


MAMBA = LayerPart(_init_mamba, mamba_mixer, _mamba_specs, (tracing.ATTN_MAMBA, tracing.ATTN_SELSCAN), counters=True,
                  validate=_validate, unsupported=lambda cfg: UNSUPPORTED, publishes=(MEMORY,))
GMU = LayerPart(_init_gmu, gmu_mixer, _gmu_specs, (tracing.ATTN_GMU,), validate=_validate,
                unsupported=lambda cfg: GMU_UNSUPPORTED, reads=(MEMORY,))
