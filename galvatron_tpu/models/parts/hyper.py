"""Manifold-constrained hyper-connections (`TransformerConfig.hc_mult` = n > 1; Xing4.0's residual path, "mHC",
arXiv:2512.24880, on "Hyper-Connections", arXiv:2409.19606): a token's state is n residual streams of
`hidden_size`, and each HALF of a layer reads ONE vector out of them and writes its output back into all:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)              float32, no learned scale
    [p | q | r] = x~ Phi                                     Phi (n hidden, n + n + n^2)
    H_pre = sigmoid(a_pre p + b_pre),  H_post = 2 sigmoid(a_post q + b_post)
    H_res = sinkhorn(exp(clip(a_res mat(r) + b_res)))        doubly stochastic to the iteration's accuracy
    u = sum_j H_pre[j] X[j];  o = Half(norm(u));  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] o

No layer part: it wraps BOTH halves of every layer (`models/base.layer_forward` asks it where a plain stack
adds `residual + o`), so neither table of `models/parts` holds it and `unsupported_reason` asks it beside the
layers' parts, as it asks `parts/loop.py`. The n streams are ONE (..., n x hidden) array, stream j the lanes
`j hidden : (j + 1) hidden`: a lane-aligned slice wherever hidden is whole 128-lane tiles (a (..., n, hidden)
bf16 array would put n = 4 rows in a 16-row tile and move four times the bytes). The streams are held in
the compute dtype, as a plain stack's one stream is; the coefficients and the mixes' sums are float32.
All of it is XLA's (`forms.HYPER`: "xla")."""

from __future__ import annotations

import math
from typing import Dict, List, Mapping, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import Params, _dense_init
from galvatron_tpu.obs import forms, telemetry, tracing

LEAVES = {"ln1": "hc1", "ln2": "hc2"}  # a half's leaves in the layer's tree, by its norm's name
COL_ERR, GAIN = telemetry.HYPER_STEP_FIELDS
COUNTERS = {COL_ERR: "max", GAIN: "mean"}  # how the stack folds them (parts/__init__.COUNTERS)
SCOPES = (tracing.HC, tracing.HC_COEF, tracing.HC_SINKHORN, tracing.HC_MIX)

INIT_RES_OFF_DIAGONAL = -8.0  # (assumed: H_res starts near the identity)

# what carries ONE hidden a token, lays the activation out by its last dim, or prices a layer's kept
# activations once has no form of n streams
UNSUPPORTED = {
    "serve": "no streams for a decoded token (hc_mult > 1: n residual streams a token, mixed by every half)",
    "autotune": "hyper-connected layers (hc_mult > 1) as plain ones: a layer's kept activations once, not n times",
    "pp": "exchange ONE hidden a token between stages, not the n residual streams of hyper-connections (hc_mult > 1)",
    "tp": "the n-stream activation of hyper-connections (hc_mult > 1: no spec for the wide array)",
    "vocab_tp": "hyper-connections (hc_mult > 1)",
    "tp_comm": "hyper-connections (hc_mult > 1)",
    "quant": "hyper-connections' mixes (hc_mult > 1)",
    "search": "hyper-connections (hc_mult > 1: activations a layer x n, a pipeline exchange of n x hidden)",
    "profile": "hyper-connections (hc_mult > 1)",
}


def unsupported(cfg) -> Mapping[str, str]:
    """What hyper-connections say to each asker that has no form of them ({} for one residual stream)."""
    return UNSUPPORTED if getattr(cfg, "hc_mult", 1) > 1 else {}


def validate(cfg: TransformerConfig) -> None:
    """Hyper-connections' clause of `TransformerConfig.__post_init__`: what stands beside `hc_mult` > 1. The
    clamp is kept as a list of two floats, as JSON states it."""
    if cfg.hc_res_clamp is not None:
        cfg.hc_res_clamp = [float(v) for v in cfg.hc_res_clamp]
    if cfg.hc_mult < 1 or (cfg.hc_mult == 1 and (cfg.hc_sinkhorn_iters or cfg.hc_res_clamp is not None)):
        raise ValueError("hc_mult=%d, hc_sinkhorn_iters=%d, hc_res_clamp=%r: the Sinkhorn steps and the clamp are "
                         "those of hc_mult > 1 residual streams" % (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_res_clamp))
    if cfg.hc_mult == 1:
        return
    beside = [what for what, has in (
        ("a multi-token-prediction module (mtp_layers > 0: how n streams enter its block is not published; "
         "build the model without it, mtp_layers=0)", cfg.mtp_layers),
        ("a post-norm stack (pre_norm False)", not cfg.pre_norm), ("sandwich norms (post_norm)", cfg.post_norm),
        ("a residual_multiplier", cfg.residual_multiplier != 1.0), ("a looped stack (loop_steps > 1)", cfg.loop_steps > 1),
        ("pred_heads > 1", cfg.pred_heads > 1), ("head_type %r" % cfg.head_type, cfg.head_type != "lm"),
        ("input_type %r" % cfg.input_type, cfg.input_type != "tokens")) if has]
    if beside or cfg.hc_sinkhorn_iters < 1 or cfg.hc_eps <= 0:
        raise ValueError(
            "hc_mult=%d (hyper-connections: n residual streams mixed by a doubly-stochastic matrix a token and a half) "
            "wants hc_sinkhorn_iters >= 1 (got %d), hc_eps > 0 (got %r) and a pre-norm stack of tokens to one lm head; "
            "it has no form beside %s" % (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, ", ".join(beside) or "nothing"))


def init_hyper(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """ONE half's leaves: `phi` (n hidden, n^2 + 2n) ~ N(0, init_std^2), its columns [pre | post | res row by
    row]; `b` (n^2 + 2n): logit(1/n) for the reads, 0 for the writes, 0 on H_res's diagonal and -8 off it; `a`
    (3,): the three learned gates (pre, post, res), `hc_init_gate`. All assumed (no published config carries a
    start): at the default gate, 0.01, H_res lies within 1e-3 of the identity, H_pre at 1/n and H_post at 1, so
    an untrained half is a pre-norm residual half on n equal streams (arXiv:2409.19606's start); at a gate of 1
    `x~ Phi` (spread 0.02 sqrt(n hidden): 2.4 at Xing4.0's widths) comes through whole, and H_pre and H_post are
    each token's own from the first step. float32 like every parameter."""
    n = cfg.hc_mult
    res = jnp.where(jnp.eye(n, dtype=bool), 0.0, INIT_RES_OFF_DIAGONAL).reshape(-1)
    b = jnp.concatenate([jnp.full((n,), math.log(1.0 / (n - 1))), jnp.zeros((n,)), res])
    return {"phi": _dense_init(rng, (n * cfg.hidden_size, n * n + 2 * n), cfg.init_std, cfg.param_dtype),
            "b": b.astype(cfg.param_dtype), "a": jnp.full((3,), cfg.hc_init_gate, cfg.param_dtype)}


def init_layer(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """What `init_layer_params` adds to a layer's tree: {} for one stream, else a half's leaves each."""
    if cfg.hc_mult == 1:
        return {}
    return {leaf: init_hyper(jax.random.fold_in(rng, i), cfg) for i, leaf in enumerate(LEAVES.values())}


def hyper_specs() -> Params:
    """Replicated (ZeRO-3 leaves them whole: 0.34 M a half)."""
    return {"phi": P(None, None), "b": P(None), "a": P(None)}


def layer_specs(cfg: TransformerConfig) -> Params:
    return {} if cfg.hc_mult == 1 else {leaf: hyper_specs() for leaf in LEAVES.values()}


class Mix(NamedTuple):
    """A half's coefficients a token, float32, each entry shaped as the streams less their last dim, + (1,):
    `pre[j]`, `post[i]`, `res[i][j]`."""
    pre: List[jax.Array]
    post: List[jax.Array]
    res: List[List[jax.Array]]


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """`iters` Sinkhorn-Knopp steps on positive (n, n, tokens) matrices, the token axis on lanes: columns then
    rows, `M / (1^T M + eps)` then `M / (M 1 + eps)`, so rows sum to 1 (less eps) and columns nearly. Unrolled,
    the sums written out over the n slices: 40 elementwise steps XLA fuses, and whose backward is autodiff's
    (40 arrays of n^2 floats a token inside a layer's recomputation: 10 MB at 4096 tokens)."""
    n = m.shape[0]
    for _ in range(iters):
        m = m / (sum(m[i] for i in range(n))[None] + eps)
        m = m / (sum(m[:, j] for j in range(n))[:, None] + eps)
    return m


def streams_dot(flat: jax.Array, phi: jax.Array) -> jax.Array:
    """`x Phi`, (tokens, n^2 + 2n) float32, from the streams as they lie (tokens, n hidden): float32 operands at
    `highest` precision (on a TPU six bf16 passes of the MXU). ONE bf16 pass on Phi in three bf16 pieces, which
    is as exact for bf16 streams, was tried on the chip and dropped: it took the same time a step (298.25
    against 298.29 ms) and the same memory, and the TPU compiler takes a float32 -> bfloat16 -> float32 pair out
    (excess precision), so pieces made by `.astype` collapse into Phi's rounding alone and every H entry read
    8e-5 off float64 (PERF.md section 6, PR 66)."""
    return jnp.dot(flat.astype(jnp.float32), phi.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)


def coefficients(p: Params, x: jax.Array, cfg: TransformerConfig) -> Tuple[Mix, jax.Array]:
    """`x` (..., n hidden), the streams -> (the half's `Mix`, `hc_res_col_err`: the worst token's worst
    |column sum of H_res - 1|, no gradient). float32: the mean square over all n hidden dims, `x~ Phi` as
    (x Phi) / rms at `highest` precision (`streams_dot`), the sigmoids, and the Sinkhorn steps on (n^2, tokens)
    with the tokens on lanes."""
    n, lead = cfg.hc_mult, x.shape[:-1]
    f32 = jnp.float32
    with jax.named_scope(tracing.HC_COEF):
        flat = x.reshape(-1, x.shape[-1])
        inv_rms = jax.lax.rsqrt(jnp.mean(jnp.square(flat.astype(f32)), axis=-1) + cfg.hc_eps)
        by_token = (streams_dot(flat, p["phi"]) * inv_rms[:, None]).T  # (n^2 + 2n, tokens)
        a, b = p["a"].astype(f32), p["b"].astype(f32)[:, None]
        pre = jax.nn.sigmoid(a[0] * by_token[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * by_token[n:2 * n] + b[n:2 * n])
        res = a[2] * by_token[2 * n:] + b[2 * n:]
        if cfg.hc_res_clamp is not None:
            res = jnp.clip(res, *cfg.hc_res_clamp)
    with jax.named_scope(tracing.HC_SINKHORN):
        res = sinkhorn(jnp.exp(res).reshape(n, n, -1), cfg.hc_sinkhorn_iters, cfg.hc_eps)
        col_err = jax.lax.stop_gradient(jnp.max(jnp.abs(sum(res[i] for i in range(n)) - 1.0)))

    def a_token(row):  # (tokens,) -> the streams' leading dims + (1,): a factor a token, broadcast over lanes
        return row.reshape(lead + (1,))

    forms.took(forms.HYPER, "xla")
    return Mix([a_token(pre[j]) for j in range(n)], [a_token(post[i]) for i in range(n)],
               [[a_token(res[i, j]) for j in range(n)] for i in range(n)]), col_err


def _streams(x: jax.Array, n: int) -> List[jax.Array]:
    c = x.shape[-1] // n
    return [x[..., j * c:(j + 1) * c].astype(jnp.float32) for j in range(n)]


def read(mix: Mix, x: jax.Array) -> jax.Array:
    """What a half READS: `sum_j H_pre[j] X[j]`, summed in float32 -> (..., hidden) in the streams' dtype."""
    with jax.named_scope(tracing.HC_MIX):
        return sum(h * s for h, s in zip(mix.pre, _streams(x, len(mix.pre)))).astype(x.dtype)


def write(mix: Mix, x: jax.Array, o: jax.Array) -> jax.Array:
    """What a half WRITES: `X'[i] = sum_j H_res[i, j] X[j] + H_post[i] o`, summed in float32 -> the streams."""
    with jax.named_scope(tracing.HC_MIX):
        streams, o32 = _streams(x, len(mix.pre)), o.astype(jnp.float32)
        return jnp.concatenate([(sum(h * s for h, s in zip(row, streams)) + post * o32).astype(x.dtype)
                                for row, post in zip(mix.res, mix.post)], axis=-1)


def widen(x: jax.Array, n: int) -> jax.Array:
    """The embedding's rows as the n streams' start: n copies side by side."""
    with jax.named_scope(tracing.HC_MIX):
        return jnp.concatenate([x] * n, axis=-1)


def contract(x: jax.Array, n: int) -> jax.Array:
    """The n streams' SUM, what the final norm reads -> (..., hidden) in the streams' dtype."""
    with jax.named_scope(tracing.HC_MIX):
        return sum(_streams(x, n)).astype(x.dtype)


def stream_gain(embedded: jax.Array, last: jax.Array, n: int) -> jax.Array:
    """`hc_stream_gain`: RMS of the streams' sum after the last layer (`last`) over the RMS of their sum before
    the first (n x the embedding's rows), float32, no gradient: the signal's gain through the stack's mixes."""
    def rms(t):
        return jnp.sqrt(jnp.mean(jnp.square(t.astype(jnp.float32))))

    return jax.lax.stop_gradient(rms(last) / jnp.maximum(n * rms(embedded), jnp.finfo(jnp.float32).tiny))


def counters(col_errs: List[jax.Array]) -> Dict[str, jax.Array]:
    """A layer's entry of its auxiliary terms from its halves' column errors."""
    return {COL_ERR: jnp.max(jnp.stack(col_errs))}
