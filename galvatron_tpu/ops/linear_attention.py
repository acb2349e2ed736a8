"""The gated delta rule in its chunked form, and the short causal convolution
that comes before it: the core of a gated-DeltaNet linear-attention layer
(Gated Delta Networks, arXiv:2412.06464; HF `modeling_qwen3_next.py`
`torch_chunk_gated_delta_rule`).

A head carries a state `S` (d_k x d_v) along the sequence, `S_0 = 0`:

    S' = exp(g_t) S_{t-1}          the gate forgets
    u_t = beta_t (v_t - S'^T k_t)  what the state does not yet say of k_t
    S_t = S' + k_t u_t^T           the delta rule writes it
    o_t = S_t^T q_t

Token by token that is `tokens` dependent steps of rank-one updates. **The
chunked form** cuts the sequence into chunks of `CHUNK` tokens. With `G` the
running sum of `g` inside a chunk and `D_tj = exp(G_t - G_j)` (j <= t), the
chunk's `u` solve a unit lower triangular system in the state `S_0` the chunk
starts from,

    (I + A) U = beta V - (beta e^G K) S_0,   A_tj = beta_t D_tj (k_t . k_j), j < t

so with `T = (I + A)^-1`, `U0 = T (beta V)` and `W = T (beta e^G K)`, `U = U0 -
W S_0`, and a whole chunk is an AFFINE map of the state:

    S_C = (e^{G_C} I - Kd^T W) S_0 + Kd^T U0      Kd_j = e^{G_C - G_j} k_j
    O   = (Q e^G - P W) S_0 + P U0                P_tj = D_tj (q_t . k_j), j <= t

Everything but `S_0` is made for all chunks at once in batched matmuls; what
runs along the sequence is ONE (d_k, d_k) x (d_k, d_v) matmul a chunk and a
head (`_carry`), and the outputs are read off the chunks' starting states
afterwards, again all at once. No array of (tokens, heads, d_k, d_v) exists:
the states kept are the chunks' (tokens / CHUNK of them).

Float32: `g`'s running sums and every exponential of them, `A`, the inverse
`T` and its products, the chunk's map of the state (`Kd^T W`, `Kd^T U0`:
whatever error they have is carried to the sequence's end), the state and the
matmul that carries it. The products on the way to the OUTPUT (`Q K^T`, `P W`,
`(Q e^G - P W) S_0`, `P U0`) run on operands of the dtype q, k, v came in
(bf16 in a bf16 model), accumulated in float32: their error stays in the
chunk it was made in.

`T` is made by forward substitution on 16 x 16 diagonal blocks, vectorised
over a head's chunks with that batch in the minor dimension (elementwise on a
TPU's lanes), and the blocks are merged by matmuls
(`[[a, 0], [c, b]]^-1 = [[a^-1, 0], [-b^-1 c a^-1, b^-1]]`). The product form
`(I - A)(I + A^2)(I + A^4) ...` is fewer operations and is NOT used: the
powers of `A` grow as binomials where neighbouring keys are alike (a run of one
repeated token), and cancel in float32 to nothing.

**The backward** is autodiff's, through the scan over the chunks and the
batched matmuls around it, with a head's chunk matrices recomputed from q, k,
v, g, beta and the kept chunk-start states (`gated_delta_rule`). A
written rule for the carried recurrence (the reverse scan `dS_n = M_n^T
dS_{n+1} + ...` with the `M_n`'s gradients formed in one batched matmul
afterwards) gave the same gradients to the bit and ran 8 % SLOWER on the chip
at 8 heads at a time, 46.0 against 42.4 ms a layer forward and backward, and
within 3 % at 16 and 32 (PERF.md, PR 35), so it is not kept.

Sequences are whole rows of the batch: neither the convolution nor the state
is cut at a document boundary inside a packed row.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

CHUNK = 64
STARTS = "gdn_chunk_starts"  # the residual a head's backward keeps
_BASE = 16  # the diagonal blocks inverted by forward substitution
_F32 = jnp.float32


def causal_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """A causal depthwise convolution over the sequence: x (B, S, C), taps
    (C, K) -> (B, S, C) with `y_t = sum_j taps[:, j] x_{t - (K - 1) + j}`, the
    positions before the sequence's start zero (PyTorch's `Conv1d(groups=C,
    padding=K - 1)` cut to the first S outputs). K shifted multiply-adds in
    float32, which a TPU fuses into one pass; no bias."""
    k, s = taps.shape[-1], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    taps = taps.astype(_F32)
    out = None
    for j in range(k):
        term = padded[:, j:j + s].astype(_F32) * taps[:, j]
        out = term if out is None else out + term
    return out.astype(x.dtype)


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """Batched matmul accumulated in float32; float32 operands are multiplied
    as float32 (a TPU's default would round them to bf16)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST, preferred_element_type=_F32)


def _t(x: jax.Array) -> jax.Array:
    return jnp.swapaxes(x, -1, -2)


def unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(..., n, n) float32, strictly lower triangular -> (I + a)^-1."""
    n = a.shape[-1]
    if n > _BASE:
        h = n // 2
        t11, t22 = unit_lower_inverse(jnp.stack([a[..., :h, :h], a[..., h:, h:]]))
        t21 = -_mm(_mm(t22, a[..., h:, :h]), t11)
        top = jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1)
        return jnp.concatenate([top, jnp.concatenate([t21, t22], axis=-1)], axis=-2)
    # row i of the inverse is e_i - sum_{j < i} a[i, j] x row j: the batch in
    # the minor dimension, so every step is elementwise over it. The matmuls
    # by the identity ARE the transposes: a transpose the compiler makes a
    # change of layout in name only, and every slice below would then read one
    # element a tile; a matmul's result lies minor dimension last
    lead = a.shape[:-2]
    eye = jnp.eye(n * n, dtype=a.dtype)
    cols = _mm(eye, _t(a.reshape((-1, n * n)))).reshape((n, n, -1))  # (n, n, batch)
    unit = jnp.eye(n, dtype=a.dtype)
    rows = []
    for i in range(n):
        row = jnp.broadcast_to(unit[i][:, None], cols.shape[1:])
        for j in range(i):
            row = row - cols[i, j] * rows[j]
        rows.append(row)
    inverse = _mm(_t(jnp.stack(rows).reshape((n * n, -1))), eye)  # (batch, n x n)
    return inverse.reshape(lead + (n, n))


def _carry(m: jax.Array, b: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """S_{n+1} = M_n S_n + B_n from S_0 = 0, n over the leading axis: -> the
    states the chunks START from, (N, ...), and the last chunk's end state."""
    def step(state, mb):
        return _mm(mb[0], state) + mb[1], state

    last, starts = jax.lax.scan(step, jnp.zeros_like(b[0]), (m, b))
    return starts, last


def _head_core(q, k, v, g, beta):
    """The rule for one value head, chunked: q, k (N, B, C, d_k), v (N, B, C,
    d_v), g, beta (N, B, C) float32 -> o (N, B, C, d_v) in v's dtype and the
    final states (B, d_k, d_v)."""
    chunk, dt = v.shape[-2], v.dtype
    total = jnp.cumsum(g, axis=-1)  # G, (N, B, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # masked BEFORE the exponential: above the diagonal the difference is
    # positive, and grows with the chunk
    decay = jnp.exp(jnp.where(lower, total[..., :, None] - total[..., None, :], -jnp.inf))
    a = jnp.where(lower & ~jnp.eye(chunk, dtype=bool), beta[..., None] * decay * _mm(k, _t(k)), 0.0)
    inverse = unit_lower_inverse(a)
    from_start = jnp.exp(total)[..., None]  # e^G
    to_end = jnp.exp(total[..., -1:] - total)[..., None]  # e^{G_C - G}
    # the chunk's affine map of the state, float32 operands: what is carried
    k32 = k.astype(_F32)
    u0 = _mm(inverse, v.astype(_F32) * beta[..., None])
    w = _mm(inverse, k32 * (beta[..., None] * from_start))
    kd_t = _t(k32 * to_end)
    eye = jnp.eye(k.shape[-1], dtype=_F32)
    starts, last = _carry(jnp.exp(total[..., -1])[..., None, None] * eye - _mm(kd_t, w),
                          _mm(kd_t, u0))
    starts = checkpoint_name(starts, STARTS)
    # the outputs, read off the chunks' starting states: operands in v's dtype
    p, w, u0 = ((decay * _mm(q, _t(k))).astype(dt), w.astype(dt), u0.astype(dt))
    q_hat = (q.astype(_F32) * from_start - _mm(p, w)).astype(dt)
    return (_mm(q_hat, starts.astype(dt)) + _mm(p, u0)).astype(dt), last


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                     *, chunk: int = CHUNK) -> Tuple[jax.Array, jax.Array]:
    """q, k (B, S, Hk, d_k), L2-normalised and q scaled; v (B, S, Hv, d_v); g
    (B, S, Hv) the log of the gate, <= 0; beta (B, S, Hv) -> o (B, S, Hv, d_v)
    in v's dtype, and the final states (B, Hv, d_k, d_v) float32. Each key
    head serves Hv / Hk consecutive value heads.

    The value heads are worked ONE AFTER THE OTHER (`lax.map`), and a head's
    backward recomputes its chunks' matrices from q, k, v, g, beta and the
    chunks' starting states, which alone are kept (a sequence's worth of
    float32 (d_k, d_v) a chunk). The matrices of all 32 heads at once are two
    gigabytes at 8192 tokens; a head's fit the chip's fast memory, and on the
    chip a layer's forward and backward take 26.9 ms a head at a time against
    29.9, 38.3, 42.4 and 47.4 ms at 2, 4, 8 and 32 heads at a time (PERF.md,
    PR 35)."""
    b, s, hv, dv = v.shape
    if s % chunk:
        raise ValueError("gated_delta_rule: a sequence of %d tokens is no multiple of the "
                         "chunk of %d" % (s, chunk))

    def chunked(x):  # (B, S, H, ...) -> (H, N, B, C, ...)
        x = x.reshape((b, s // chunk, chunk) + x.shape[2:])
        return x.transpose((3, 1, 0, 2) + tuple(range(4, x.ndim)))

    q, k = (chunked(jnp.repeat(x, hv // x.shape[2], axis=2)) for x in (q, k))
    core = jax.checkpoint(_head_core, policy=jax.checkpoint_policies.save_only_these_names(STARTS))
    o, last = jax.lax.map(lambda xs: core(*xs),
                          (q, k, chunked(v), chunked(g.astype(_F32)), chunked(beta.astype(_F32))))
    return o.transpose(2, 1, 3, 0, 4).reshape(b, s, hv, dv), jnp.moveaxis(last, 0, 1)
