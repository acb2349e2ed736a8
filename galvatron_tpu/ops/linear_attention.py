"""The gated delta rule in its chunked form, and the short causal convolution
that comes before it: the core of a gated-DeltaNet linear-attention layer
(Gated Delta Networks, arXiv:2412.06464; HF `modeling_qwen3_next.py`
`torch_chunk_gated_delta_rule`), and of a Kimi-Delta-Attention layer, whose
gate is a vector a head (the last section but one).

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
head (`_carry`; the kernels: two smaller ones), and the outputs are read off
the chunks' starting states afterwards, again all at once. No array of (tokens, heads, d_k, d_v) exists:
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

**Two forms of the one rule** (`gated_delta_rule(impl=)`; "auto" takes the
kernels where the operands lie on TPUs, heads are multiples of 128 wide and
the chunk is 64, and the XLA form everywhere else, the CPU among it):

*The kernel form* (below the XLA form; what a TPU runs): two Pallas kernels,
`gdn_fwd` and `gdn_bwd` (`jax.custom_vjp`), on chunks of 128 tokens. A grid
step holds one value head's (d_k, d_v) float32 state in VMEM scratch and walks
a block of chunks: the chunks' own matrices (`G`, the decay mask, `A`, `T`,
`W`, `U0`, `P`) for the whole block at once in VMEM, then the state chunk by
chunk as `u = U0 - W S_0`, `S_C = e^{G_C} S_0 + Kd^T u` (two products where
forming `Kd^T W` first would be three), then the outputs. Value head h reads
key head h // (Hv / Hk) through the blocks' index maps: q and k are never
repeated. `T` is made IN the kernel: the 16 x 16 diagonal blocks by
elimination a column at a time (forward substitution), all blocks of a chunk
side by side in the lanes, then the same merges by matmul. The backward walks
the chunks from the last with `dS` in VMEM, makes the chunks' matrices again
from q, k, v, g, beta and what the forward KEPT (every chunk's `T` and the
state it started from: a rule's residuals are not recomputed, so under a
layer's `jax.checkpoint` the recomputation runs the forward kernel once and
the backward kernel finds them), and writes the five gradients once; a key
head's `dq`, `dk` are summed over its value heads after the kernel. On the
chip (PERF.md, PR 36) a layer at the Qwen3-Next cell's widths takes 3.3 ms a
call of `gdn_fwd` and 5.5 ms a call of `gdn_bwd`; with what XLA does around
them 3.95 ms forward and 11.6 ms forward and backward, where the XLA form
takes 7.0 and 28.4.

*The XLA form* (`impl="xla"`): the backward is autodiff's, through the scan
over the chunks and the batched matmuls around it, with a head's chunk
matrices recomputed from q, k, v, g, beta and the kept chunk-start states
(`_xla_rule`). A written rule for the carried recurrence (the reverse scan
`dS_n = M_n^T dS_{n+1} + ...` with the `M_n`'s gradients formed in one batched
matmul afterwards) gave the same gradients to the bit and ran 8 % SLOWER on
the chip at 8 heads at a time (PERF.md, PR 35), so it is not kept. It is the
oracle of the kernels' tests, and what every test of a small head runs.

**Around the core** (the convolution, SiLU, the L2 norms before it, the gated
RMSNorm after it: models/parts/linear.linear_mixer states them in XLA, which is what
the CPU runs): where the operands lie on TPUs they run as four lane-aligned
Pallas passes beside the core's two kernels, all six one `jax.custom_vjp`
(`mixer_form` decides as `gated_delta_rule` does, `kernel_mixer` is the rule;
below the core's kernels). On the chip (PERF.md, PR 38) a layer's passes take
0.55 ms before the core and 0.32 after it forward, 0.95 and 0.52 backward, 3.2
ms a layer and step where XLA's elementwise passes over (tokens, heads, 128)
views took about 8, and the two projections' matmuls, their operands and
results plain arrays now, run at 92 % of the MXU where they ran at 55 to 65.

**The per-channel rule** (`kda_rule`: Kimi Delta Attention, arXiv:2510.26692).
The gate is a VECTOR over the key's channels, `g_t in R^{d_k}` a head: `S' =
Diag(exp(g_t)) S_{t-1}`, a ROW of the state forgetting at its own rate, and the
rest as above. `G` is then (tokens, d_k) and everything above holds with every
`e^G` a per-channel scaling of k or q: `W = T (beta (K . e^G))`, `Kd_j = k_j .
e^{G_C - G_j}`, `S_C = Diag(e^{G_C}) S_0 + Kd^T (U0 - W S_0)`, `O = ((Q . e^G) -
P W) S_0 + P U0`. What changes is that the decay enters the contraction: `A_tj
= beta_t sum_c k_tc k_jc e^{G_tc - G_jc}` and `P_tj` alike with q. It no longer
factors out as a mask; `(k_t e^{G_t}) . (k_j e^{-G_j})` overflows float32 as
soon as a channel forgets fast; and `e^{G_t - G_j}` whole is (chunk, chunk, d_k)
a chunk and head. `_channel_products` cuts the chunk into sub-blocks of 16
tokens: between blocks the later block's first token is the reference and both
factors' exponents are <= 0, a plain matmul; inside a diagonal block the (16,
16, d_k) decays are formed whole. `_head_core` runs either rule by the gate's
rank, sharing the triangular solve, the carry and the read-out. That is the
XLA form (`kda_rule(impl="xla")`; the CPU's path and the tests' oracle). On a
TPU the rule has kernels of its own, `kda_fwd` and `kda_bwd` (below the scalar
rule's, whose walk, solve and carried state they share; `impl="auto"` decides
as `gated_delta_rule` does): the reference token is taken by HALVING, a tile's
later half against its earlier half and so on down to pairs of tokens, so
every decayed product is a plain matmul on the MXU and every exponent a sum of
g's, <= 0; the forward keeps every tile's `T`, `kk` and starting state, so a
layer's recomputation runs it once and the backward not at all. On the chip
(PERF.md, PR 43) a layer at the Kimi-Linear cell's widths takes 6.5 ms a call
of `kda_fwd` and 10.6 ms a call of `kda_bwd`, 23.6 ms a layer and step under
the layer's recomputation where the XLA form took 74.6 (its backward runs its
forward again, a head at a time).

Sequences are whole rows of the batch: neither the convolution nor the state
is cut at a document boundary inside a packed row.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops.kernels import (NN, NT, TILE, TN, KernelSharding, dot, on_kernels, rows_a_device,
                                       traced_once)

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


def _channel_products(q, k, total):
    """The decayed products of a chunk whose gate is a vector a token (KDA): q,
    k (..., C, d_k), total = G (..., C, d_k) float32, the gate's running sums ->
    (kk, qk), each (..., C, C) float32 with `sum_c x_tc k_jc e^{G_tc - G_jc}`
    (x = k, x = q) where j <= t and 0 above the diagonal.

    The decay does not factor out of the sum over c, `(x_t e^{G_t}) . (k_j
    e^{-G_j})` overflows float32 as soon as a channel forgets fast, and
    `e^{G_t - G_j}` whole is (C, C, d_k) a chunk. So the chunk is cut into
    blocks of `_BASE` tokens. BETWEEN blocks the later block's first token r
    is the reference: `(x_t e^{G_t - G_r}) . (k_j e^{G_r - G_j})`, both
    exponents <= 0 (r <= t, j < r), a plain matmul; an exponential that
    underflows stands for a product that is smaller still. INSIDE a diagonal
    block the (16, 16, d_k) decays are formed whole, masked before the
    exponential. `kk` feeds the triangular solve: float32 operands. `qk` is
    on the way to the output: its matmul takes operands of q's dtype."""
    c, dk = k.shape[-2:]
    lead, dt, blocks = k.shape[:-2], q.dtype, c // _BASE
    k32, q32 = k.astype(_F32), q.astype(_F32)
    g_sub, k_sub, q_sub = (x.reshape(lead + (blocks, _BASE, dk)) for x in (total, k32, q32))
    lower = jnp.tril(jnp.ones((_BASE, _BASE), bool))[..., None]
    kj = k_sub[..., None, :, :] * jnp.exp(
        jnp.where(lower, g_sub[..., :, None, :] - g_sub[..., None, :, :], -jnp.inf))  # (.., t, j, d_k)
    same = jnp.eye(blocks, dtype=_F32)[:, None, :, None]

    def whole(diagonal, between):  # (.., blocks, 16, 16), (.., blocks, 16, C) -> (.., C, C)
        return (diagonal[..., None, :] * same).reshape(lead + (c, c)) + between.reshape(lead + (c, c))

    ref = g_sub[..., :1, :]  # G_r, (.., blocks, 1, d_k)
    rows = jnp.exp(g_sub - ref)
    earlier = (jnp.arange(c) // _BASE)[None, :] < jnp.arange(blocks)[:, None]  # token j before block I
    kc = k32[..., None, :, :] * jnp.exp(
        jnp.where(earlier[..., None], ref - total[..., None, :, :], -jnp.inf))  # (.., blocks, C, d_k)
    kk = whole(jnp.sum(k_sub[..., :, None, :] * kj, axis=-1), _mm(k_sub * rows, _t(kc)))
    qk = whole(jnp.sum(q_sub[..., :, None, :] * kj, axis=-1),
               _mm((q_sub * rows).astype(dt), _t(kc.astype(dt))))
    return kk, qk


def _head_core(q, k, v, g, beta):
    """The rule for one value head, chunked: q, k (N, B, C, d_k), v (N, B, C,
    d_v), beta (N, B, C) float32, and g float32 either (N, B, C), a scalar a
    token (gated DeltaNet), or (N, B, C, d_k), a channel of the key its own
    (KDA: every `e^G` below then scales k's or q's channels, and the state's
    rows) -> o (N, B, C, d_v) in v's dtype and the final states (B, d_k, d_v)."""
    chunk, dt = v.shape[-2], v.dtype
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    strict = lower & ~jnp.eye(chunk, dtype=bool)
    eye = jnp.eye(k.shape[-1], dtype=_F32)
    if g.ndim == k.ndim:
        total = jnp.cumsum(g, axis=-2)  # G, (N, B, C, d_k)
        kk, p = _channel_products(q, k, total)
        a = jnp.where(strict, beta[..., None] * kk, 0.0)
        from_start = jnp.exp(total)  # e^G
        to_end = jnp.exp(total[..., -1:, :] - total)  # e^{G_C - G}
        keep = jnp.exp(total[..., -1, :])[..., None] * eye
    else:
        total = jnp.cumsum(g, axis=-1)  # G, (N, B, C)
        # masked BEFORE the exponential: above the diagonal the difference is
        # positive, and grows with the chunk
        decay = jnp.exp(jnp.where(lower, total[..., :, None] - total[..., None, :], -jnp.inf))
        a = jnp.where(strict, beta[..., None] * decay * _mm(k, _t(k)), 0.0)
        p = decay * _mm(q, _t(k))
        from_start = jnp.exp(total)[..., None]  # e^G
        to_end = jnp.exp(total[..., -1:] - total)[..., None]  # e^{G_C - G}
        keep = jnp.exp(total[..., -1])[..., None, None] * eye
    inverse = unit_lower_inverse(a)
    # the chunk's affine map of the state, float32 operands: what is carried
    k32 = k.astype(_F32)
    u0 = _mm(inverse, v.astype(_F32) * beta[..., None])
    w = _mm(inverse, k32 * (beta[..., None] * from_start))
    kd_t = _t(k32 * to_end)
    starts, last = _carry(keep - _mm(kd_t, w), _mm(kd_t, u0))
    starts = checkpoint_name(starts, STARTS)
    # the outputs, read off the chunks' starting states: operands in v's dtype
    p, w, u0 = p.astype(dt), w.astype(dt), u0.astype(dt)
    q_hat = (q.astype(_F32) * from_start - _mm(p, w)).astype(dt)
    return (_mm(q_hat, starts.astype(dt)) + _mm(p, u0)).astype(dt), last


def _xla_rule(q, k, v, g, beta, chunk):
    """The XLA form of `gated_delta_rule`. The value heads are worked ONE
    AFTER THE OTHER (`lax.map`), and a head's backward recomputes its chunks' matrices from q, k, v, g, beta and the
    chunks' starting states, which alone are kept (a sequence's worth of
    float32 (d_k, d_v) a chunk). The matrices of all 32 heads at once are two
    gigabytes at 8192 tokens; a head's fit the chip's fast memory, and on the
    chip a layer's forward and backward take 26.9 ms a head at a time against
    29.9, 38.3, 42.4 and 47.4 ms at 2, 4, 8 and 32 heads at a time (PERF.md,
    PR 35)."""
    b, s, hv, dv = v.shape

    def chunked(x):  # (B, S, H, ...) -> (H, N, B, C, ...)
        x = x.reshape((b, s // chunk, chunk) + x.shape[2:])
        return x.transpose((3, 1, 0, 2) + tuple(range(4, x.ndim)))

    q, k = (chunked(jnp.repeat(x, hv // x.shape[2], axis=2)) for x in (q, k))
    core = jax.checkpoint(_head_core, policy=jax.checkpoint_policies.save_only_these_names(STARTS))
    o, last = jax.lax.map(lambda xs: core(*xs),
                          (q, k, chunked(v), chunked(g.astype(_F32)), chunked(beta.astype(_F32))))
    return o.transpose(2, 1, 3, 0, 4).reshape(b, s, hv, dv), jnp.moveaxis(last, 0, 1)


# --- the kernel form -------------------------------------------------------
#
# The same rule on chunks of TILE = 128 tokens (the chunk's length is free:
# the mathematics above holds for any; two of the XLA form's chunks make one
# of the kernels'), so a chunk's matrices (the decay mask, A, T, P) are (128,
# 128): whole tiles of the vector unit and whole passes of the MXU, and half
# as many dependent steps along the sequence. A grid step walks `_BLOCK`
# tiles of one value head of one row of the batch, the head's (d_k, d_v)
# state in VMEM scratch from the step before; nothing of a tile but `T` and
# the state it started from (both only for the backward) is written to HBM.

_ROWS = 8  # a tile's per-token scalars, rows of one float32 (8, 128) (`_scalars`)
_BLOCK = 8  # tiles a grid step walks where its operands are 2 bytes wide
_VMEM = 64 * 2**20  # what a kernel may hold of the chip's 128 MiB: a block's tiles' matrices at once


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _invariants():
    """Masks of a tile's (128, 128): j <= t and j < t, the identity, and for
    `_inverses` the lane's 16 x 16 block, the packed identity and the blocks
    each merge reads (the lower left quarter of every diagonal block of twice
    the size)."""
    row, col = _iota((TILE, TILE), 0), _iota((TILE, TILE), 1)
    below, size = [], _BASE
    while size < TILE:
        below.append(((row // (2 * size)) == (col // (2 * size))) & ((row // size) % 2 == 1)
                     & ((col // size) % 2 == 0))
        size *= 2
    return dict(lower=row >= col, strict=row > col, eye=(row == col).astype(_F32), below=below,
                lane_block=_iota((_BASE, TILE), 1) // _BASE,
                unit=(_iota((_BASE, TILE), 0) == _iota((_BASE, TILE), 1) % _BASE).astype(_F32))


def _inverses(a, inv):
    """(n, 128, 128) float32, strictly lower -> (I + a)^-1 of each. The 16 x
    16 diagonal blocks by elimination (`X <- X - a[:, j] X[j, :]`, j
    ascending: forward substitution, a column at a time) on all of them at
    once, block b's rows in the lanes 16 b to 16 b + 15 of a tile's (16, 128);
    then the merges of `unit_lower_inverse`, `t21 = -t22 a21 t11`: a matmul
    each side on the rows that change (the lower half of every block being
    merged)."""
    n, blocks, lane_block = a.shape[0], TILE // _BASE, inv["lane_block"]
    packed = jnp.zeros((n, _BASE, TILE), _F32)  # packed[., i, 16 b + c] = a[., 16 b + i, 16 b + c]
    for b in range(blocks):
        packed = jnp.where(lane_block == b, a[:, b * _BASE:(b + 1) * _BASE, :], packed)
    flat = packed.reshape(n * _BASE, TILE)
    lanes = jnp.concatenate([lane_block * _BASE] * n, axis=0)
    x = jnp.broadcast_to(inv["unit"], packed.shape)
    for j in range(_BASE - 1):
        column = jnp.take_along_axis(flat, lanes + j, axis=1).reshape(packed.shape)  # a[:, j], a block
        x = x - column * x[:, j:j + 1, :]
    pieces = [jnp.where(lane_block == b, x, 0.0) for b in range(blocks)]  # `size` rows each
    size = _BASE
    for below in inv["below"]:
        lower_halves = jnp.concatenate(pieces[1::2], axis=1)
        moved = dot(dot(lower_halves, jnp.where(below, a, 0.0), NN),
                    jnp.concatenate(pieces, axis=1), NN)
        pieces = [jnp.concatenate([pieces[2 * b], pieces[2 * b + 1] - moved[:, b * size:(b + 1) * size]],
                                  axis=1) for b in range(len(pieces) // 2)]
        size *= 2
    return pieces[0]


def _tiles_of(ref):  # a block's tokens (n x 128, d) -> (n, 128, d)
    return ref[...].reshape(ref.shape[0] // TILE, TILE, ref.shape[1])


def _columns(rows, inv):
    """A block's rows (n, 8, 128) as columns, (n, 128, 8): the product with the
    identity is the transpose (exact: one factor is 1, the others 0), one for
    the block."""
    n = rows.shape[0]
    cols = dot(inv["eye"], rows.reshape(n * _ROWS, TILE), NT)
    return jnp.stack([cols[:, tile * _ROWS:(tile + 1) * _ROWS] for tile in range(n)])


def _local(q, k, v, rows, inv, inverse=None):
    """What a block's n tiles make of q, k, v (n, 128, d), G and beta without
    their states, all n at once: the products of one tile follow those of
    another in the program, none waiting for the other's result. rows (n, 8,
    128) hold G, beta and the tile's last G a token; `inverse` is `T` where it
    was kept."""
    cols = _columns(rows, inv)
    g_row, g_col, beta, g_end = rows[:, 0:1, :], cols[:, :, 0:1], cols[:, :, 1:2], cols[:, :, 2:3]
    from_start = jnp.exp(g_col)
    k32 = k.astype(_F32)
    decay = jnp.exp(jnp.where(inv["lower"], g_col - g_row, -jnp.inf))
    kk = dot(k, k, NT)
    if inverse is None:
        inverse = _inverses(jnp.where(inv["strict"], beta * decay * kk, 0.0), inv)
    rhs = jnp.concatenate([k32 * (beta * from_start), v.astype(_F32) * beta], axis=2)  # [Rw | Ru]
    return dict(beta=beta, from_start=from_start, to_end=jnp.exp(g_end - g_col), decay=decay,
                kk=kk, qk=dot(q, k, NT), inverse=inverse, rhs=rhs, wu=dot(inverse, rhs, NN),
                k32=k32)


def _keeps(rows_ref, i, width):
    """e^{G_C} of the block's tile i, a (1, width) row: the rows hold G_C in
    every lane, so the row that scales the state needs no broadcast."""
    row = jnp.exp(rows_ref[i][2:3, :])
    return row if width == TILE else jnp.concatenate([row] * (width // TILE), axis=1)


def _here(step, block, tiles):
    """How many tiles of the block of step `step` exist (the last block of a
    sequence need not be whole)."""
    return block if tiles % block == 0 else jnp.minimum(block, tiles - step * block)


def _walk(here, dk, keeps, s_ref, starts_ref, wu_ref, kd_ref, u_ref):
    """The state through a block's `here` tiles, two dependent products each:
    `u = U0 - W S_0`, `S_C = keeps(i) S_0 + Kd^T u`; every tile's start and u
    are left in `starts_ref`, `u_ref`."""
    def tile(i, carry):
        state = s_ref[...]
        starts_ref[i] = state
        u = wu_ref[i][:, dk:] - dot(wu_ref[i][:, :dk], state, NN)  # U0 - W S_0
        u_ref[i] = u
        s_ref[...] = keeps(i, state) * state + dot(kd_ref[i], u, TN)
        return carry

    jax.lax.fori_loop(0, here, tile, None)


def _walk_back(here, keeps, ds_ref, dends_ref, fo_ref, kd_ref, du_ref, qgdo_ref, w_ref):
    """`_walk`'s gradient from the block's last tile: `du = P^T do + Kd dS_C`,
    `dS_0 = keeps(i) dS_C + (q e^G)^T do - W^T du`; every tile's `dS_C` and du
    are left in `dends_ref`, `du_ref`."""
    def tile(j, carry):
        i = here - 1 - j
        dstate = ds_ref[...]
        dends_ref[i] = dstate
        du = fo_ref[i] + dot(kd_ref[i], dstate, NN)
        du_ref[i] = du
        ds_ref[...] = keeps(i, dstate) * dstate + qgdo_ref[i] - dot(w_ref[i], du, TN)
        return carry

    jax.lax.fori_loop(0, here, tile, None)


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, o_ref, *rest, block, tiles, keep):
    """One grid step of the forward walk over a block of tiles, the head's
    state in `s_ref` from the step before: the tiles' own matrices all at
    once, then the state tile by tile (two dependent products each), then the
    outputs all at once."""
    if keep:
        starts_ref, t_ref, last_ref, s_ref, wu_ref, kd_ref, u_ref = rest
    else:
        last_ref, s_ref, wu_ref, kd_ref, u_ref, starts_ref = rest
    step = pl.program_id(2)
    dt = v_ref.dtype
    dk = q_ref.shape[1]
    inv = _invariants()
    q, k, v = _tiles_of(q_ref), _tiles_of(k_ref), _tiles_of(v_ref)
    m = _local(q, k, v, rows_ref[...], inv)
    if keep:
        t_ref[...] = m["inverse"]
    wu_ref[...] = m["wu"]
    kd_ref[...] = m["k32"] * m["to_end"]

    @pl.when(step == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    _walk(_here(step, block, tiles), dk, lambda i, state: _keeps(rows_ref, i, state.shape[1]),
          s_ref, starts_ref, wu_ref, kd_ref, u_ref)
    p = (m["decay"] * m["qk"]).astype(dt)
    o = (dot((q.astype(_F32) * m["from_start"]).astype(dt), starts_ref[...].astype(dt), NN)
         + dot(p, u_ref[...].astype(dt), NN))
    o_ref[...] = o.reshape(o_ref.shape).astype(dt)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, starts_ref, t_ref, do_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, drows_ref,
                ds_ref, fo_ref, kd_ref, w_ref, qgdo_ref, du_ref, dends_ref, *, block, tiles):
    """One grid step of the reverse walk: a block's tiles of one value head
    from the last, `ds_ref` the gradient of the state that the later tiles
    left. The tiles' matrices are made again from q, k, v, G, beta, the kept
    `T` and the states the tiles started from, all at once; the state's
    gradient goes tile by tile (two dependent products each, and each tile's
    `dS_C` is kept in `dends_ref`); the rest all at once again.

    With `u = U0 - W S_0`, `o = (q e^G) S_0 + P u` and `S_C = e^{G_C} S_0 +
    Kd^T u`: `du = P^T do + Kd dS_C`, `dS_0 = e^{G_C} dS_C + (q e^G)^T do -
    W^T du`, `dW = -du S_0^T`, `dU0 = du`; through `[W | U0] = T [Rw | Ru]`:
    `d[Rw | Ru] = T^T [dW | dU0]`, `dT = [dW | dU0] [Rw | Ru]^T`, and `dA =
    -T^T dT T^T` on the strictly lower part; the rest is elementwise."""
    step = pl.program_id(2)
    dt = v_ref.dtype
    dk = q_ref.shape[1]
    inv = _invariants()
    q, k, v, do = _tiles_of(q_ref), _tiles_of(k_ref), _tiles_of(v_ref), _tiles_of(do_ref)
    rows = rows_ref[...]
    m = _local(q, k, v, rows, inv, t_ref[...])
    beta, from_start, to_end, decay, inverse, rhs, k32 = (
        m[name] for name in "beta from_start to_end decay inverse rhs k32".split())
    states = starts_ref[...]
    qg32 = q.astype(_F32) * from_start
    kd = k32 * to_end
    p = (decay * m["qk"]).astype(dt)
    w = m["wu"][:, :, :dk]
    u = m["wu"][:, :, dk:] - dot(w, states, NN)
    fo_ref[...] = dot(p, do, TN)  # P^T do
    qgdo_ref[...] = dot(qg32.astype(dt), do, TN)
    kd_ref[...] = kd
    w_ref[...] = w

    @pl.when(step == 0)
    def _():
        ds_ref[...] = dlast_ref[...]

    here = _here(pl.num_programs(2) - 1 - step, block, tiles)  # the reverse walk's block

    _walk_back(here, lambda i, dstate: _keeps(rows_ref, i, dstate.shape[1]),
               ds_ref, dends_ref, fo_ref, kd_ref, du_ref, qgdo_ref, w_ref)

    def rows_sum(x):  # (n, 128, m) -> (n, 128, 1)
        return jnp.sum(x, axis=2, keepdims=True)

    def as_row(col):  # (n, 128, 1) -> (n, 1, 128)
        return jnp.sum(col * inv["eye"], axis=1, keepdims=True)

    def total(x):  # (n, a, b) -> (n, 1, 1)
        return jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=2, keepdims=True)

    du, dends = du_ref[...], dends_ref[...]
    dkd = dot(u, dends, NT)
    dqg = dot(do, states.astype(dt), NT)
    dp = dot(do, u.astype(dt), NT)
    dwu = jnp.concatenate([-dot(du, states, NT), du], axis=2)  # [dW | dU0]
    drhs = dot(inverse, dwu, TN)
    da = jnp.where(inv["strict"],
                   -dot(dot(inverse, dot(dwu, rhs, NT), TN), inverse, NT), 0.0)
    drw, dru = drhs[:, :, :dk], drhs[:, :, dk:]
    dqk = (dp * decay).astype(dt)
    dkk = da * (beta * decay)  # k k^T reads k on both sides
    dq_ref[...] = (dqg * from_start + dot(dqk, k, NN)).reshape(dq_ref.shape).astype(dq_ref.dtype)
    dk_ref[...] = (drw * (beta * from_start) + dkd * to_end + dot(dqk, q, TN)
                   + dot(dkk, k32, NN) + dot(dkk, k32, TN)).reshape(dk_ref.shape).astype(dk_ref.dtype)
    dv_ref[...] = (dru * beta).reshape(dv_ref.shape).astype(dv_ref.dtype)
    # beta and G: the sums over a token's row, and D's two sides
    dbeta = (rows_sum(dru * v.astype(_F32)) + rows_sum(drw * k32) * from_start
             + rows_sum(da * decay * m["kk"]))
    e = (da * beta * m["kk"] + dp * m["qk"]) * decay  # dD D
    dg = rows_sum(dqg * qg32 + drw * rhs[:, :, :dk] - dkd * kd) + rows_sum(e)
    # G_C is the tile's last G
    dend = total(dkd * kd) + jnp.exp(rows[:, 2:3, 0:1]) * total(dends * states)
    last_lane = _iota((1, TILE), 1) == TILE - 1
    drows_ref[:, 0:1, :] = (as_row(dg) - jnp.sum(e, axis=1, keepdims=True)
                            + jnp.where(last_lane, dend, 0.0))
    drows_ref[:, 1:2, :] = as_row(dbeta)


def _scalars(g, beta):
    """g, beta (B, S, Hv) -> what the kernels read of them, (B, Hv, tiles, 8,
    128) float32, a tile of tokens a row and a token a lane: G (the running
    sum of g inside the tile), beta, and the tile's last G in EVERY lane (a
    row that scales the state needs no broadcast in the kernel)."""
    b, s, hv = g.shape

    def heads_first(x):
        return x.astype(_F32).transpose(0, 2, 1).reshape(b, hv, s // TILE, TILE)

    total, beta = jnp.cumsum(heads_first(g), axis=-1), heads_first(beta)
    rows = jnp.stack([total, beta, jnp.broadcast_to(total[..., -1:], total.shape)], axis=3)
    return jnp.pad(rows, ((0, 0),) * 3 + ((0, _ROWS - 3), (0, 0)))


def _block(s, dv, itemsize):
    """Tiles a grid step walks: `_BLOCK` at the cell's widths (bf16, d_v 128),
    fewer where the operands are wider (the scoped VMEM holds two of every
    block)."""
    return max(1, min(_BLOCK * 2 * TILE // (itemsize * dv), s // TILE))


def _call(kernel, name, dims, dtypes, reverse, in_kinds, out_kinds, scratch_kinds, operands):
    """A walk over (batch, value head, blocks of tiles), the last axis in
    order, from the sequence's last block with `reverse`. `dims` = (B, S, Hk,
    d_k, Hv, d_v), `dtypes` = (q's, v's). Kinds of blocks: "key" / "value"
    (a block's tokens of one head, d_k / d_v wide; a key head serves Hv / Hk
    value heads: the index map sends value head h to key head h // (Hv / Hk),
    and no q or k is repeated), "keys" (d_k wide a VALUE head), "gate" (the
    same block float32: the per-channel rule's g and its gradient), "rows",
    "starts", "inverse" (a block's tiles), "state" (a head's)."""
    b, s, hk, dk, hv, dv = dims
    block, tiles = _block(s, dv, dtypes[1].itemsize), s // TILE
    steps = pl.cdiv(tiles, block)

    def at(step):  # the block a step walks
        return steps - 1 - step if reverse else step

    def tokens(width, serves):
        return pl.BlockSpec((None, block * TILE, width), lambda i, h, c: (i, at(c), h // serves))

    def a_tile(*shape):
        return pl.BlockSpec((None, None, block) + shape, lambda i, h, c: (i, h, at(c), 0, 0))

    specs = {"key": tokens(dk, hv // hk), "keys": tokens(dk, 1), "gate": tokens(dk, 1), "value": tokens(dv, 1),
             "rows": a_tile(_ROWS, TILE), "starts": a_tile(dk, dv), "inverse": a_tile(TILE, TILE),
             "state": pl.BlockSpec((None, None, dk, dv), lambda i, h, c: (i, h, 0, 0))}
    shapes = {"keys": ((b, s, hv * dk), dtypes[0]), "gate": ((b, s, hv * dk), _F32),
              "value": ((b, s, hv * dv), dtypes[1]),
              "rows": ((b, hv, tiles, _ROWS, TILE), _F32), "starts": ((b, hv, tiles, dk, dv), _F32),
              "inverse": ((b, hv, tiles, TILE, TILE), _F32), "state": ((b, hv, dk, dv), _F32),
              # scratch alone: a block's tiles by d_k, by d_v, by both side by side
              "by_k": ((b, hv, tiles, TILE, dk), _F32), "by_v": ((b, hv, tiles, TILE, dv), _F32),
              "by_kv": ((b, hv, tiles, TILE, dk + dv), _F32)}
    return pl.pallas_call(
        functools.partial(kernel, block=block, tiles=tiles),
        grid=(b, hv, steps),
        in_specs=[specs[kind] for kind in in_kinds], out_specs=[specs[kind] for kind in out_kinds],
        out_shape=[jax.ShapeDtypeStruct(*shapes[kind]) for kind in out_kinds],
        scratch_shapes=[pltpu.VMEM(shapes[kind][0][2:] if kind == "state" else
                                   (block,) + shapes[kind][0][3:], _F32) for kind in scratch_kinds],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        name=name,
    )(*operands)


def _flat(x):  # (B, S, H, d) -> (B, S, H d): a head's block is then (tokens, d), whole tiles
    return x.reshape(x.shape[:2] + (-1,))


def _dims(q, v):  # q (B, S, Hk, d_k), v (B, S, Hv, d_v) -> (B, S, Hk, d_k, Hv, d_v)
    return v.shape[:2] + q.shape[2:] + v.shape[2:]


def _flat_forward(dims, q, k, v, g, beta, keep):
    """The forward kernel on q, k (B, S, Hk d_k) and v (B, S, Hv d_v), the
    layout a head's block is cut from: -> o (B, S, Hv d_v), the final states,
    and with `keep` what the backward reads again (the scalars' rows, the
    states the tiles started from, every tile's `T`)."""
    rows = _scalars(g, beta)
    out = _call(functools.partial(_fwd_kernel, keep=keep), "gdn_fwd", dims, (q.dtype, v.dtype), False,
                ["key", "key", "value", "rows"], ["value"] + ["starts", "inverse"] * keep + ["state"],
                ["state", "by_kv", "by_k", "by_v"] + ["starts"] * (not keep), (q, k, v, rows))
    return out[0], out[-1], ((rows,) + tuple(out[1:3]) if keep else None)


def _flat_backward(dims, q, k, v, kept, do, dlast):
    """The backward kernel on the flat operands and what `_flat_forward`
    kept: -> dq, dk (B, S, Hv d_k: a VALUE head's share each, the heads a key
    head serves side by side), dv (B, S, Hv d_v), dg, dbeta (B, S, Hv)
    float32."""
    b, s, _, _, hv, _ = dims
    rows, starts, inverse = kept
    dq, dk, dv, drows = _call(
        _bwd_kernel, "gdn_bwd", dims, (q.dtype, v.dtype), True,
        ["key", "key", "value", "rows", "starts", "inverse", "value", "state"],
        ["keys", "keys", "value", "rows"],
        ["state", "by_v", "by_k", "by_k", "starts", "by_v", "starts"],
        (q, k, v, rows, starts, inverse, do, dlast.astype(_F32)))

    def tokens_first(x):  # (B, Hv, tiles, 128) -> (B, S, Hv)
        return x.reshape(b, hv, s).transpose(0, 2, 1)

    # G is g's running sum inside a tile: g_t reaches every G_i, i >= t
    dg = jnp.flip(jnp.cumsum(jnp.flip(drows[..., 0, :], -1), axis=-1), -1)
    return dq, dk, dv, tokens_first(dg), tokens_first(drows[..., 1, :])


def _forward(q, k, v, g, beta, keep):
    """`_flat_forward` on q, k (B, S, Hk, d_k), v (B, S, Hv, d_v): o comes
    back as v came."""
    o, last, kept = _flat_forward(_dims(q, v), _flat(q), _flat(k), _flat(v), g, beta, keep)
    return o.reshape(v.shape), last, kept


@jax.custom_vjp
def _kernel_rule(q, k, v, g, beta):
    return _forward(q, k, v, g, beta, keep=False)[:2]


def _kernel_rule_fwd(q, k, v, g, beta):
    o, last, kept = _forward(q, k, v, g, beta, keep=True)
    return (o, last), (q, k, v, g, beta, kept)


def _kernel_rule_bwd(residuals, cotangents):
    q, k, v, g, beta, kept = residuals
    do, dlast = cotangents
    (b, s, hv, dv), (hk, dk) = v.shape, q.shape[2:]
    dq, dk_, dv_, dg, dbeta = _flat_backward(_dims(q, v), _flat(q), _flat(k), _flat(v), kept,
                                             _flat(do), dlast)

    def to_key_heads(x):
        """A key head's gradient is the sum over the value heads it serves,
        which lie side by side: slices a whole number of lanes wide, and no
        axis to reduce over (XLA made that one two relayouts and a reduction)."""
        x = x.reshape(b, s, hk, (hv // hk) * dk)
        served = [x[..., at:at + dk].astype(_F32) for at in range(0, x.shape[-1], dk)]
        return functools.reduce(jnp.add, served).astype(x.dtype)

    return (to_key_heads(dq), to_key_heads(dk_), dv_.reshape(v.shape),
            dg.astype(g.dtype), dbeta.astype(beta.dtype))


_kernel_rule.defvjp(_kernel_rule_fwd, _kernel_rule_bwd)


def _kernel_form(q, k, v, g, beta):
    """The kernels on whole tiles, the scalar rule's or the per-channel
    rule's by the gate's rank: a rest gets tokens of zeros behind it (beta 0:
    they write nothing; g 0: they forget nothing)."""
    rule = _kda_kernel_rule if g.ndim == k.ndim else _kernel_rule
    s = v.shape[1]
    if s % TILE == 0:
        return rule(q, k, v, g, beta)
    q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, TILE - s % TILE)) + ((0, 0),) * (x.ndim - 2))
                        for x in (q, k, v, g, beta))
    o, last = rule(q, k, v, g, beta)
    return o[:, :s], last


def _sharded_kernel_form(q, k, v, g, beta, sharding: Optional[KernelSharding]):
    """`_kernel_form` a device on its own rows of the batch (`kernels.rows_a_device`)."""
    return rows_a_device(_kernel_form, sharding, (q, k, v, g, beta), (), (4, 4))


# --- the per-channel rule, the kernel form ----------------------------------
#
# `kda_fwd` and `kda_bwd`: the walk, the solve and the carried state of the
# kernels above (`_call`, `_inverses`, the loop over a block's tiles), on the
# same tiles of 128 tokens. What differs is where the decay sits. It is inside
# the contraction over d_k, so a tile's `kk` and `qk` are no product times a
# mask; and it scales the state's ROWS, so `e^{G_C}` is wanted as a column.
#
# The decayed products by halving. Cut the tile into two halves with the later
# half's first token r the reference: for t >= r > j, `e^{G_t - G_j} = e^{G_t -
# G_r} e^{G_r - G_j}`, both exponents <= 0, so that quarter of the tile is ONE
# plain product of `x . e^{G - G_r}` (the later half's rows) with `k . e^{G_r -
# G}` (the earlier half's). The two halves' own lower triangles are cut the
# same way, and so on down to pairs of tokens: log2(128) = 7 levels, each one
# (128, d_k) x (d_k, 128) product of which the level's quarter-blocks are kept
# (`_levels`' `pairs`); every t > j is kept at exactly one level, the one at
# which t and j part. A level's exponents are partial sums of g, `sum_{r < i <=
# t} g_i` in the later half and `sum_{j < i <= r} g_i` in the earlier: ROWS of
# one 0/1 matrix times g, never a difference of two running sums, and one
# array `E = exp(.)` (128, d_k) serves both sides (`x . E` the rows, `k . E`
# the columns; which half a token lies in says which it is). `e^G` and `e^{G_C
# - G}` are two more such matrices (j <= t; j > t), so ONE product `sums @ g`
# (9 x 128, 128) x (128, d_k) makes every exponent of a tile: exact, the 0/1
# matrix in bf16 and g as three bf16 parts (`_thirds`). No exponent is
# positive, for any g <= 0, at any level: nothing can overflow, and an
# exponential that underflows stands for a product that is smaller still.
# `_channel_products` (the XLA form) does the same between its 16-token blocks
# and forms the (16, 16, d_k) decays whole inside them; here those are four
# more levels of the same product: a float32 and a bf16 product each on the
# MXU, where the decays whole are 2 x 262144 multiply-adds and 4096 lane
# reductions a tile on the vector unit.
#
# Kept for the backward: every tile's `T`, the state it started from and its
# `kk` (which only dbeta reads: seven float32 products to make again, 64 KB to
# read). The backward makes `E`, `qk`, `W`, `u` again, walks `dS` from the last
# tile, and sends each level's `dkk`, `dqk` back through its product to q, k
# and `E`; g's gradient is `sums^T` times the levels' `dX` stacked, one product
# with a contraction over 9 x 128.


def _levels(backward=False):
    """-> sums (9 x 128, 128) bf16 of 0 and 1, and the seven levels' `pairs`
    (128, 128) bool; with `backward` their transposes behind them. Rows 128 l
    .. 128 l + 127 of `sums` times g (128, d_k) are level l's exponents
    (halves of 64, 32, .. 1 tokens; see above), the last two blocks G =
    `sum_{i <= t} g_i` and G_C - G = `sum_{i > t} g_i`. `pairs[l]`[t, j]: t
    and j part at level l (the same block of twice the half, t in its later
    half, j in its earlier)."""
    row, col = _iota((TILE, TILE), 0), _iota((TILE, TILE), 1)
    halves = [TILE >> level for level in range(1, TILE.bit_length())]

    def terms(t, i):  # which g_i token t's exponents sum, a level a block
        out = []
        for half in halves:
            ref = t // (2 * half) * (2 * half) + half
            later = t >= ref
            out.append((later & (i > ref) & (i <= t)) | (~later & (i > t) & (i <= ref)))
        return [x.astype(_F32).astype(jnp.bfloat16) for x in out + [i <= t, i > t]]

    def parted(t, j):
        return [(t >= ref) & (j < ref) & (j // (2 * half) == t // (2 * half))
                for half in halves for ref in [t // (2 * half) * (2 * half) + half]]

    out = (jnp.concatenate(terms(row, col), axis=0), parted(row, col))
    return out + (jnp.concatenate(terms(col, row), axis=1), parted(col, row)) if backward else out


def _thirds(x):
    """float32 -> three bf16 side by side in the lanes whose sum is x to the
    last bit (8 + 8 + 8 bits of mantissa): a product of x with a 0/1 matrix is
    then three bf16 passes of the MXU and exact, where `_dot` on float32
    operands splits BOTH sides and takes six."""
    parts, rest = [], x
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(_F32)
    return jnp.concatenate(parts, axis=-1)


def _whole(x):  # the three partial products of `_thirds` -> their sum
    d = x.shape[-1] // 3
    return x[..., :d] + x[..., d:2 * d] + x[..., 2 * d:]


def _level(e, l):  # level l's block of the stacked exponentials (n, 9 x 128, d_k)
    return e[:, l * TILE:(l + 1) * TILE]


def _kda_local(q, k, v, g, rows, inv, sums, pairs, inverse=None, kk=None):
    """`_local` for a gate (n, 128, d_k): what a block's n tiles make of q, k,
    v, g and beta without their states. `inverse`, `kk`: `T` and the decayed
    `k k^T` where they were kept."""
    n, dt, dv = g.shape[0], v.dtype, v.shape[2]
    beta = _columns(rows, inv)[:, :, 1:2]
    parts = _thirds(g)
    e = jnp.exp(jnp.stack([_whole(dot(sums, parts[tile], NN)) for tile in range(n)]))
    k32, q32 = k.astype(_F32), q.astype(_F32)
    qk = inv["eye"] * jnp.sum(q32 * k32, axis=2, keepdims=True)  # t = j: no decay
    made = jnp.zeros((n, TILE, TILE), _F32) if kk is None else None
    for l, pair in enumerate(pairs):
        ke = k32 * _level(e, l)
        qk = qk + jnp.where(pair, dot((q32 * _level(e, l)).astype(dt), ke.astype(dt), NT), 0.0)
        if kk is None:
            made = made + jnp.where(pair, dot(ke, ke, NT), 0.0)
    kk = made if kk is None else kk
    from_start, to_end = _level(e, len(pairs)), _level(e, len(pairs) + 1)
    if inverse is None:
        inverse = _inverses(beta * kk, inv)
    rhs = jnp.concatenate([k32 * (beta * from_start), v.astype(_F32) * beta], axis=2)  # [Rw | Ru]
    # e^{G_C} as the state's rows want it, (n, d_k, d_v): g's sum over a tile's tokens in every column
    ones, dk = jnp.ones((n, TILE, dv), jnp.bfloat16), g.shape[2]
    keep = jnp.exp(sum(dot(parts[:, :, at:at + dk], ones, TN) for at in range(0, 3 * dk, dk)))
    return dict(beta=beta, e=e, from_start=from_start, to_end=to_end, kk=kk, qk=qk, inverse=inverse,
                rhs=rhs, wu=dot(inverse, rhs, NN), k32=k32, q32=q32, keep=keep)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, o_ref, *rest, block, tiles, keep):
    """`_fwd_kernel` for the per-channel rule: g_ref a block's (tokens, d_k)
    float32 of the head, rows_ref beta alone (row 1)."""
    if keep:
        starts_ref, t_ref, kk_ref, last_ref, s_ref, wu_ref, kd_ref, u_ref, keep_ref = rest
    else:
        last_ref, s_ref, wu_ref, kd_ref, u_ref, keep_ref, starts_ref = rest
    step = pl.program_id(2)
    dt = v_ref.dtype
    dk = q_ref.shape[1]
    sums, pairs = _levels()
    q, k, v = _tiles_of(q_ref), _tiles_of(k_ref), _tiles_of(v_ref)
    m = _kda_local(q, k, v, _tiles_of(g_ref), rows_ref[...], _invariants(), sums, pairs)
    if keep:
        t_ref[...] = m["inverse"]
        kk_ref[...] = m["kk"]
    wu_ref[...] = m["wu"]
    kd_ref[...] = m["k32"] * m["to_end"]
    keep_ref[...] = m["keep"]

    @pl.when(step == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    _walk(_here(step, block, tiles), dk, lambda i, _: keep_ref[i], s_ref, starts_ref, wu_ref, kd_ref, u_ref)
    o = (dot((m["q32"] * m["from_start"]).astype(dt), starts_ref[...].astype(dt), NN)
         + dot(m["qk"].astype(dt), u_ref[...].astype(dt), NN))
    o_ref[...] = o.reshape(o_ref.shape).astype(dt)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, rows_ref, starts_ref, t_ref, kk_ref, do_ref, dlast_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, drows_ref,
                    ds_ref, fo_ref, kd_ref, w_ref, qgdo_ref, du_ref, dends_ref, keep_ref, *, block, tiles):
    """`_bwd_kernel` for the per-channel rule. The state's walk and the solve
    are the scalar rule's with `P = qk`, `A = beta kk` (the decay is inside
    them) and `e^{G_C}` a row of the state its own. Behind them `dqk = dP` and
    `dkk = beta dA` go back level by level: with `KE = k . E`, `QE = q . E`,
    `dKE = dkk KE + dkk^T KE + dqk^T QE`, `dQE = dqk KE` on the level's pairs,
    `dE = dKE . k + dQE . q`, and the exponents' `dX = dE . E` of all levels,
    of `e^G` and of `e^{G_C - G}` stacked go through `sums^T` to g in one
    product; `e^{G_C}`'s share reaches every token of its tile alike."""
    step = pl.program_id(2)
    dt = v_ref.dtype
    dk, dv = q_ref.shape[1], v_ref.shape[1]
    inv = _invariants()
    sums, pairs, sums_t, pairs_t = _levels(backward=True)
    q, k, v, do = _tiles_of(q_ref), _tiles_of(k_ref), _tiles_of(v_ref), _tiles_of(do_ref)
    m = _kda_local(q, k, v, _tiles_of(g_ref), rows_ref[...], inv, sums, pairs, t_ref[...], kk_ref[...])
    beta, e, from_start, to_end, inverse, rhs, k32, q32 = (
        m[name] for name in "beta e from_start to_end inverse rhs k32 q32".split())
    n = e.shape[0]
    states = starts_ref[...]
    qg32 = q32 * from_start
    kd = k32 * to_end
    w = m["wu"][:, :, :dk]
    u = m["wu"][:, :, dk:] - dot(w, states, NN)
    fo_ref[...] = dot(m["qk"].astype(dt), do, TN)  # P^T do
    qgdo_ref[...] = dot(qg32.astype(dt), do, TN)
    kd_ref[...] = kd
    w_ref[...] = w
    keep_ref[...] = m["keep"]

    @pl.when(step == 0)
    def _():
        ds_ref[...] = dlast_ref[...]

    here = _here(pl.num_programs(2) - 1 - step, block, tiles)  # the reverse walk's block

    _walk_back(here, lambda i, _: keep_ref[i], ds_ref, dends_ref, fo_ref, kd_ref, du_ref, qgdo_ref, w_ref)

    def rows_sum(x):  # (n, 128, m) -> (n, 128, 1)
        return jnp.sum(x, axis=2, keepdims=True)

    du, dends = du_ref[...], dends_ref[...]
    dkd = dot(u, dends, NT)
    dqg = dot(do, states.astype(dt), NT)
    dp = dot(do, u.astype(dt), NT)
    dwu = jnp.concatenate([-dot(du, states, NT), du], axis=2)  # [dW | dU0]
    drhs = dot(inverse, dwu, TN)
    da = jnp.where(inv["strict"],
                   -dot(dot(inverse, dot(dwu, rhs, NT), TN), inverse, NT), 0.0)
    drw, dru = drhs[:, :, :dk], drhs[:, :, dk:]
    dkk = da * beta
    diagonal = rows_sum(dp * inv["eye"])  # qk's t = j
    dq = dqg * from_start + diagonal * k32
    dk_ = drw * (beta * from_start) + dkd * to_end + diagonal * q32
    dx = []
    dkk_t = jnp.swapaxes(dkk, 1, 2)
    for l, (pair, pair_t) in enumerate(zip(pairs, pairs_t)):
        scale = _level(e, l)
        qe, ke = q32 * scale, k32 * scale
        # the pair's later tokens read `dkk`'s rows, its earlier tokens the columns: one product
        both = jnp.where(pair, dkk, 0.0) + jnp.where(pair_t, dkk_t, 0.0)
        dqk_l = jnp.where(pair, dp, 0.0).astype(dt)
        dke = dot(both, ke, NN) + dot(dqk_l, qe.astype(dt), TN)
        dqe = dot(dqk_l, ke.astype(dt), NN)
        dq = dq + dqe * scale
        dk_ = dk_ + dke * scale
        dx.append(dke * ke + dqe * qe)
    dx += [dqg * qg32 + drw * rhs[:, :, :dk], dkd * kd]  # e^G's, e^{G_C - G}'s
    dx = jnp.concatenate(dx, axis=1)
    # e^{G_C} scales the state's rows: its exponent is the sum of ALL the tile's g
    dend = dot(jnp.ones((n, _ROWS, dv), _F32), dends * states * m["keep"], NT)[:, 0:1, :]
    dx = _thirds(dx)
    dg = jnp.stack([_whole(dot(sums_t, dx[tile], NN)) for tile in range(n)]) + dend
    dq_ref[...] = dq.reshape(dq_ref.shape).astype(dq_ref.dtype)
    dk_ref[...] = dk_.reshape(dk_ref.shape).astype(dk_ref.dtype)
    dv_ref[...] = (dru * beta).reshape(dv_ref.shape).astype(dv_ref.dtype)
    dg_ref[...] = dg.reshape(dg_ref.shape)
    dbeta = rows_sum(dru * v.astype(_F32)) + rows_sum(drw * k32 * from_start) + rows_sum(da * m["kk"])
    drows_ref[:, 1:2, :] = jnp.sum(dbeta * inv["eye"], axis=1, keepdims=True)


# (the trace reads the passes' tile sizes, which scripts/linear_passes_sweep.py sets)
_traced_once = functools.partial(traced_once, sizes=lambda: (_TOKENS, _LANES, _AT_ONCE))


def _beta_rows(beta):
    """beta (B, S, H) -> (B, H, tiles, 8, 128) float32, `_scalars`' layout
    with beta alone (row 1): the per-channel rule's g is an operand of its own."""
    b, s, h = beta.shape
    rows = beta.astype(_F32).transpose(0, 2, 1).reshape(b, h, s // TILE, 1, TILE)
    return jnp.pad(rows, ((0, 0),) * 3 + ((1, _ROWS - 2), (0, 0)))


@_traced_once(0, 6)
def _kda_flat_forward(dims, q, k, v, g, beta, keep):
    """`kda_fwd` on q, k (B, S, H d_k), g the same float32 and v (B, S, H d_v),
    the layout a head's block is cut from: -> o (B, S, H d_v), the final
    states, and with `keep` what `kda_bwd` reads again (beta's rows, the states
    the tiles started from, every tile's `T` and `kk`)."""
    rows = _beta_rows(beta)
    out = _call(functools.partial(_kda_fwd_kernel, keep=keep), "kda_fwd", dims, (q.dtype, v.dtype),
                False, ["key", "key", "value", "gate", "rows"],
                ["value"] + ["starts", "inverse", "inverse"] * keep + ["state"],
                ["state", "by_kv", "by_k", "by_v", "starts"] + ["starts"] * (not keep), (q, k, v, g, rows))
    return out[0], out[-1], ((rows,) + tuple(out[1:4]) if keep else None)


@_traced_once(0)
def _kda_flat_backward(dims, q, k, v, g, kept, do, dlast):
    """`kda_bwd` on the flat operands and what `_kda_flat_forward` kept: ->
    dq, dk (B, S, H d_k), dv (B, S, H d_v), dg (B, S, H d_k) float32, dbeta
    (B, S, H) float32."""
    b, s, _, _, h, _ = dims
    rows, starts, inverse, kk = kept
    dq, dk, dv, dg, drows = _call(
        _kda_bwd_kernel, "kda_bwd", dims, (q.dtype, v.dtype), True,
        ["key", "key", "value", "gate", "rows", "starts", "inverse", "inverse", "value", "state"],
        ["keys", "keys", "value", "gate", "rows"],
        ["state", "by_v", "by_k", "by_k", "starts", "by_v", "starts", "starts"],
        (q, k, v, g, rows, starts, inverse, kk, do, dlast.astype(_F32)))
    return dq, dk, dv, dg, drows[..., 1, :].reshape(b, h, s).transpose(0, 2, 1)


def _kda_forward(q, k, v, g, beta, keep):
    """`_kda_flat_forward` on q, k, g (B, S, H, d_k), v (B, S, H, d_v): o
    comes back as v came."""
    o, last, kept = _kda_flat_forward(_dims(q, v), _flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)), beta, keep)
    return o.reshape(v.shape), last, kept


@jax.custom_vjp
def _kda_kernel_rule(q, k, v, g, beta):
    return _kda_forward(q, k, v, g, beta, keep=False)[:2]


def _kda_kernel_rule_fwd(q, k, v, g, beta):
    o, last, kept = _kda_forward(q, k, v, g, beta, keep=True)
    return (o, last), (q, k, v, g, beta, kept)


def _kda_kernel_rule_bwd(residuals, cotangents):
    q, k, v, g, beta, kept = residuals
    do, dlast = cotangents
    dq, dk, dv, dg, dbeta = _kda_flat_backward(_dims(q, v), _flat(q), _flat(k), _flat(v), _flat(g.astype(_F32)),
                                               kept, _flat(do), dlast)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype))


_kda_kernel_rule.defvjp(_kda_kernel_rule_fwd, _kda_kernel_rule_bwd)


# --- around the core, the kernel form ---------------------------------------
#
# What a gated-DeltaNet mixer and a Kimi-Delta-Attention mixer do between
# their projections and the core (models/parts/linear.linear_mixer and parts/kda.kda_mixer are
# the definitions: the XLA forms), as passes over (tokens, channels) arrays in
# which a head is a block of whole 128-lane columns: no (tokens, heads, d) view
# exists, so nothing is relaid, no norm's scale is broadcast to full size and
# no slice of the projection's output is written out (a block is read where it
# lies, through its index map). ONE set of kernels for both mixers; what
# differs between them is a `Layout`, static at trace time: where q, k, v lie
# in the projection's output (`Wqkvz`'s [q | k | v | z] with a key head
# serving several value heads, or `Wqkv`'s [q | k | v] with one each), which
# array the output gate z lies in (the projection's output, or one of its own)
# and the gate's activation (SiLU, or the sigmoid).
#
#   before the core  `conv_norm_fwd`: a block of q's, k's or v's channels of
#     the projection's (tokens, channels) output -> the convolution in float32
#     (the tokens before a tile from the block of `_HALO` rows that ends where
#     the tile starts; zeros before the sequence), rounded where `causal_conv`
#     rounds, SiLU, rounded, and for q and k the L2 norm over a head's lanes
#     (q scaled by d_k ** -0.5). One call each for q, k and v.
#   the per-channel rule's gate  `kda_gate_fwd`: f = (y Wfa) Wfb as it leaves
#     the matmul, `dt_bias` a channel and `A_log` a head -> g = -exp(A_log)
#     softplus(f + dt_bias), written once as the float32 (tokens, H d_k) array
#     `kda_fwd` reads. The scalar rule's g is (tokens, heads): XLA's.
#   after the core   `gated_norm_fwd`: RMSNorm of o over a head's lanes times
#     the norm's scale times the activation of z's block, float32, rounded once.
#
# The backwards (`gated_norm_bwd`, `conv_norm_bwd`, `kda_gate_bwd`) make the
# forward's cheap arithmetic again from the inputs (the projection's output and
# the taps; o, z and the scale; f, `dt_bias` and `A_log`) and fill ONE
# cotangent of the projection's output, each its own column blocks
# (`input_output_aliases` hands the array on), so that no sum of padded parts
# is left for XLA; z's cotangent goes into the array z lies in. The taps', the
# scale's, `dt_bias`'s and `A_log`'s gradients leave as a tile's partial sums.
# A key head's dq, dk are summed over the value heads it serves as
# `conv_norm_bwd` reads them. `_kernel_mixer` ties the passes and the scalar
# core's two kernels into one `jax.custom_vjp`, `_kda_kernel_mixer` the passes
# and the per-channel core's.

# On the chip (scripts/linear_passes_sweep.py; PERF.md, PR 38) the passes are
# bound by the vector unit, not by HBM (a v5e has no bf16 arithmetic: about 40
# float32 operations an element of `conv_norm_fwd`), and larger steps are
# faster: tokens x lanes x tokens at once 512 x 512 x 32 -> 1024 x 1024 x 64
# takes `conv_norm_fwd` 0.99 -> 0.80 ms a layer and `conv_norm_bwd` 1.26 ->
# 0.93 by the sweep's clock (in the cell's trace 0.75 -> 0.55 and 1.27 ->
# 0.95); staging the tile as float32 or carrying eight rows costs the same.
_TOKENS = 1024  # tokens a grid step of a pass holds (a half, a quarter, an eighth where the sequence asks)
_LANES = 1024  # channels a grid step holds: whole heads
_HALO = 16  # rows of one bf16 tile: the block of tokens next to a tile's edge
_AT_ONCE = 64  # tokens a loop step works on, a head at a time: what the registers hold
_TAPS = 8  # the taps a channel, padded to a float32 tile's rows: at most so many

Heads = collections.namedtuple("Heads", "key_heads d_k value_heads d_v")  # `_call`'s dims after (B, S), in its order


def _tokens(s):
    """The passes' tile of tokens for a sequence of s, or None."""
    return next((t for t in (_TOKENS, _TOKENS // 2, _TOKENS // 4, _TOKENS // 8) if s % t == 0), None)


def _lanes(d, *widths):
    """The widest block of whole heads of d, at most `_LANES`, that divides
    every one of `widths` (segments and the columns they start at), or None."""
    for heads in range(max(1, _LANES // d), 0, -1):
        if all(width % (heads * d) == 0 for width in widths):
            return heads * d
    return None


def _stage(buf, x_ref, before_ref, first, after_ref=None):
    """A tile's tokens as float32 in `buf` from row `_HALO` on, the block
    before it in the rows before (zeros for a sequence's first tile) and, for
    the backward, the block after it behind."""
    t = x_ref.shape[0]
    buf[0:_HALO] = jnp.where(first, 0.0, before_ref[...].astype(_F32))
    buf[_HALO:_HALO + t] = x_ref[...].astype(_F32)
    if after_ref is not None:
        buf[_HALO + t:] = after_ref[...].astype(_F32)


def _conv_rows(buf, r0, rows, lanes, w, taps):
    """`causal_conv`'s float32 sums for `rows` tokens from the tile's token r0
    on, a head's lanes: -> them, and x_{t - (taps - 1) + j} for each tap j.
    The tokens are shifted by rotating the rows with the eight before them."""
    ext = buf[pl.ds(r0 + _HALO - 8, rows + 8), lanes]
    shifted = [ext[8:] if j == taps - 1 else pltpu.roll(ext, taps - 1 - j, 0)[8:] for j in range(taps)]
    out = None
    for j in range(taps):
        term = shifted[j] * w[j:j + 1]
        out = term if out is None else out + term
    return out, shifted


def _dsilu(x, sig):  # d (x sigmoid(x)) / dx, sig = sigmoid(x)
    return sig * (1.0 + x * (1.0 - sig))


def _activate(a, dtype, scale):
    """The convolution's sums -> c (rounded as `causal_conv` rounds), its
    sigmoid, s = SiLU (rounded), the L2 norm's factor and the result; `scale`
    None: no norm."""
    c = a.astype(dtype).astype(_F32)
    sig = jax.nn.sigmoid(c)
    s = (c * sig).astype(dtype).astype(_F32)
    if scale is None:
        return c, sig, s, None, s
    r = jax.lax.rsqrt(jnp.sum(s * s, axis=-1, keepdims=True) + 1e-6)
    return c, sig, s, r, s * r if scale == 1.0 else s * r * scale


def _conv_fwd_kernel(x_ref, before_ref, taps_ref, o_ref, buf, *, taps, d, scale):
    t, c = x_ref.shape
    _stage(buf, x_ref, before_ref, pl.program_id(1) == 0)

    def rows(n, carry):
        r0 = pl.multiple_of(n * _AT_ONCE, _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            a, _ = _conv_rows(buf, r0, _AT_ONCE, lanes, taps_ref[:, lanes], taps)
            o_ref[pl.ds(r0, _AT_ONCE), lanes] = _activate(a, o_ref.dtype, scale)[-1].astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, rows, None)


def _conv_bwd_kernel(x_ref, before_ref, after_ref, taps_ref, d_ref, dafter_ref, *rest,
                     taps, d, serves, scale):
    """A tile's tokens of a block of channels: the forward's arithmetic again
    on the tile and on the `_HALO` tokens after it (their convolutions read
    this tile's last tokens), da = the cotangent of the convolution's sums in
    `dbuf`, the taps' partial sums over the tile's own tokens, then dx_t =
    sum_j w_j da_{t + taps - 1 - j}. `serves` value heads' shares of a key
    head's cotangent lie side by side in `d_ref` and are summed as they are
    read. Before the outputs in `rest`, where an earlier call began it: the
    cotangent array itself, which other calls fill elsewhere: never read."""
    dx_ref, dtaps_ref, buf, dbuf, acc = rest[-5:]
    t, c = x_ref.shape
    tile = pl.program_id(1)
    _stage(buf, x_ref, before_ref, tile == 0, after_ref)
    acc[...] = jnp.zeros_like(acc)

    def da_rows(r0, rows, h, from_ref, at):
        lanes = slice(h * d, (h + 1) * d)
        a, shifted = _conv_rows(buf, r0, rows, lanes, taps_ref[:, lanes], taps)
        c_, sig, s, r, _ = _activate(a, x_ref.dtype, scale)
        got = None
        for m in range(serves):
            share = from_ref[pl.ds(at, rows), pl.ds((h * serves + m) * d, d)].astype(_F32)
            got = share if got is None else got + share
        if scale is not None:
            unit = s * r
            if scale != 1.0:
                got = got * scale
            got = r * (got - unit * jnp.sum(unit * got, axis=-1, keepdims=True))
        return got * _dsilu(c_, sig), shifted

    def first_walk(n, carry):
        r0 = pl.multiple_of(n * _AT_ONCE, _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            da, shifted = da_rows(r0, _AT_ONCE, h, d_ref, r0)
            dbuf[pl.ds(r0, _AT_ONCE), lanes] = da
            for j in range(taps):
                acc[j, :, lanes] += jnp.sum((da * shifted[j]).reshape(_AT_ONCE // 8, 8, d), axis=0)
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, first_walk, None)
    beyond = tile == pl.num_programs(1) - 1  # nothing lies after the sequence's last tile
    for h in range(c // d):
        dbuf[t:t + _HALO, h * d:(h + 1) * d] = jnp.where(beyond, 0.0, da_rows(t, _HALO, h, dafter_ref, 0)[0])

    def second_walk(n, carry):
        r0 = pl.multiple_of(n * _AT_ONCE, _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            ext, w = dbuf[pl.ds(r0, _AT_ONCE + 8), lanes], taps_ref[:, lanes]
            dx = None
            for j in range(taps):
                ahead = taps - 1 - j  # da of the token `ahead` later
                term = (ext if ahead == 0 else pltpu.roll(ext, _AT_ONCE + 8 - ahead, 0))[:_AT_ONCE] * w[j:j + 1]
                dx = term if dx is None else dx + term
            dx_ref[pl.ds(r0, _AT_ONCE), lanes] = dx.astype(dx_ref.dtype)
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, second_walk, None)
    dtaps_ref[...] = jnp.zeros_like(dtaps_ref)
    for j in range(taps):
        dtaps_ref[j:j + 1, :] = jnp.sum(acc[j], axis=0, keepdims=True)


# the output gate's activation of z given sigmoid(z), and its derivative
_GATES = {"silu": (lambda z, sig: z * sig, _dsilu),
          "sigmoid": (lambda z, sig: sig, lambda z, sig: sig * (1.0 - sig))}


def _gated(o, z, w, eps):
    """o, z a head's lanes of some tokens, w (1, d) -> float32: the unit-rms
    o, the norm's factor, z, its sigmoid."""
    o32, z32 = o.astype(_F32), z.astype(_F32)
    r = jax.lax.rsqrt(jnp.mean(o32 * o32, axis=-1, keepdims=True) + eps)
    return o32 * r, r, z32, jax.nn.sigmoid(z32)


def _gate_fwd_kernel(o_ref, z_ref, scale_ref, out_ref, *, d, eps, gate):
    t, c = o_ref.shape
    w, (act, _) = scale_ref[...], _GATES[gate]

    def rows(n, carry):
        at = pl.ds(pl.multiple_of(n * _AT_ONCE, _AT_ONCE), _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            unit, _, z32, sig = _gated(o_ref[at, lanes], z_ref[at, lanes], w, eps)
            out_ref[at, lanes] = (unit * w * act(z32, sig)).astype(out_ref.dtype)
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, rows, None)


def _gate_bwd_kernel(o_ref, z_ref, scale_ref, d_ref, dz_ref, do_ref, dscale_ref, *, d, eps, gate):
    """out = unit w act(z), unit = o r: dz, do and the scale's partial sum
    over the step's tokens and heads (eight rows of them: the rows' sum is
    taken outside)."""
    t, c = o_ref.shape
    w, (act, dact) = scale_ref[...], _GATES[gate]
    dscale_ref[...] = jnp.zeros_like(dscale_ref)

    def rows(n, carry):
        at = pl.ds(pl.multiple_of(n * _AT_ONCE, _AT_ONCE), _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            unit, r, z32, sig = _gated(o_ref[at, lanes], z_ref[at, lanes], w, eps)
            got = d_ref[at, lanes].astype(_F32)
            dnormed = got * act(z32, sig)
            dz_ref[at, lanes] = (got * (unit * w) * dact(z32, sig)).astype(dz_ref.dtype)
            dscale_ref[...] += jnp.sum((dnormed * unit).reshape(_AT_ONCE // 8, 8, d), axis=0)
            dunit = dnormed * w
            do_ref[at, lanes] = (r * (dunit - unit * jnp.mean(dunit * unit, axis=-1, keepdims=True))
                                 ).astype(do_ref.dtype)
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, rows, None)


def _softplus(x):
    """-> softplus(x) as `jax.nn.softplus` sums it (max(x, 0) + log1p(e^-|x|))
    and sigmoid(x), its derivative, from the same exponential."""
    e = jnp.exp(-jnp.abs(x))
    return jnp.maximum(x, 0.0) + jnp.log1p(e), jnp.where(x >= 0, 1.0, e) / (1.0 + e)


def _channel_gate_fwd_kernel(f_ref, rows_ref, g_ref, *, d):
    """The per-channel rule's gate, float32: rows_ref's row 0 is -exp(A_log)
    (a head's in each of its lanes), row 1 `dt_bias`."""
    t, c = f_ref.shape

    def rows(n, carry):
        at = pl.ds(pl.multiple_of(n * _AT_ONCE, _AT_ONCE), _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            g_ref[at, lanes] = rows_ref[0:1, lanes] * _softplus(f_ref[at, lanes].astype(_F32) + rows_ref[1:2, lanes])[0]
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, rows, None)


def _channel_gate_bwd_kernel(f_ref, rows_ref, dg_ref, df_ref, sums_ref, *, d):
    """g = rate softplus(x), x = f + dt_bias: df = dx rounded, and the step's
    partial sums over its tokens, eight rows each (the rows' sums are taken
    outside): of dx (`dt_bias`'s gradient) and of dg g (`A_log`'s: dg / dA_log
    = g; a head's lanes still to be summed)."""
    t, c = f_ref.shape
    sums_ref[...] = jnp.zeros_like(sums_ref)

    def rows(n, carry):
        at = pl.ds(pl.multiple_of(n * _AT_ONCE, _AT_ONCE), _AT_ONCE)
        for h in range(c // d):
            lanes = slice(h * d, (h + 1) * d)
            rate = rows_ref[0:1, lanes]
            sp, sig = _softplus(f_ref[at, lanes].astype(_F32) + rows_ref[1:2, lanes])
            dg = dg_ref[at, lanes]
            dx = dg * (rate * sig)
            df_ref[at, lanes] = dx.astype(df_ref.dtype)
            sums_ref[0:8, lanes] += jnp.sum(dx.reshape(_AT_ONCE // 8, 8, d), axis=0)
            sums_ref[8:16, lanes] += jnp.sum((dg * (rate * sp)).reshape(_AT_ONCE // 8, 8, d), axis=0)
        return carry

    jax.lax.fori_loop(0, t // _AT_ONCE, rows, None)


def _pass(kernel, name, grid, in_specs, out_specs, out_shape, scratch=(), aliases=None):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=list(scratch), input_output_aliases=aliases or {},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * len(grid),
                                             vmem_limit_bytes=_VMEM),
        name=name)


# Columns of an array of (tokens, channels): the first, how many, a head's
# width, the block of channels a pass takes of them (None where no block of
# whole heads fits), what their L2 norm is scaled by (None: no norm) and how
# many value heads' shares of their cotangent the core's backward hands on
# side by side.
Segment = collections.namedtuple("Segment", "start width d lanes scale serves")
# What the passes are told of the mixer that calls them: its heads, q's, k's
# and v's columns of the projection's output, z's columns of the array the
# output gate lies in, the gate's activation (a key of `_GATES`) and the names
# (obs/forms.py's parts) the passes are counted under.
Layout = collections.namedtuple("Layout", "heads qkv z gate counted")


def _qkv(heads: Heads):
    """q's, k's, v's columns of a projection's output that starts [q | k | v]."""
    keys, values = heads.key_heads * heads.d_k, heads.value_heads * heads.d_v
    key_lanes, shares = _lanes(heads.d_k, keys), heads.value_heads // heads.key_heads
    return (Segment(0, keys, heads.d_k, key_lanes, heads.d_k ** -0.5, shares),
            Segment(keys, keys, heads.d_k, key_lanes, 1.0, shares),
            Segment(2 * keys, values, heads.d_v, _lanes(heads.d_v, 2 * keys, values), None, 1))


def linear_layout(heads: Heads) -> Layout:
    """A gated-DeltaNet mixer's: `Wqkvz`'s output [q | k | v | z], a key head
    serving value / key heads, SiLU(z) the output gate."""
    qkv = _qkv(heads)
    v = qkv[2]  # z lies behind v, as wide and cut into the same blocks
    return Layout(heads, qkv, v._replace(start=v.start + v.width), "silu", (forms.CONV_NORM, forms.GATED_NORM))


def kda_layout(heads: Heads) -> Layout:
    """A Kimi-Delta-Attention mixer's: `Wqkv`'s output [q | k | v], the output
    gate an array of its own, sigmoid(z); and the per-channel gate's pass."""
    values = heads.value_heads * heads.d_v
    return Layout(heads, _qkv(heads), Segment(0, values, heads.d_v, _lanes(heads.d_v, values), None, 1),
                  "sigmoid", (forms.KDA_CONV_NORM, forms.KDA_GATE, forms.KDA_GATED_NORM))


def _taps_rows(taps):  # (channels, K) -> (_TAPS, channels) float32, a tap a row
    return jnp.pad(taps.astype(_F32).T, ((0, _TAPS - taps.shape[1]), (0, 0)))


def _conv_blocks(seg, s, t):
    """What both convolution passes read of a segment: its tile of the
    projection's output, the `_HALO` tokens before it (the tile's own first
    where there are none: read as zeros) and its taps; and the index of the
    block of `_HALO` tokens after tile n (the sequence's last where there
    are none)."""
    first, halos = seg.start // seg.lanes, t // _HALO

    def after(n):
        return jnp.minimum((n + 1) * halos, s // _HALO - 1)

    return [pl.BlockSpec((None, t, seg.lanes), lambda i, n, j: (i, n, first + j)),
            pl.BlockSpec((None, _HALO, seg.lanes), lambda i, n, j: (i, jnp.maximum(n * halos - 1, 0), first + j)),
            pl.BlockSpec((_TAPS, seg.lanes), lambda i, n, j: (0, first + j))], after


def _conv_norm(segments, x, taps):
    """The pass before the core: x (B, S, channels) the projection's output,
    `segments` q's, k's and v's columns of it, taps (their channels; K) -> q, k
    (B, S, Hk d_k), v (B, S, Hv d_v)."""
    b, s, _ = x.shape
    t, rows = _tokens(s), _taps_rows(taps)
    return [_pass(
        functools.partial(_conv_fwd_kernel, taps=taps.shape[1], d=seg.d, scale=seg.scale),
        "conv_norm_fwd", (b, s // t, seg.width // seg.lanes), _conv_blocks(seg, s, t)[0],
        pl.BlockSpec((None, t, seg.lanes), lambda i, n, j: (i, n, j)),
        jax.ShapeDtypeStruct((b, s, seg.width), x.dtype),
        [pltpu.VMEM((t + _HALO, seg.lanes), _F32)])(x, x, rows) for seg in segments]


def _conv_norm_bwd(segments, x, taps, cotangents, into=None):
    """`_conv_norm`'s backward: `cotangents` dq, dk (B, S, Hv d_k: a value
    head's share each) and dv (B, S, Hv d_v); `into` the projection's output's
    cotangent where another pass has filled columns of it (z's), None where q,
    k, v are all of it -> it with q's, k's, v's filled, and the taps' gradient
    (channels, K) float32."""
    b, s, _ = x.shape
    t, rows = _tokens(s), _taps_rows(taps)
    dtaps = []
    for seg, got in zip(segments, cotangents):
        c, first = seg.lanes, seg.start // seg.lanes
        (tile, before, taps_block), after = _conv_blocks(seg, s, t)
        begun = into is not None  # the array is handed on from call to call
        into, partial = _pass(
            functools.partial(_conv_bwd_kernel, taps=taps.shape[1], d=seg.d, serves=seg.serves, scale=seg.scale),
            "conv_norm_bwd", (b, s // t, seg.width // c),
            [tile, before,
             pl.BlockSpec((None, _HALO, c), lambda i, n, j, after=after, first=first: (i, after(n), first + j)),
             taps_block,
             pl.BlockSpec((None, t, seg.serves * c), lambda i, n, j: (i, n, j)),
             pl.BlockSpec((None, _HALO, seg.serves * c), lambda i, n, j, after=after: (i, after(n), j))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * begun,
            [tile, pl.BlockSpec((None, None, _TAPS, c), lambda i, n, j: (i, n, 0, j))],
            [jax.ShapeDtypeStruct(x.shape, x.dtype),
             jax.ShapeDtypeStruct((b, s // t, _TAPS, seg.width), _F32)],
            [pltpu.VMEM((t + 2 * _HALO, c), _F32), pltpu.VMEM((t + _HALO, c), _F32),
             pltpu.VMEM((_TAPS, 8, c), _F32)],
            aliases={6: 0} if begun else None)(x, x, x, rows, got, got, *([into] * begun))
        dtaps.append(jnp.sum(partial, axis=(0, 1))[:taps.shape[1]].T)
    return into, jnp.concatenate(dtaps, axis=0)


def _gated_norm(layout, eps, o, within, scale):
    """The pass after the core: o (B, S, Hv d_v), z = `layout.z`'s columns of
    `within`, scale (d_v,) -> RMSNorm(o; scale) a head x the gate's activation
    of z, (B, S, Hv d_v)."""
    b, s, width = o.shape
    d = layout.heads.d_v
    t, c, first = _tokens(s), layout.z.lanes, layout.z.start // layout.z.lanes
    return _pass(
        functools.partial(_gate_fwd_kernel, d=d, eps=eps, gate=layout.gate), "gated_norm_fwd",
        (b, s // t, width // c),
        [pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, j)),
         pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, first + j)),
         pl.BlockSpec((1, d), lambda i, n, j: (0, 0))],
        pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, j)),
        jax.ShapeDtypeStruct(o.shape, o.dtype))(o, within, scale.astype(_F32)[None])


def _gated_norm_bwd(layout, eps, o, within, scale, dout):
    """`_gated_norm`'s backward: -> `within`'s cotangent with z's columns
    filled AND NO OTHER (where that is the projection's output,
    `_conv_norm_bwd` fills the rest), do, the scale's gradient float32."""
    b, s, width = o.shape
    d = layout.heads.d_v
    t, c, first = _tokens(s), layout.z.lanes, layout.z.start // layout.z.lanes
    here = pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, j))
    dwithin, do, partial = _pass(
        functools.partial(_gate_bwd_kernel, d=d, eps=eps, gate=layout.gate), "gated_norm_bwd",
        (b, s // t, width // c),
        [here, pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, first + j)),
         pl.BlockSpec((1, d), lambda i, n, j: (0, 0)), here],
        [pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, first + j)), here,
         pl.BlockSpec((None, None, None, 8, d), lambda i, n, j: (i, n, j, 0, 0))],
        [jax.ShapeDtypeStruct(within.shape, within.dtype), jax.ShapeDtypeStruct(o.shape, o.dtype),
         jax.ShapeDtypeStruct((b, s // t, width // c, 8, d), _F32)],
    )(o, within, scale.astype(_F32)[None], dout)
    return dwithin, do, jnp.sum(partial, axis=(0, 1, 2, 3))


def _gate_rows(heads, dt_bias, a_log):
    """-> (8, H d_k) float32: row 0 -exp(A_log), a head's in each of its
    lanes, row 1 `dt_bias`."""
    rate = jnp.repeat(-jnp.exp(a_log.astype(_F32)), heads.d_k)
    return jnp.pad(jnp.stack([rate, dt_bias.astype(_F32)]), ((0, _ROWS - 2), (0, 0)))


@_traced_once(0)
def _channel_gate(layout, f, dt_bias, a_log):
    """The per-channel rule's gate: f (B, S, H d_k) as the matmul left it,
    dt_bias (H d_k,), a_log (H,) -> g = -exp(A_log) softplus(f + dt_bias),
    (B, S, H d_k) float32."""
    b, s, width = f.shape
    t, c = _tokens(s), layout.qkv[0].lanes
    here = pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, j))
    return _pass(
        functools.partial(_channel_gate_fwd_kernel, d=layout.heads.d_k), "kda_gate_fwd", (b, s // t, width // c),
        [here, pl.BlockSpec((_ROWS, c), lambda i, n, j: (0, j))], here,
        jax.ShapeDtypeStruct(f.shape, _F32))(f, _gate_rows(layout.heads, dt_bias, a_log))


@_traced_once(0)
def _channel_gate_bwd(layout, f, dt_bias, a_log, dg):
    """`_channel_gate`'s backward: dg (B, S, H d_k) float32 -> df in f's
    dtype, `dt_bias`'s gradient (H d_k,) and `A_log`'s (H,), float32."""
    b, s, width = f.shape
    heads = layout.heads
    t, c = _tokens(s), layout.qkv[0].lanes
    here = pl.BlockSpec((None, t, c), lambda i, n, j: (i, n, j))
    df, partial = _pass(
        functools.partial(_channel_gate_bwd_kernel, d=heads.d_k), "kda_gate_bwd", (b, s // t, width // c),
        [here, pl.BlockSpec((_ROWS, c), lambda i, n, j: (0, j)), here],
        [here, pl.BlockSpec((None, None, 2 * _ROWS, c), lambda i, n, j: (i, n, 0, j))],
        [jax.ShapeDtypeStruct(f.shape, f.dtype), jax.ShapeDtypeStruct((b, s // t, 2 * _ROWS, width), _F32)],
    )(f, _gate_rows(heads, dt_bias, a_log), dg)
    sums = jnp.sum(partial.reshape(b * (s // t), 2, _ROWS, width), axis=(0, 2))
    return df, sums[0], jnp.sum(sums[1].reshape(-1, heads.d_k), axis=1)


def _mixer(layout, eps, qkvz, taps, scale, g, beta, keep):
    """Convolution and norms, the core, the gated norm, each under its scope
    (the call sits under neither: an op carries one of the two)."""
    dims = qkvz.shape[:2] + tuple(layout.heads)
    with jax.named_scope(tracing.ATTN_LINEAR):
        q, k, v = _conv_norm(layout.qkv, qkvz, taps)
    with jax.named_scope(tracing.ATTN_DELTA):
        o, last, kept = _flat_forward(dims, q, k, v, g, beta, keep)
    with jax.named_scope(tracing.ATTN_LINEAR):
        out = _gated_norm(layout, eps, o, qkvz, scale)
    return (out, last), (qkvz, taps, scale, q, k, v, kept, o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kernel_mixer(layout, eps, qkvz, taps, scale, g, beta):
    return _mixer(layout, eps, qkvz, taps, scale, g, beta, keep=False)[0]


def _kernel_mixer_fwd(layout, eps, qkvz, taps, scale, g, beta):
    return _mixer(layout, eps, qkvz, taps, scale, g, beta, keep=True)


def _kernel_mixer_bwd(layout, eps, residuals, cotangents):
    qkvz, taps, scale, q, k, v, kept, o = residuals
    dout, dlast = cotangents
    dims = qkvz.shape[:2] + tuple(layout.heads)
    with jax.named_scope(tracing.ATTN_LINEAR):
        dqkvz, do, dscale = _gated_norm_bwd(layout, eps, o, qkvz, scale, dout)
    with jax.named_scope(tracing.ATTN_DELTA):
        dq, dk, dv, dg, dbeta = _flat_backward(dims, q, k, v, kept, do, dlast)
    with jax.named_scope(tracing.ATTN_LINEAR):
        dqkvz, dtaps = _conv_norm_bwd(layout.qkv, qkvz, taps, (dq, dk, dv), dqkvz)
    return dqkvz, dtaps.astype(taps.dtype), dscale.astype(scale.dtype), dg, dbeta


_kernel_mixer.defvjp(_kernel_mixer_fwd, _kernel_mixer_bwd)


# The shared passes as the per-channel mixer calls them (`_traced_once`: the
# Kimi cell's KDA layers lie in three runs of layers, so a step would trace
# each pass nine times; the linear mixer's calls stay as they were, and its
# cell's compiled step the parent's to the last instruction's number).
_kda_conv_norm, _kda_conv_norm_bwd = _traced_once(0)(_conv_norm), _traced_once(0)(_conv_norm_bwd)
_kda_gated_norm, _kda_gated_norm_bwd = _traced_once(0, 1)(_gated_norm), _traced_once(0, 1)(_gated_norm_bwd)


def _kda_mixer(layout, eps, qkv, taps, scale, f, dt_bias, a_log, z, beta, keep):
    """`_mixer` for the per-channel rule: the gate's pass beside the
    convolution's, the core's two kernels and beta's rows alone under
    `gt.attn.kda_rule` (its roofline reads that scope), all else under
    `gt.attn.kda_mixer`. The third result is the counter's mean of exp(g) a
    row of the batch: no gradient flows through it."""
    dims = qkv.shape[:2] + tuple(layout.heads)
    with jax.named_scope(tracing.ATTN_KDA):
        q, k, v = _kda_conv_norm(layout.qkv, qkv, taps)
        g = _channel_gate(layout, f, dt_bias, a_log)
    with jax.named_scope(tracing.ATTN_KDA_RULE):
        o, last, kept = _kda_flat_forward(dims, q, k, v, g, beta, keep)
    with jax.named_scope(tracing.ATTN_KDA):
        out = _kda_gated_norm(layout, eps, o, z, scale)
        decay = jnp.mean(jnp.exp(g), axis=(1, 2))
    return (out, last, decay), (qkv, taps, scale, f, dt_bias, a_log, z, q, k, v, g, kept, o)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _kda_kernel_mixer(layout, eps, qkv, taps, scale, f, dt_bias, a_log, z, beta):
    return _kda_mixer(layout, eps, qkv, taps, scale, f, dt_bias, a_log, z, beta, keep=False)[0]


def _kda_kernel_mixer_fwd(layout, eps, qkv, taps, scale, f, dt_bias, a_log, z, beta):
    return _kda_mixer(layout, eps, qkv, taps, scale, f, dt_bias, a_log, z, beta, keep=True)


def _kda_kernel_mixer_bwd(layout, eps, residuals, cotangents):
    qkv, taps, scale, f, dt_bias, a_log, z, q, k, v, g, kept, o = residuals
    dout, dlast, _ = cotangents
    dims = qkv.shape[:2] + tuple(layout.heads)
    with jax.named_scope(tracing.ATTN_KDA):
        dz, do, dscale = _kda_gated_norm_bwd(layout, eps, o, z, scale, dout)
    with jax.named_scope(tracing.ATTN_KDA_RULE):
        dq, dk, dv, dg, dbeta = _kda_flat_backward(dims, q, k, v, g, kept, do, dlast)
    with jax.named_scope(tracing.ATTN_KDA):
        dqkv, dtaps = _kda_conv_norm_bwd(layout.qkv, qkv, taps, (dq, dk, dv))
        df, dbias, da_log = _channel_gate_bwd(layout, f, dt_bias, a_log, dg)
    return (dqkv, dtaps.astype(taps.dtype), dscale.astype(scale.dtype), df, dbias.astype(dt_bias.dtype),
            da_log.astype(a_log.dtype), dz, dbeta)


_kda_kernel_mixer.defvjp(_kda_kernel_mixer_fwd, _kda_kernel_mixer_bwd)


def mixer_form(x: jax.Array, taps: jax.Array, layout: Layout, *, impl: str = "auto",
               sharding: Optional[KernelSharding] = None) -> str:
    """The form the passes around the core take for this projection's output
    (B, S, channels), these taps (channels of q, k, v; K) and the calling
    mixer's `layout`: "pallas" (`kernel_mixer` / `kda_kernel_mixer`: with the
    core, one rule) or "xla" (the caller's own arithmetic around
    `gated_delta_rule` / `kda_rule`). `impl` "auto": the kernels where the
    core would take its own (TPUs, heads multiples of 128 wide, one device or
    whole rows of the batch a device) and the sequence is a multiple of the
    passes' smallest tile of tokens, the taps at most `_TAPS` and a block of
    whole heads divides every segment. Said to `obs/forms`, a pass a part
    (`layout.counted`)."""
    if impl == "auto":
        heads = layout.heads
        fits = (heads.d_k % TILE == 0 and heads.d_v % TILE == 0 and _tokens(x.shape[1]) is not None
                and taps.shape[1] <= _TAPS and heads.value_heads % heads.key_heads == 0
                and all(seg.lanes is not None for seg in layout.qkv + (layout.z,)))
        impl = "pallas" if on_kernels(sharding, x.shape[0], fits)[0] else "xla"
    for part in layout.counted:
        forms.took(part, impl)
    return impl


def kernel_mixer(qkvz: jax.Array, taps: jax.Array, scale: jax.Array, g: jax.Array, beta: jax.Array,
                 layout: Layout, *, eps: float, sharding: Optional[KernelSharding] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """A gated-DeltaNet mixer between its two projections, the kernel form
    (where `mixer_form` says "pallas" of `linear_layout`): qkvz (B, S, [q | k |
    v | z]), taps (channels of q, k, v; K), scale (d_v,) the gated norm's, g,
    beta (B, S, Hv) float32 -> RMSNorm(o) x SiLU(z) (B, S, Hv d_v) in qkvz's
    dtype and the final states (B, Hv, d_k, d_v) float32. Its ops carry
    `gt.attn.linear` or, the core's, `gt.attn.delta`: call it under neither."""
    forms.took(forms.DELTA_RULE, "pallas")  # the core's form
    return rows_a_device(functools.partial(_kernel_mixer, layout, eps), on_kernels(sharding, qkvz.shape[0], True)[1],
                         (qkvz, taps, scale, g, beta), (1, 2), (3, 4))


def kda_kernel_mixer(qkv: jax.Array, taps: jax.Array, scale: jax.Array, f: jax.Array, dt_bias: jax.Array,
                     a_log: jax.Array, z: jax.Array, beta: jax.Array, layout: Layout, *, eps: float,
                     sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A Kimi-Delta-Attention mixer between its projections, the kernel form
    (where `mixer_form` says "pallas" of `kda_layout`): qkv (B, S, [q | k |
    v]), taps (their channels; K), scale (d_v,) the gated norm's, f (B, S, H
    d_k) the gate's low-rank projection and z (B, S, H d_v) the output gate's
    as the matmuls left them, dt_bias (H d_k,), a_log (H,), beta (B, S, H)
    float32 -> RMSNorm(o) x sigmoid(z) (B, S, H d_v) in qkv's dtype, the final
    states (B, H, d_k, d_v) float32 and the mean of exp(g) a row of the batch
    (a counter: no gradient). Its ops carry `gt.attn.kda_mixer` or, the
    core's, `gt.attn.kda_rule`: call it under neither."""
    forms.took(forms.KDA_RULE, "pallas")  # the core's form
    return rows_a_device(functools.partial(_kda_kernel_mixer, layout, eps),
                         on_kernels(sharding, qkv.shape[0], True)[1],
                         (qkv, taps, scale, f, dt_bias, a_log, z, beta), (1, 2, 4, 5), (3, 4, 1))


def gated_delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
                     *, chunk: int = CHUNK, impl: str = "auto",
                     sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, jax.Array]:
    """q, k (B, S, Hk, d_k), L2-normalised and q scaled; v (B, S, Hv, d_v); g
    (B, S, Hv) the log of the gate, <= 0; beta (B, S, Hv) -> o (B, S, Hv, d_v)
    in v's dtype, and the final states (B, Hv, d_k, d_v) float32. Each key
    head serves Hv / Hk consecutive value heads.

    `impl`: "pallas" the kernels, "xla" the XLA form, "auto" the kernels where
    they can run: the operands on TPUs (`sharding`'s mesh says so, as in
    `core_attention`; with none, the default backend), d_k and d_v multiples
    of 128, the chunk 64, and the call on one device or, with `sharding`, on
    whole rows of the batch a device and all heads on each (the linear layers
    have no other layout). Everything else, the CPU among it, takes the XLA
    form."""
    s = v.shape[1]
    if s % chunk:
        raise ValueError("gated_delta_rule: a sequence of %d tokens is no multiple of the "
                         "chunk of %d" % (s, chunk))
    kernels, sharding = on_kernels(
        sharding, v.shape[0], chunk == CHUNK and q.shape[3] % TILE == 0 and v.shape[3] % TILE == 0)
    if impl == "auto":
        impl = "pallas" if kernels else "xla"
    forms.took(forms.DELTA_RULE, impl)
    if impl == "xla":
        return _xla_rule(q, k, v, g, beta, chunk)
    return _sharded_kernel_form(q, k, v, g, beta, sharding)


def kda_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
             *, chunk: int = CHUNK, impl: str = "auto",
             sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, jax.Array]:
    """Kimi Delta Attention's rule (arXiv:2510.26692): the delta rule whose
    gate is a VECTOR a head, a row of the (d_k, d_v) state forgetting at its
    own rate, `S' = Diag(exp(g_t)) S_{t-1}` and the rest as above. q, k (B, S,
    H, d_k), L2-normalised and q scaled; v (B, S, H, d_v); g (B, S, H, d_k)
    the log of the gate, <= 0; beta (B, S, H) -> o (B, S, H, d_v) in v's dtype,
    and the final states (B, H, d_k, d_v) float32.

    `impl` as in `gated_delta_rule`: "pallas" the kernels (`kda_fwd`,
    `kda_bwd`), "xla" the XLA form (`_xla_rule`: a head at a time, the chunks'
    starting states kept, autodiff's backward; the decayed products by
    `_channel_products`), "auto" the kernels where the operands lie on TPUs
    (`sharding`'s mesh says so; with none, the default backend), d_k and d_v
    are multiples of 128 and the call sits on one device or, with `sharding`,
    on whole rows of the batch a device; everything else, the CPU among it,
    the XLA form. Said to `obs/forms` as `KDA_RULE`'s "pallas" / "xla". Any length: a
    rest (of a tile; of `chunk` in the XLA form) is padded with tokens that
    neither forget nor write (g = beta = 0)."""
    kernels, sharding = on_kernels(sharding, v.shape[0], q.shape[3] % TILE == 0 and v.shape[3] % TILE == 0)
    if impl == "auto":
        impl = "pallas" if kernels else "xla"
    forms.took(forms.KDA_RULE, impl)
    if impl == "pallas":
        return _sharded_kernel_form(q, k, v, g, beta, sharding)
    s = v.shape[1]
    rest = -s % chunk
    if rest:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, rest)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    o, last = _xla_rule(q, k, v, g, beta, chunk)
    return o[:, :s], last
