"""Mamba-2's state-space dual (SSD) in its chunked form: the core of a
state-space layer (Transformers are SSMs, arXiv:2405.21060; HF
`modeling_granitemoehybrid.py` `torch_forward`).

A head carries a state `h` (d_head x d_state) along the sequence, `h_0 = 0`:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T     A < 0 a head, dt_t > 0
    y_t = h_t C_t + D x_t

`B_t` and `C_t` (d_state,) are shared by the heads of a GROUP: by every head
where the model has one group (Granite-4.0-H: `bm`, `cm` of (B, S, d_state)),
by `heads / groups` consecutive heads where it has several (Nemotron-H's 8
groups of 8 heads: (B, S, groups, d_state)). It is the
gated delta rule (ops/linear_attention.py) WITHOUT the rank-one erase: what a
token writes does not depend on the state, so no triangular system is solved
and a chunk's map of the state is a scalar decay and a sum.

**The chunked form** cuts the sequence into chunks of `CHUNK` tokens. With
`G` the running sum of `dt A` inside a chunk,

    y_t  = sum_{j <= t} e^{G_t - G_j} (C_t . B_j) dt_j x_j  +  e^{G_t} h_c C_t
    h_c' = e^{G_last} h_c + sum_j e^{G_last - G_j} dt_j x_j B_j^T

`h_c` the state the chunk starts from. `C B^T` (tokens x tokens a chunk) is
the same for all heads of a group and made once a group; a head's part is its decay mask
`e^{G_t - G_j}`, masked BEFORE the exponential (above the diagonal the
difference is positive and grows with the chunk). What runs along the
sequence is one multiply-add of the states a chunk (`_carry`).

**Two forms of it, chosen by what the call observes** (`ssd_scan`):

- **XLA's** (the CPU, the tests' oracle, every shape the kernels do not take):
  the heads are worked `HEADS_AT_ONCE` at a time (`lax.map`; of a model with
  groups the heads worked at once lie in ONE group, so at most a group's, and
  the map runs over the groups' B and C beside the heads'), so the masks
  alive at once are (heads at once, chunks, CHUNK, CHUNK) and never all
  heads': 64 heads x 32 chunks x 128 x 128 float32 would be 128 MiB a tensor
  at 4096 tokens; what runs along the sequence is a `lax.scan` over the
  chunks (`_carry`). Its backward is autodiff's through a group's arithmetic,
  made again from x, dt, A, B, C and the chunks' STARTING STATES, which alone
  are kept (`jax.checkpoint` saving `STARTS`: a sequence's worth of float32
  (d_head, d_state) a chunk and head). On the chip a layer's scan at the
  Granite-4.0-H cell's widths takes 0.66 ms forward and 2.09 forward +
  backward at chunks of 128 and 16 heads at a time, at Nemotron-H's (8 groups
  of 8 heads, 8192 tokens) 1.52 / 4.54 (PERF.md, PR 72: calls queued back to
  back; PR 39's and PR 71's 1.28 / 3.22 and 2.25 / 5.79 were a fence a call,
  half a millisecond and more of host in each).
- **The kernels'** (`ssd_fwd`, `ssd_bwd`; on TPUs): a grid step is one chunk of
  a block of heads (up to `CHANNELS` channels), a part of one group or whole
  groups, the chunks of a row in order and the block's states (heads x d_head, d_state)
  float32 in VMEM between them. x is read as it lies, (tokens, heads x
  d_head), and turned in VMEM so that the TOKENS lie in the lanes: a head's
  floats a token are rows, its channels whole sublanes, every product's
  result 128 lanes wide, and a group's B and C meet all its heads in one
  product. The masks are made in VMEM, a head's at a time, and never reach
  HBM; HBM carries x, dt, B, C, y once and a state a chunk and head, the
  rule's residual, the same `STARTS` hold. 0.34 / 0.86 ms at Granite's
  widths and 0.82 / 2.35 at Nemotron-H's (PERF.md, PR 72, which has the sweep
  of chunks and blocks: chunks of 256 are no better at either length, blocks
  of 64 heads 3 to 5 % better and twice as long to compile, so `CHUNK` and
  `CHANNELS` are one number each for both cells).
- **The kernels' backward is written** (`jax.custom_vjp`): the same walk from
  the last chunk, carrying the cotangent of the state; a chunk's masks and
  products are made again from x, dt, A, B, C and the kept starting state
  (`_bwd_kernel` has the formulas); dB and dC are sums over a group's heads,
  of which a block writes its share and XLA adds the blocks'; the cotangents
  of the final state and of the counter are taken as zero: the layers read y
  alone.

Float32: `dt`, `dt A`, their running sums and every exponential of them;
the chunk's contribution to the state (float32 operands at the highest
matmul precision: whatever error it has is carried to the sequence's end),
the state and its carry. The products on the way to the OUTPUT (`C B^T`,
the masked product with x, `C h_c`) run on operands of the dtype x came in,
accumulated in float32: their error stays in the chunk it was made in. (The
kernels make the highest precision's six passes in three where one operand is
B in bfloat16, which is exact: `_exact`.) `state_dtype` rounds the carried
state after every chunk (float32 is the rule; the tests' and the chip check's
control carries it in bfloat16, the XLA form's alone).

A sequence that is no multiple of the chunk is padded at its end with
tokens of `dt = 0`: they neither decay the state nor write to it, and their
outputs are cut off. The published `mamba_chunk_size` (256) is a kernel's
block and no mathematics; the chunk here is what the chip likes.

Sequences are whole rows of the batch: the state is not reset at a document
boundary inside a packed row.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.obs import forms
from galvatron_tpu.ops.kernels import NN, NT, TILE, TN, KernelSharding, dot, on_kernels, rows_a_device, traced_once

CHUNK = 128
HEADS_AT_ONCE = 16
STARTS = "ssd_chunk_starts"  # the residual a group's backward keeps
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _carry(decay: jax.Array, add: jax.Array, state_dtype) -> Tuple[jax.Array, jax.Array]:
    """h_{c+1} = decay_c h_c + add_c from h_0 = 0, c over the leading axis:
    -> the states the chunks START from, (N, ...), and the last chunk's end
    state. The carried state is rounded to `state_dtype` a chunk."""
    def step(state, da):
        new = da[0][..., None, None] * state + da[1]
        if state_dtype != _F32:
            # not a cast there and back, which the TPU compiler takes out
            kind = jnp.finfo(state_dtype)
            new = jax.lax.reduce_precision(new, exponent_bits=kind.nexp, mantissa_bits=kind.nmant)
        return new, state

    last, starts = jax.lax.scan(step, jnp.zeros_like(add[0]), (decay, add))
    return starts, last


def _group_core(x, dt, a, d, bm, cm, state_dtype):
    """The scan for one group of heads, chunked: x (N, B, G, C, P) in the
    compute dtype, dt (N, B, G, C) float32, a (G,) float32 < 0, d (G,) float32,
    bm, cm (N, B, C, S) -> y (N, B, G, C, P) in x's dtype, the final states
    (B, G, P, S) float32 and the largest |h| at any chunk's end."""
    chunk, dtype = x.shape[-2], x.dtype
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # G, (N, B, G, C), <= 0 and falling: the running sum as a product with a
    # triangle of ones, which a TPU runs on the MXU (`jnp.cumsum` lowers to a
    # `reduce_window` that took 13 of the scan's 37 ms a step: PERF.md, PR 39)
    total = jnp.einsum("nbgj,tj->nbgt", dt * a[:, None], lower.astype(_F32),
                       precision=_HIGHEST, preferred_element_type=_F32)
    decay = jnp.exp(jnp.where(lower, total[..., :, None] - total[..., None, :], -jnp.inf))
    cb = jnp.einsum("nbts,nbjs->nbtj", cm, bm, preferred_element_type=_F32)
    x32 = x.astype(_F32)
    xdt = x32 * dt[..., None]  # dt_j x_j, float32
    within = jnp.einsum("nbgtj,nbgjp->nbgtp", (cb[:, :, None] * decay).astype(dtype),
                        xdt.astype(dtype), preferred_element_type=_F32)
    # what the chunk adds to the state, float32 operands: what is carried
    to_end = jnp.exp(total[..., -1:] - total)  # e^{G_last - G_j}
    add = jnp.einsum("nbgjp,nbjs->nbgps", xdt * to_end[..., None], bm.astype(_F32),
                     precision=_HIGHEST, preferred_element_type=_F32)
    starts, last = _carry(jnp.exp(total[..., -1]), add, state_dtype)
    starts = checkpoint_name(starts, STARTS)
    # the part read off the state the chunk starts from: operands in x's dtype
    from_start = jnp.einsum("nbts,nbgps->nbgtp", cm, starts.astype(dtype),
                            preferred_element_type=_F32) * jnp.exp(total)[..., None]
    peak = jnp.maximum(jnp.max(jnp.abs(starts)), jnp.max(jnp.abs(last)))
    skip = x32 * d[:, None, None]
    return (within + from_start + skip).astype(dtype), last, peak


# ---------------------------------------------------------------------------
# The kernel form (the module's docstring): a grid step is one chunk of one
# block of heads; the walk is (batch, blocks of heads, chunks in order).
# ---------------------------------------------------------------------------

CHANNELS = 2048  # the channels a grid step holds, at most: 32 heads of 64 (scripts/ssd_sweep.py)
_VMEM = 32 * 2**20  # what a kernel may hold of the chip's 128 MiB (a block of 2048 channels takes 24)


def _triangle(t, later_in_lanes):
    """(t, t) bool: position `row <= column` (or `row >= column`)."""
    rows, cols = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0), jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return rows <= cols if later_in_lanes else rows >= cols


def _exact(x32, w, dims):
    """The product of a float32 `x32` with `w` at the highest precision, `dims`
    `NN` or `NT`. A `w` in bfloat16 is exact, so the six passes of the highest
    precision are three: `x32` cut into three bfloat16 addends (which hold all
    of a float32's 24 bits), side by side along the contracted axis against
    `w` three times."""
    if w.dtype != jnp.bfloat16:
        return dot(x32, w.astype(_F32), dims)
    high = x32.astype(w.dtype)
    rest = x32 - high.astype(_F32)
    mid = rest.astype(w.dtype)
    low = (rest - mid.astype(_F32)).astype(w.dtype)
    return dot(jnp.concatenate([high, mid, low], axis=1), jnp.concatenate([w] * 3, axis=dims[1][0]), dims)


def _sums(dt_ref, a_ref, n):
    """A chunk's per-token floats of a block's heads, a head a ROW (heads,
    T), float32: dt, G the running sum of `dt A` (a product with a triangle of
    ones on the MXU), e^G (what is left of the starting state at a token),
    e^{G_last - G} (what is left of a token's write at the chunk's end), and
    e^{G_last} across a tile's lanes, (heads, n): the sum of all of `dt A`,
    the same product with ones (Mosaic knows no broadcast of one float along
    sublanes and lanes at once)."""
    dt = dt_ref[...]
    t = dt.shape[1]
    ones = jnp.concatenate([_triangle(t, True).astype(jnp.bfloat16), jnp.ones((t, max(t, n)), jnp.bfloat16)], axis=1)
    sums = _exact(dt * a_ref[...], ones, NN)  # one product: G beside G_last across the lanes
    g, g_last = sums[:, :t], sums[:, t:]
    return dt, g, jnp.exp(g), jnp.exp(g_last[:, :t] - g), jnp.exp(g_last[:, :n])


def _groups(width, p, n, groups):
    """A block's groups of B and C: (the group's lanes of the block's B and C,
    the group's rows of (heads x P, tokens), its heads as (the head's number
    in the block, its rows, its rows within the group))."""
    mine = width // groups
    return [(slice(q * n, (q + 1) * n), slice(q * mine, (q + 1) * mine),
             [(at // p, slice(at, at + p), slice(at - q * mine, at - q * mine + p))
              for at in range(q * mine, (q + 1) * mine, p)]) for q in range(groups)]


def _fwd_kernel(d_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, *rest, p, groups, keep):
    """The chunked form for one chunk and a block of heads: D (H,) in SMEM, x
    (T, heads x P) as it lies, dt (heads, T), B and C (T, groups x N) of the
    block's `groups` groups (one, of which the block is a part, or several
    whole ones), A (heads, 1) -> y (T, heads x P), the state the chunk STARTED
    from (heads x P, N) and, after a row's last chunk, the final state and the
    largest |h| a lane. The chunks of a row run in order and `state` holds the
    block's state between them.

    x is turned once, (heads x P, T): the TOKENS in the lanes. A head's floats
    a token (dt, e^G, ...) are then rows that multiply along the sublanes, a
    head's channels are whole sublanes, every vector is 128 lanes full and the
    products' results are 128 wide: y^T = (dt x)^T (C B^T * M)^T + h C^T. The
    one float a token that is wanted down the sublanes is the mask's G_j."""
    if keep:
        start_ref, last_ref, peak_ref, state, yt_scr, xs_scr = rest
    else:
        last_ref, peak_ref, state, yt_scr, xs_scr = rest
    j, k = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype
    t, width = x_ref.shape
    n = b_ref.shape[1] // groups

    @pl.when(k == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        peak_ref[...] = jnp.zeros_like(peak_ref)

    dt, g, from_start, to_end, left = _sums(dt_ref, a_ref, n)
    nh = dt.shape[0]
    g_down = g.T  # (T, heads): G_j down the sublanes
    kept = dt * to_end  # dt_j e^{G_last - G_j}
    xt = x_ref[...].astype(_F32).T  # (heads x P, T)
    h = state[...]
    if keep:
        start_ref[...] = h
    later = _triangle(t, True)
    for lanes, mine, heads in _groups(width, p, n, groups):
        b, c = b_ref[:, lanes], c_ref[:, lanes]
        cbt = dot(b, c, NT)  # (T, T): B_j . C_t, the same for all heads of the group
        from_h = dot(h[mine].astype(dtype), c, NT)  # h_c C_t, every head's of the group: operands in x's dtype
        for i, rows, local in heads:
            row = lambda r: r[i:i + 1, :]  # noqa: E731
            # the decay mask e^{G_t - G_j}, j down the sublanes, masked BEFORE the exponential
            mask = jnp.exp(jnp.where(later, row(g) - g_down[:, i:i + 1], -jnp.inf))
            xi = xt[rows]
            within = dot((xi * row(dt)).astype(dtype), (cbt * mask).astype(dtype), NN)
            yt_scr[rows, :] = (within + from_h[local] * row(from_start) + xi * d_ref[j * nh + i]).astype(dtype)
            xs_scr[rows, :] = xi * row(kept)  # float32: what is carried
        # what the chunk adds to the state, float32 operands at the highest precision, a group's heads in one product
        add = _exact(xs_scr[mine, :], b, NN)
        for i, rows, local in heads:
            state[rows, :] = left[i:i + 1, :] * h[rows] + add[local]
    y_ref[...] = yt_scr[...].T
    new = state[...]
    peak_ref[...] = jnp.maximum(peak_ref[...], jnp.max(jnp.abs(new), axis=0, keepdims=True))

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = new


def _bwd_kernel(d_ref, x_ref, dt_ref, b_ref, c_ref, a_ref, start_ref, dy_ref,
                dx_ref, ddt_ref, ddta_ref, db_ref, dc_ref, dd_ref,
                carried, ended, dxt_scr, dys_scr, xs_scr, dd_scr, rows_scr, *, p, groups):
    """The chunked form's gradients for one chunk and a block of heads, the
    chunk's masks and products made again from x, dt, A, B, C and the state it
    started from, x and dy turned as `_fwd_kernel` turns x. The chunks of a
    row run from the LAST; `carried` holds the cotangent `g` of the state the
    chunk ENDS in (zero after the sequence's end: the final state's cotangent
    is taken as zero) and `ended` that state itself, which is the state the
    chunk walked before this one started from. With `W = (C B^T) * M` the
    masked product, `r_j = e^{G_last - G_j}`, `u_j = dt_j x_j`, everything a
    head but B, C and what is summed over the block's heads:

        du   = W^T dy + r (B g)              dx = dt du + D dy      ddt = x . du + A d(dtA)
        dC   = (sum dW * M) B + e^G dy h_c   dB = (sum dW * M)^T C + (r u) g^T        dW = dy u^T
        dG_t = dy_t . (W u)_t - u_t . (W^T dy)_t + e^{G_t} dy_t . (C_t h_c) - r_t u_t . (B g)_t
        dG_last += g . h_end                 d(dtA)_i = sum_{t >= i} dG_t
        g'   = e^{G_last} g + C^T (e^G dy)   dD = sum dy x

    (`dG`'s first two terms are the rows' and the columns' sums of ONE matrix,
    `dW * W`, written as sums over a head's channels, down the sublanes: no
    mask's gradient is made. In `d(dtA)`'s sums all of that matrix but the
    pairs of tokens on either side of i cancels, so both read the SAME
    operands, u rounded to x's dtype as the forward's product read it: with a
    float32 u in the one, what should cancel stayed as rounding and the
    gradients of `A_log` and `dt_bias` lay 0.15 off the float32 reference
    against 0.03, PERF.md, PR 72. What `G_last` takes through every `r_t` and
    through `e^{G_last} h_c` is `g . h_end`, for `h_end` is `e^{G_last}` times
    a sum that knows no `G_last`.) `(r u) g^T`, B's gradient through the state,
    runs on operands of x's dtype as C's through the output does: it is
    rounded to B's dtype. -> dx (T, heads x P), ddt and d(dtA) (heads, T),
    this block's share of dB and dC (T, N) float32, and of dD a channel."""
    j, k = pl.program_id(1), pl.program_id(2)
    dtype = x_ref.dtype
    t, width = x_ref.shape
    n = b_ref.shape[1] // groups

    @pl.when(k == 0)
    def _():
        carried[...] = jnp.zeros_like(carried)
        ended[...] = jnp.zeros_like(ended)
        dd_scr[...] = jnp.zeros_like(dd_scr)

    dt, g, from_start, to_end, left = _sums(dt_ref, a_ref, n)
    nh = dt.shape[0]
    g_down = g.T  # (T, heads): G_t down the sublanes
    xt, dyt = x_ref[...].astype(_F32).T, dy_ref[...].astype(_F32).T  # (heads x P, T)
    h, gs = start_ref[...], carried[...]
    at_end = jnp.sum(gs * ended[...], axis=1, keepdims=True)  # (heads x P, 1): g . h_end, a channel's
    ended[...] = h
    earlier = _triangle(t, False)
    for lanes, mine, heads in _groups(width, p, n, groups):
        b, c = b_ref[:, lanes], c_ref[:, lanes]
        cb = dot(c, b, NT)  # (T, T): C_t . B_j, t down the sublanes
        from_h = dot(h[mine].astype(dtype), c, NT)  # h_c C_t
        bg = _exact(gs[mine], b, NT)  # g B_j: the state's path, float32 at the highest precision
        dcb = jnp.zeros((t, t), _F32)
        for i, rows, local in heads:
            row = lambda r: r[i:i + 1, :]  # noqa: E731
            # the decay mask e^{G_t - G_j}, t down the sublanes, masked BEFORE the exponential
            mask = jnp.exp(jnp.where(earlier, g_down[:, i:i + 1] - row(g), -jnp.inf))
            w = (cb * mask).astype(dtype)
            xi, dyi = xt[rows], dyt[rows]
            u = xi * row(dt)
            u16 = u.astype(dtype)  # as the forward's product read it
            back, kept = dot(dyi.astype(dtype), w, NN), row(to_end) * bg[local]  # (W^T dy)^T; what the end kept
            through = back + kept  # du
            within = dot(u16, w, NT)  # (W u)^T
            dxt_scr[rows, :] = (row(dt) * through + d_ref[j * nh + i] * dyi).astype(dtype)
            dys = dyi * row(from_start)
            rows_scr[0, i:i + 1, :] = jnp.sum(through * xi, axis=0, keepdims=True)  # ddt through u
            rows_scr[1, i:i + 1, :] = jnp.sum(dyi * within - u16.astype(_F32) * back + dys * from_h[local] - u * kept,
                                              axis=0, keepdims=True)  # dG
            rows_scr[2, i:i + 1, :] = jnp.broadcast_to(jnp.sum(at_end[rows], axis=0, keepdims=True), (1, t))
            # dW from x and dy as they lie: the head's rows of the turned arrays are its lanes of these
            dcb = dcb + dot(dy_ref[:, rows], x_ref[:, rows], NT) * row(dt) * mask
            xs_scr[rows, :] = (u * row(to_end)).astype(dtype)
            dys_scr[rows, :] = dys.astype(dtype)
            dd_scr[rows, :] += dyi * xi
        dcb = dcb.astype(dtype)
        dys = dys_scr[mine, :]
        db_ref[:, lanes] = dot(dcb, c, TN) + dot(xs_scr[mine, :], gs[mine].astype(dtype), TN)
        dc_ref[:, lanes] = dot(dcb, b, NN) + dot(dys, h[mine].astype(dtype), TN)
        from_y = dot(dys, c, NN)  # (e^G dy)^T C: the cotangent the chunk's start takes from its outputs
        for i, rows, local in heads:
            carried[rows, :] = left[i:i + 1, :] * gs[rows] + from_y[local]
    dx_ref[...] = dxt_scr[...].T
    ddta = dot(rows_scr[1], earlier.astype(_F32), NN) + rows_scr[2]  # d(dtA)_i = sum of dG_t over t >= i
    ddta_ref[...] = ddta
    ddt_ref[...] = rows_scr[0] + a_ref[...] * ddta

    @pl.when(k == pl.num_programs(2) - 1)
    def _():
        dd_ref[...] = jnp.sum(dd_scr[...], axis=1, keepdims=True)


def _call(kernel, name, dims, dtype, last_first, in_kinds, out_kinds, scratch_kinds, operands):
    """A walk over (batch, blocks of heads, chunks): a row's chunks in order
    (from the last with `last_first`), a block's state, or its cotangent,
    crossing from chunk to chunk in scratch. `dims` = (B, S, H, P, groups, N,
    chunk, heads a block), S whole chunks. Kinds of blocks: "tokens" (a
    chunk's tokens of a block of heads, x's dtype), "rows" (dt and its like as
    (B, H, S) float32), "group" (a chunk of the block's group, or groups, of
    B or C, (B, S, groups x N)), "decay" (A as (H, 1)), "skip" (D (H,), whole in SMEM),
    "state" (a chunk's, of (B, K, H x P, N)), "last" (B, H x P, N), "lane" (B,
    blocks, 1, N), "channel" (B, H x P, 1), "shares" (a block's share of dB
    or dC, (B, blocks a group, S, groups x N)); scratch alone: "turned32",
    "turned16" (a block's channels by a chunk's tokens), "carried", "rows3"."""
    b, s, h, p, groups, n, chunk, block = dims
    chunks, width = s // chunk, block * p
    a_group, held = max(1, h // groups // block), max(1, block * groups // h)  # blocks a group, groups a block

    def at(k):
        return chunks - 1 - k if last_first else k

    specs = {"tokens": pl.BlockSpec((None, chunk, width), lambda i, j, k: (i, at(k), j)),
             "rows": pl.BlockSpec((None, block, chunk), lambda i, j, k: (i, j, at(k))),
             "group": pl.BlockSpec((None, chunk, held * n), lambda i, j, k: (i, at(k), j // a_group)),
             "decay": pl.BlockSpec((block, 1), lambda i, j, k: (j, 0)),
             "skip": pl.BlockSpec(memory_space=pltpu.SMEM),
             "state": pl.BlockSpec((None, None, width, n), lambda i, j, k: (i, at(k), j, 0)),
             "last": pl.BlockSpec((None, width, n), lambda i, j, k: (i, j, 0)),
             "lane": pl.BlockSpec((None, None, 1, n), lambda i, j, k: (i, j, 0, 0)),
             "channel": pl.BlockSpec((None, width, 1), lambda i, j, k: (i, j, 0)),
             "shares": pl.BlockSpec((None, None, chunk, held * n),
                                    lambda i, j, k: (i, j % a_group, at(k), j // a_group))}
    shapes = {"tokens": ((b, s, h * p), dtype), "rows": ((b, h, s), _F32), "state": ((b, chunks, h * p, n), _F32),
              "last": ((b, h * p, n), _F32), "lane": ((b, h // block, 1, n), _F32), "channel": ((b, h * p, 1), _F32),
              "shares": ((b, a_group, s, groups * n), _F32),
              "turned32": ((width, chunk), _F32), "turned16": ((width, chunk), dtype), "carried": ((width, n), _F32),
              "rows3": ((3, block, chunk), _F32)}
    return pl.pallas_call(
        functools.partial(kernel, p=p, groups=held),
        grid=(b, h // block, chunks),
        in_specs=[specs[kind] for kind in in_kinds], out_specs=[specs[kind] for kind in out_kinds],
        out_shape=[jax.ShapeDtypeStruct(*shapes[kind]) for kind in out_kinds],
        scratch_shapes=[pltpu.VMEM(*shapes[kind]) for kind in scratch_kinds],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
        name=name,
    )(*operands)


def _flat(x, dt, a, bm, cm, d, chunk, *more):
    """The operands as the kernels read them, the sequence padded to whole
    chunks with zeros (dt = 0: no decay, nothing written): D (H,), x (B, S', H
    P), dt (B, H, S'), B and C (B, S', groups N), A (H, 1), and `more` as x."""
    s = x.shape[1]
    chunk = min(chunk, s)

    def whole(t):
        return jnp.pad(t.reshape(t.shape[:2] + (-1,)), ((0, 0), (0, -s % chunk), (0, 0)))

    return chunk, (d.astype(_F32), whole(x), jnp.swapaxes(whole(dt), 1, 2), whole(bm), whole(cm),
                   a.astype(_F32)[:, None]) + tuple(whole(t) for t in more)


def _dims(x, bm, chunk, block):
    b, s, h, p = x.shape
    return (b, s + -s % chunk, h, p, 1 if bm.ndim == 3 else bm.shape[2], bm.shape[-1], chunk, block)


@traced_once(0, 1, 2)
def _kernel_forward(chunk, block, keep, x, dt, a, bm, cm, d):
    """`ssd_fwd` -> y (B, S, H, P), the final states (B, H, P, N), the largest
    |h| a row of the batch, and with `keep` the chunks' starting states (B, K,
    H P, N)."""
    b, s, h, p = x.shape
    chunk, operands = _flat(x, dt, a, bm, cm, d, chunk)
    out = _call(functools.partial(_fwd_kernel, keep=keep), "ssd_fwd", _dims(x, bm, chunk, block), x.dtype, False,
                ["skip", "tokens", "rows", "group", "group", "decay"],
                ["tokens"] + ["state"] * keep + ["last", "lane"], ["carried", "turned16", "turned32"], operands)
    y, last, peak = out[0], out[-2], out[-1]
    return (y[:, :s].reshape(x.shape), last.reshape(b, h, p, -1), jnp.max(peak, axis=(1, 2, 3)),
            out[1] if keep else None)


@traced_once(0, 1)
def _kernel_backward(chunk, block, x, dt, a, bm, cm, d, starts, dy):
    """`ssd_bwd` and what XLA adds up of its shares."""
    b, s, h, p = x.shape
    chunk, operands = _flat(x, dt, a, bm, cm, d, chunk, dy)
    dx, ddt, ddta, db, dc, dd = _call(
        _bwd_kernel, "ssd_bwd", _dims(x, bm, chunk, block), x.dtype, True,
        ["skip", "tokens", "rows", "group", "group", "decay", "state", "tokens"],
        ["tokens", "rows", "rows", "shares", "shares", "channel"],
        ["carried", "carried", "turned16", "turned16", "turned16", "turned32", "rows3"],
        operands[:6] + (starts, operands[6]))
    db, dc = (jnp.sum(t, axis=1)[:, :s].reshape(bm.shape).astype(bm.dtype) for t in (db, dc))
    da = jnp.sum(operands[2] * ddta, axis=(0, 2))  # dt = 0 in the padded tail
    return (dx[:, :s].reshape(x.shape), jnp.swapaxes(ddt, 1, 2)[:, :s].astype(dt.dtype), da.astype(a.dtype), db, dc,
            jnp.sum(dd.reshape(b, h, p), axis=(0, 2)).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_scan(x, dt, a, bm, cm, d, chunk, block):
    return _kernel_forward(chunk, block, False, x, dt, a, bm, cm, d)[:3]


def _kernel_scan_fwd(x, dt, a, bm, cm, d, chunk, block):
    y, last, peak, starts = _kernel_forward(chunk, block, True, x, dt, a, bm, cm, d)
    return (y, last, peak), (x, dt, a, bm, cm, d, starts)


def _kernel_scan_bwd(chunk, block, kept, cotangents):
    return _kernel_backward(chunk, block, *kept, cotangents[0])


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def _heads_a_block(heads: int, groups: int, p: int) -> int:
    """The heads a grid step of the kernels holds: the largest divisor of the
    heads up to `CHANNELS` channels that is a part of one group or whole groups."""
    a_group = heads // groups
    return max(g for g in range(1, max(1, min(CHANNELS // p, heads)) + 1)
               if heads % g == 0 and (a_group % g == 0 or g % a_group == 0))


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array, cm: jax.Array, d: jax.Array,
             *, chunk: int = CHUNK, heads_at_once: int = HEADS_AT_ONCE,
             state_dtype=_F32, impl: str = "auto",
             sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, H, P); dt (B, S, H) float32, after its softplus; a (H,) < 0;
    bm, cm (B, S, d_state), shared by all the heads, or (B, S, groups,
    d_state), head n reading group n // (H / groups); d (H,) the skip -> y (B, S,
    H, P) in x's dtype, the final states (B, H, P, d_state) float32, and the
    largest magnitude of any head's state at any chunk's end (a scalar).
    Differentiable in x, dt, a, bm, cm, d through `y` (the kernels take the
    cotangents of the final state and of the counter as zero: the layers read
    y alone; the XLA form differentiates through all three).

    `chunk`: tokens a chunk (any: the mathematics holds for all, and a
    sequence that is no multiple is padded with `dt = 0`); `heads_at_once`:
    the heads whose masks are alive together in the XLA form (the largest
    divisor of a group's heads up to it); `state_dtype`: what the carried
    state is rounded to a chunk.

    `impl` as in `selective_scan`: "pallas" the kernels (`ssd_fwd`,
    `ssd_bwd`), "xla" the XLA form, "auto" the kernels where the operands lie
    on TPUs (`sharding`'s mesh says so; with none, the default backend), the
    heads are 64 or 128 wide with states of 128 (the widths compiled for the
    chip; tests/ops/test_tpu_compile_ssd.py holds the cells'), a block of heads
    (`_heads_a_block`) is whole tiles of lanes and of dt's sublanes, the chunk
    whole tiles of 128 tokens, the state float32 and the call sits on one
    device or, with `sharding`, on whole rows of the batch a device;
    everything else, the CPU among it, the XLA form. Said to `obs/forms` as
    `SSD`'s "pallas: ..." or the XLA form's string."""
    b, s, h, p = x.shape
    groups = 1 if bm.ndim == 3 else bm.shape[2]
    assert h % groups == 0, (h, groups)
    chunk, state_dtype = int(chunk), jnp.dtype(state_dtype)
    block = _heads_a_block(h, groups, p)
    # a block's lanes whole tiles, its rows of dt a tile's sublanes (or all the heads)
    fits = (p in (64, 128) and (block * p) % TILE == 0 and (block % 8 == 0 or block == h) and bm.shape[-1] == TILE
            and min(chunk, s) % TILE == 0 and state_dtype == _F32)
    kernels, sharding = on_kernels(sharding, b, fits)
    if impl == "auto":
        impl = "pallas" if kernels else "xla"
    plural = "" if groups == 1 else "s"
    if impl == "pallas":
        if state_dtype != _F32:
            raise ValueError("ssd_scan: the kernels hold a float32 state; got one in %s" % state_dtype.name)
        forms.took(forms.SSD, "pallas: %d group%s x %d heads a block" % (groups, plural, block))
        y, last, peak = rows_a_device(lambda *operands: _kernel_scan(*operands, chunk, block), sharding,
                                      (x, dt.astype(_F32), a, bm, cm, d), (2, 5), (4, 4, 1))
        return y, last, jnp.max(peak)
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    n = (s + pad) // chunk
    group = max(g for g in range(1, min(heads_at_once, h // groups) + 1) if (h // groups) % g == 0)
    forms.took(forms.SSD, "%d group%s x %d heads at once" % (groups, plural, group))
    def heads_first(t):  # (B, S', H, ...) -> (H / G, N, B, G, C, ...)
        t = t.reshape((b, n, chunk, h // group, group) + t.shape[3:])
        return t.transpose((3, 1, 0, 4, 2) + tuple(range(5, t.ndim)))

    shared = bm.ndim == 3  # one group: every batch of heads reads the same B and C
    if shared:
        bm, cm = (t.reshape(b, n, chunk, -1).transpose(1, 0, 2, 3) for t in (bm, cm))
    else:
        # (B, S', groups, d_state) -> (H / G, N, B, C, d_state): a batch of heads its group's B and C
        bm, cm = (t.reshape(b, n, chunk, groups, -1).transpose(3, 1, 0, 2, 4) for t in (bm, cm))
        if h // groups > group:  # several batches of heads a group
            bm, cm = (jnp.repeat(t, h // groups // group, axis=0) for t in (bm, cm))
    core = jax.checkpoint(functools.partial(_group_core, state_dtype=state_dtype),
                          policy=jax.checkpoint_policies.save_only_these_names(STARTS))
    y, last, peak = jax.lax.map(
        (lambda g: core(*g, bm, cm)) if shared else (lambda g: core(*g)),
        (heads_first(x), heads_first(dt.astype(_F32)),
         a.astype(_F32).reshape(h // group, group), d.astype(_F32).reshape(h // group, group))
        + (() if shared else (bm, cm)))
    # (H / G, N, B, G, C, P) -> (B, S', H, P)
    y = y.transpose(2, 1, 4, 0, 3, 5).reshape(b, s + pad, h, p)[:, :s]
    return y, last.transpose(1, 0, 2, 3, 4).reshape(b, h, p, -1), jnp.max(peak)
