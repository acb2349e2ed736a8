"""Mamba-1's selective scan: the core of a Mamba-1 layer (Mamba, arXiv:2312.00752;
Phi-4-mini-flash's nine layers in thirty-two, arXiv:2507.06607).

A channel c carries a state `h[c, :]` of `N` floats along the sequence, `h_0 = 0`:

    h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] x_t[c]     A < 0, dt_t > 0
    m_t[c]    = sum_n C_t[n] h_t[c, n] + D[c] x_t[c]

`B_t` and `C_t` (N,) are shared by every channel. It differs from Mamba-2's
scan (ops/ssd.py) in ONE thing that decides the form: the decay is a float a
(channel, state), not a float a head, so a chunk's map of the state has no
matmul form worth having (the tokens x tokens decay mask of `ops/ssd.py` would
be one a (channel, state): 81920 of them at Phi-4-mini-flash's widths). The
work is elementwise over a (channels, N) state a token, which the vector unit
and HBM bound and the MXU cannot help.

**The form**: the sequence is cut into `CHUNK`-token chunks, K of them, and
what runs along the sequence is never one step a token:

1. a chunk's contribution to the state it ends in, from a zero start, is a
   SUM and no recurrence: `end_k = sum_p exp(A (D_last - D_p)) dt_p x_p B_p`,
   `D` the running sum of `dt` inside the chunk (the decays' running sums in
   log space: every exponent is `A x (a later sum - an earlier one)`, <= 0);
2. the states the chunks START from are one multiply-add a chunk
   (`start_{k+1} = exp(A D_last) start_k + end_k`);
3. from its true start every chunk runs its `CHUNK` tokens.

Steps 1 and 2 are what makes a long state accurate: a term of the final
state is multiplied by ONE exponential a chunk, of a summed exponent, not by
a product of up to 8192 rounded decays (the chip's `exp` reads about 1e-6 x
|its argument| low; a state carried token by token lies 1.4e-4 off a float64
recurrence at the sequence's end, this form 2e-6: PERF.md section 6, PR 57).

**Two forms of it, chosen by what the call observes** (`selective_scan`):

- **XLA's** (the CPU, the tests' oracle, every shape the kernels do not
  take): step 2 is a `lax.scan` of K steps on a (N, channels) state, and step
  3 runs ALL CHUNKS AT ONCE, a `lax.scan` over the position in the chunk whose
  carry is every chunk's state, (K, N, channels) float32, read and written
  once a position. A pass is `CHUNK + K` dependent steps (192 at 8192 tokens
  and chunks of 128) and no (B, S, channels, N) array exists, forward or
  backward; the price is HBM: step 3 moves every state once a token, 2 x
  tokens x channels x N x 4 bytes a pass (5.4 GB at the published widths).
- **The kernels'** (`selscan_fwd`, `selscan_bwd`; on TPUs): a grid step holds
  ONE chunk's state for a block of `CHANNELS` channels, (N, channels a block)
  float32 with the states in the sublanes and the channels in the lanes, in
  registers through the chunk's positions; the chunks of a row run in order
  and the state between them stays in VMEM. The three steps are one walk: the
  positions run `h` from the chunk's start and, beside it, step 1's sum (its
  running sums a product with a triangle of ones on the MXU, as here), and
  the chunk's end is step 2's multiply-add. HBM carries x, dt, B, C, m once
  and a state a CHUNK (21 MB a sequence), the rule's residual.

**The backward is written** (`jax.custom_vjp`): it keeps x, dt, A, B, C, D and
the chunks' STARTING states, recomputes inside a chunk and never differentiates
through the loops. The cotangent of the state runs the same three steps
mirrored (a sum a chunk, K steps backward, the positions backward). Where a
term needs the state AND its cotangent at one token (`dA`, `ddt`), the XLA
form cuts the position loop into blocks of `BLOCK` positions: the states at
the blocks' starts are made in one forward sweep, and each block's `BLOCK`
states are remade just before its cotangents run backward through them, so
(CHUNK / BLOCK + BLOCK) states a chunk are alive, not CHUNK (a position reads
ONE stored state, its predecessor's); the kernel remakes a chunk's CHUNK
states and decays into VMEM (8 MiB a block of 512 channels) and walks back
through them, the chunks of a row from the last. dB and dC are sums over the
channels: a grid step adds its tiles of channels, sums the lanes on the MXU
and writes its block's share, which XLA adds up.

Float32: dt, A, the running sums, every exponential, the state, its carry and
every sum over N or over channels, whatever dtype x came in; x and m are in
the compute dtype. `state_dtype` rounds the carried state after every token
(float32 is the rule; the tests' and the chip check's control carries it in
bfloat16). A sequence that is no multiple of the chunk is padded at its end
with tokens of `dt = 0`, which neither decay the state nor write to it.
The cotangents of the final state and of the counter are taken as zero: the
layers read `m` alone.
"""

from __future__ import annotations

import functools
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.obs import forms
from galvatron_tpu.ops.kernels import NN, NT, TILE, KernelSharding, dot, on_kernels, rows_a_device, traced_once

CHUNK = 128  # (scripts/selscan_sweep.py: 64, 128 and 256 lie within a tenth of one another; 128 holds the least)
BLOCK = 8  # positions whose states the backward holds at once (divides the chunk, or the chunk is one block)
_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def _rounded(state, state_dtype):
    if state_dtype == _F32:
        return state
    kind = jnp.finfo(state_dtype)  # not a cast there and back, which the TPU compiler takes out
    return jax.lax.reduce_precision(state, exponent_bits=kind.nexp, mantissa_bits=kind.nmant)


def _running_sums(dt):
    """(B, K, T, C) float32 -> the inclusive running sum along T: a product with
    a triangle of ones, which a TPU runs on the MXU (`jnp.cumsum` lowers to a
    slow `reduce_window` there: PERF.md, PR 39)."""
    t = dt.shape[2]
    lower = jnp.tril(jnp.ones((t, t), _F32))
    return jnp.einsum("bkjc,tj->bktc", dt, lower, precision=_HIGHEST, preferred_element_type=_F32)


def _across_chunks(decay, add, last_first: bool, state_dtype=_F32):
    """`s' = decay_k s + add_k` along the chunk axis (axis 1 of (B, K, N, C)) from
    zero, forward or (`last_first`) backward -> the state each chunk is ENTERED
    with in that direction, (B, K, N, C), and the state after the last one."""
    def step(state, da):
        return _rounded(da[0] * state + da[1], state_dtype), state

    xs = (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(add, 1, 0))
    final, entered = jax.lax.scan(step, jnp.zeros_like(add[:, 0]), xs, reverse=last_first)
    return jnp.moveaxis(entered, 0, 1), final


def _by_position(t):
    """(B, K, T, ...) -> (T, B, K, ...): what a scan over the position in the chunk reads a step."""
    return jnp.moveaxis(t, 2, 0)


def _states_from(start, a, dt_p, u_p, b_p, state_dtype):
    """The positions' states from the chunks' `start`, all chunks at once: `lax.scan`
    over (T', B, K, ...) inputs -> the state after the last position, and the state
    BEFORE every position (T', B, K, N, C): `h_{p-1}` beside position p."""
    def step(h, t):
        dt, u, b = t
        after = _rounded(jnp.exp(dt[:, :, None, :] * a) * h + u[:, :, None, :] * b[..., None], state_dtype)
        return after, h

    return jax.lax.scan(step, start, (dt_p, u_p, b_p))


def _chunked(x, dt, b, c, chunk):
    """Pad the sequence to whole chunks (dt = 0: no decay, nothing written) and
    cut it: (B, S, ...) -> (B, K, T, ...), float32 but x."""
    s = x.shape[1]
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in (x, dt, b, c))
    k = (s + pad) // chunk

    def cut(t, dtype=_F32):
        return t.reshape(t.shape[0], k, chunk, t.shape[-1]).astype(dtype)

    return cut(x, x.dtype), cut(dt), cut(b), cut(c)


def _forward(x, dt, a, b, c, d, chunk, state_dtype):
    """-> m (B, S, C) in x's dtype, the final state (B, C, N), the counter, and
    the chunks' starting states (B, K, N, C)."""
    s, dtype = x.shape[1], x.dtype
    xk, dtk, bk, ck = _chunked(x, dt, b, c, chunk)
    a_t = a.astype(_F32).T  # (N, C): the channels in the lanes
    u = dtk * xk.astype(_F32)  # dt_p x_p
    sums = _running_sums(dtk)
    total = sums[:, :, -1]  # (B, K, C)
    # 1. a chunk's contribution to its end state: a sum over its positions, exponents <= 0
    to_end = jnp.exp((total[:, :, None] - sums)[:, :, :, None, :] * a_t)  # (B, K, T, N, C), fused into the sum
    end = jnp.sum(to_end * (u[:, :, :, None, :] * bk[..., None]), axis=2)
    # 2. the states the chunks start from
    starts, final = _across_chunks(jnp.exp(total[:, :, None, :] * a_t), end, False, state_dtype)
    # 3. every chunk's positions from its true start, all chunks at once
    def step(h, t):
        dt_p, u_p, b_p, c_p = t
        h = _rounded(jnp.exp(dt_p[:, :, None, :] * a_t) * h + u_p[:, :, None, :] * b_p[..., None], state_dtype)
        return h, jnp.sum(c_p[..., None] * h, axis=2)

    _, m = jax.lax.scan(step, starts, tuple(_by_position(t) for t in (dtk, u, bk, ck)))
    m = jnp.moveaxis(m, 0, 2).reshape(x.shape[0], -1, x.shape[2])[:, :s]
    m = (m + d.astype(_F32) * x.astype(_F32)).astype(dtype)
    peak = jnp.maximum(jnp.max(jnp.abs(starts)), jnp.max(jnp.abs(final)))
    return m, jnp.swapaxes(final, 1, 2), peak, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, a, b, c, d, chunk, state_dtype):
    return _forward(x, dt, a, b, c, d, chunk, state_dtype)[:3]


def _scan_fwd(x, dt, a, b, c, d, chunk, state_dtype):
    m, final, peak, starts = _forward(x, dt, a, b, c, d, chunk, state_dtype)
    return (m, final, peak), (x, dt, a, b, c, d, starts)


def _scan_bwd(chunk, state_dtype, kept, cotangents):
    """The gradients of x, dt, A, B, C, D from m's cotangent (the final
    state's and the counter's are taken as zero). With `g_t` the cotangent of
    `h_t`, `g_t = C_t gm_t + exp(dt_{t+1} A) g_{t+1}`:

        dC_t[n] = sum_c gm_t[c] h_t[c, n]            dB_t[n] = sum_c g_t[c, n] dt_t[c] x_t[c]
        w_t = g_t * exp(dt_t A) h_{t-1}              s_t[c] = sum_n g_t[c, n] B_t[n]
        dx_t = dt_t s_t + D gm_t                     ddt_t = x_t s_t + sum_n A w_t
        dA = sum_t dt_t w_t                          dD = sum_t gm_t x_t
    """
    x, dt, a, b, c, d, starts = kept
    gm = cotangents[0]
    s, dtype = x.shape[1], x.dtype
    xk, dtk, bk, ck = _chunked(x, dt, b, c, chunk)
    gmk = _chunked(gm, dt, b, c, chunk)[0].astype(_F32)
    t_len = xk.shape[2]
    a_t = a.astype(_F32).T
    x32 = xk.astype(_F32)
    u = dtk * x32
    sums = _running_sums(dtk)
    total = sums[:, :, -1]
    # the cotangent each chunk's END state is entered with, from the chunks after it: a chunk's
    # own share is a sum over its positions (exponents <= 0), then K steps backward
    from_start = jnp.exp(sums[:, :, :, None, :] * a_t)  # (B, K, T, N, C), fused into the sum
    own = jnp.sum(from_start * (gmk[:, :, :, None, :] * ck[..., None]), axis=2)
    enters, _ = _across_chunks(jnp.exp(total[:, :, None, :] * a_t), own, True)
    # the positions, in blocks: the states at the blocks' starts in one sweep forward ...
    block = BLOCK if t_len % BLOCK == 0 else t_len
    by_block = tuple(_by_position(t).reshape((t_len // block, block) + t.shape[:2] + t.shape[3:])
                     for t in (dtk, u, bk, ck, x32, gmk))

    def block_start(h, t):
        return _states_from(h, a_t, t[0], t[1], t[2], state_dtype)[0], h

    _, block_starts = jax.lax.scan(block_start, starts, by_block[:3])

    # ... then block by block backward: its states again, and the cotangent through them. A position reads
    # ONE stored state, its predecessor's: its own is what the position after it read (the carry's third)
    def one_block(carry, t):
        g_in, da = carry  # the cotangent entering the block's last state from after it; dA so far
        h0, (dt_b, u_b, b_b, c_b, x_b, gm_b) = t
        last, before = _states_from(h0, a_t, dt_b, u_b, b_b, state_dtype)

        def position(carry, t):
            g_in, da, h_p = carry
            dt_p, u_p, b_p, c_p, x_p, gm_p, h_before = t
            g = c_p[..., None] * gm_p[:, :, None, :] + g_in  # (B, K, N, C)
            decay = jnp.exp(dt_p[:, :, None, :] * a_t)
            w = g * decay * h_before
            s_p = jnp.sum(g * b_p[..., None], axis=2)  # (B, K, C)
            out = (dt_p * s_p,  # dx, but for D's term
                   x_p * s_p + jnp.sum(w * a_t, axis=2),  # ddt
                   jnp.sum(g * u_p[:, :, None, :], axis=3),  # dB (B, K, N)
                   jnp.sum(h_p * gm_p[:, :, None, :], axis=3))  # dC
            da = da + jnp.sum(w * dt_p[:, :, None, :], axis=(0, 1))
            return (decay * g, da, h_before), out

        (g_in, da, _), outs = jax.lax.scan(position, (g_in, da, last), (dt_b, u_b, b_b, c_b, x_b, gm_b, before),
                                           reverse=True)
        return (g_in, da), outs

    (_, da), outs = jax.lax.scan(one_block, (enters, jnp.zeros_like(a_t)), (block_starts, by_block), reverse=True)

    def whole(t):  # (blocks, block, B, K, ...) -> (B, S, ...)
        t = jnp.moveaxis(t.reshape((t_len,) + t.shape[2:]), 0, 2)
        return t.reshape(t.shape[0], -1, t.shape[-1])[:, :s]

    dx, ddt, db, dc = (whole(t) for t in outs)
    gm32, xs32 = gm.astype(_F32), x.astype(_F32)
    dx = dx + d.astype(_F32) * gm32
    dd = jnp.sum(gm32 * xs32, axis=(0, 1))
    return (dx.astype(dtype), ddt.astype(dt.dtype), da.T.astype(a.dtype), db.astype(b.dtype),
            dc.astype(c.dtype), dd.astype(d.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


# ---------------------------------------------------------------------------
# The kernel form (the module's docstring): a grid step is one chunk of one
# block of channels; the walk is (batch, chunks in order, blocks of channels).
# ---------------------------------------------------------------------------

CHANNELS = 512  # the channels a grid step holds (scripts/selscan_sweep.py)
UNROLL = 8  # positions a trip of the kernels' loops
_VMEM = 64 * 2**20  # what a kernel may hold of the chip's 128 MiB (the backward's chunk of states and decays is 8 MiB)


def _columns_to_lanes(rows_ref, out_ref):
    """rows (N, T), a float a (state, position) shared by every channel ->
    out[t] (N, 128): position t's column across a tile of lanes, what a
    position multiplies a (N, channels) state by. Once a chunk, on the MXU: row
    (t, n) of a (T N, T) matrix holds rows[n, t] at column t and zeros, and
    its product with ones lays that float across the lanes, exactly."""
    rows = rows_ref[...]
    n, t = rows.shape
    at = jax.lax.broadcasted_iota(jnp.int32, (t, n, t), 0) == jax.lax.broadcasted_iota(jnp.int32, (t, n, t), 2)
    alone = jnp.where(at, rows[None], 0.0).reshape(t * n, t)
    out_ref[...] = dot(alone, jnp.ones((t, out_ref.shape[2]), _F32), NN).reshape(out_ref.shape)


def _begin(bt_ref, ct_ref, b_scr, c_scr, carried):
    """What a grid step does before its positions -> the block of channels it
    is: B and C, the chunk's whatever the block, are laid across the lanes at
    the chunk's first block, and a row's first chunk walked finds zeros in
    what is carried from chunk to chunk."""
    k, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        _columns_to_lanes(bt_ref, b_scr)
        _columns_to_lanes(ct_ref, c_scr)

    @pl.when(k == 0)
    def _():
        carried[j] = jnp.zeros(carried.shape[1:], _F32)

    return j


def _positions(count, unroll, body, carry, last_first=False):
    """`carry = body(t, carry)` over a chunk's positions in order (or from the
    last): a loop of `unroll` positions a trip (Mosaic unrolls a `fori_loop`
    whole or not at all)."""
    unroll = unroll if count % unroll == 0 else 1

    def trip(i, carry):
        for j in range(unroll):
            t = i * unroll + j
            carry = body(count - 1 - t if last_first else t, carry)
        return carry

    return jax.lax.fori_loop(0, count // unroll, trip, carry)


def _lanes(tile, width):
    """(N, 128) -> (N, width): the same tile under every tile of channels."""
    return tile if tile.shape[1] == width else jnp.concatenate([tile] * (width // tile.shape[1]), axis=1)


def _over_tiles(prod, lanes):
    """(N, width) -> (N, lanes): the tiles of channels added up, what is left
    of a sum over channels for the MXU (`_lane_sums`)."""
    return functools.reduce(operator.add, (prod[:, i:i + lanes] for i in range(0, prod.shape[1], lanes)))


def _lane_sums(parts_ref):
    """(rows, 128) float32 -> (1, rows): every row summed over its lanes, a
    product with ones on the MXU (idle here), float32 throughout."""
    return dot(jnp.ones((8, parts_ref.shape[1]), _F32), parts_ref[...], NT)[0:1]


def _sums_in_the_chunk(dt_ref):
    """dt (T, Cb) -> its inclusive running sum along T, as `_running_sums`: a
    product with a triangle of ones on the MXU (idle here), float32."""
    t = dt_ref.shape[0]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0) >= jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))
    return dot(lower.astype(_F32), dt_ref[...], NN)


def _fwd_kernel(x_ref, dt_ref, bt_ref, ct_ref, a_ref, d_ref, m_ref, end_ref, b_scr, c_scr, u_scr, y_scr, left_scr,
                carried, *, unroll):
    """The form's three steps for one chunk and a block of channels: x, dt (T,
    Cb), B, C (N, T), A (N, Cb), D (1, Cb) -> m (T, Cb) float32 and the state
    the chunk ends in (N, Cb). The chunks of a row run in order and `carried`
    holds every block's state between them. The positions run the state `h`
    from the chunk's start, which `m` reads, and beside it step 1's SUM, the
    chunk's own contribution to its end with ONE exponential of a summed
    exponent a term (`left_scr`: what is left of the chunk's dt after a
    position); the next chunk starts from `exp(A sum dt) start + that sum`,
    step 2. (`h` handed on instead would be a product of 8192 rounded decays
    at the sequence's end: the module's docstring.)"""
    t_len, width = dt_ref.shape
    j = _begin(bt_ref, ct_ref, b_scr, c_scr, carried)
    start = carried[j]
    u_scr[...] = dt_ref[...] * x_ref[...].astype(_F32)
    sums = _sums_in_the_chunk(dt_ref)
    total = sums[t_len - 1:t_len]
    left_scr[...] = total - sums
    a = a_ref[...]

    def position(t, states):
        h, own = states
        row = pl.ds(t, 1)
        add = u_scr[row, :] * _lanes(b_scr[t], width)
        h = jnp.exp(dt_ref[row, :] * a) * h + add
        y_scr[row, :] = jnp.sum(_lanes(c_scr[t], width) * h, axis=0, keepdims=True)
        return h, own + jnp.exp(left_scr[row, :] * a) * add

    _, own = _positions(t_len, unroll, position, (start, jnp.zeros_like(start)))
    m_ref[...] = y_scr[...] + d_ref[...] * x_ref[...].astype(_F32)
    carried[j] = end_ref[...] = jnp.exp(total * a) * start + own


def _bwd_kernel(x_ref, dt_ref, gm_ref, bt_ref, ct_ref, a_ref, d_ref, start_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                b_scr, c_scr, u_scr, gm_scr, h_scr, decay_scr, s_scr, aw_scr, pb_scr, pc_scr, carried, *, unroll):
    """`_scan_bwd`'s formulas for one chunk and a block of channels: the
    chunk's states again from its kept start, all T of them and their decays
    in VMEM, then the positions backward from the cotangent entering the
    chunk's end. The chunks of a row run from the LAST, and `carried` holds
    every block's cotangent between them (zero after the sequence's end),
    handed on as the positions leave it: a gradient's share from 8192 tokens on is a product of as many
    rounded decays, some 1e-4 off and long decayed, where the XLA form's
    `enters` sums the exponents a chunk; no limit on a gradient is that fine.
    -> dx, ddt (T, Cb), this block's share of dB and dC (1, T N), the chunk's
    share of dA (N, Cb)."""
    t_len, width = dt_ref.shape
    n, lanes = b_scr.shape[1:]
    j = _begin(bt_ref, ct_ref, b_scr, c_scr, carried)
    u_scr[...] = dt_ref[...] * x_ref[...].astype(_F32)
    gm_scr[...] = gm_ref[...].astype(_F32)
    a = a_ref[...]
    h_scr[0] = start_ref[...]

    def forward(t, h):  # h_scr[t] the state BEFORE position t
        row = pl.ds(t, 1)
        decay_scr[t] = decay = jnp.exp(dt_ref[row, :] * a)  # one exponential a (position, state, channel) a pass
        h = decay * h + u_scr[row, :] * _lanes(b_scr[t], width)
        h_scr[t + 1] = h
        pc_scr[pl.ds(pl.multiple_of(t * n, n), n), :] = _over_tiles(h * gm_scr[row, :], lanes)  # dC_t's parts
        return h

    _positions(t_len, unroll, forward, start_ref[...])

    def backward(t, carry):
        g_in, da = carry  # the cotangent entering position t's state from after it; dA so far
        row = pl.ds(t, 1)
        dt_t = dt_ref[row, :]
        g = _lanes(c_scr[t], width) * gm_scr[row, :] + g_in
        g_in = g * decay_scr[t]
        w = g_in * h_scr[t]
        s_scr[row, :] = jnp.sum(g * _lanes(b_scr[t], width), axis=0, keepdims=True)
        aw_scr[row, :] = jnp.sum(w * a, axis=0, keepdims=True)
        pb_scr[pl.ds(pl.multiple_of(t * n, n), n), :] = _over_tiles(g * u_scr[row, :], lanes)  # dB_t's parts
        return g_in, da + w * dt_t

    carried[j], da_ref[...] = _positions(t_len, unroll, backward, (carried[j], jnp.zeros_like(a)), last_first=True)
    dx_ref[...] = (dt_ref[...] * s_scr[...] + d_ref[...] * gm_scr[...]).astype(dx_ref.dtype)
    ddt_ref[...] = x_ref[...].astype(_F32) * s_scr[...] + aw_scr[...]
    db_ref[...] = _lane_sums(pb_scr)
    dc_ref[...] = _lane_sums(pc_scr)


def _call(kernel, name, chunk, block, dims, last_first, in_kinds, out_kinds, scratch_kinds, dtype, operands):
    """A walk over (batch, chunks, blocks of channels): a row's chunks in order
    (from the last with `last_first`), a chunk's blocks in order (the chunk's
    B and C are laid across the lanes at its first; the state, or its
    cotangent, crosses from chunk to chunk in "carried"). `dims` = (B, S, C,
    N), S whole chunks. Kinds of blocks: "tokens" (a chunk's tokens of a block
    of channels, the operands' dtype) and "tokens32", "rows" (B or C as (B, N,
    S)), "weights" (A as (N, C)), "skip" (D as (1, C)), "state" (a chunk's, of
    (B, K, N, C)), "shares" (a block's share of dB or dC, (B, C / Cb, 1, S
    N)); scratch alone: "across" (a chunk's B or C
    by position across a tile of lanes), "states" (the chunk's T + 1 states),
    "a_chunk" (T, Cb), "parts" (T N, 128), "carried" (every block's state)."""
    b, s, ch, n = dims
    chunks, lanes = s // chunk, min(TILE, block)

    def at(k):
        return chunks - 1 - k if last_first else k

    specs = {"tokens": pl.BlockSpec((None, chunk, block), lambda i, k, j: (i, at(k), j)),
             "rows": pl.BlockSpec((None, n, chunk), lambda i, k, j: (i, 0, at(k))),
             "weights": pl.BlockSpec((n, block), lambda i, k, j: (0, j)),
             "skip": pl.BlockSpec((1, block), lambda i, k, j: (0, j)),
             "state": pl.BlockSpec((None, None, n, block), lambda i, k, j: (i, at(k), 0, j)),
             "shares": pl.BlockSpec((None, None, 1, chunk * n), lambda i, k, j: (i, j, 0, at(k)))}
    specs["tokens32"] = specs["tokens"]
    shapes = {"tokens": ((b, s, ch), dtype), "tokens32": ((b, s, ch), _F32),
              "state": ((b, chunks, n, ch), _F32), "shares": ((b, ch // block, 1, s * n), _F32),
              "across": ((chunk, n, lanes), _F32), "states": ((chunk + 1, n, block), _F32),
              "a_chunk": ((chunk, block), _F32), "parts": ((chunk * n, lanes), _F32),
              "carried": ((ch // block, n, block), _F32)}
    return pl.pallas_call(
        functools.partial(kernel, unroll=UNROLL),
        grid=(b, chunks, ch // block),
        in_specs=[specs[kind] for kind in in_kinds], out_specs=[specs[kind] for kind in out_kinds],
        out_shape=[jax.ShapeDtypeStruct(*shapes[kind]) for kind in out_kinds],
        scratch_shapes=[pltpu.VMEM(*shapes[kind]) for kind in scratch_kinds],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
        name=name,
    )(*operands)


def _as_rows(t):
    """(B, S, N) -> (B, N, S) float32: a state's floats by position in the lanes."""
    return jnp.swapaxes(t.astype(_F32), 1, 2)


def _whole_chunks(chunk, *ts):
    """(B, S, ...) each -> the tokens a chunk and each padded to whole chunks
    with zeros (dt = 0: no decay, nothing written)."""
    s = ts[0].shape[1]
    chunk = min(chunk, s)
    return chunk, tuple(jnp.pad(t, ((0, 0), (0, -s % chunk), (0, 0))) for t in ts)


@traced_once(0, 1)
def _kernel_forward(chunk, block, x, dt, a, b, c, d):
    """`_forward` as `selscan_fwd`; the counter a row of the batch."""
    s = x.shape[1]
    chunk, (xp, dtp, bp, cp) = _whole_chunks(chunk, x, dt, b, c)
    dims = (x.shape[0], xp.shape[1], x.shape[2], a.shape[1])
    m, ends = _call(
        _fwd_kernel, "selscan_fwd", chunk, block, dims, False,
        ["tokens", "tokens32", "rows", "rows", "weights", "skip"], ["tokens32", "state"],
        ["across", "across", "a_chunk", "a_chunk", "a_chunk", "carried"], x.dtype,
        (xp, dtp, _as_rows(bp), _as_rows(cp), a.astype(_F32).T, d.astype(_F32)[None]))
    starts = jnp.pad(ends[:, :-1], ((0, 0), (1, 0), (0, 0), (0, 0)))  # a chunk starts where the one before it ended
    # m leaves the kernel float32 and is rounded HERE, as the XLA form rounds it: XLA fuses the cast into what reads
    # m (the layer's gate) as it does there; rounded inside the kernel, the step's roundings are others (PERF.md, PR 58)
    return (m[:, :s].astype(x.dtype), jnp.swapaxes(ends[:, -1], 1, 2), jnp.max(jnp.abs(ends), axis=(1, 2, 3)),
            starts)


@traced_once(0, 1)
def _kernel_backward(chunk, block, x, dt, a, b, c, d, starts, gm):
    """`_scan_bwd` as `selscan_bwd` and what XLA adds up of its shares."""
    s, n = x.shape[1], a.shape[1]
    chunk, (xp, dtp, bp, cp, gmp) = _whole_chunks(chunk, x, dt, b, c, gm)
    dims = (x.shape[0], xp.shape[1], x.shape[2], n)
    dx, ddt, db, dc, da = _call(
        _bwd_kernel, "selscan_bwd", chunk, block, dims, True,
        ["tokens", "tokens32", "tokens", "rows", "rows", "weights", "skip", "state"],
        ["tokens", "tokens32", "shares", "shares", "state"],
        ["across", "across", "a_chunk", "a_chunk", "states", "states", "a_chunk", "a_chunk", "parts", "parts",
         "carried"],
        x.dtype, (xp, dtp, gmp, _as_rows(bp), _as_rows(cp), a.astype(_F32).T, d.astype(_F32)[None],
                  starts))
    db, dc = (jnp.sum(t.reshape(t.shape[0], t.shape[1], -1, n), axis=1)[:, :s] for t in (db, dc))
    dd = jnp.sum(gm.astype(_F32) * x.astype(_F32), axis=(0, 1))
    return (dx[:, :s], ddt[:, :s].astype(dt.dtype), jnp.sum(da, axis=(0, 1)).T.astype(a.dtype), db.astype(b.dtype),
            dc.astype(c.dtype), dd.astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _kernel_scan(x, dt, a, b, c, d, chunk, block):
    return _kernel_forward(chunk, block, x, dt, a, b, c, d)[:3]


def _kernel_scan_fwd(x, dt, a, b, c, d, chunk, block):
    m, final, peak, starts = _kernel_forward(chunk, block, x, dt, a, b, c, d)
    return (m, final, peak), (x, dt, a, b, c, d, starts)


def _kernel_scan_bwd(chunk, block, kept, cotangents):
    return _kernel_backward(chunk, block, *kept, cotangents[0])


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array,
                   *, chunk: int = CHUNK, state_dtype=_F32, impl: str = "auto",
                   sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, C) in the compute dtype; dt (B, S, C) float32, after its
    softplus; a (C, N) < 0; b, c (B, S, N), shared by the channels; d (C,) the
    skip -> m (B, S, C) in x's dtype, the final states (B, C, N) float32, and
    the largest magnitude of any state at any chunk's end (a scalar).

    `chunk`: tokens a chunk (any: the mathematics holds for all, and a sequence
    that is no multiple is padded with `dt = 0`); `state_dtype`: what the
    carried state is rounded to a token. Differentiable in x, dt, a, b, c, d
    through `m` (the module's docstring).

    `impl` as in `linear_attention.kda_rule`: "pallas" the kernels
    (`selscan_fwd`, `selscan_bwd`), "xla" the XLA form, "auto" the kernels
    where the operands lie on TPUs (`sharding`'s mesh says so; with none, the
    default backend), the channels are whole blocks of `CHANNELS`, N is a
    multiple of 8, the chunk whole tiles of 128 tokens, the state float32 and
    the call sits on one device or, with `sharding`, on whole rows of the
    batch a device; everything else, the CPU among it, the XLA form. Said to
    `obs/forms` as `SELECTIVE_SCAN`'s "pallas" / "xla"."""
    chunk, state_dtype = int(chunk), jnp.dtype(state_dtype)
    fits = (x.shape[2] % CHANNELS == 0 and a.shape[1] % 8 == 0 and min(chunk, x.shape[1]) % TILE == 0
            and state_dtype == _F32)
    kernels, sharding = on_kernels(sharding, x.shape[0], fits)
    if impl == "auto":
        impl = "pallas" if kernels else "xla"
    forms.took(forms.SELECTIVE_SCAN, impl)
    if impl == "xla":
        return _scan(x, dt.astype(_F32), a, b, c, d, chunk, state_dtype)
    if x.shape[2] % CHANNELS or state_dtype != _F32:
        raise ValueError("selective_scan: the kernels hold a float32 state for blocks of %d channels; got %d "
                         "channels and a state in %s" % (CHANNELS, x.shape[2], state_dtype.name))
    m, final, peak = rows_a_device(lambda *operands: _kernel_scan(*operands, chunk, CHANNELS), sharding,
                                   (x, dt.astype(_F32), a, b, c, d), (2, 5), (3, 3, 1))
    return m, final, jnp.max(peak)
