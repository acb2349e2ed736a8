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

**The form** (XLA's, on every backend): the sequence is cut into `CHUNK`-token
chunks, K of them, and what runs along the sequence is never one step a token:

1. a chunk's contribution to the state it ends in, from a zero start, is a
   SUM and no recurrence: `end_k = sum_p exp(A (D_last - D_p)) dt_p x_p B_p`,
   `D` the running sum of `dt` inside the chunk (the decays' running sums in
   log space: every exponent is `A x (a later sum - an earlier one)`, <= 0);
2. the states the chunks START from are one multiply-add a chunk (`lax.scan`
   of K steps on a (N, channels) state: `start_{k+1} = exp(A D_last) start_k +
   end_k`);
3. from its true start every chunk runs its `CHUNK` tokens ALL CHUNKS AT
   ONCE: a `lax.scan` over the position in the chunk whose carry is every
   chunk's state, (K, N, channels) float32, read and written once a position.

So a pass is `CHUNK + K` dependent steps (192 at 8192 tokens and chunks of
128) and not 8192, the arrays alive are a chunk's-worth of states ((K, N,
channels) = 1 / CHUNK of the (tokens, channels, N) array, 21 MB a sequence at
the published widths) and no (B, S, channels, N) array exists, forward or
backward. The price is HBM: step 3 moves every state once a token, 2 x tokens
x channels x N x 4 bytes a pass (5.4 GB at the published widths), which a
kernel that kept a channel block's state in VMEM would not (PERF.md section
7, "Selective-scan layers").

**The backward is written** (`jax.custom_vjp`): it keeps x, dt, A, B, C, D and
the chunks' STARTING states, recomputes inside a chunk and never differentiates
through the loops. The cotangent of the state runs the same three steps
mirrored (a sum a chunk, K steps backward, the positions backward); where a
term needs the state AND its cotangent at one token (`dA`, `ddt`), the
position loop is cut into blocks of `BLOCK` positions: the states at the
blocks' starts are made in one forward sweep, and each block's `BLOCK` states
are remade just before its cotangents run backward through them, so
(CHUNK / BLOCK + BLOCK) states a chunk are alive, not CHUNK. A position of the
backward reads ONE stored state, its predecessor's (its own is what the
position after it read, handed on in the loop's carry).

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
from typing import Tuple

import jax
import jax.numpy as jnp

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


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array, d: jax.Array,
                   *, chunk: int = CHUNK, state_dtype=_F32) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, C) in the compute dtype; dt (B, S, C) float32, after its
    softplus; a (C, N) < 0; b, c (B, S, N), shared by the channels; d (C,) the
    skip -> m (B, S, C) in x's dtype, the final states (B, C, N) float32, and
    the largest magnitude of any state at any chunk's end (a scalar).

    `chunk`: tokens a chunk (any: the mathematics holds for all, and a sequence
    that is no multiple is padded with `dt = 0`); `state_dtype`: what the
    carried state is rounded to a token. Differentiable in x, dt, a, b, c, d
    through `m` (the module's docstring)."""
    return _scan(x, dt.astype(_F32), a, b, c, d, int(chunk), jnp.dtype(state_dtype))
