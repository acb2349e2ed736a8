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

The heads are worked `HEADS_AT_ONCE` at a time (`lax.map`; of a model with
groups the heads worked at once lie in ONE group, so at most a group's, and
the map runs over the groups' B and C beside the heads'), so the masks
alive at once are (heads at once, chunks, CHUNK, CHUNK) and never all heads': 64
heads x 32 chunks x 128 x 128 float32 would be 128 MiB a tensor at 4096
tokens. On the chip (PERF.md, PR 39; scripts/ssd_sweep.py) a layer at the
Granite-4.0-H cell's widths takes 1.28 ms forward and 3.22 forward + backward
at chunks of 128 and 16 heads at a time; 8 heads 1.30 / 3.44, chunks of 64
and 256 and all 64 heads at once are slower, and so is a form with two
64-wide heads side by side in a tile's 128 lanes (1.93 / 4.26). With 8 groups
of 8 heads at 8192 tokens (Nemotron-H's; PERF.md, PR 71; `scripts/ssd_sweep.py
--groups 8 --tokens 8192`, `chiprun_out/ssd_sweep_groups.json`) a block's scan
takes 2.25 / 5.79 ms at chunks of 128 and a group's 8 heads at once, 2.51 /
6.81 at 4 heads, 4.83 / 14.43 at chunks of 64 and 1.94 / 5.45 at chunks of 256
(2 ms of that cell's 288 ms step: `CHUNK` stays one number for both cells).

**The backward** is autodiff's through a group's arithmetic, made again from
x, dt, A, B, C and the chunks' STARTING STATES, which alone are kept
(`jax.checkpoint` saving `STARTS`: a sequence's worth of float32 (d_head,
d_state) a chunk and head): the chunk-boundary states are kept and everything
within a chunk is recomputed.

Float32: `dt`, `dt A`, their running sums and every exponential of them;
the chunk's contribution to the state (float32 operands at the highest
matmul precision: whatever error it has is carried to the sequence's end),
the state and its carry. The products on the way to the OUTPUT (`C B^T`,
the masked product with x, `C h_c`) run on operands of the dtype x came in,
accumulated in float32: their error stays in the chunk it was made in.
`state_dtype` rounds the carried state after every chunk (float32 is the
rule; the tests' and the chip check's control carries it in bfloat16).

A sequence that is no multiple of the chunk is padded at its end with
tokens of `dt = 0`: they neither decay the state nor write to it, and their
outputs are cut off. The published `mamba_chunk_size` (256) is a kernel's
block and no mathematics; the chunk here is what the chip likes.

Sequences are whole rows of the batch: the state is not reset at a document
boundary inside a packed row.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from galvatron_tpu.obs import forms

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


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, bm: jax.Array, cm: jax.Array, d: jax.Array,
             *, chunk: int = CHUNK, heads_at_once: int = HEADS_AT_ONCE,
             state_dtype=_F32) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x (B, S, H, P); dt (B, S, H) float32, after its softplus; a (H,) < 0;
    bm, cm (B, S, d_state), shared by all the heads, or (B, S, groups,
    d_state), head n reading group n // (H / groups); d (H,) the skip -> y (B, S,
    H, P) in x's dtype, the final states (B, H, P, d_state) float32, and the
    largest magnitude of any head's state at any chunk's end (a scalar).

    `chunk`: tokens a chunk (any: the mathematics holds for all, and a
    sequence that is no multiple is padded with `dt = 0`); `heads_at_once`:
    the heads whose masks are alive together (the largest divisor of a
    group's heads up to it); `state_dtype`: what the carried state is rounded to a chunk."""
    b, s, h, p = x.shape
    groups = 1 if bm.ndim == 3 else bm.shape[2]
    assert h % groups == 0, (h, groups)
    chunk = min(chunk, s)
    pad = -s % chunk
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                         for t in (x, dt, bm, cm))
    n = (s + pad) // chunk
    group = max(g for g in range(1, min(heads_at_once, h // groups) + 1) if (h // groups) % g == 0)
    forms.took(forms.SSD, "%d group%s x %d heads at once" % (groups, "" if groups == 1 else "s", group))

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
