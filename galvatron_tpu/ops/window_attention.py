"""Softmax attention over a window of the last keys as two Pallas kernels
whose work follows the BAND (`ops/attention.core_attention(window=)` on a
TPU): query i sees the keys `i - window < j <= i`.

A grid step is one block of `b` queries of one query head: beside the
queries' own block of keys it is handed the `ceil((window - 1) / b)` blocks
before it and no other, so the whole band of its rows lies in VMEM at once
and the softmax is ONE pass, no running maximum and no rescaling (what a
flash kernel carries from key block to key block). Blocks wholly outside the
band are neither loaded nor multiplied, forward and backward. The grid is
(batch, key head, query block, query head of the key head's group) with the
group INNERMOST: the blocks of k and v keep their index while the group's
query heads pass, so they are fetched once a key head (GQA by indexing,
nothing repeated), and the backward sums dk and dv over the group in the
output block it holds.

The backward is one kernel a query block too: it makes the probabilities
again from q and k (the rows are whole, so nothing of the forward is kept
but q, k and v), `delta = sum_j p dp`, and dq, and its share of dk and dv a
key block: one output array a position of the key block in the step (own,
one before, ...), which the caller shifts by whole blocks and adds (`_bwd`).
Both custom calls carry their names, `window_attn_fwd` and `window_attn_bwd`,
which none of the flash kernels' begin with.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# queries a grid step; the keys it multiplies are the block's own and the
# window's reach before it, (window - 1) / BLOCK blocks rounded up: at a window
# of 512 a row meets 2.0 x its band at 512, 1.5 x at 256, against half as many
# and half as long grid steps. scripts/window_attn_sweep.py times them on the chip
BLOCK = 512
MAX_KEY_BLOCKS = 4  # a step's key blocks all lie in VMEM: a window of up to 3 blocks and the block's own
_VMEM = 64 * 2**20


def block_for(seq: int, window: int, block: int = 0) -> int:
    """The query block the kernels would take at this length and window, or 0
    where they have no form: the largest of `block` (default `BLOCK`), its
    halves down to 128 that divides the sequence and reaches the window in at
    most `MAX_KEY_BLOCKS` - 1 blocks."""
    b = block or BLOCK
    while b >= 128:
        if seq % b == 0 and math.ceil((window - 1) / b) < MAX_KEY_BLOCKS:
            return b
        b //= 2
    return 0


def _band(step, block: int, before: int, window: int):
    """(block, (before + 1) x block) bool: which of a step's keys each of its
    queries sees. Column c is key (step - before) x block + c, row r query
    step x block + r: the key is not after the query, less than `window`
    before it, and not before the sequence (the first steps' blocks before it
    are block 0 again, masked here)."""
    shape = (block, (before + 1) * block)
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    back = rows - cols + before * block  # the query's position less the key's
    return (back >= 0) & (back < window) & (cols >= (before - step) * block)


def _probabilities(q, keys, step, *, scale: float, block: int, window: int):
    """The band's softmax of one query block, the rows whole: float32 (block,
    keys) before the division by the rows' sums, and one over those sums (block, 1)."""
    before = len(keys) - 1
    s = jnp.concatenate([jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                         for k in keys], axis=1) * scale
    s = jnp.where(_band(step, block, before, window), s, MASK_VALUE)
    p = jnp.exp(s - jnp.max(s, axis=1, keepdims=True))
    return p, 1.0 / jnp.sum(p, axis=1, keepdims=True)


def _fwd_kernel(q_ref, *refs, scale: float, block: int, window: int, blocks: int):
    k_refs, v_refs, o_ref = refs[:blocks], refs[blocks:2 * blocks], refs[2 * blocks]
    p, one_over = _probabilities(q_ref[...], [r[...] for r in k_refs], pl.program_id(2),
                                 scale=scale, block=block, window=window)
    p = p.astype(v_refs[0].dtype)
    o = sum(jnp.dot(p[:, j * block:(j + 1) * block], v_refs[j][...], preferred_element_type=jnp.float32)
            for j in range(blocks))
    o_ref[...] = (o * one_over).astype(o_ref.dtype)  # the rows' sums divide (block, head_dim), not (block, keys)


def _bwd_kernel(q_ref, do_ref, *refs, scale: float, block: int, window: int, blocks: int):
    k_refs, v_refs = refs[:blocks], refs[blocks:2 * blocks]
    dq_ref, dk_refs, dv_refs = refs[2 * blocks], refs[2 * blocks + 1:3 * blocks + 1], refs[3 * blocks + 1:]
    q, do = q_ref[...], do_ref[...]
    p, one_over = _probabilities(q, [r[...] for r in k_refs], pl.program_id(2), scale=scale, block=block, window=window)
    p = p * one_over
    dp = jnp.concatenate([jax.lax.dot_general(do, r[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                          for r in v_refs], axis=1)
    ds = (p * (dp - jnp.sum(p * dp, axis=1, keepdims=True)) * scale).astype(q.dtype)
    p = p.astype(q.dtype)
    dq_ref[...] = sum(jnp.dot(ds[:, j * block:(j + 1) * block], k_refs[j][...], preferred_element_type=jnp.float32)
                      for j in range(blocks)).astype(dq_ref.dtype)

    @pl.when(pl.program_id(3) == 0)  # the group's first query head: the key head's sums start
    def _():
        for ref in dk_refs + dv_refs:
            ref[...] = jnp.zeros_like(ref)

    for j in range(blocks):
        cols = slice(j * block, (j + 1) * block)
        dk_refs[j][...] += jax.lax.dot_general(ds[:, cols], q, (((0,), (0,)), ((), ())),
                                               preferred_element_type=jnp.float32)
        dv_refs[j][...] += jax.lax.dot_general(p[:, cols], do, (((0,), (0,)), ((), ())),
                                               preferred_element_type=jnp.float32)


def _pallas(kernel, name: str, q, k, *, window: int, scale: float, block: int, last_axis: str):
    """(the kernel's `pallas_call` but for its specs and shapes, the block spec
    of a (batch, heads, seq, head_dim) array at the query heads, those of one
    at the key heads a position of the step's key blocks, and that of a key
    head's output a step, the keys' blocks before a step's own)."""
    b, nh, s, hd = q.shape
    nkv, before = k.shape[1], math.ceil((window - 1) / block)
    group, shape = nh // nkv, (None, None, block, hd)
    at_query = pl.BlockSpec(shape, lambda b, h, i, g: (b, h * group + g, i, 0))
    at_key = [pl.BlockSpec(shape, lambda b, h, i, g, j=j: (b, h, jnp.maximum(i - before + j, 0), 0))
              for j in range(before + 1)]
    a_step = pl.BlockSpec(shape, lambda b, h, i, g: (b, h, i, 0))
    call = functools.partial(
        pl.pallas_call, functools.partial(kernel, scale=scale, block=block, window=window, blocks=before + 1),
        grid=(b, nkv, s // block, group), name=name,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3 + (last_axis,),
                                             vmem_limit_bytes=_VMEM))
    return call, at_query, at_key, a_step, before


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def window_attention(q, k, v, window: int, scale: float, block: int):
    """q (B, nh, S, hd), k and v (B, nkv, S, hd), nkv dividing nh, S a multiple
    of `block` (`block_for`) -> (B, nh, S, hd): softmax(q k^T x scale) v over
    the keys `i - window < j <= i`, query head h on key head h // (nh / nkv)."""
    return _fwd(q, k, v, window, scale, block)[0]


def _fwd(q, k, v, window: int, scale: float, block: int):
    call, at_query, at_key, _, before = _pallas(_fwd_kernel, "window_attn_fwd", q, k, window=window, scale=scale,
                                                block=block, last_axis="parallel")
    out = call(in_specs=[at_query] + at_key * 2, out_specs=at_query,
               out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype))(q, *[k] * (before + 1), *[v] * (before + 1))
    return out, (q, k, v)


def _bwd(window: int, scale: float, block: int, kept, do):
    q, k, v = kept
    call, at_query, at_key, a_step, before = _pallas(_bwd_kernel, "window_attn_bwd", q, k, window=window, scale=scale,
                                                     block=block, last_axis="arbitrary")  # the group's sums
    sums = jax.ShapeDtypeStruct(k.shape, jnp.float32)
    dq, *shares = call(
        in_specs=[at_query] * 2 + at_key * 2, out_specs=[at_query] + [a_step] * (2 * before + 2),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)] + [sums] * (2 * before + 2),
    )(q, do, *[k] * (before + 1), *[v] * (before + 1))

    def gathered(parts):
        """Share j of step i belongs to key block i - before + j: moved there by
        whole blocks (a pad behind the sequence, then a slice: XLA:TPU has shifted
        a concatenation within tiles, PERF.md section 7) and added."""
        total = parts[before]
        for j in range(before):
            reach = (before - j) * block
            total = total + jnp.pad(parts[j], ((0, 0), (0, 0), (0, reach), (0, 0)))[:, :, reach:]
        return total

    return dq, gathered(shares[:before + 1]).astype(k.dtype), gathered(shares[before + 1:]).astype(v.dtype)


window_attention.defvjp(_fwd, _bwd)
