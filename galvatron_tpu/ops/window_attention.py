"""Softmax attention over a window of the last keys as two Pallas kernels
whose work follows the BAND (`ops/attention.core_attention(window=)` on a
TPU): query i sees the keys `i - window < j <= i`.

The operands are read AS PROJECTED: q, k, v, the output and every cotangent
are (batch, seq, heads x head_dim) arrays, what the projections' matmuls write
and `wo`'s reads, and a head is a block of head_dim lanes (a multiple of 128)
of them: no (batch, heads, seq, head_dim) copy of anything is made. What lay
between the q projection and the kernel, and between the kernel and `wo`, as
passes over a q-sized array rides the kernels (PR 50): **rope on q** (the
half-split turn of whole heads: a step turns its block in VMEM from two
float32 tables, `x * cos + roll(x, head_dim / 2) * sin`, rounds it once, and
the backward turns dq back by the transposed rotation before it writes it; k
comes turned, or a step would turn it again for each of its group's query
heads) and **the head's gate** (`sigmoid` of the head's column of the (batch,
seq, heads) logits on the float32 sums before the output's one rounding; the
backward gates `do` in VMEM and yields the logits' cotangent). Both are
optional operands: a call without them is the band alone.

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
Under a gate `delta` is also `sum_d (do x gate) x the ungated output`, so the
logits' cotangent is `delta x (1 - sigmoid)`: the ungated output is neither
kept nor made again.
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


def _head_column(gate_ref, head):
    """(block, heads) gate logits -> sigmoid of head `head`'s column, (block, 1)
    float32: the column is picked by a mask and a sum over the lanes (a lane
    cannot be indexed by a grid position; picking it on the MXU, by a product
    with a one-hot (heads, 128) matrix, read 0.3 ms a call slower on the chip:
    PERF.md section 6, PR 50)."""
    logits = gate_ref[...].astype(jnp.float32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    return jax.nn.sigmoid(jnp.sum(jnp.where(lanes == head, logits, 0.0), axis=1, keepdims=True))


def _operands(refs, blocks: int, rope: bool, gate: bool, leading: int):
    """A kernel's refs as (the `leading` first, (cos, sin) or None, the gate
    logits or None, k's blocks, v's blocks, the outputs)."""
    first, refs = refs[:leading], refs[leading:]
    tables, refs = (refs[:2], refs[2:]) if rope else (None, refs)
    gates, refs = (refs[0], refs[1:]) if gate else (None, refs)
    return first, tables, gates, refs[:blocks], refs[blocks:2 * blocks], refs[2 * blocks:]


def _query(q_ref, tables):
    """The step's queries as the matmuls read them: where the call brought the
    rotation's tables (`ops/rope.half_split_tables`: [cos | cos], [-sin | sin]),
    turned in float32, `apply_rotary`'s half-split form with a head's halves
    swapped by a roll of the lanes, and rounded once."""
    if tables is None:
        return q_ref[...]
    q = q_ref[...].astype(jnp.float32)
    return (q * tables[0][...] + pltpu.roll(q, q.shape[1] // 2, 1) * tables[1][...]).astype(q_ref.dtype)


def _fwd_kernel(*refs, scale: float, block: int, window: int, blocks: int, group: int, rope: bool, gate: bool):
    (q_ref,), tables, gates, k_refs, v_refs, (o_ref,) = _operands(refs, blocks, rope, gate, 1)
    p, one_over = _probabilities(_query(q_ref, tables), [r[...] for r in k_refs], pl.program_id(2),
                                 scale=scale, block=block, window=window)
    p = p.astype(v_refs[0].dtype)
    o = sum(jnp.dot(p[:, j * block:(j + 1) * block], v_refs[j][...], preferred_element_type=jnp.float32)
            for j in range(blocks))
    if gate:  # the head's gate on the float32 sums: one rounding, the output's
        one_over = one_over * _head_column(gates, pl.program_id(1) * group + pl.program_id(3))
    o_ref[...] = (o * one_over).astype(o_ref.dtype)  # the rows' sums divide (block, head_dim), not (block, keys)


def _bwd_kernel(*refs, scale: float, block: int, window: int, blocks: int, group: int, rope: bool, gate: bool):
    (q_ref, do_ref), tables, gates, k_refs, v_refs, outs = _operands(refs, blocks, rope, gate, 2)
    dq_ref, outs = outs[0], outs[1:]
    dg_ref, outs = (outs[0], outs[1:]) if gate else (None, outs)
    dk_refs, dv_refs = outs[:blocks], outs[blocks:]
    q, do = _query(q_ref, tables), do_ref[...]
    if gate:  # the cotangent of the UNGATED output, rounded where the product after the call rounded it
        open_ = _head_column(gates, pl.program_id(1) * group + pl.program_id(3))
        do = (do.astype(jnp.float32) * open_).astype(do.dtype)
    p, one_over = _probabilities(q, [r[...] for r in k_refs], pl.program_id(2), scale=scale, block=block, window=window)
    p = p * one_over
    dp = jnp.concatenate([jax.lax.dot_general(do, r[...], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                          for r in v_refs], axis=1)
    delta = jnp.sum(p * dp, axis=1, keepdims=True)  # = sum_d do x the ungated output: nothing of the forward is read
    ds = (p * (dp - delta) * scale).astype(q.dtype)
    p = p.astype(q.dtype)
    dq = sum(jnp.dot(ds[:, j * block:(j + 1) * block], k_refs[j][...], preferred_element_type=jnp.float32)
             for j in range(blocks))
    if tables is not None:  # the rotation's transpose: the tables' product first, then the roll
        dq = dq * tables[0][...] + pltpu.roll(dq * tables[1][...], dq.shape[1] // 2, 1)
    dq_ref[...] = dq.astype(dq_ref.dtype)
    if gate:
        # d logit = sum_d (do x ungated output) x sigmoid' = (delta / sigmoid) x sigmoid (1 - sigmoid); the group's
        # query heads fill the (block, group) block a lane each
        lanes = jax.lax.broadcasted_iota(jnp.int32, dg_ref.shape, 1)
        dg_ref[...] = jnp.where(lanes == pl.program_id(3), delta * (1.0 - open_), dg_ref[...])

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


def _pallas(kernel, name: str, first, k, v, tables, gates, *, window: int, scale: float, block: int, head_dim: int,
            last_axis: str):
    """The kernel's `pallas_call` on `first` (q; q and do), the tables and gate
    logits the call brought, and k's and v's blocks of a step, as a function of
    its out_specs and out_shape -> (that, the block spec of a (batch, seq, heads
    x head_dim) array at a step's query head, that of a key head's output a
    step, the keys' blocks before a step's own, the query heads a key head)."""
    q = first[0]
    nkv, before = k.shape[2] // head_dim, math.ceil((window - 1) / block)
    group, shape = q.shape[2] // k.shape[2], (None, block, head_dim)
    at_query = pl.BlockSpec(shape, lambda b, h, i, g: (b, i, h * group + g))
    at_key = [pl.BlockSpec(shape, lambda b, h, i, g, j=j: (b, jnp.maximum(i - before + j, 0), h))
              for j in range(before + 1)]
    a_step = pl.BlockSpec(shape, lambda b, h, i, g: (b, i, h))
    rows = lambda width: pl.BlockSpec((None, block, width), lambda b, h, i, g: (b, i, 0))  # noqa: E731
    brought = list(tables or ()) + ([] if gates is None else [gates])  # all heads' rows of a step, whatever its head
    body = functools.partial(kernel, scale=scale, block=block, window=window, blocks=before + 1, group=group,
                             rope=tables is not None, gate=gates is not None)

    def call(out_specs, out_shape):
        return pl.pallas_call(
            body, grid=(q.shape[0], nkv, q.shape[1] // block, group), name=name,
            in_specs=[at_query] * len(first) + [rows(t.shape[2]) for t in brought] + at_key * 2,
            out_specs=out_specs, out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3 + (last_axis,),
                                                 vmem_limit_bytes=_VMEM),
        )(*first, *brought, *[k] * (before + 1), *[v] * (before + 1))

    return call, at_query, a_step, before, group


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def window_attention(q, k, v, tables, gates, window: int, scale: float, block: int, head_dim: int):
    """q (B, S, nh x head_dim), k and v (B, S, nkv x head_dim), AS PROJECTED: a
    head is a block of `head_dim` lanes (a multiple of 128) of what the matmul
    wrote; nkv divides nh, S is a multiple of `block` (`block_for`) -> (B, S,
    nh x head_dim): softmax(q k^T x scale) v over the keys `i - window < j <=
    i`, query head h on key head h // (nh / nkv). `tables`: None, or (cos, sin)
    float32 (B, S, head_dim) of `ops/rope.half_split_tables`: q comes UNTURNED
    and a step turns its block in VMEM, in float32, rounded once (k comes
    turned: a step would turn it again for each of its group's query heads).
    `gates`: None, or (B, S, nh) logits: head h's output x sigmoid(its column),
    on the float32 sums before the output's one rounding."""
    return _fwd(q, k, v, tables, gates, window, scale, block, head_dim)[0]


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))  # traced once a shape, not once a layer run and phase
def _forward(q, k, v, tables, gates, window: int, scale: float, block: int, head_dim: int):
    call, at_query, _, _, _ = _pallas(_fwd_kernel, "window_attn_fwd", (q,), k, v, tables, gates, window=window,
                                      scale=scale, block=block, head_dim=head_dim, last_axis="parallel")
    return call(at_query, jax.ShapeDtypeStruct(q.shape, q.dtype))


def _fwd(q, k, v, tables, gates, window: int, scale: float, block: int, head_dim: int):
    return _forward(q, k, v, tables, gates, window, scale, block, head_dim), (q, k, v, tables, gates)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _bwd(window: int, scale: float, block: int, head_dim: int, kept, do):
    q, k, v, tables, gates = kept
    call, at_query, a_step, before, group = _pallas(
        _bwd_kernel, "window_attn_bwd", (q, do), k, v, tables, gates, window=window, scale=scale, block=block,
        head_dim=head_dim, last_axis="arbitrary")  # the group's sums
    out_specs, out_shape = [at_query], [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if gates is not None:
        # the gate logits' cotangent a key head, its group's query heads the lanes: a step's block is revisited by
        # the group alone (a (block, heads) block of all heads would come back a key head later)
        out_specs.append(pl.BlockSpec((None, None, block, group), lambda b, h, i, g: (b, h, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((q.shape[0], k.shape[2] // head_dim, q.shape[1], group), jnp.float32))
    dq, *shares = call(out_specs + [a_step] * (2 * before + 2),
                       out_shape + [jax.ShapeDtypeStruct(k.shape, jnp.float32)] * (2 * before + 2))
    dgates = None
    if gates is not None:
        dgates, shares = shares[0].transpose(0, 2, 1, 3).reshape(gates.shape).astype(gates.dtype), shares[1:]

    def gathered(parts):
        """Share j of step i belongs to key block i - before + j: moved there by
        whole blocks (a pad behind the sequence, then a slice: XLA:TPU has shifted
        a concatenation within tiles, PERF.md section 7) and added."""
        total = parts[before]
        for j in range(before):
            reach = (before - j) * block
            total = total + jnp.pad(parts[j], ((0, 0), (0, reach), (0, 0)))[:, reach:]
        return total

    return (dq, gathered(shares[:before + 1]).astype(k.dtype), gathered(shares[before + 1:]).astype(v.dtype),
            None, dgates)  # (nothing flows to the tables: they come from positions)


window_attention.defvjp(_fwd, _bwd)
