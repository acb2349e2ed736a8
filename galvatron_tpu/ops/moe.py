"""Routed (sparse) experts: a float32 router, top-k, dropless dispatch.

The feed-forward half of a mixture-of-experts block as OLMoE runs it
(arXiv:2409.02060; HF `OlmoeSparseMoeBlock`): every token is sent to the
`k` experts its router ranks highest and the result is the sum of their
outputs weighted by the router's probabilities. **Dropless**: there is no
capacity factor, no padding to a fixed capacity and no dropped token; the
`tokens x k` assignments are sorted by expert, the rows gathered in that
order, and each expert multiplies its own ragged group of rows
(`grouped_matmul`: on a TPU the Pallas megablox kernels, each call at the
tiling its shapes take, `gmm_tiling`; elsewhere `jax.lax.ragged_dot`;
`scripts/moe_gmm_sweep.py` is the chip measurement behind the rule).

The router runs in float32 at `highest` matmul precision whatever the
compute dtype: the choice of experts is a discrete function of its logits,
and a bf16 router flips near-ties. Its two auxiliary losses come back with
the output, as the sums and counts they are made of, so that a caller that
splits the tokens over devices can add them up first:

    load balancing   E x sum_e f_e P_e   f_e = assignments to e / tokens
                                         P_e = mean router probability of e
    router z-loss    mean_t logsumexp(logits_t)^2

**The layout is k-major**: assignment `j x tokens + t` is token `t`'s `j`-th
choice, so whatever is in token order is `k` slabs of `(tokens, hidden)` and
the sums over `k` (the combine's forward, the dispatch's backward) add slabs.
A TPU tiles an array's two minor dimensions by 8 x 128: with `k` there, as in
`(tokens, k, hidden)`, every `k` that is not a multiple of 8 is padded to one,
each reshape to `(tokens x k, hidden)` moves every row, and the compiler
fuses nothing across it (float32 copies of all rows; PERF.md, PR 34). The ROWS
enter the grouped matmuls as they always did, expert by expert and token by
token within one: the sort's key is `expert x tokens + t`.

Dispatch and combine are permutations, so their transposes are gathers too
(`_dispatch`, `_combine`), not the scatter-adds autodiff would derive, and the
combine's backward works in expert order, where the rows and their cotangent
live: it keeps the bf16 rows and never a float32 array of every row. **On a
TPU three of a block's five moves of `k x tokens` rows are Pallas kernels that
copy row by row with queued DMAs** (`rows_form`, "The row movers" below: the
sums over k of the combine's forward and of the dispatch's backward, and the
combine's backward); the XLA gathers stay as the CPU's path, the path of every
shape the kernels do not take, the dispatch's own forward, and the tests'
oracle. Permutations of `k x tokens` SCALARS (the inverse order, the weights
in expert order, their gradient back in token order) are sorts (`_permuted`).

A second router (`score="sigmoid"`: DeepSeek-V3's, as GLM-4.7-Flash
configures it, `topk_method: noaux_tc`): each expert's score is the sigmoid
of its own logit, the `k` experts are chosen by score PLUS a bias that takes
no gradient, and the weights are the chosen experts' scores alone,
renormalised and scaled. That router has no auxiliary loss; it hands back the
assignments an expert got, which the train step moves the bias by.

**A share of the experts** (`held=(first, count)`): the router ranks all the
experts, this program holds the kernels of `count` of them and computes their
part of the result for the tokens sent to them; what the experts held
elsewhere would add is left out. Every assignment is still sorted, gathered
and combined (the shapes are static, and under expert parallelism the chip
that owns a token moves all of its assignments): the router, the dispatch and
the combine work on all `k x tokens` rows. **The experts work on a WINDOW of
them** (`window_rows`, `_windowed_block`): the held experts' rows are one
contiguous range of the sorted assignments, so both grouped matmuls, the
activation between them, the kernels' fill of the rows they skip and all of
their backward run over a static number of rows, `WINDOW_OVER_EVEN` times the
even share, that starts at the row tile below the range's first row; the
result is laid into zeros for the combine. A block whose held rows outgrow
the window takes the whole range instead, chosen on the device from the
counts (`jax.lax.cond`): nothing is dropped and nothing is rounded
differently, and the `step` event's `expert_window_fallbacks` counts such
blocks. Where the window would be no shorter than the range none is built.
What is NOT windowed: the router, the sort, the dispatch's gather of every
assignment's row, the combine's sum over k and both of their backwards.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops.kernels import KernelSharding, lies_on_tpu

# load_max_over_mean always; load_balance, router_z (the softmax router's
# losses); counts (E,) and bias_abs_max (a router with a bias); rows_held (a
# share of the experts) and window_fallbacks (its blocks, over the devices, whose
# experts took the whole range: 0 or 1 here): moe_aux_names says which for a
# configuration
Aux = Dict[str, jax.Array]


def moe_aux_names(score: str, bias: bool, held: bool) -> Tuple[str, ...]:
    return (("load_max_over_mean",)
            + (("load_balance", "router_z") if score == "softmax" else ())
            + (("counts", "bias_abs_max") if bias else ())
            + (("rows_held", "window_fallbacks") if held else ()))


# The megablox kernels' tiles (rows, K, N). `GMM_TILING` is what every call took
# until PR 69, measured on a v5e at OLMoE's shapes (65536 rows in 64 groups, K
# and N of 1024 and 2048: forward + backward of the two matmuls 17.5 ms against
# 24.2 for XLA:TPU's own `ragged_dot` kernel and 188 at megablox's default
# 128-tiles; PERF.md, PR 27), and is still where `gmm_tiling` starts from and
# what OLMoE's calls take. A tile wider than its array is not refused, it is
# MASKED and multiplied (a K or N of 512 at twice the passes, 2304 at 3 x 1024),
# and a 512-row tile that straddles a group's edge is visited once a group: so
# since PR 69 each call's tiling is chosen from that call's shapes. What
# `scripts/moe_gmm_sweep.py` measured on a v5e at the seven routed cells' shapes
# (`chiprun_out/moe_gmm_sweep.json`, each kernel alone at every candidate, uneven
# groups; ms a call, the parent's tiling -> the rule's):
# - a tile is a multiple of 128 that DIVIDES its dim (Laguna's 512-wide experts,
#   `gmm` of the down projection: 0.540 -> 0.193);
# - groups of at most 512 rows at an even routing take 256-row tiles (`gmm` of
#   Laguna's up projection at (512 | 256 | 128, 2048, 1024): 0.534, 0.368, 0.379)
#   and `tgmm`, whose row tile is the contracted dim, 128 (0.633 -> 0.514 -> 0.501);
# - `gmm` holds its contracted dim WHOLE where the blocks fit (one K step: a
#   group's kernel block stays in VMEM from row tile to row tile and the float32
#   sums are never read back; Laguna's up projection at (256, 1024 | 2048, 1024):
#   0.439, 0.368), then widens the N tile while they fit (Kimi-Linear's down
#   projection at (256, 1024, 768 | 1152 | 2304): 0.113, 0.109, 0.105); `tgmm`
#   widens its result tile's N (GLM's up projection at (128, 1024, 1024 | 1536):
#   0.540, 0.512);
# - "fit": the blocks a grid step holds by the kernels' own specs (operands and
#   result twice, the float32 sums once) inside `GMM_VMEM`, the largest count
#   that compiled in every kernel at every shape of the sweep; a count of 16 MiB
#   ran out of the scoped VMEM at OLMoE's shapes ((512, 2048, 1024)), which is
#   why OLMoE's calls, 1024 rows a group, K and N that 1024 divides, come out at
#   (512, 1024, 1024) as before. 4-byte operands were not timed: they keep tiles
#   of half the width, as before;
# - **a dim that NO multiple of 128 divides** (PR 71: Nemotron-H's experts are
#   1856 = 14.5 x 128 wide, the N of the up projection and the K of the down)
#   is ONE block, the dim whole, where the blocks fit: a block as wide as its
#   array is whole tiles to Mosaic, masked nowhere, and multiplies no padding,
#   where the 1024-wide tile it would otherwise keep is 2 x 1024 over 1856 with
#   megablox's mask on the rest. `chiprun_out/moe_gmm_sweep.json`,
#   `nemo3n-c1-s8k` (3072 even rows in 8 groups, uneven, ms a call, the masked
#   1024 -> the dim whole): `gmm` of the up projection (256, 896, 1024 | 1856)
#   0.351 -> 0.277, its `gmm_t` (256, 1024 | 1856, 896) 0.354 -> 0.273, its
#   `tgmm` (128, 896, 1024 | 1856) 0.356 -> 0.278; the down projection's 0.331 ->
#   0.279, 0.345 -> 0.301, 0.364 -> 0.320: a block's six kinds of call 2.101 ->
#   1.728 (the parent's one tiling 3.049; the best of every candidate 1.605,
#   with 128-row tiles and a K of 2688 whole, whose blocks are past `GMM_VMEM`).
#   The rival, the kernels padded to 1920 = 15 x 128 columns of zeros in the
#   compute copy (`relu(0)^2 = 0`, so exact), read 1.892 at its best tilings
#   and costs the pad and the slice besides: not taken.
GMM_TILING = (512, 1024, 1024)
GMM_KERNELS = ("gmm", "gmm_t", "tgmm")  # a grouped matmul's forward, its rows' cotangent, its kernels'
GROUP_ROW_TILE = 256  # of groups of at most `GMM_TILING[0]` rows at an even routing
TGMM_ROW_TILE = 128  # and of their `tgmm`
GMM_WHOLE = 2304  # the widest K or N the sweep held in one tile (Kimi-Linear's hidden size)
GMM_VMEM = 29 << 19  # 14.5 MiB of the 16 MiB of scoped VMEM


def row_tile(even_rows: float) -> int:
    """The row tile of a block whose groups hold `even_rows` rows at an even
    routing (`k x tokens / experts`): what its `gmm` calls take. A power of
    two no larger than `GMM_TILING[0]`, on which a share's window starts and
    of which it is whole tiles."""
    cap = GMM_TILING[0]
    return cap if even_rows > cap else min(cap, GROUP_ROW_TILE)


def _tiles_of(dim: int, most: int):
    """The multiples of 128 that divide `dim`, up to `most`, ascending; of a
    dim that none divides, the dim itself where it is no wider than `most`
    (ONE block, as wide as its array)."""
    if dim % 128:
        return [dim] if dim <= most else []
    return [tile for tile in range(128, min(dim, most) + 1, 128) if dim % tile == 0]


def _fit(dim: int, cap: int) -> int:
    """The largest multiple of 128 that divides `dim` and is no larger than
    `cap`: no block is wider than its array and megablox masks no rest. A dim
    that no multiple of 128 divides and that is wider than `cap` starts from
    `cap`, the rest of its last tile masked (Nemotron-H's 1856: `gmm_tiling`
    then takes it whole where the blocks fit)."""
    return (_tiles_of(dim, cap) or [cap])[-1]


def gmm_blocks_bytes(kernel: str, tiling: Tuple[int, int, int], itemsize: int = 2) -> int:
    """What a grid step of a megablox kernel holds in VMEM by its own specs:
    the two operand blocks and the result block twice (the pipeline's two
    buffers) and the float32 sums once. `gmm`: (tm, tk), (tk, tn) -> (tm, tn);
    `tgmm`: (tm, tk), (tm, tn) -> (tk, tn)."""
    tm, tk, tn = tiling
    if kernel == "tgmm":
        return 2 * itemsize * (tm * tk + tm * tn + tk * tn) + 4 * tk * tn
    return 2 * itemsize * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn


def gmm_tiling(kernel: str, k: int, n: int, even_rows: float, itemsize: int = 2) -> Tuple[int, int, int]:
    """(tm, tk, tn) of ONE megablox call, from that call's shapes: `kernel`
    (`GMM_KERNELS`), the dim its K tiles run over and the dim its N tiles run
    over (`gmm` of (G, K, N) kernels: K, N; `gmm_t`, the same kernels
    transposed: N, K; `tgmm`, whose row tile is the contracted dim and whose
    result tile is (tk, tn): K, N), the rows a group holds at an even routing
    and the operands' width in bytes. The rule and what it was measured
    against: the comment above `GMM_TILING`."""
    assert kernel in GMM_KERNELS, kernel
    wide = itemsize // 2  # 1 for bf16, 2 for float32
    tm, tk, tn = row_tile(even_rows), _fit(k, GMM_TILING[1] // wide), _fit(n, GMM_TILING[2] // wide)
    if wide != 1:
        return tm, tk, tn

    def fit(*tiling):
        return gmm_blocks_bytes(kernel, tiling, itemsize) <= GMM_VMEM

    if kernel == "tgmm" and even_rows <= GMM_TILING[0]:
        tm = min(tm, TGMM_ROW_TILE)
    # `gmm` holds its contracted dim whole where the blocks fit; every kernel a K that no multiple of 128 divides
    if (kernel != "tgmm" or k % 128) and k <= GMM_WHOLE and fit(tm, k, tn):
        tk = k
    tn = max([tn] + [wider for wider in _tiles_of(n, GMM_WHOLE) if fit(tm, tk, wider)])
    return tm, tk, tn


def matmul_calls(k: int, n: int):
    """((kernel, the dim its K tiles run over, the dim its N tiles run over), ...)
    of the three megablox calls of a grouped matmul of (G, K, N) kernels."""
    return tuple(zip(GMM_KERNELS, ((k, n), (n, k), (k, n))))


def _takes_megablox(on_tpu: bool, rows: int, even_rows: float) -> bool:
    return on_tpu and rows % row_tile(even_rows) == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _megablox(rows, kernels, group_sizes, first_group, even_rows):
    """`grouped_matmul` on the megablox `gmm`. The rule is written here and
    not left to megablox's own, which hands its forward's ONE tiling to the
    transposed `gmm` and to `tgmm`: each takes its own (`grouped_matmul_bwd`)."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    tiling = gmm_tiling("gmm", *kernels.shape[1:], even_rows, rows.dtype.itemsize)
    offset = None if first_group is None else jnp.int32(first_group)
    return megablox.backend.gmm(rows, kernels, group_sizes, rows.dtype, tiling, offset)


def _megablox_fwd(rows, kernels, group_sizes, first_group, even_rows):
    return _megablox(rows, kernels, group_sizes, first_group, even_rows), (rows, kernels, group_sizes)


def _megablox_bwd(first_group, even_rows, res, g):
    rows, kernels, group_sizes = res
    return grouped_matmul_bwd(rows, kernels, group_sizes, g, True, first_group, even_rows) + (None,)


_megablox.defvjp(_megablox_fwd, _megablox_bwd)


def grouped_matmul(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array,
                   on_tpu: bool = False, first_group: Optional[int] = None,
                   even_rows: Optional[float] = None) -> jax.Array:
    """(M, K) rows sorted by group x (G, K, N) kernels -> (M, N): row i is
    multiplied by the kernel of the group it falls in. Groups may be empty.
    On a TPU, where the rows fill whole tiles, the megablox kernels at the
    tiling their shapes take (`gmm_tiling`; their names carry the caller's
    scope into a trace; XLA's `ragged-dot` custom call carries none);
    otherwise `jax.lax.ragged_dot`.

    `first_group`: the kernels are those of groups `first_group` to
    `first_group + G` of more groups than G (`group_sizes` counts them all);
    the rows of the other groups come back zero, and send no gradient. The
    megablox kernels visit the held groups' tiles alone (`group_offset`,
    their own form of a sharded expert dim).

    `even_rows`: the rows a group holds at an even routing, where the rows
    are a window of the assignments; None: they are all of them."""
    if even_rows is None:
        even_rows = rows.shape[0] / group_sizes.shape[0]
    if _takes_megablox(on_tpu, rows.shape[0], even_rows):
        return _megablox(rows, kernels, group_sizes, first_group, even_rows)
    if first_group is not None:
        # ragged_dot has no such form: zero kernels stand in the other groups' places
        after = group_sizes.shape[0] - first_group - kernels.shape[0]
        kernels = jnp.pad(kernels, ((first_group, after), (0, 0), (0, 0)))
    return jax.lax.ragged_dot(rows, kernels, group_sizes)


def grouped_matmul_bwd(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array, g: jax.Array,
                       on_tpu: bool = False, first_group: Optional[int] = None,
                       even_rows: Optional[float] = None):
    """The cotangents of `grouped_matmul`'s rows and kernels for the
    cotangent `g` of its result, for a rule that is written out: on a TPU the
    two calls megablox's own rule makes (`gmm` with the kernels transposed,
    `tgmm`), each at its own tiling, made here so that they carry the caller's
    scope as the forward's do (a `jax.vjp` taken under a scope names its
    backward after the scope's TRANSFORM, and the readers of a trace tell
    kernels by the scope's words); elsewhere `ragged_dot`'s transposes."""
    if even_rows is None:
        even_rows = rows.shape[0] / group_sizes.shape[0]
    if _takes_megablox(on_tpu, rows.shape[0], even_rows):
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        (_, k, n), wide = kernels.shape, rows.dtype.itemsize
        offset = None if first_group is None else jnp.int32(first_group)
        d_rows = megablox.backend.gmm(g, kernels, group_sizes, rows.dtype, gmm_tiling("gmm_t", n, k, even_rows, wide),
                                      offset, transpose_rhs=True)
        d_kernels = megablox.backend.tgmm(rows.swapaxes(0, 1), g, group_sizes, kernels.dtype,
                                          gmm_tiling("tgmm", k, n, even_rows, wide), offset, kernels.shape[0])
        return d_rows, d_kernels
    return jax.vjp(lambda rows, kernels: grouped_matmul(rows, kernels, group_sizes, on_tpu, first_group, even_rows),
                   rows, kernels)[1](g)


def _permuted(values, inverse):
    """values[index] for a permutation `index` given its INVERSE: entry i is
    the value whose slot the inverse sends to i, so a sort of (inverse,
    values) by the first lays them out. A TPU sorts 81920 pairs in 0.1 ms and
    gathers or scatters as many scalars in 0.4 to 0.7 (PERF.md, PR 40)."""
    return jax.lax.sort((inverse, values), num_keys=1)[1]


def _k_major(x):
    """(tokens, k) -> (k x tokens,): entry j x tokens + t is x[t, j]. Columns
    laid end to end: a transpose and a reshape would be a relayout on a TPU
    wherever k does not fill a tile."""
    return jnp.concatenate([x[:, j] for j in range(x.shape[1])])


# ---------------------------------------------------------- the row movers
# A (rows, hidden) bf16 array lies in HBM in (16, 128) tiles: a row is pieces
# of 256 B, each interleaved with its 15 neighbour rows', and cannot be copied
# alone. So a source of rows is first PACKED (`_pack_rows`, one streaming pass): column c
# beside column c + hidden / 2 in one uint32, a row's hidden / 2 words as
# `hidden / 256` sublane rows of 128. An array of 128 32-bit words a row lies
# in HBM row after row, its (8, 128) tiles one after another, so a packed row
# is `hidden x 2` contiguous bytes WHEREVER it starts (4 KB at 2048, one tile;
# 4.5 KB at Kimi-Linear's 2304, a tile and an eighth that straddles a tile's
# edge seven times in eight), and one DMA moves it: Mosaic takes a slice of
# `hidden / 256` sublane rows at a dynamic start that is no multiple of 8, in
# HBM and in VMEM (jax 0.9.0; PR 40 held it did not and took multiples of 2048
# alone; PR 63 measured the rows end to end against a pitch of whole tiles, 16
# sublane rows a row at 2304: equal to the bit both, and end to end the faster
# in all three kernels, PERF.md). The movers queue one such copy a row into a
# VMEM buffer, a whole grid step's rows ahead of the arithmetic (the next
# step's copies are issued between the current step's vector work), read the
# buffer back with a sublane stride (lane tile q of 16 rows at once: the
# (rows, hidden) layout again, so the gathered rows are never written to HBM),
# and unpack a word's halves with a shift and a mask.
# The three kernels are jitted and inlined: a step calls each from several
# traces (a `custom_vjp`'s primal and its forward rule, every scanned run) and
# is traced anew at every start; a plain function traces its kernel's body
# each time, 0.1 s a call here and 0.3 on a chip's host, where jit's cache
# hands the one jaxpr of a shape to every site, under the site's own scope.
# The tiles are arguments so that they are in its key.
ROWS_BACK_TILE = 64  # tokens a grid step of `moe_rows_back`: k x 64 copies in flight
ROWS_OUT_TILE = 512  # assignments a grid step of `moe_rows_out`
PACK_TILE = 512  # rows a grid step of `moe_rows_pack`
# the most a block may be for the movers to take it: every assignment's index
# is prefetched into SMEM, 1 MiB on a v5e, of which these are three quarters;
# hidden 8192 does not fit the packing pass's scoped VMEM, and nothing between
# was measured (tests/ops/test_tpu_compile_routed.py compiles the kernels AT the
# bounds, and at every width between them); and the least: no narrower row was
# measured, and no cell has one
ROWS_MAX_ASSIGNMENTS = 196608
ROWS_MAX_HIDDEN = 4096
ROWS_MIN_HIDDEN = 2048
_GROUP = 16  # rows the arithmetic takes at a time: a bf16 tile's sublanes
_LANES = 128
_HIGH = 0xFFFF0000


def rows_form(on_tpu: bool, dtype, hidden: int, tokens: int, k: int) -> str:
    """"kernel" where the movers take a block's shape: on a TPU, bf16 rows
    that pack into whole 128-lane rows of words (hidden a multiple of 256)
    from `ROWS_MIN_HIDDEN` to `ROWS_MAX_HIDDEN` wide, whole grid steps of
    tokens and of assignments, no more of them than the kernels hold. "xla"
    everywhere else: the CPU, float32 rows (the packing is bf16's), every
    other width and length."""
    takes = (on_tpu and dtype == jnp.bfloat16 and hidden % (2 * _LANES) == 0
             and ROWS_MIN_HIDDEN <= hidden <= ROWS_MAX_HIDDEN and k * tokens <= ROWS_MAX_ASSIGNMENTS
             and tokens % ROWS_BACK_TILE == 0 and (k * tokens) % ROWS_OUT_TILE == 0
             and tokens % PACK_TILE == 0)
    return "kernel" if takes else "xla"


def _words(x):
    """bf16 -> uint32 with the value's 16 bits in the high half."""
    return jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)


def _halves(word):
    """A packed word -> its two bf16 values as float32, exactly."""
    return (jax.lax.bitcast_convert_type(word << 16, jnp.float32),
            jax.lax.bitcast_convert_type(word & jnp.uint32(_HIGH), jnp.float32))


@functools.partial(jax.jit, static_argnames="tile", inline=True)
def _pack_rows(x: jax.Array, tile: int) -> jax.Array:
    """(R, H) bf16 -> (R x H / 256, 128) uint32: row r is sublane rows
    r x H / 256 onward, word (q, l) of it holds column q x 128 + l (low half)
    and column H / 2 + q x 128 + l (high half). `tile` (`PACK_TILE`): rows a
    grid step."""
    rows, hidden = x.shape
    sub, half = hidden // (2 * _LANES), hidden // 2
    tile = min(tile, rows)

    def kernel(x_ref, out_ref):
        for q in range(sub):
            at = q * _LANES
            low = _words(x_ref[:, at:at + _LANES]) >> 16
            high = _words(x_ref[:, half + at:half + at + _LANES]) & jnp.uint32(_HIGH)
            out_ref[pl.ds(q, tile, stride=sub), :] = high | low

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((rows * sub, _LANES), jnp.uint32),
        grid=(rows // tile,),
        in_specs=[pl.BlockSpec((tile, hidden), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile * sub, _LANES), lambda i: (i, 0)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="moe_rows_pack")(x)


def _mover(index_ref, src_ref, buf, sem, *, tile: int, sub: int, slabs: int = 1, stride: int = 0):
    """What the two movers share: `fetch(step, slot, first, count)` queues the
    copies of rows [first, first + count) of a grid step's tile into `slot`,
    slab by slab (slab j reads its index at `j x stride + step x tile + row`),
    a group's 16 descriptors a turn of ONE loop. Unrolled, the k x 16
    descriptors of a group were most of the kernels' text: they doubled the
    seconds a routed step takes to trace and lower, which a warm start pays
    every time. A site's call with a turn of 8 reads 4 % slower than with one
    of 16, and that 1 to 5 % slower than unrolled (PERF.md, PR 40).
    `wait(slot)` blocks until a whole tile has landed."""
    def fetch(step, slot, first=0, count=tile):
        turns = count // _GROUP

        def group(n, carry):
            j, row = n // turns, first + n % turns * _GROUP
            at = j * stride + step * tile + row
            for r in range(_GROUP):
                src = pl.multiple_of(index_ref[at + r] * sub, sub)
                pltpu.make_async_copy(
                    src_ref.at[pl.ds(src, sub)],
                    buf.at[slot, j, pl.ds(pl.multiple_of((row + r) * sub, sub), sub)],
                    sem.at[slot]).start()
            return carry

        jax.lax.fori_loop(0, slabs * turns, group, 0)

    def wait(slot):
        def slab(j, carry):
            pltpu.make_async_copy(src_ref.at[pl.ds(0, tile * sub)], buf.at[slot, j],
                                  sem.at[slot]).wait()
            return carry

        jax.lax.fori_loop(0, slabs, slab, 0)

    return fetch, wait


def _pipelined(fetch, wait, steps: int, tile: int, work):
    """A grid step: the rows of this step's tile were queued a step ago (the
    first step queues its own); wait for them, then for each group of rows
    queue the NEXT step's copies of that group and do this step's `work(slot,
    first row)` on it, so the scalar core's descriptors and the vector work
    share the instruction stream. The last step queues the first tile again
    (no branch in the loop) and drains it."""
    i = pl.program_id(0)
    slot = i % 2
    ahead = jnp.where(i + 1 < steps, i + 1, 0)

    @pl.when(i == 0)
    def _():
        fetch(0, 0)

    wait(slot)

    def group(g, carry):
        first = pl.multiple_of(g * _GROUP, _GROUP)
        fetch(ahead, 1 - slot, first, _GROUP)
        work(slot, first)
        return carry

    jax.lax.fori_loop(0, tile // _GROUP, group, 0)

    @pl.when(i == steps - 1)
    def _():
        wait(1 - slot)


@functools.partial(jax.jit, static_argnames=("tokens", "hidden", "dtype", "tile"), inline=True)
def _rows_back(packed: jax.Array, inv_order: jax.Array, weights: Optional[jax.Array],
               tokens: int, hidden: int, dtype, tile: int) -> jax.Array:
    """`_sum_over_k` as a kernel (`moe_rows_back`): packed rows in expert
    order -> (tokens, hidden). A grid step is a tile of `tile` tokens
    (`ROWS_BACK_TILE`); token t's k rows `inv_order[j x tokens + t]` arrive by
    DMA, slab j of the buffer each, and are summed in float32 in that order of
    j (times `weights[t, j]`), rounded once."""
    k = inv_order.shape[0] // tokens
    sub, half = hidden // (2 * _LANES), hidden // 2
    steps = tokens // tile

    def kernel(inv_ref, src_ref, *refs):
        w_ref = refs[0] if weights is not None else None
        out_ref, buf, sem = refs[-3:]
        fetch, wait = _mover(inv_ref, src_ref, buf, sem, tile=tile, sub=sub, slabs=k, stride=tokens)

        def work(slot, first):
            rows = pl.ds(first, _GROUP)
            if w_ref is not None:
                w = [jnp.broadcast_to(w_ref[rows, j:j + 1], (_GROUP, _LANES)) for j in range(k)]

            def lane_tile(q, carry):
                low = high = None
                for j in range(k):
                    lo, hi = _halves(buf[slot, j, pl.ds(first * sub + q, _GROUP, stride=sub), :])
                    if w_ref is not None:
                        lo, hi = lo * w[j], hi * w[j]
                    low = lo if low is None else lo + low
                    high = hi if high is None else hi + high
                at = pl.multiple_of(q * _LANES, _LANES)
                out_ref[rows, pl.ds(at, _LANES)] = low.astype(dtype)
                out_ref[rows, pl.ds(pl.multiple_of(half + at, _LANES), _LANES)] = high.astype(dtype)
                return carry

            jax.lax.fori_loop(0, sub, lane_tile, 0)  # unrolled: 1 to 3 % faster, a second more to trace a step

        _pipelined(fetch, wait, steps, tile, work)

    operands = (inv_order, packed) + (() if weights is None else (weights,))
    in_specs = [pl.BlockSpec(memory_space=pl.ANY)]
    if weights is not None:
        in_specs.append(pl.BlockSpec((tile, k), lambda i, inv: (i, 0)))
    need = 2 * k * tile * hidden * 2 + 4 * tile * hidden * 2
    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((tokens, hidden), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,), in_specs=in_specs,
            out_specs=pl.BlockSpec((tile, hidden), lambda i, inv: (i, 0)),
            scratch_shapes=[pltpu.VMEM((2, k, tile * sub, _LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=need + (8 << 20)),
        name="moe_rows_back")(*operands)


def _lanes_to_sublanes(row):
    """(1, 128) -> (128, 128), entry (r, c) = row[0, r]: a row of per-row
    scalars as the column the vector unit multiplies by, through the
    transpose unit."""
    return jnp.broadcast_to(row, (_LANES, _LANES)).T


@functools.partial(jax.jit, static_argnames="tile", inline=True)
def _rows_out(packed: jax.Array, token_of: jax.Array, out: jax.Array, w: jax.Array, tile: int):
    """The combine's backward in one pass (`moe_rows_out`): packed cotangent
    rows in token order, `out` the block's rows in expert order and `w` their
    weights (float32) -> `(g x w)` in the rows' dtype and `sum(out x g)` a row
    in float32, where g, row i, is the source's row `token_of[i]`; `tile`
    (`ROWS_OUT_TILE`) assignments a grid step. The gathered rows themselves
    never leave VMEM. (The dispatch's forward, the same move with no
    arithmetic, stays XLA's gather: in the step its 33 MB source sits in fast
    memory and XLA is the faster; `scripts/moe_rows_sweep.py` keeps that form
    of the kernel to measure it; PERF.md, PR 40.)"""
    count, hidden = out.shape
    dtype = out.dtype
    sub, half = hidden // (2 * _LANES), hidden // 2
    steps, blocks = count // tile, tile // _LANES

    def kernel(tok_ref, src_ref, out_ref, w_ref, rows_ref, sums_ref, buf, sem, w_col, sums_col):
        fetch, wait = _mover(tok_ref, src_ref, buf, sem, tile=tile, sub=sub)
        for b in range(blocks):
            at = b * _LANES
            w_col[at:at + _LANES, :] = _lanes_to_sublanes(w_ref[:, at:at + _LANES])

        def work(slot, first):
            rows = pl.ds(first, _GROUP)
            partial = None  # a row's products, lane by lane: (16, 128) partial sums
            weight = w_col[rows, :]
            for q in range(sub):
                lo, hi = _halves(buf[slot, 0, pl.ds(first * sub + q, _GROUP, stride=sub), :])
                at = q * _LANES
                products = (out_ref[rows, at:at + _LANES].astype(jnp.float32) * lo
                            + out_ref[rows, half + at:half + at + _LANES].astype(jnp.float32) * hi)
                partial = products if partial is None else partial + products
                rows_ref[rows, at:at + _LANES] = (lo * weight).astype(dtype)
                rows_ref[rows, half + at:half + at + _LANES] = (hi * weight).astype(dtype)
            sums_col[rows, :] = partial

        _pipelined(fetch, wait, steps, tile, work)
        # a row's 128 partial sums added up, 128 rows' sums laid along the lanes
        for b in range(blocks):
            at = b * _LANES
            sums_ref[:, at:at + _LANES] = jnp.sum(sums_col[at:at + _LANES, :].T, axis=0, keepdims=True)

    tiles = pl.BlockSpec((tile, hidden), lambda i, tok: (i, 0))
    a_row = pl.BlockSpec((None, 1, tile), lambda i, tok: (i, 0, 0))
    d_out, sums = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((count, hidden), dtype),
                   jax.ShapeDtypeStruct((steps, 1, tile), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), tiles, a_row], out_specs=(tiles, a_row),
            scratch_shapes=[pltpu.VMEM((2, 1, tile * sub, _LANES), jnp.uint32),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((tile, _LANES), jnp.float32),
                            pltpu.VMEM((tile, _LANES), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=8 * tile * hidden * 2 + (8 << 20)),
        name="moe_rows_out")(token_of, packed, out, w.reshape(steps, 1, tile))
    return d_out, sums.reshape(count)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(form, y, order, inv_order):
    """Row order[i] % tokens of y, for every assignment i in expert order:
    each token's row k times over. The cotangent of token t is the sum of its
    k assignments' cotangents, gathered back into token order and summed over
    the major axis. `form` (`rows_form`): the XLA gathers or the row movers."""
    return y[order % y.shape[0]]


def _dispatch_fwd(form, y, order, inv_order):
    return _dispatch(form, y, order, inv_order), (inv_order, y.shape[0])


def _dispatch_bwd(form, res, g):
    inv_order, tokens = res
    return _sum_over_k(form, g, inv_order, tokens, None), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _sum_over_k(form, rows, inv_order, tokens, weights):
    """sum_j rows[inv_order[j x tokens + t]] (x weights[t, j]) in float32, j
    = 0 .. k-1 in that order, rounded once to the rows' dtype: (k x tokens,
    H) rows in expert order -> (tokens, H). The kernel form: `_rows_back`.
    The XLA form: one gather into token order, then
    its k slabs of `tokens` rows as slices, and no reshape between the gather
    and the sum: a reshape there the TPU compiler moves off the gather and
    then fuses nothing across (a float32 copy of every row, written and read
    again). A gather a slab instead: 1 % faster at k = 4 in the one routed
    cell, 1 % slower at k = 8 in the other and 2 % more memory there (PERF.md,
    PR 34)."""
    if form == "kernel":
        return _rows_back(_pack_rows(rows, PACK_TILE), inv_order, weights, tokens, rows.shape[1],
                          rows.dtype, ROWS_BACK_TILE)
    total = None
    rows = rows[inv_order]
    for j in range(inv_order.shape[0] // tokens):
        term = rows[j * tokens:(j + 1) * tokens].astype(jnp.float32)
        if weights is not None:
            term = term * weights[:, j, None]
        total = term if total is None else term + total
    return total.astype(rows.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(form, out, weights, order, inv_order):
    """(k x tokens, H) rows in expert order, float32 (tokens, k) weights ->
    (tokens, H): token t's k rows, weighted and summed in float32."""
    return _sum_over_k(form, out, inv_order, weights.shape[0], weights)


def _combine_fwd(form, out, weights, order, inv_order):
    return _combine(form, out, weights, order, inv_order), (out, weights, order, inv_order)


def _combine_bwd(form, res, g):
    # in EXPERT order, where the rows and their cotangent live: the token's
    # cotangent gathered to each of its assignments (as `_dispatch` gathers
    # the token's row), then one pass over it and the rows
    out, weights, order, inv_order = res
    tokens, k = weights.shape
    w = _permuted(_k_major(weights), inv_order)  # = _k_major(weights)[order]
    if form == "kernel":
        d_out, d_w = _rows_out(_pack_rows(g, PACK_TILE), order % tokens, out, w, ROWS_OUT_TILE)
    else:
        g = g[order % tokens].astype(jnp.float32)
        d_out = (g * w[:, None]).astype(out.dtype)
        d_w = jnp.sum(out.astype(jnp.float32) * g, axis=-1)
    d_w = _permuted(d_w, order)  # = d_w[inv_order]
    d_w = jnp.stack([d_w[j * tokens:(j + 1) * tokens] for j in range(k)], axis=1)
    return d_out, d_w, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def router_logits(y: jax.Array, router_kernel: jax.Array) -> jax.Array:
    """(T, H) x (H, E) -> float32 (T, E), multiplied in float32."""
    return jnp.dot(y.astype(jnp.float32), router_kernel.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


# ------------------------------------------------------- a share's window
# The held experts' rows are rows [start, end) of the sorted assignments,
# start and end known on the device alone. The window is a STATIC number of
# rows that holds them at an even routing with room to spare, and begins at
# the row tile at or below `start`: every held group keeps its offset within
# the megablox kernels' row tiles, so they partition and accumulate as over
# the whole range and the results are the same to the bit. What the window
# buys: megablox fills the rows it skips of every call's result with a select
# over ALL of it, the activation and its backward run over all of it, and with
# 8 of 32 experts held three quarters of those rows are another chip's (45 of
# the experts' 92 ms a step there; PERF.md, PR 47). The window's own tile
# stays `GMM_TILING[0]` whatever row tile the calls take (every one divides
# it): at 256-row tiles Qwen3-Next's window is 7936 rows and not 8192, XLA's
# memory-space assignment then leaves the combine's backward's packed source
# in HBM, and `moe_rows_out` reads it at half the pace (4.5 ms a step;
# PERF.md, PR 69).
# A `cond` chooses, and a `cond` has a price the block is shaped by. What
# passes into one waits through both of its branches and what comes out of one
# is a buffer of its own, and the step's memory is the larger branch's: so
# the block from the gather of its rows to the sum of the dispatch's backward
# sits INSIDE the branches (its operands and results are (tokens, hidden) and
# the kernels), and the whole-range branch, seldom taken, gives up time for
# memory. Differentiated, a `cond` keeps the residuals of both branches and
# fills the untaken one's with zeros: so the block has a written rule whose
# residuals are its inputs, and the backward makes the forward again (under
# `--checkpoint 1` the layer's recomputation then has nothing of the block
# left to make; without it the block pays one more forward of its experts).
WINDOW_OVER_EVEN = 1.5  # x `k x tokens x held / experts`; three cells' steps read 0.91 to 1.08 of it


def window_rows(assignments: int, num_experts: int, held: Optional[Tuple[int, int]]) -> int:
    """Rows of the window a share's experts work on: `WINDOW_OVER_EVEN` times
    the even share in whole 512-row tiles (`GMM_TILING[0]`, which every
    call's row tile divides), and a tile for the alignment. 0 where none is
    built: all experts held, or a window no shorter than the range."""
    if held is None:
        return 0
    tile = GMM_TILING[0]
    rows = (math.ceil(WINDOW_OVER_EVEN * assignments * held[1] / num_experts / tile) + 1) * tile
    return rows if rows < assignments else 0


def _gmm_in(share, rows, wi, sizes):
    with jax.named_scope(tracing.MOE_GMM_IN):
        return grouped_matmul(rows, wi, sizes, share.on_tpu, share.held[0], share.even)


def _gmm_out(share, mid, wo, sizes):
    with jax.named_scope(tracing.MOE_GMM_OUT):
        return grouped_matmul(mid, wo, sizes, share.on_tpu, share.held[0], share.even)


def _say_tilings(wi_shape, wo_shape, even_rows: float, itemsize: int) -> None:
    """The tilings a block's grouped matmuls take, to whoever records the
    step's forms: one entry a distinct (kernel, the dims its K and N tiles
    run over, even rows a group), as `grouped_matmul` and
    `grouped_matmul_bwd` choose them."""
    for _, k, n in (wi_shape, wo_shape):
        for kernel, dims in matmul_calls(k, n):
            form = "%s %dx%d r%g: %dx%dx%d" % ((kernel,) + dims + (even_rows,)
                                               + gmm_tiling(kernel, *dims, even_rows, itemsize))
            forms.took(forms.GMM_TILES, form, key=form)


class _Share(NamedTuple):
    """What `_windowed_block` is built from, static at trace time (and the
    key its two rules are traced once under: the window's tile is in it for that)."""
    activate: object
    on_tpu: bool
    form: str  # of the row movers (`rows_form`)
    held: Tuple[int, int]
    length: int  # of the window, rows
    even: float  # rows a group at an even routing: what the kernels' tilings are chosen by
    tile: int  # `GMM_TILING[0]`: the window starts on one and is whole ones


def _place_window(counts, held: Tuple[int, int], length: int, tile: int):
    """Where the window lies for these `counts` (assignments an expert, all
    the experts): its first row, a multiple of the row tile where the range
    is one; the rows of each expert inside it, which add up to `length`; and
    whether the held experts' rows all are."""
    edges = jnp.concatenate([jnp.zeros((1,), counts.dtype), jnp.cumsum(counts)])
    first_row = jnp.minimum(edges[held[0]] // tile * tile, edges[-1] - length)
    inside = jnp.clip(edges, first_row, first_row + length)
    return first_row, inside[1:] - inside[:-1], edges[held[0] + held[1]] <= first_row + length


def _two_ways(share: _Share, counts):
    """Whether the window holds the held experts' rows, and the two ways over
    the sorted rows, each as (`take`, `lay`, the rows an expert): the window's
    rows cut out of an array of all and laid back into zeros, or all the rows
    as they are."""
    first_row, sizes, fits = _place_window(counts, share.held, share.length, share.tile)

    def take(x):
        return jax.lax.dynamic_slice_in_dim(x, first_row, share.length)

    def lay(x, rows):
        return jax.lax.dynamic_update_slice_in_dim(jnp.zeros((rows, x.shape[1]), x.dtype), x, first_row, 0)

    return fits, (take, lay, sizes), (lambda x: x, lambda x, rows: x, counts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _windowed_block(share: _Share, y, wi, wo, counts, weights, order, inv_order):
    """A share's block from the gather of its rows to the combine: (tokens,
    H) -> (tokens, H). The experts work on the window (`_place_window`) where
    the held experts' rows lie inside it, and on the whole range where they
    do not, chosen on the device (`jax.lax.cond`); the dispatch and the
    combine move all the rows either way.

    A written rule whose residuals are its inputs: differentiated, a `cond`
    hands its backward the residuals of BOTH branches and fills the untaken
    branch's with zeros, whole-range arrays among them. The backward makes
    the forward again inside its own `cond` (under `--checkpoint 1` the
    layer's recomputation then has nothing of this block to make), and
    everything as long as the rows is made and used up inside a branch:
    what passes into a `cond` waits through both of its branches, and the
    step's memory is the larger branch's."""
    return _windowed_fwd(share, y, wi, wo, counts, weights, order, inv_order)[0]


def _gathered(form, y, order, inv_order):
    with jax.named_scope(tracing.MOE_DISPATCH):
        return _dispatch(form, y, order, inv_order)


@functools.partial(jax.jit, static_argnums=0)
def _windowed_fwd(share, y, wi, wo, counts, weights, order, inv_order):
    # the scopes are opened inside a branch: a reader of the trace tells the
    # grouped matmuls apart by the word after the experts' scope, and a cond
    # puts its own path between the two
    def forward(take, lay, sizes):
        rows = _gathered(share.form, y, order, inv_order)
        with jax.named_scope(tracing.MOE_EXPERTS):
            mid = share.activate(_gmm_in(share, take(rows), wi, sizes))
            out = lay(_gmm_out(share, mid, wo, sizes), rows.shape[0])
        with jax.named_scope(tracing.MOE_COMBINE):
            return _sum_over_k(share.form, out, inv_order, weights.shape[0], weights)

    fits, windowed, whole = _two_ways(share, counts)
    out = jax.lax.cond(fits, lambda: forward(*windowed), lambda: forward(*whole))
    return out, (y, wi, wo, counts, weights, order, inv_order)


@functools.partial(jax.jit, static_argnums=0)
def _windowed_bwd(share, res, g):
    y, wi, wo, counts, weights, order, inv_order = res
    form, on_tpu, first, even = share.form, share.on_tpu, share.held[0], share.even

    def backward(take, lay, sizes, again):
        """The block's forward up to the experts' result, and back from the
        combine to the dispatch. `again`, over all the rows, of which (rows,
        width) is the largest array the block makes: the up projection is made
        a second time AFTER the combine's backward and does not wait through
        it (the step's memory is the larger branch's, and that one is seldom
        taken: a kernel's time is nothing to it); the barrier is what keeps
        the second from being the first."""
        rows = _gathered(form, y, order, inv_order)
        with jax.named_scope(tracing.MOE_EXPERTS):
            part = take(rows)
            mid = _gmm_in(share, part, wi, sizes)
            act = share.activate(mid)
            out = lay(_gmm_out(share, act, wo, sizes), rows.shape[0])
        with jax.named_scope(tracing.MOE_COMBINE):
            d_out, d_weights, _, _ = _combine_bwd(form, (out, weights, order, inv_order), g)
        if again:
            sizes, d_out = jax.lax.optimization_barrier((sizes, d_out))
            with jax.named_scope(tracing.MOE_EXPERTS):
                mid = _gmm_in(share, part, wi, sizes)
        activate_bwd = jax.vjp(share.activate, mid)[1]  # taken under no scope: the scope's words stay plain
        with jax.named_scope(tracing.MOE_EXPERTS):
            with jax.named_scope(tracing.MOE_GMM_OUT):
                d_act, d_wo = grouped_matmul_bwd(act, wo, sizes, take(d_out), on_tpu, first, even)
            d_mid, = activate_bwd(d_act)
            with jax.named_scope(tracing.MOE_GMM_IN):
                d_part, d_wi = grouped_matmul_bwd(part, wi, sizes, d_mid, on_tpu, first, even)
            d_rows = lay(d_part, rows.shape[0])
        with jax.named_scope(tracing.MOE_DISPATCH):
            return _sum_over_k(form, d_rows, inv_order, y.shape[0], None), d_wi, d_wo, d_weights

    fits, windowed, whole = _two_ways(share, counts)
    d_y, d_wi, d_wo, d_weights = jax.lax.cond(
        fits, lambda: backward(*windowed, False), lambda: backward(*whole, True))
    # held as they are: moved into the branches, the casts to the parameters'
    # float32 would make the kernels' gradients twice the size there
    d_wi, d_wo = jax.lax.optimization_barrier((d_wi, d_wo))
    return d_y, d_wi, d_wo, None, d_weights, None, None


_windowed_block.defvjp(_windowed_fwd, _windowed_bwd)


def _local_moe(y, router_kernel, bias, wi, wo, *, k: int, norm_topk_prob: bool, activate, dtype,
               on_tpu: bool, score: str = "softmax", scale: float = 1.0,
               held: Optional[Tuple[int, int]] = None, stat_axes: Tuple[str, ...] = ()):
    """The block on the tokens one device holds; `stat_axes` are the mesh
    axes the router's statistics are summed over (the batch's)."""
    tokens = y.shape[0]
    num_experts = router_kernel.shape[-1]
    form = rows_form(on_tpu and y.dtype == dtype, y.dtype, y.shape[1], tokens, k)
    forms.took(forms.MOE_ROWS, form)
    with jax.named_scope(tracing.MOE_ROUTER):
        logits = router_logits(y, router_kernel)
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            weights, experts = jax.lax.top_k(probs, k)  # (T, k)
            if norm_topk_prob:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            z_sum = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
            prob_sum = jnp.sum(probs, axis=0)  # (E,)
        else:
            scores = jax.nn.sigmoid(logits)
            ranked = scores if bias is None else scores + jax.lax.stop_gradient(bias)
            experts = jax.lax.top_k(ranked, k)[1]
            weights = jnp.take_along_axis(scores, experts, axis=-1)  # of the scores alone
            if norm_topk_prob:
                weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        if scale != 1.0:
            weights = weights * scale
    with jax.named_scope(tracing.MOE_DISPATCH):
        # k-major: assignment j x tokens + t is token t's j-th choice
        flat = _k_major(experts)
        assert num_experts * tokens < 2**31
        slot = jnp.arange(k * tokens, dtype=jnp.int32)
        # a token's k experts are distinct, so the keys are: by expert, by token within one
        order = jnp.argsort(flat * tokens + slot % tokens).astype(jnp.int32)
        inv_order = _permuted(slot, order)
        counts = jnp.sum(flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype),
                         axis=0, dtype=jnp.int32)
    even = k * tokens / num_experts  # rows a group at an even routing
    length = window_rows(k * tokens, num_experts, held)
    if _takes_megablox(on_tpu, length or k * tokens, even):
        _say_tilings(wi.shape, wo.shape, even, y.dtype.itemsize)
    if length:
        share = _Share(activate, on_tpu, form, held, length, even, GMM_TILING[0])
        with jax.named_scope(tracing.MOE_EXPERTS):  # the kernels' casts are the experts', as without a window
            kernels = wi.astype(dtype), wo.astype(dtype)
        out = _windowed_block(share, y, *kernels, counts, weights, order, inv_order).astype(dtype)
    else:
        rows = _gathered(form, y, order, inv_order)  # (T*k, H), sorted by expert
        offset = {} if held is None else {"first_group": held[0]}
        with jax.named_scope(tracing.MOE_EXPERTS):
            with jax.named_scope(tracing.MOE_GMM_IN):
                mid = grouped_matmul(rows, wi.astype(dtype), counts, on_tpu, **offset)
            mid = activate(mid)
            with jax.named_scope(tracing.MOE_GMM_OUT):
                out = grouped_matmul(mid, wo.astype(dtype), counts, on_tpu, **offset)
        with jax.named_scope(tracing.MOE_COMBINE):
            out = _combine(form, out, weights, order, inv_order).astype(dtype)
    if held is not None:
        forms.took(forms.EXPERT_WINDOW, length)  # (0: no window)
    with jax.named_scope(tracing.MOE_ROUTER):
        total = jnp.float32(tokens)
        counts_f = counts.astype(jnp.float32)
        if score == "softmax":
            if stat_axes:
                z_sum, prob_sum, counts_f, total = jax.lax.psum(
                    (z_sum, prob_sum, counts_f, total), stat_axes)
            aux = {
                "load_balance": num_experts * jnp.sum(counts_f / total * (prob_sum / total)),
                "router_z": z_sum / total,
            }
        else:
            if stat_axes:
                counts_f = jax.lax.psum(counts_f, stat_axes)
            aux = {}
        aux["load_max_over_mean"] = jnp.max(counts_f) / jnp.mean(counts_f)
        if bias is not None:
            # the whole batch's assignments an expert: what the step moves the bias by
            aux["counts"] = counts_f
            aux["bias_abs_max"] = jnp.max(jnp.abs(bias))
        if held is not None:
            aux["rows_held"] = jnp.sum(
                jax.lax.dynamic_slice_in_dim(counts_f, held[0], held[1]))
            fits = _place_window(counts, held, length, GMM_TILING[0])[2] if length else True
            fell = 1.0 - jnp.float32(fits)
            aux["window_fallbacks"] = jax.lax.psum(fell, stat_axes) if stat_axes else fell
    return out, aux


def swiglu(mid: jax.Array) -> jax.Array:
    """(M, 2F), gate beside up -> silu(gate) x up, (M, F)."""
    ffn = mid.shape[-1] // 2
    return jax.nn.silu(mid[:, :ffn]) * mid[:, ffn:]


def moe_ffn(y: jax.Array, router_kernel: jax.Array, wi: jax.Array, wo: jax.Array, *,
            experts_per_token: int, norm_topk_prob: bool = False, activate=swiglu,
            dtype=jnp.bfloat16, sharding: Optional[KernelSharding] = None,
            score: str = "softmax", bias: Optional[jax.Array] = None, scale: float = 1.0,
            held: Optional[Tuple[int, int]] = None) -> Tuple[jax.Array, Aux]:
    """y (B, S, H) -> (B, S, H) and the router's auxiliary terms
    (`moe_aux_names`). `score`, `bias` (E,), `scale` and `held`: the second
    router and a share of the experts, as the module's docstring has them;
    with `held`, `wi` and `wo` lead with the held experts' count.

    `router_kernel` (H, E); `wi` (E, H, W) with W = 2F for SwiGLU, the gate's
    F columns beside the up projection's (one matmul, and no reshape of a
    kernel whose minor dims a TPU tiles); `wo` (E, F, H); `activate` maps the
    (rows, W) product to (rows, F). The platform is read off the mesh, as
    attention does, so a compile for a described TPU takes the chip's branch.
    On a mesh of more than one device (`sharding`, as attention's) each
    device sorts and multiplies the batch rows it holds against whole
    experts, in a region manual over every mesh axis, and the router's
    statistics are summed over the batch axes; there is no expert
    parallelism here, so the experts' kernels enter the region whole."""
    b, s, h = y.shape
    on_tpu = lies_on_tpu(sharding)
    kw = dict(k=experts_per_token, norm_topk_prob=norm_topk_prob, activate=activate,
              dtype=dtype, on_tpu=on_tpu, score=score, scale=scale, held=held)
    if sharding is None or sharding.mesh.size == 1:
        out, aux = _local_moe(y.reshape(b * s, h), router_kernel, bias, wi, wo, **kw)
        return out.reshape(b, s, h), aux

    batch_axes = tuple(sharding.batch_axes)

    def body(y, router_kernel, wi, wo, *bias):
        lb, ls, _ = y.shape
        out, aux = _local_moe(y.reshape(lb * ls, h), router_kernel, bias[0] if bias else None,
                              wi, wo, stat_axes=batch_axes, **kw)
        return out.reshape(lb, ls, h), aux

    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = sharding.mesh if ctx.empty else ctx
    tokens = P(batch_axes or None, None, None)
    operands = (y, router_kernel, wi, wo) + (() if bias is None else (bias,))
    names = moe_aux_names(score, bias is not None, held is not None)
    return jax.shard_map(
        body, mesh=use_mesh, in_specs=(tokens,) + (P(),) * (len(operands) - 1),
        out_specs=(tokens, {name: P() for name in names}),
        axis_names=set(use_mesh.axis_names) - set(use_mesh.manual_axes),
        check_vma=False,
    )(*operands)
