"""EVA attention (Zheng et al., ICLR 2023, as EvaByte runs it): softmax
attention that is EXACT inside the query's own window and sees everything
before that window as ONE pooled key and value a chunk, in the same softmax.

With `W` = window, `c` = chunk, `C = W / c`, a head's learned `phi`, `mu`
(head_dim each) and q, k already turned by rope:

- **pooling** (`pooled`): chunk j holds positions `c j .. c j + c - 1`;
  `a = softmax_i(<phi, k_i>)` over the chunk's positions (unscaled),
  `K~_j = sum_i a_i k_i + mu`, `V~_j = sum_i a_i v_i`. Small: one pass over k
  and v on the vector unit in float32, left to XLA, forward and backward;
- **aggregation** (`aggregate`): query t of window `n = t // W` scores
  `scale <q_t, k_i>` for `n W <= i <= t` and `scale <q_t, K~_j>` for every
  chunk `j < n C` (the chunks of every EARLIER window, none of its own); ONE
  softmax over the union, `out = sum_i p_i v_i + sum_j p_j V~_j`. Window 0 is
  plain causal attention.

The aggregation has two forms. **The XLA form** (`_xla_aggregate`) runs the
equations a window at a time (`lax.map` under `jax.checkpoint`: one window's
float32 scores live at a time, never (S, S)); any sequence that is a multiple
of the chunk, a last partial window being a shorter window. It is the CPU's
path and the tests' oracle. **The Pallas pair** (`eva_agg_fwd`, `eva_agg_bwd`)
reads q, k, v AS PROJECTED, (batch, seq, heads x head_dim) with a head a block
of head_dim lanes, as `ops/window_attention.py` does. A grid step is one block
of `BLOCK` queries of one head beside its WINDOW's keys and values (fetched
once a window: the block index does not move while the window's query blocks
pass) and the head's pooled keys and values, whole. The softmax runs ONLINE
over what the block sees and nothing else: its own key block under the causal
mask first (every row sees its own key, so the running maximum is finite from
the start), then the whole key blocks before it in the window, then one
window's `C` pooled keys at a time for each earlier window; float32 scores and
sums, the operands' dtype on the MXU. No (S, S) array and no per-window logits
reach HBM. The forward keeps two floats a row, in one (batch, heads, 8, seq)
array: the log of the normaliser and the softmax MASS THAT FELL ON POOLED KEYS
(the two partial sums it holds anyway), the step's `eva_pooled_mass`. The
backward makes the probabilities again from the kept log-normaliser and
`delta = sum_d do x o` (made by XLA, a float a row), one kernel a query block:
dq, and dk and dv summed in VMEM over the window's query blocks and written
once a window, the cotangents of `K~`, `V~` summed in VMEM over ALL later
query blocks and written once a head. XLA carries those two through the
pooling to k, v, phi and mu.

`aggregate(impl="auto")` takes the kernels where `fits` says so on TPUs
(`ops/kernels.on_kernels`) and says which form it took to `obs/forms`
(`EVA_ATTENTION`: "pallas" | "xla").
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.obs import forms
from galvatron_tpu.ops.kernels import NN, NT, TILE, TN, KernelSharding, dot, on_kernels, rows_a_device, traced_once

_F32 = jnp.float32
MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)
# queries a grid step, and the keys of one product in it: the diagonal block is computed whole and half of
# it masked, a tenth of a window's pairs at 512 on 2048 (PERF.md section 7, EVA layers: no sweep is on record)
BLOCK = 512
STAT_ROWS = 8  # the kept row statistics' sublanes: row 0 the log-normaliser, row 1 the pooled mass (delta, backward)
_VMEM = 64 * 2**20


# ====================================================================== pooling
def _whole_chunks(seq: int, window: int, chunk: int) -> None:
    if seq % chunk or window % chunk:
        raise ValueError("eva attention pools whole chunks: a sequence of %d and a window of %d are no multiples "
                         "of the chunk, %d" % (seq, window, chunk))


def _chunks(t, chunk: int):
    b, s, nh, hd = t.shape
    return t.reshape(b, s // chunk, chunk, nh, hd)


def _chunk_weights(kc, phi):
    """`softmax_i(<phi, k_i>)` over a chunk's positions, float32 (B, chunks, chunk, nh, 1)."""
    return jax.nn.softmax(jnp.sum(kc.astype(_F32) * phi.astype(_F32), axis=-1), axis=2)[..., None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pooled(k, v, phi, mu, chunk: int):
    kc, vc = _chunks(k, chunk), _chunks(v, chunk)
    a = _chunk_weights(kc, phi)
    return ((jnp.sum(a * kc.astype(_F32), axis=2) + mu.astype(_F32)).astype(k.dtype),
            jnp.sum(a * vc.astype(_F32), axis=2).astype(v.dtype))


def _pooled_fwd(k, v, phi, mu, chunk):
    return _pooled(k, v, phi, mu, chunk), (k, v, phi, mu)


def _pooled_bwd(chunk, kept, cotangents):
    """Written out, so that every pass over k and v reads them as they lie (the
    compute dtype) and writes a few floats a chunk or a cotangent in the compute
    dtype: left to autodiff, XLA:TPU kept a dozen float32 copies of k- and
    v-sized arrays alive across a layer's backward (1.3 GiB at the EvaByte
    cell's sizes; PERF.md section 6, PR 61). The weights are made again from k."""
    k, v, phi, mu = kept
    kc, vc = _chunks(k, chunk), _chunks(v, chunk)
    dkp, dvp = (t.astype(_F32)[:, :, None] for t in cotangents)  # (B, chunks, 1, nh, hd)
    a = _chunk_weights(kc, phi)
    da = jnp.sum(dkp * kc.astype(_F32) + dvp * vc.astype(_F32), axis=-1, keepdims=True)
    dlogits = a * (da - jnp.sum(a * da, axis=2, keepdims=True))  # the softmax's transpose, a chunk
    dk = (a * dkp + dlogits * phi.astype(_F32)).astype(k.dtype).reshape(k.shape)
    dv = (a * dvp).astype(v.dtype).reshape(v.shape)
    dphi = jnp.sum(dlogits * kc.astype(_F32), axis=(0, 1, 2)).astype(phi.dtype)
    return dk, dv, dphi, jnp.sum(cotangents[0].astype(_F32), axis=(0, 1)).astype(mu.dtype)


_pooled.defvjp(_pooled_fwd, _pooled_bwd)


def pooled(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array, *, chunk: int) -> Tuple[jax.Array, jax.Array]:
    """k, v (B, S, nh, hd), k turned; phi, mu (nh, hd) -> K~, V~ (B, S / chunk,
    nh, hd) in k's dtype: a chunk's positions weighted by `softmax_i(<phi,
    k_i>)`, `mu` added to the pooled key alone. Float32 on the vector unit (an
    MXU product at the default precision would round phi and k to bfloat16),
    with a written backward (`_pooled_bwd`)."""
    _whole_chunks(k.shape[1], chunk, chunk)
    return _pooled(k, v, phi, mu, chunk)


# ================================================================= the XLA form
def _xla_aggregate(q, k, v, kp, vp, window: int, chunk: int, scale: float, score_dtype):
    """`aggregate`'s equations a window at a time -> (out (B, S, nh, hd), the
    pooled mass a query (B, nh, S) float32)."""
    b, s, nh, hd = q.shape
    window = min(window, s)
    windows, per = -(-s // window), window // chunk
    pad = windows * window - s  # a last, partial window: padded keys lie after every query, padded queries are cut
    q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0))) for t in (q, k, v))
    kp, vp = (jnp.pad(t, ((0, 0), (0, windows * per - t.shape[1]), (0, 0), (0, 0))) for t in (kp, vp))
    causal = jnp.tril(jnp.ones((window, window), bool))
    chunks = jnp.arange(kp.shape[1])

    def one(n):
        qn, kn, vn = (jax.lax.dynamic_slice_in_dim(t, n * window, window, axis=1) for t in (q, k, v))
        own = jnp.einsum("bqhd,bkhd->bhqk", qn, kn, preferred_element_type=_F32) * scale
        far = jnp.einsum("bqhd,bjhd->bhqj", qn, kp, preferred_element_type=_F32) * scale
        logits = jnp.concatenate([jnp.where(causal, own, MASK_VALUE),
                                  jnp.where(chunks < n * per, far, MASK_VALUE)], axis=-1)
        logits = logits.astype(score_dtype).astype(_F32)
        p = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
        total = jnp.sum(p, axis=-1)
        sums = (jnp.einsum("bhqk,bkhd->bqhd", p[..., :window].astype(v.dtype), vn, preferred_element_type=_F32)
                + jnp.einsum("bhqj,bjhd->bqhd", p[..., window:].astype(v.dtype), vp, preferred_element_type=_F32))
        out = sums / total.transpose(0, 2, 1)[..., None]  # the rows' sums divide (q, hd), not (q, keys)
        return out.astype(q.dtype), jnp.sum(p[..., window:], axis=-1) / total

    out, mass = jax.lax.map(jax.checkpoint(one), jnp.arange(windows))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, windows * window, nh, hd)[:, :s]
    return out, mass.transpose(1, 2, 0, 3).reshape(b, nh, windows * window)[:, :, :s]


# ================================================================== the kernels
def block_for(window: int) -> int:
    """The query block the kernels take under this window: `BLOCK`, or its
    halves down to 128, whichever first divides the window; 0: none does."""
    b = BLOCK
    while b >= TILE:
        if window % b == 0:
            return b
        b //= 2
    return 0


def fits(q_shape, window: int, chunk: int) -> bool:
    """Whether the kernels have a form of a (B, S, nh, hd) call: heads of whole
    128-lane tiles, whole windows of whole query blocks, and a window's pooled
    keys a whole tile of 128 (its slice of the pooled array starts on a tile)."""
    _, s, _, hd = q_shape
    return bool(hd % TILE == 0 and window % chunk == 0 and (window // chunk) % TILE == 0
                and s % window == 0 and block_for(window) > 0)


def _scores(q, keys, scale: float, score_dtype):
    s = dot(q, keys, NT) * scale
    return s if score_dtype == _F32 else s.astype(score_dtype).astype(_F32)


def _causal(block: int):
    rows = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    return rows >= jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)


def _rows(j, size: int):
    """Rows `j size .. (j + 1) size - 1` of a ref, a slice that starts on a tile."""
    return pl.ds(pl.multiple_of(j * size, size), size)


def _where_it_is(per_window: int):
    """(the query block's window, its place among the window's query blocks)."""
    i = pl.program_id(2)
    return i // per_window, i % per_window


def _fwd_kernel(q_ref, k_ref, v_ref, kp_ref, vp_ref, o_ref, stats_ref, *, scale: float, block: int,
                per_window: int, pooled_a_window: int, score_dtype):
    n, qi = _where_it_is(per_window)
    q = q_ref[...]

    def met(keys, values, carry, far: bool = False, mask=None):
        """The running maximum, the sums of the window's keys and of the pooled keys (`far`: which these are)
        and the weighted values, after these keys."""
        m, near_sum, far_sum, acc = carry
        s = _scores(q, keys, scale, score_dtype)
        if mask is not None:
            s = jnp.where(mask, s, MASK_VALUE)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        old, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        added = jnp.sum(p, axis=1, keepdims=True)
        return (m_new, old * near_sum + (0.0 if far else added), old * far_sum + (added if far else 0.0),
                old * acc + dot(p.astype(values.dtype), values, NN))

    def own(j):
        return k_ref[_rows(j, block), :], v_ref[_rows(j, block), :]

    def pooled_of(w):
        return kp_ref[_rows(w, pooled_a_window), :], vp_ref[_rows(w, pooled_a_window), :]

    column = jnp.zeros((block, 1), _F32)
    carry = (jnp.full((block, 1), MASK_VALUE, _F32), column, column, jnp.zeros((block, q.shape[1]), _F32))
    # the block's own keys first: every row sees its own, so the maximum is finite from here on
    carry = met(*own(qi), carry, mask=_causal(block))
    carry = jax.lax.fori_loop(0, qi, lambda j, c: met(*own(j), c), carry)
    m, near_sum, far_sum, acc = jax.lax.fori_loop(0, n, lambda w, c: met(*pooled_of(w), c, far=True), carry)
    total = near_sum + far_sum
    o_ref[...] = (acc / total).astype(o_ref.dtype)
    # two floats a row, as rows of lanes: a (block, 128) tile with them in lanes 0 and 1, turned
    lanes = jax.lax.broadcasted_iota(jnp.int32, (block, TILE), 1)
    kept = jnp.where(lanes == 0, m + jnp.log(total), jnp.where(lanes == 1, far_sum / total, 0.0))
    stats_ref[...] = kept.T[:STAT_ROWS]


def _bwd_kernel(q_ref, k_ref, v_ref, kp_ref, vp_ref, do_ref, stats_ref, dq_ref, dk_ref, dv_ref, dkp_ref, dvp_ref,
                dk_scr, dv_scr, dkp_scr, dvp_scr, *, scale: float, block: int, per_window: int,
                pooled_a_window: int, score_dtype):
    n, qi = _where_it_is(per_window)

    @pl.when(qi == 0)  # the window's first query block: its keys' sums start
    def _():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(pl.program_id(2) == 0)  # the head's first: the pooled keys' sums start
    def _():
        dkp_scr[...] = jnp.zeros_like(dkp_scr)
        dvp_scr[...] = jnp.zeros_like(dvp_scr)

    q, do = q_ref[...], do_ref[...]
    # the rows' two floats come as rows of lanes: padded to a (128, block) tile and turned, they are columns
    kept = jnp.concatenate([stats_ref[...], jnp.zeros((TILE - STAT_ROWS, block), _F32)], axis=0).T
    lse, delta = kept[:, 0:1], kept[:, 1:2]

    def met(j, size, refs, sums, dq, mask=None):
        """Block j (of `size` rows) of the keys and values `refs`: its share of dq, and of their sums `sums`."""
        rows = _rows(j, size)
        keys, values = refs[0][rows, :], refs[1][rows, :]
        s = _scores(q, keys, scale, score_dtype)
        if mask is not None:
            s = jnp.where(mask, s, MASK_VALUE)
        p = jnp.exp(s - lse)
        ds = (p * (dot(do, values, NT) - delta) * scale).astype(q.dtype)
        sums[0][rows, :] += dot(ds, q, TN)
        sums[1][rows, :] += dot(p.astype(do.dtype), do, TN)
        return dq + dot(ds, keys, NN)

    def own(j, dq, mask=None):
        return met(j, block, (k_ref, v_ref), (dk_scr, dv_scr), dq, mask)

    dq = own(qi, jnp.zeros((block, q.shape[1]), _F32), _causal(block))
    dq = jax.lax.fori_loop(0, qi, own, dq)
    dq = jax.lax.fori_loop(
        0, n, lambda w, dq: met(w, pooled_a_window, (kp_ref, vp_ref), (dkp_scr, dvp_scr), dq), dq)
    dq_ref[...] = dq.astype(dq_ref.dtype)

    @pl.when(qi == per_window - 1)  # the window's last query block: its keys' sums are whole
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        dkp_ref[...] = dkp_scr[...].astype(dkp_ref.dtype)
        dvp_ref[...] = dvp_scr[...].astype(dvp_ref.dtype)


def _specs(q, kp, window: int, block: int, head_dim: int):
    """The block specs of a step: a query block of a head, the window's keys or
    values, the head's pooled keys or values, the rows' kept floats."""
    per_window = window // block
    a_block = pl.BlockSpec((None, block, head_dim), lambda b, h, i: (b, i, h))
    a_window = pl.BlockSpec((None, window, head_dim), lambda b, h, i: (b, i // per_window, h))
    all_pooled = pl.BlockSpec((None, kp.shape[1], head_dim), lambda b, h, i: (b, 0, h))
    kept = pl.BlockSpec((None, None, STAT_ROWS, block), lambda b, h, i: (b, h, 0, i))
    return a_block, a_window, all_pooled, kept


def _sizes(q, window: int, chunk: int, head_dim: int, score: str):
    block = block_for(window)
    return block, dict(block=block, per_window=window // block, pooled_a_window=window // chunk,
                       score_dtype=jnp.dtype(score)), (q.shape[0], q.shape[2] // head_dim, q.shape[1] // block)


@traced_once(0, 1, 2, 3, 4)
def _forward(window: int, chunk: int, scale: float, head_dim: int, score: str, q, k, v, kp, vp):
    block, sizes, grid = _sizes(q, window, chunk, head_dim, score)
    a_block, a_window, all_pooled, kept = _specs(q, kp, window, block, head_dim)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, **sizes), grid=grid, name="eva_agg_fwd",
        in_specs=[a_block, a_window, a_window, all_pooled, all_pooled], out_specs=[a_block, kept],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((grid[0], grid[1], STAT_ROWS, q.shape[1]), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_VMEM),
    )(q, k, v, kp, vp)


@traced_once(0, 1, 2, 3, 4)
def _backward(window: int, chunk: int, scale: float, head_dim: int, score: str, q, k, v, kp, vp, do, stats):
    block, sizes, grid = _sizes(q, window, chunk, head_dim, score)
    a_block, a_window, all_pooled, kept = _specs(q, kp, window, block, head_dim)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, **sizes), grid=grid, name="eva_agg_bwd",
        in_specs=[a_block, a_window, a_window, all_pooled, all_pooled, a_block, kept],
        out_specs=[a_block, a_window, a_window, all_pooled, all_pooled],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (q, k, v, kp, vp)],
        scratch_shapes=[pltpu.VMEM((window, head_dim), _F32)] * 2 + [pltpu.VMEM((kp.shape[1], head_dim), _F32)] * 2,
        # the query blocks of a head in turn: the window's and the head's sums are held across them
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM),
    )(q, k, v, kp, vp, do, stats)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _kernel_aggregate(q, k, v, kp, vp, window: int, chunk: int, scale: float, head_dim: int, score: str):
    """q, k, v (B, S, nh x head_dim) as projected and turned, kp, vp (B, S /
    chunk, nh x head_dim) -> (out (B, S, nh x head_dim), the pooled mass a
    query (B, nh, S) float32, which no gradient flows through)."""
    return _kernel_aggregate_fwd(q, k, v, kp, vp, window, chunk, scale, head_dim, score)[0]


def _kernel_aggregate_fwd(q, k, v, kp, vp, window, chunk, scale, head_dim, score):
    out, stats = _forward(window, chunk, scale, head_dim, score, q, k, v, kp, vp)
    return (out, stats[:, :, 1]), (q, k, v, kp, vp, out, stats[:, :, 0])


def _kernel_aggregate_bwd(window, chunk, scale, head_dim, score, kept, cotangents):
    q, k, v, kp, vp, out, lse = kept
    do = cotangents[0]
    b, s, _ = q.shape
    delta = jnp.sum((out.astype(_F32) * do.astype(_F32)).reshape(b, s, -1, head_dim), axis=-1).transpose(0, 2, 1)
    stats = jnp.pad(jnp.stack([lse, delta], axis=2), ((0, 0), (0, 0), (0, STAT_ROWS - 2), (0, 0)))
    return tuple(_backward(window, chunk, scale, head_dim, score, q, k, v, kp, vp, do, stats))


_kernel_aggregate.defvjp(_kernel_aggregate_fwd, _kernel_aggregate_bwd)


# ===================================================================== the call
# What a score is rounded to before the softmax, read as a call is traced: float32 in every run. The control of
# scripts/evabyte_chip_check.py and of the tests sets it to bfloat16 around a trace of its own (the next lower
# precision, which the limits have to tell from this one); nothing else writes it.
_SCORES = _F32


def aggregate(q: jax.Array, k: jax.Array, v: jax.Array, kp: jax.Array, vp: jax.Array, *, window: int, chunk: int,
              sm_scale: float, impl: str = "auto", sharding: Optional[KernelSharding] = None,
              ) -> Tuple[jax.Array, jax.Array]:
    """q, k, v (B, S, nh, hd), turned; kp, vp (B, S / chunk, nh, hd) of
    `pooled` -> (out (B, S, nh, hd), the share of each query's softmax mass
    that fell on pooled keys (B, nh, S), float32, no gradient). `impl`:
    "pallas" the kernels, "xla" the XLA form, "auto" the kernels where the
    operands lie on TPUs (`sharding`'s mesh says so; with none, the default
    backend), `fits` holds and the call sits on one device or, with `sharding`,
    on whole rows of the batch a device; everything else, the CPU among it,
    the XLA form. Said to `obs/forms` as `EVA_ATTENTION`'s "pallas" / "xla"."""
    _whole_chunks(q.shape[1], window, chunk)
    fitting = fits(q.shape, window, chunk)
    kernels, sharding = on_kernels(sharding, q.shape[0], fitting)
    if impl == "auto":
        impl = "pallas" if kernels else "xla"
    forms.took(forms.EVA_ATTENTION, impl)
    if impl == "xla":
        return _xla_aggregate(q, k, v, kp, vp, window, chunk, sm_scale, jnp.dtype(_SCORES))
    if impl != "pallas" or not fitting:
        raise ValueError("eva attention: impl %r at q %s, window %d, chunk %d: the kernels take heads of whole "
                         "128-lane tiles, whole windows of whole query blocks and 128 pooled keys a window or a "
                         "multiple" % (impl, tuple(q.shape), window, chunk))
    hd = q.shape[3]
    flat = [t.reshape(t.shape[0], t.shape[1], -1) for t in (q, k, v, kp, vp)]  # as projected: nothing moves
    out, mass = rows_a_device(
        lambda *operands: _kernel_aggregate(*operands, window, chunk, sm_scale, hd, jnp.dtype(_SCORES).name),
        sharding, flat, (), (3, 3))
    return out.reshape(q.shape), mass


def eva_attention(q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array, *, window: int,
                  chunk: int, sm_scale: float, impl: str = "auto",
                  sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, jax.Array]:
    """EVA attention on (B, S, nh, hd) operands already turned: `pooled`, then
    `aggregate` -> (out, the pooled mass a query). The mixer
    (models/parts/eva.py) calls the two under a scope each."""
    kp, vp = pooled(k, v, phi, mu, chunk=chunk)
    return aggregate(q, k, v, kp, vp, window=window, chunk=chunk, sm_scale=sm_scale, impl=impl, sharding=sharding)
