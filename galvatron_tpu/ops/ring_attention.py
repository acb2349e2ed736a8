"""Ring attention (context parallelism) via shard_map + ppermute.

TPU-native re-design of the reference's ring/zigzag flash attention
(galvatron/core/runtime/tensor_parallel/transformer.py:2252-2670, adapted
there from zhuzilin/ring-flash-attention): K/V blocks rotate around the cp
ring with `lax.ppermute` while an online-softmax accumulator folds in each
block's contribution. The python ring loop unrolls under jit so XLA can
overlap each step's ppermute with the previous step's block compute.

Two departures from the reference:

1. **Position-driven masking.** The causal mask is computed from the *global
   position arrays* carried with the activations (`q_pos >= k_pos`), not from
   block indices. Any sequence layout — contiguous blocks or zigzag — is
   therefore correct automatically.
2. **Zigzag as data layout.** The reference transforms activations
   linear<->zigzag between layers (redistribute.py:8-44). Here, a transformer
   is permutation-equivariant given per-token positions, so the zigzag
   balance trick is applied ONCE as a global sequence permutation in the
   input pipeline (`zigzag_permutation`), and every layer — cp or not — sees
   the same layout. No runtime layout transforms at strategy boundaries.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.ops.attention import DEFAULT_MASK_VALUE, repeat_kv
from galvatron_tpu.parallel.mesh import LayerAxes, mesh_axis_size

NEG_INF = DEFAULT_MASK_VALUE


def zigzag_permutation(seq_len: int, cp: int) -> np.ndarray:
    """Global seq permutation placing chunks (i, 2cp-1-i) on shard i
    (reference redistribute.py:8-27). Returns idx s.t. x_zigzag = x[idx]."""
    assert seq_len % (2 * cp) == 0, "seq_len must divide 2*cp"
    chunk = seq_len // (2 * cp)
    order = []
    for r in range(cp):
        order += [r, 2 * cp - 1 - r]
    idx = np.concatenate([np.arange(c * chunk, (c + 1) * chunk) for c in order])
    return idx


def inverse_permutation(idx: np.ndarray) -> np.ndarray:
    inv = np.empty_like(idx)
    inv[idx] = np.arange(len(idx))
    return inv


def _key_chunking(sk: int, key_chunk: int) -> Tuple[int, int]:
    C = min(key_chunk, sk)
    while sk % C:
        C //= 2
    return C, sk // C


def _ring_forward(q, k, v, q_pos, k_pos, bias, *, cp_axes: Tuple[str, ...],
                  cp_size: int, causal: bool, sm_scale: float,
                  key_chunk: int = 512):
    """Per-shard ring attention forward. q: (b, sq, nh, hd); k/v:
    (b, sk, nh, hd); q_pos/k_pos: (b, sq)/(b, sk) global positions; bias:
    optional additive (b, 1, 1, sk) local key-bias slice that rotates with k.
    Returns (out (b, sq, nh, hd), lse (b, nh, sq)) — the logsumexp feeds the
    hand-written ring backward.

    Each ring step folds its K/V block in BLOCKWISE: a `lax.scan` over
    `key_chunk`-sized key chunks carries the online-softmax state
    (acc, row_max, row_sum), so the peak live buffer is (b, nh, sq,
    key_chunk) fp32 — O(sq * key_chunk) — never the full (sq, sk) logits the
    round-2 implementation materialised (O(S^2/cp), which defeated CP at
    exactly the lengths CP exists for; the reference runs flash inside each
    ring step for the same reason, transformer.py:2335-2422)."""
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    C, nc = _key_chunking(sk, key_chunk)
    # derive the online-softmax state from q so it carries q's varying-manual-
    # axes type — a plain jnp.zeros carry would fail lax.scan's vma check
    # inside the shard_map
    zero_q = q.transpose(0, 2, 1, 3).astype(jnp.float32) * 0.0  # (b, nh, sq, hd)
    acc = zero_q
    row_max = zero_q[..., 0] - jnp.inf
    row_sum = zero_q[..., 0]
    n = cp_size
    perm = [(j, (j + 1) % n) for j in range(n)]
    has_bias = bias is not None

    def chunk_step(carry, inp):
        acc, row_max, row_sum = carry
        k_c, v_c, kp_c, b_c = inp
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k_c, preferred_element_type=jnp.float32)
        logits = logits * sm_scale
        if has_bias:
            logits = logits + b_c.astype(jnp.float32)
        if causal:
            mask = q_pos[:, None, :, None] >= kp_c[:, None, None, :]
            logits = jnp.where(mask, logits, NEG_INF)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        # guard -inf rows (fully masked chunk)
        safe_max = jnp.where(jnp.isfinite(new_max), new_max, 0.0)
        corr = jnp.exp(jnp.where(jnp.isfinite(row_max), row_max - safe_max, -jnp.inf))
        corr = jnp.where(jnp.isfinite(row_max), corr, 0.0)
        probs = jnp.exp(logits - safe_max[..., None])
        if causal:
            probs = jnp.where(mask, probs, 0.0)
        row_sum = row_sum * corr + jnp.sum(probs, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", probs.astype(v_c.dtype), v_c,
            preferred_element_type=jnp.float32,
        )
        return (acc, new_max, row_sum), None

    k_cur, v_cur, kpos_cur, bias_cur = k, v, k_pos, bias
    for step in range(n):
        xs = (
            k_cur.reshape(b, nc, C, nh, hd).transpose(1, 0, 2, 3, 4),
            v_cur.reshape(b, nc, C, nh, hd).transpose(1, 0, 2, 3, 4),
            kpos_cur.reshape(b, nc, C).transpose(1, 0, 2),
            (bias_cur.reshape(b, 1, 1, nc, C).transpose(3, 0, 1, 2, 4)
             if has_bias else jnp.zeros((nc, 1), jnp.float32)),
        )
        (acc, row_max, row_sum), _ = jax.lax.scan(
            chunk_step, (acc, row_max, row_sum), xs
        )
        if step < n - 1:
            k_cur = jax.lax.ppermute(k_cur, cp_axes, perm)
            v_cur = jax.lax.ppermute(v_cur, cp_axes, perm)
            kpos_cur = jax.lax.ppermute(kpos_cur, cp_axes, perm)
            if has_bias:
                bias_cur = jax.lax.ppermute(bias_cur, cp_axes, perm)
    out = acc / jnp.maximum(row_sum, 1e-37)[..., None]
    # lse: -inf for fully-masked rows (row_sum 0) so the backward zeroes them
    lse = jnp.where(row_sum > 0.0, row_max + jnp.log(jnp.maximum(row_sum, 1e-37)), -jnp.inf)
    return out.transpose(0, 2, 1, 3).astype(q.dtype), lse


def _ring_backward(res, dout, *, cp_axes: Tuple[str, ...], cp_size: int,
                   causal: bool, sm_scale: float, has_bias: bool,
                   key_chunk: int = 512):
    """Hand-scheduled ring backward (the reference re-runs the zigzag ring
    with explicit comm/compute overlap, transformer.py:2423-2553; autodiff
    through the unrolled forward is correct but unscheduled and retraces the
    whole online-softmax scan in transpose).

    Flash-style: probabilities are RECOMPUTED per key chunk from the saved
    logsumexp — no per-chunk residuals survive the forward. The K/V blocks
    and their (dk, dv, dbias) accumulators rotate around the ring TOGETHER,
    so after the full cycle every accumulated gradient block is back on the
    device that owns it; the unrolled python loop lets XLA overlap each
    step's ppermutes with the next block's matmuls, exactly as the forward
    does."""
    q, k, v, q_pos, k_pos, bias, out, lse = res
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    C, nc = _key_chunking(sk, key_chunk)
    n = cp_size
    perm = [(j, (j + 1) % n) for j in range(n)]

    qT = q.transpose(0, 2, 1, 3).astype(jnp.float32)  # (b, nh, sq, hd)
    doT = dout.transpose(0, 2, 1, 3).astype(jnp.float32)
    outT = out.transpose(0, 2, 1, 3).astype(jnp.float32)
    # delta_i = rowsum(dO * O): the softmax-normalisation term of dS
    delta = jnp.sum(doT * outT, axis=-1)  # (b, nh, sq)
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    live = jnp.isfinite(lse)[..., None]  # fully-masked rows contribute nothing

    def chunk_bwd(dq_acc, inp):
        k_c, v_c, kp_c, b_c = inp  # (b, C, nh, hd) / (b, C) / (b, 1, 1, C)
        kT = k_c.transpose(0, 2, 1, 3).astype(jnp.float32)  # (b, nh, C, hd)
        vT = v_c.transpose(0, 2, 1, 3).astype(jnp.float32)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qT, kT,
                            preferred_element_type=jnp.float32) * sm_scale
        if has_bias:
            logits = logits + b_c.astype(jnp.float32)
        if causal:
            mask = q_pos[:, None, :, None] >= kp_c[:, None, None, :]
            logits = jnp.where(mask, logits, NEG_INF)
        p = jnp.where(live, jnp.exp(logits - lse_safe[..., None]), 0.0)
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p, doT,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", doT, vT,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None])
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds, kT,
                                     preferred_element_type=jnp.float32) * sm_scale
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds, qT,
                          preferred_element_type=jnp.float32) * sm_scale
        db_c = jnp.sum(ds, axis=(1, 2))[:, None, None, :]  # (b, 1, 1, C)
        return dq_acc, (dk_c, dv_c, db_c)

    def chunked(t, shape):
        return t.reshape(shape).transpose(1, 0, *range(2, len(shape)))

    # derive accumulators from the inputs so they carry the varying-manual-
    # axes type (a plain jnp.zeros fails lax.scan's vma check in shard_map)
    dq = qT * 0.0
    dk_rot = k.astype(jnp.float32) * 0.0
    dv_rot = v.astype(jnp.float32) * 0.0
    db_rot = bias.astype(jnp.float32) * 0.0 if has_bias else None
    k_cur, v_cur, kpos_cur, bias_cur = k, v, k_pos, bias
    for step in range(n):
        xs = (
            chunked(k_cur, (b, nc, C, nh, hd)),
            chunked(v_cur, (b, nc, C, nh, hd)),
            chunked(kpos_cur, (b, nc, C)),
            (bias_cur.reshape(b, 1, 1, nc, C).transpose(3, 0, 1, 2, 4)
             if has_bias else jnp.zeros((nc, 1), jnp.float32)),
        )
        dq, (dk_c, dv_c, db_c) = jax.lax.scan(chunk_bwd, dq, xs)
        # ys are (nc, b, nh, C, hd) / (nc, b, 1, 1, C) -> home block layouts
        dk_rot = dk_rot + dk_c.transpose(1, 0, 3, 2, 4).reshape(b, sk, nh, hd)
        dv_rot = dv_rot + dv_c.transpose(1, 0, 3, 2, 4).reshape(b, sk, nh, hd)
        if has_bias:
            db_rot = db_rot + db_c.transpose(1, 2, 3, 0, 4).reshape(b, 1, 1, sk)
        # rotate blocks and their gradient accumulators together: after the
        # n-step full cycle each accumulator lands back on its owner; the
        # data blocks themselves are dead after the last step (same guard as
        # the forward), only the accumulators need the final rotation home
        if step < n - 1:
            k_cur = jax.lax.ppermute(k_cur, cp_axes, perm)
            v_cur = jax.lax.ppermute(v_cur, cp_axes, perm)
            kpos_cur = jax.lax.ppermute(kpos_cur, cp_axes, perm)
            if has_bias:
                bias_cur = jax.lax.ppermute(bias_cur, cp_axes, perm)
        dk_rot = jax.lax.ppermute(dk_rot, cp_axes, perm)
        dv_rot = jax.lax.ppermute(dv_rot, cp_axes, perm)
        if has_bias:
            db_rot = jax.lax.ppermute(db_rot, cp_axes, perm)
    dq_out = dq.transpose(0, 2, 1, 3).astype(q.dtype)
    return (dq_out, dk_rot.astype(k.dtype), dv_rot.astype(v.dtype),
            db_rot.astype(jnp.float32) if has_bias else None)


def _make_ring_fn(cp_axes: Tuple[str, ...], cp_size: int, causal: bool,
                  sm_scale: float, has_bias: bool, tp_axes: Tuple[str, ...] = (),
                  use_custom_vjp: bool = True):
    """The per-shard ring attention with the hand-written ring VJP attached
    (use_custom_vjp=False keeps plain autodiff through the unrolled forward —
    the parity oracle in tests/ops/test_attention.py)."""
    kw = dict(cp_axes=cp_axes, cp_size=cp_size, causal=causal, sm_scale=sm_scale)

    def fwd_impl(q, k, v, q_pos, k_pos, bias):
        # maskless calls carry a dummy zeros bias operand (shard_map needs a
        # consistent arity); pass None through so the forward keeps its
        # bias-free path and XLA dead-code-eliminates the operand
        return _ring_forward(q, k, v, q_pos, k_pos,
                             bias if has_bias else None, **kw)

    if not use_custom_vjp:
        return lambda q, k, v, qp, kp, bias: fwd_impl(q, k, v, qp, kp, bias)[0]

    @jax.custom_vjp
    def f(q, k, v, q_pos, k_pos, bias):
        return fwd_impl(q, k, v, q_pos, k_pos, bias)[0]

    def f_fwd(q, k, v, q_pos, k_pos, bias):
        out, lse = fwd_impl(q, k, v, q_pos, k_pos, bias)
        return out, (q, k, v, q_pos, k_pos, bias, out, lse)

    def f_bwd(res, dout):
        dq, dk, dv, db = _ring_backward(res, dout, has_bias=has_bias, **kw)
        if has_bias and tp_axes:
            # the bias enters the shard_map tp-invariant while heads are
            # tp-sharded: the local head-sum is a partial — reduce it (the
            # psum autodiff would have inserted for the replicated operand)
            db = jax.lax.psum(db, tp_axes)
        # positions are integral (float0 tangents); the dummy bias of maskless
        # calls still receives its (dead) cotangent
        zero_pos = np.zeros(res[3].shape, jax.dtypes.float0)
        zero_kpos = np.zeros(res[4].shape, jax.dtypes.float0)
        return (dq, dk, dv, zero_pos, zero_kpos,
                db if has_bias else jnp.zeros_like(res[5]))

    f.defvjp(f_fwd, f_bwd)
    return f


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    positions: jax.Array,
    *,
    mesh: Mesh,
    axes: LayerAxes,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    use_custom_vjp: bool = True,
) -> jax.Array:
    """Ring attention over `axes.cp`. Inputs are GLOBAL arrays:
    q/k/v (B, S, nh, hd) sharded (dp, cp, tp, -), positions (B, S) (dp, cp);
    bias: optional additive (B, 1, 1, S) key bias (padding masks) whose key
    dim shards over cp and rotates with K/V around the ring — the reference's
    ring path is causal-only and rejects masks; this one supports padded
    (bert-style) batches under CP. The backward is the hand-scheduled ring
    VJP (use_custom_vjp=False falls back to autodiff, kept as the tests'
    parity oracle)."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if k.shape[2] != q.shape[2]:
        n_rep = q.shape[2] // k.shape[2]
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)

    from galvatron_tpu.parallel.spec import _ax

    bd, cp, tp = _ax(axes.batch_axes), _ax(axes.cp), _ax(axes.tp)
    qkv_spec = P(bd, cp, tp, None)
    pos_spec = P(bd, cp)
    bias_spec = P(bd, None, None, cp)
    cp_size = mesh_axis_size(mesh, axes.cp)
    has_bias = bias is not None
    ring_fn = _make_ring_fn(tuple(axes.cp), cp_size, causal, sm_scale,
                            has_bias, tp_axes=tuple(axes.tp),
                            use_custom_vjp=use_custom_vjp)
    body = ring_fn
    if bias is None:
        # a full-shape zero operand satisfies bias_spec's cp sharding (the
        # body ignores it when bias is None, so XLA dead-code-eliminates it)
        bias_in = jnp.zeros((q.shape[0], 1, 1, q.shape[1]), jnp.float32)
    else:
        bias_in = jnp.broadcast_to(
            bias.astype(jnp.float32), (q.shape[0], 1, 1, q.shape[1])
        )
    # When called inside another manual region (the 1F1B schedule is manual
    # over 'pp'), shard_map must receive the CONTEXT abstract mesh (whose
    # already-manual axes are typed Manual) and only make the within-stage
    # axes manual here.
    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = mesh if ctx.empty else ctx
    return jax.shard_map(
        body,
        mesh=use_mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, pos_spec, pos_spec, bias_spec),
        out_specs=qkv_spec,
        axis_names=set(axes.dp) | set(axes.cp) | set(axes.tp),
    )(q, k, v, positions, positions, bias_in)
