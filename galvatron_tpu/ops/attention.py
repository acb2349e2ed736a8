"""Core attention with XLA / Pallas-flash dispatch.

TPU-native counterpart of the reference's CoreAttention /
FlashSelfOrCrossAttention dispatch (galvatron/core/runtime/tensor_parallel/
transformer.py:306,432,860-892). The parallel forms differ structurally:

- Megatron-TP / Megatron-SP / Ulysses all reduce to *local* attention on
  (B, S, nh/shard, hd) activations — GSPMD materialises the surrounding
  all-gather (SP) or all-to-all (Ulysses, reference transformer.py:1928-2177)
  when resharding from seq-sharded to head-sharded, so one code path serves
  all three.
- Ring/zigzag context parallelism keeps blockwise softmax state across
  `ppermute` steps and lives in ops/ring_attention.py.

Layouts here are (batch, seq, heads, head_dim) ("BSNH"); the pallas kernel
path transposes to its (batch, heads, seq, head_dim) convention.

Attention over a WINDOW (`core_attention(window=)`: query i sees the keys
`i - window < j <= i`) has two forms: the band mask on XLA's logits, and on a
TPU the band kernels of `ops/window_attention.py`, whose grid steps load and
multiply the blocks the band touches and no others (`_pallas_window`), on the
operands as projected: a head a block of lanes, no transpose, and q's rope
and the head's gate in the kernels where the caller hands them on
(`window_takes_kernels`, `q_rope`, `head_gate`).
"""

from __future__ import annotations

import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import window_attention
from galvatron_tpu.ops.kernels import KernelSharding, lies_on_tpu

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """GQA: expand (B, S, n_kv, hd) to (B, S, n_kv*n_rep, hd)
    (reference ParallelAttention GQA, transformer.py:576-583)."""
    if n_rep == 1:
        return k
    b, s, nkv, hd = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, nkv, n_rep, hd)).reshape(b, s, nkv * n_rep, hd)


def _xla_attention(q, k, v, *, causal: bool, sm_scale: float, bias=None, q_offset=0, window=None):
    """Einsum attention with fp32 softmax; XLA fuses mask+softmax into the MXU
    matmuls. `q_offset` shifts the causal mask for cross-shard blocks.
    `window`: a query sees the `window` keys up to its own, the band
    `q_pos - window < k_pos <= q_pos` (the whole (sq, sk) logits are made and
    masked: the CPU's path and the tests' oracle)."""
    b, sq, nh, hd = q.shape
    sk = k.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * sm_scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0) + q_offset
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        seen = q_pos >= k_pos if window is None else (q_pos >= k_pos) & (q_pos - k_pos < window)
        logits = jnp.where(seen, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _flash_divisor(s: int, cap: int) -> int:
    """Largest block size <= cap that divides the sequence (the kernel
    requires block | seq; callers guarantee s % 128 == 0)."""
    b = cap
    while b > 128:
        if s % b == 0:
            return b
        b //= 2
    return 128 if s % 128 == 0 else s


def _flash_block_sizes(sq: int, sk: int):
    """1024-query x 512-key blocks (the largest divisors of the sequence up
    to those): one KV stripe stays resident in VMEM per query block. What
    they reach on a v5e is the benchmark's ``flash_roofline``: 40.7 % of the
    kernels' compute roofline at 2048 tokens, 58.2 % at 8192 (ledger, PR 24:
    qwen7-c1-s2k, qwen7-c1-s8k). No sweep of other sizes is on record."""
    from jax.experimental.pallas.ops.tpu.flash_attention import BlockSizes

    bq = _flash_divisor(sq, 1024)
    bk = _flash_divisor(sk, 512)
    return BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk, block_q_dkv=bq,
        block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq,
    )


def _pallas_flash(q, k, v, *, causal: bool, sm_scale: float, segment_ids=None):
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention

    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    out = flash_attention(
        qt, kt, vt, segment_ids=segment_ids, causal=causal, sm_scale=sm_scale,
        block_sizes=_flash_block_sizes(q.shape[1], k.shape[1]),
    )
    return out.transpose(0, 2, 1, 3)


def _pallas_window(q, k, v, *, window: int, sm_scale: float, q_rope=None, head_gate=None):
    """Windowed causal attention of (B, S, nh, hd) queries on (B, S, nkv, hd)
    keys and values, nkv dividing nh, through the repo's band kernels
    (`ops/window_attention.py`: a query block beside the key blocks its band
    touches, one pass of softmax, k and v fetched once a key head and never
    repeated). The kernels read the operands AS PROJECTED, a head a block of
    hd lanes of a (B, S, heads x hd) array: the reshapes move nothing. `q_rope`,
    `head_gate`: `core_attention`'s, the kernels' `tables` and `gates`."""
    block = window_attention.block_for(q.shape[1], window)
    q3, k3, v3 = (t.reshape(t.shape[0], t.shape[1], -1) for t in (q, k, v))
    return window_attention.window_attention(q3, k3, v3, q_rope, head_gate, window, sm_scale, block,
                                             q.shape[3]).reshape(q.shape)


def _sharded_kernel(kernel, q, k, v, sharding: KernelSharding, segment_ids=None, beside=()):
    """`kernel(q, k, v, segment_ids, *beside's operands)` per device under a
    manual region (see KernelSharding): `_pallas_flash` or `_pallas_window`,
    each device on its own batch rows and heads (`beside`: (operand, its spec)
    pairs, the window kernels' tables and gate logits). Inside an enclosing
    manual region (the 1F1B schedule is manual over 'pp') shard_map must
    receive the CONTEXT abstract mesh, whose already-manual axes are typed
    Manual, as ring_attention does."""
    from jax.experimental.pallas.ops.tpu.flash_attention import SegmentIds

    bd, hd = sharding.batch_axes or None, sharding.head_axes or None
    qkv_spec = P(bd, None, hd, None)
    operands, in_specs = [q, k, v], [qkv_spec] * 3
    if segment_ids is not None:
        operands += [segment_ids.q, segment_ids.kv]
        in_specs += [P(bd, None)] * 2
    ids = len(operands) - 3
    for operand, spec in beside:
        operands.append(operand)
        in_specs.append(spec)

    def body(q, k, v, *rest):
        return kernel(q, k, v, SegmentIds(*rest[:ids]) if ids else None, *rest[ids:])

    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = sharding.mesh if ctx.empty else ctx
    return jax.shard_map(
        body,
        mesh=use_mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        axis_names=set(use_mesh.axis_names) - set(use_mesh.manual_axes),
        check_vma=False,
    )(*operands)


def padding_bias_to_segment_ids(bias: jax.Array):
    """(B, 1, 1, Sk) additive 0/-1e9 key-padding bias -> flash SegmentIds.

    Valid tokens get segment 1, padded tokens segment 0; the kernel only
    attends within equal segments, which reproduces the padding semantics
    exactly on valid rows (valid q x valid k see bias 0, padded keys are
    excluded). Padded QUERY rows attend within the pad segment instead of
    over valid keys — their outputs are garbage under both schemes and are
    masked downstream (the same contract as the reference's varlen flash,
    transformer.py:432-510, which drops padded rows entirely)."""
    from jax.experimental.pallas.ops.tpu.flash_attention import SegmentIds

    valid = (bias[:, 0, 0, :] > -1e8).astype(jnp.int32)  # (B, Sk)
    return SegmentIds(q=valid, kv=valid)


_FALLBACKS_SAID = set()


def _say_fallback_once(q_shape, sk: int, biased: bool, splits: bool, window=None) -> None:
    """One line a shape when `impl="auto"` leaves the kernel on a TPU although
    the sequence is in whole tiles: what XLA's form costs there. `window`: the
    call is over a window of that many keys, whose kernels take no bias, no
    other mask than the causal band and heads of whole 128-lane tiles."""
    key = (tuple(q_shape), sk, biased, splits, window)
    if key in _FALLBACKS_SAID:
        return
    _FALLBACKS_SAID.add(key)
    b, sq, nh, hd = q_shape
    why = ("a bias the window kernels cannot take" if biased and window is not None else
           "a bias the kernel cannot take as segment ids" if biased and splits else
           "batch or heads the mesh does not divide" if not splits else
           "head_dim %d (the window kernels compile at multiples of 128)" % hd if window is not None and hd % 128 else
           "a window too wide for the window kernels' few key blocks a step" if window is not None else
           "head_dim %d (the kernel compiles at 64 and at multiples of 128)" % hd)
    logging.getLogger(__name__).warning(
        "core_attention: XLA attention on a TPU at q %s, %d keys%s (%s): it materialises float32 "
        "logits of (%d, %d, %d, %d), %.2f GiB a call, where the %s kernel holds a block",
        tuple(q_shape), sk, "" if window is None else ", a window of %d" % window, why,
        b, nh, sq, sk, b * nh * sq * sk * 4 / 2**30, "flash" if window is None else "window")


def core_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    bias: Optional[jax.Array] = None,
    impl: str = "auto",
    bias_type: str = "additive",
    sharding: Optional[KernelSharding] = None,
    window: Optional[int] = None,
    q_rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    head_gate: Optional[jax.Array] = None,
) -> jax.Array:
    """Multi-head attention on (B, S, nh, hd) tensors (kv may have fewer heads:
    GQA is expanded here). `window`: causal self-attention in which query i
    sees the keys `i - window < j <= i`, its own among them (Mistral's and
    HF's `sliding_window`); `_windowed` picks its form, and a window that
    reaches the whole sequence is plain causal attention. `q_rope`, `head_gate`
    (a call that `window_takes_kernels` says the window kernels take, and no
    other): q comes UNTURNED with its rotation's two tables
    (`ops/rope.half_split_tables`) and the kernels turn it block by block; the
    output is multiplied, a head, by sigmoid of its column of the (B, S, nh)
    logits in the kernels' epilogue: neither pass touches HBM. bias_type="key_padding" declares `bias` to be the
    (B, 1, 1, Sk) 0/-1e9 key-padding bias from padding_attn_bias **of a
    SELF-attention call** (the same padding applies to queries and keys —
    the segment-id lowering reuses the key mask for the query side, which is
    wrong for equal-length cross-attention with different q/kv padding; use
    the default bias_type there). The flash path then lowers it to segment
    ids instead of falling back to the O(S^2) XLA path (the reference keeps
    varlen flash for padded batches, transformer.py:432-510); a generic
    additive bias (T5 relative positions) still falls back.

    ``sharding`` says how q/k/v are laid out when the caller runs under GSPMD
    on a mesh (see KernelSharding); callers already inside a manual region
    over those axes (parallel/tp_shard_map.py) pass None. It also decides
    `on_tpu`: the mesh's devices, not the process default backend, so a
    compile for a described TPU topology takes the same branch as the chip."""
    if sm_scale is None:
        sm_scale = 1.0 / (q.shape[-1] ** 0.5)
    if bias_type == "key_padding" and q.shape[1] != k.shape[1]:
        # the segment-id lowering reuses the key mask for the query side; on
        # a cross-attention call with q_len != kv_len that is detectably
        # wrong — fail loudly instead of producing silently wrong rows (the
        # equal-length cross-attention case remains the caller's contract)
        raise ValueError(
            "bias_type='key_padding' is a SELF-attention contract (query and "
            "key padding assumed identical); got q_len=%d != kv_len=%d — use "
            "the default additive bias_type for cross-attention"
            % (q.shape[1], k.shape[1])
        )
    if k.shape[2] != q.shape[2]:
        assert q.shape[2] % k.shape[2] == 0, "q heads must be a multiple of kv heads"
    if window is not None:
        return _windowed(q, k, v, window=window, causal=causal, sm_scale=sm_scale, bias=bias,
                         impl=impl, sharding=sharding, q_rope=q_rope, head_gate=head_gate)
    if q_rope is not None or head_gate is not None:
        raise ValueError("q_rope and head_gate are the window kernels' (core_attention(window=)); without a "
                         "window the caller turns q and multiplies by the gate")
    if k.shape[2] != q.shape[2]:
        n_rep = q.shape[2] // k.shape[2]
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)
    # a key-padding bias may ride the flash path as segment ids — but only at
    # kernel-tileable shapes (block sizes must divide seq in multiples of
    # 128); anything else keeps the XLA fallback, including on the explicit
    # impl="flash" families (gpt_fa/llama_fa), which previously fell back for
    # EVERY bias and must not start crashing on untileable padded batches
    seg_flash_ok = (
        bias is not None and bias_type == "key_padding"
        and bias.ndim == 4 and bias.shape[1] == 1 and bias.shape[2] == 1
        and bias.shape[3] == k.shape[1] and q.shape[1] == k.shape[1]
        and q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
    )
    # the pallas kernel is TPU-only
    on_tpu = lies_on_tpu(sharding)
    if sharding is not None and sharding.mesh.size == 1:
        sharding = None  # one device: the kernel needs no manual region
    # under a manual region the kernel sees whole batch rows and heads only
    splits = sharding is None or sharding.divides(q.shape[0], q.shape[2])
    if impl == "auto":
        # the kernel's blocks are whole 128-token tiles of the sequence, and
        # Mosaic compiles its three kernels at heads of whole 128-lane tiles
        # (the kernel refuses a wider head that is none: 192) and at 64
        # (Granite's; tests/ops/test_tpu_compile.py), nothing narrower tried
        tileable = q.shape[1] % 128 == 0 and k.shape[1] % 128 == 0
        ok_shapes = (
            tileable and (q.shape[3] % 128 == 0 or q.shape[3] == 64)
            and (bias is None or seg_flash_ok) and splits
        )
        # on a TPU the kernel (blocks: _flash_block_sizes) wherever the
        # shapes allow it: it never materialises the (b, nh, s, s) fp32
        # logits. XLA's fused attention has not been timed against it on
        # the chip; every benchmark cell runs the kernel.
        impl = "flash" if (on_tpu and ok_shapes) else "xla"
        if on_tpu and tileable and impl == "xla":
            _say_fallback_once(q.shape, k.shape[1], bias is not None, splits)
    if impl == "flash":
        if bias is not None and (not seg_flash_ok or not on_tpu):
            # the pallas flash kernel takes no generic additive bias; fall
            # back rather than silently dropping it. Off-TPU the segment-id
            # kernel dispatch is also gated off: explicit impl="flash"
            # families (gpt_fa/llama_fa) with a padded batch must keep the
            # XLA fallback on CPU instead of crashing in the pallas kernel
            # (unbiased explicit flash stays TPU-only as documented).
            return _xla_attention(q, k, v, causal=causal, sm_scale=sm_scale, bias=bias)
        seg = padding_bias_to_segment_ids(bias) if bias is not None else None
        if sharding is None:
            return _pallas_flash(q, k, v, causal=causal, sm_scale=sm_scale,
                                 segment_ids=seg)
        if not splits:
            raise ValueError(
                "attn impl='flash': batch %d / heads %d do not divide over "
                "mesh axes %s / %s — the kernel cannot run sharded"
                % (q.shape[0], q.shape[2], sharding.batch_axes, sharding.head_axes))
        return _sharded_kernel(
            lambda q, k, v, seg: _pallas_flash(q, k, v, causal=causal, sm_scale=sm_scale, segment_ids=seg),
            q, k, v, sharding, seg)
    if impl == "xla":
        return _xla_attention(q, k, v, causal=causal, sm_scale=sm_scale, bias=bias)
    raise ValueError("unknown attention impl %r" % impl)


def _whole_heads_a_device(sharding: Optional[KernelSharding], q_shape, k_shape) -> bool:
    """Under a manual region the window kernels see whole batch rows, query heads and key heads only."""
    return (sharding is None or sharding.mesh.size == 1
            or (sharding.divides(q_shape[0], q_shape[2]) and sharding.divides(q_shape[0], k_shape[2])))


def window_takes_kernels(q_shape, k_shape, *, window: int, biased: bool = False, impl: str = "auto",
                         sharding: Optional[KernelSharding] = None) -> bool:
    """Whether `core_attention(window=)` runs a call of these (B, S, heads, hd)
    shapes as the window kernels: on a TPU (the mesh's devices, else the
    default backend), unless `impl="xla"` asks otherwise, a call without a bias,
    heads of whole 128-lane tiles, whole batch rows, query heads and key heads a
    device, and a query block that divides the sequence and reaches the window
    in a few key blocks (`window_attention.block_for`). What such a call may
    bring: `core_attention`'s `q_rope` and `head_gate`."""
    return bool(impl != "xla" and lies_on_tpu(sharding) and q_shape[3] % 128 == 0 and not biased
                and _whole_heads_a_device(sharding, q_shape, k_shape)
                and window_attention.block_for(q_shape[1], window) > 0)


def _windowed(q, k, v, *, window: int, causal: bool, sm_scale: float, bias, impl: str,
              sharding: Optional[KernelSharding], q_rope=None, head_gate=None) -> jax.Array:
    """`core_attention` over a window, k and v at their own heads. On a TPU
    the window kernels (`_pallas_window`) wherever they have a form
    (`window_takes_kernels`); the band mask on XLA's logits everywhere else
    (the CPU; `impl="xla"`), said once a shape where a TPU takes it at a
    tileable length. Decided by what the call observes and said to `obs/forms`:
    `WINDOW_ATTENTION`'s "pallas" or "xla" a call, and `WINDOW_OPERANDS`'s
    "as_projected" beside the first where q came unturned with its tables
    (`q_rope`)."""
    if not causal or q.shape[1] != k.shape[1] or window < 1:
        raise ValueError("a window of %d keys is causal self-attention's (query i sees keys i - window < j <= i); "
                         "got causal=%s, %d queries on %d keys" % (window, causal, q.shape[1], k.shape[1]))
    if impl not in ("auto", "flash", "xla"):
        raise ValueError("unknown attention impl %r" % impl)
    kernel = window_takes_kernels(q.shape, k.shape, window=window, biased=bias is not None, impl=impl,
                                  sharding=sharding)
    if not kernel and (q_rope is not None or head_gate is not None):
        raise ValueError("q_rope and head_gate ride the window kernels alone: ask `window_takes_kernels` first, "
                         "and turn q and multiply by the gate around a call it refuses")
    if lies_on_tpu(sharding) and q.shape[1] % 128 == 0 and impl == "auto" and not kernel:
        _say_fallback_once(q.shape, k.shape[1], bias is not None, _whole_heads_a_device(sharding, q.shape, k.shape),
                           window)
    forms.took(forms.WINDOW_ATTENTION, "pallas" if kernel else "xla")
    if not kernel:
        n_rep = q.shape[2] // k.shape[2]
        return _xla_attention(q, repeat_kv(k, n_rep), repeat_kv(v, n_rep), causal=True, sm_scale=sm_scale,
                              bias=bias, window=window)
    if q_rope is not None:
        forms.took(forms.WINDOW_OPERANDS, "as_projected")
    if sharding is None or sharding.mesh.size == 1:  # one device: the kernels need no manual region
        return _pallas_window(q, k, v, window=window, sm_scale=sm_scale, q_rope=q_rope, head_gate=head_gate)
    bd, hd = sharding.batch_axes or None, sharding.head_axes or None
    beside = [(t, P(bd, None, None)) for t in q_rope or ()]
    beside += [] if head_gate is None else [(head_gate, P(bd, None, hd))]

    def run(q, k, v, _seg, *rest):  # the tables first, the gate logits last, as `beside` has them
        return _pallas_window(q, k, v, window=window, sm_scale=sm_scale,
                              q_rope=rest[:2] if q_rope is not None else None,
                              head_gate=rest[-1] if head_gate is not None else None)

    return _sharded_kernel(run, q, k, v, sharding, beside=beside)
