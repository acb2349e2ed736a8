"""shard_map-native tensor-parallel layer execution with decomposed,
ppermute-overlapped collectives.

The GSPMD path (models/base.layer_forward) leaves every TP collective to the
compiler: the all-gathers/reduce-scatters implied by the column/row kernel
shardings serialize with the matmuls they feed. T3 (arXiv:2401.16677) shows
that fine-grained overlap of producer compute with those collectives is the
next step-time lever; on TPU the native idiom is DECOMPOSED collectives —
the ppermute-pipelined chunking ops/ring_attention.py already uses for
attention, generalized here to the dense TP layers:

- **column-parallel** (qkv / mlp-in kernels, ``P(..., tp)``): the megatron-sp
  seq-sharded activation is ring-all-gathered while each arriving block is
  immediately consumed by its chunk of the matmul (`_col_matmul`);
- **row-parallel** (attn-out / mlp-out kernels, ``P(tp, ...)``): the partial
  products are computed chunk-by-chunk and reduce-scattered through a
  rotating ring accumulator (`_row_matmul`), so each chunk's matmul overlaps
  the previous chunk's ppermute.

`manual_layer_forward` composes them into a full transformer block under ONE
`jax.shard_map` over the layer's dp+tp mesh axes, selected by the runtime
knob ``tp_comm_mode``:

- ``gspmd``     — the existing compiler-derived path (default);
- ``shard_map`` — manual collectives, undecomposed (`lax.all_gather` /
  `lax.psum_scatter`): the collectives become visible and schedulable (the
  prerequisite for quantized collectives, ROADMAP item 2) but still
  serialize with the matmuls;
- ``overlap``   — the decomposed ppermute rings above, with a custom_vjp so
  the backward overlaps symmetrically (dx reduce-scatter ring + dw
  accumulation share one rotation, mirroring the forward).

Numerics contract: both manual modes compute the same mathematical layer as
GSPMD (parity-tested to tolerance — reduction orders differ); configs the
manual path cannot express are REFUSED with a GLS012 diagnostic, never
silently approximated. Inside the manual region every reshape is a plain
local op, which also keeps it clear of the GSPMD sharded-reshape miscompile
class (WA004).

Autodiff note: `jax.shard_map` types every value by the manual axes it varies
over. Parameter leaves enter with their dp axes dropped from the in_spec
(replicated and ZeRO-3-gathered operands), so they are dp-INVARIANT while the
activations vary over dp: wherever the two meet, jax inserts the cast to
varying whose transpose is the psum of the cotangent over dp — the
data-parallel gradient sum, with no manual psum in the body. A custom_vjp
hides its inside from that mechanism and must return cotangents of its
inputs' own type, so the ring matmuls make the cast themselves, outside the
custom_vjp (`_vary_like`). The parity suite (tests/models/test_tp_comm_mode.py)
pins loss AND grads against GSPMD for every supported tp/zero3/scan
combination.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.config.strategy import (
    HybridParallelConfig,
    LayerStrategy,
    layer_runs,
)
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes, layer_axes, mesh_axis_size

Params = Dict[str, Any]


# ------------------------------------------------------------------ support
def manual_tp_reason(cfg, hp: HybridParallelConfig,
                     strategy: LayerStrategy) -> Optional[str]:
    """Why the manual shard_map path cannot run one layer's strategy, or None
    when it can. Pure host-side check (the strategy linter calls it with no
    tracing); layers with tp=1 have no TP collectives to make visible and are
    reported as supported — run_layers executes them through the (identical)
    GSPMD path and the linter warns the knob is inert."""
    tp = strategy.tp
    if tp <= 1:
        return None
    if strategy.sp:
        return "ulysses sequence parallelism (use_sp=1) is not expressible " \
               "in the manual TP path"
    if strategy.cp > 1:
        return "context parallelism (cp=%d) composes through " \
               "ops/ring_attention.py, not the manual TP path" % strategy.cp
    if not hp.sequence_parallel:
        return "the manual TP path requires megatron-sp activation sharding " \
               "(--sequence-parallel); --no-sequence-parallel layers keep GSPMD"
    if cfg is None:
        # linter without a model config: structural checks only
        return None
    num_heads = getattr(cfg, "num_heads", None)
    if num_heads is None:
        return "model family without a flat num_heads (t5/swin custom " \
               "trees) is not wired through the manual TP path"
    if getattr(cfg, "qk_norm", False):
        return "qk_norm (a norm over all heads of q and of k) is not wired " \
               "through the manual TP path"
    if num_heads % tp != 0:
        return "num_heads=%d not divisible by tp=%d (GSPMD pads; the " \
               "manual path refuses)" % (num_heads, tp)
    num_kv = getattr(cfg, "num_kv_heads", None) or num_heads
    if num_kv % tp != 0:
        return "num_kv_heads=%d not divisible by tp=%d" % (num_kv, tp)
    ffn = getattr(cfg, "ffn_hidden", None)
    if ffn is not None and ffn % tp != 0:
        return "ffn_hidden=%d not divisible by tp=%d" % (ffn, tp)
    seq = getattr(cfg, "max_seq_len", None)
    if seq is not None and seq % tp != 0:
        return "max_seq_len=%d not divisible by tp=%d (megatron-sp shards " \
               "the sequence over the tp axes)" % (seq, tp)
    return None


def assert_manual_tp_supported(cfg, hp: HybridParallelConfig,
                               strategy: LayerStrategy):
    """Trace-time refusal (GLS012 DiagnosticError) — the loud half of the
    never-silently-differ contract; the strategy linter reports the same
    reason pre-trace through lint_hp."""
    reason = manual_tp_reason(cfg, hp, strategy)
    if reason is not None:
        from galvatron_tpu.analysis import diagnostics as D

        raise D.DiagnosticError([D.make(
            "GLS012", "tp_comm_mode=%r: %s" % (hp.tp_comm_mode, reason),
            key="tp_comm_mode",
        )])


def wants_manual_tp(hp: Optional[HybridParallelConfig],
                    axes: Optional[LayerAxes]) -> bool:
    """Whether run_layers should route this layer through the manual path:
    the knob asks for it AND the layer actually has tp collectives (tp=1
    layers execute the identical GSPMD program — the knob is inert, which
    the linter warns about, rather than wrong)."""
    if hp is None or axes is None:
        return False
    mode = getattr(hp, "tp_comm_mode", "gspmd")
    return mode in ("shard_map", "overlap") and len(axes.tp) > 0


# ------------------------------------------------------------- ring helpers
def _ring_perm(n: int) -> List[Tuple[int, int]]:
    return [(j, (j + 1) % n) for j in range(n)]


def _flat_axis_index(axis_names: Tuple[str, ...], sizes: Tuple[int, ...]):
    """Flattened (row-major, major->minor — the order ppermute/all_gather
    flatten a tuple of axis names) index of this device along `axis_names`."""
    idx = jnp.int32(0)
    for name, size in zip(axis_names, sizes):
        idx = idx * size + jax.lax.axis_index(name)
    return idx


# ------------------------------------------------------- quantized payloads
def _q_encode(x, quant):
    """Wire-encode a ring payload: (payload, scales) under a quantized
    tp_comm_quant, a bf16 cast for 'bf16', the array itself for None/'none'.
    Encoded ONCE before a rotation — the payload stays encoded through every
    hop and each consumer dequantizes only the block it multiplies
    (EQuARX-style: the wire carries int8, the MXU sees fp)."""
    from galvatron_tpu.parallel import quant_collectives as QC

    if quant is None or quant[0] == "none":
        return x
    dtype, block = quant
    if dtype == "bf16":
        return x.astype(jnp.bfloat16)
    return QC.quantize_blockwise(x, dtype, block) + (x.shape, x.dtype)


def _q_decode(enc, quant):
    from galvatron_tpu.parallel import quant_collectives as QC

    if quant is None or quant[0] == "none":
        return enc
    if quant[0] == "bf16":
        return enc  # bf16 feeds the matmul directly
    payload, scales, shape, dt = enc
    return QC.dequantize_blockwise(payload, scales, shape, dt)


def _q_permute(enc, quant, tp_axes, perm):
    if quant is None or quant[0] in ("none", "bf16"):
        return jax.lax.ppermute(enc, tp_axes, perm)
    payload, scales, shape, dt = enc
    return (jax.lax.ppermute(payload, tp_axes, perm),
            jax.lax.ppermute(scales, tp_axes, perm), shape, dt)


# --------------------------------------------------- column-parallel matmul
def _col_matmul_chunks(x, w, *, tp_axes, n, sizes, quant=None):
    """Decomposed all-gather + matmul: x (B, s, H) is this device's
    megatron-sp seq shard; w (H, ...) its column shard. Each ring step
    matmuls the block currently held and places it at the block's global
    seq offset, then rotates — the python-unrolled loop lets XLA overlap
    each step's ppermute with the previous block's matmul, exactly as the
    ring-attention forward does. Under ``quant`` the rotating activation is
    wire-encoded once (int8/fp8 blockwise or bf16) and every hop moves the
    encoded payload; each step dequantizes only the block it consumes.
    Returns (B, n*s, ...)."""
    b, s = x.shape[0], x.shape[1]
    tail = w.shape[1:]
    idx = _flat_axis_index(tp_axes, sizes)
    out = jnp.zeros((b, n * s) + tail, x.dtype)
    perm = _ring_perm(n)
    x_cur = _q_encode(x, quant)
    for step in range(n):
        src = jnp.mod(idx - step, n)  # whose block x_cur originally was
        blk = jnp.einsum("bsh,h...->bs...", _q_decode(x_cur, quant), w)
        out = jax.lax.dynamic_update_slice(
            out, blk.astype(x.dtype),
            (jnp.int32(0), src * s) + (jnp.int32(0),) * len(tail))
        if step < n - 1:
            x_cur = _q_permute(x_cur, quant, tp_axes, perm)
    return out


def _col_matmul_dense(x, w, *, tp_axes, n, sizes, quant=None):
    """Undecomposed manual form (mode='shard_map'): one all-gather, one
    matmul — visible collectives, no overlap. Under ``quant`` the activation
    is wire-encoded before the gather (the all-gather moves payload+scales)
    and dequantized once on arrival."""
    if quant is not None and quant[0] not in ("none",):
        from galvatron_tpu.parallel import quant_collectives as QC

        dtype, block = quant
        if dtype == "bf16":
            x_full = jax.lax.all_gather(
                x.astype(jnp.bfloat16), tp_axes, axis=1, tiled=True)
            return jnp.einsum("bsh,h...->bs...", x_full, w)
        payload, scales = QC.quantize_blockwise(x, dtype, block)
        pg = jax.lax.all_gather(payload, tp_axes)   # (n, nblk, block)
        sg = jax.lax.all_gather(scales, tp_axes)    # (n, nblk)
        parts = QC.dequantize_blockwise(
            pg.reshape(-1, pg.shape[-1]), sg.reshape(-1),
            (n,) + x.shape, x.dtype)
        x_full = jnp.moveaxis(parts, 0, 1).reshape(
            x.shape[0], n * x.shape[1], x.shape[2])
        return jnp.einsum("bsh,h...->bs...", x_full, w)
    del n, sizes
    x_full = jax.lax.all_gather(x, tp_axes, axis=1, tiled=True)
    return jnp.einsum("bsh,h...->bs...", x_full, w)


def _col_bwd_chunks(x, w, g, *, tp_axes, n, sizes):
    """Hand-scheduled column backward: ONE rotation serves both grads —
    x rotates as in the forward so each step contributes its chunk of
    dw = gathered(x)^T @ g, while the dx reduce-scatter accumulator rides
    the same ring home (dest arithmetic as in `_row_matmul_chunks`)."""
    s = x.shape[1]
    idx = _flat_axis_index(tp_axes, sizes)
    perm = _ring_perm(n)
    dw = jnp.zeros_like(w)
    dx = None
    x_cur = x
    for step in range(n):
        src = jnp.mod(idx - step, n)
        g_src = jax.lax.dynamic_slice_in_dim(g, src * s, s, 1)
        dw = dw + jnp.einsum("bsh,bs...->h...", x_cur, g_src)
        dest = jnp.mod(idx - 1 - step, n)
        g_dest = jax.lax.dynamic_slice_in_dim(g, dest * s, s, 1)
        part = jnp.einsum("bs...,h...->bsh", g_dest, w)
        dx = part if dx is None else jax.lax.ppermute(dx, tp_axes, perm) + part
        if step < n - 1:
            x_cur = jax.lax.ppermute(x_cur, tp_axes, perm)
    return dx, dw


# ------------------------------------------------------ row-parallel matmul
def _row_matmul_chunks(x, w, *, tp_axes, n, sizes, quant=None):
    """Decomposed matmul + reduce-scatter: x (B, S, f) full-seq with f the
    row shard, w (f, H). A ring accumulator destined for device d starts at
    d+1 and hops +1 each step picking up that device's partial for block d;
    after n-1 hops it lands home fully reduced. Each step's chunk matmul
    overlaps the accumulator's ppermute. Under ``quant`` each accumulator
    hop is wire-encoded (re-quantized per hop — the partial sums change) and
    the running sum stays in the compute dtype, the ZeRO++ reduce-scatter
    discipline. Returns the megatron-sp shard (B, S/n, H)."""
    from galvatron_tpu.parallel.quant_collectives import _wire_hop

    s = x.shape[1] // n
    idx = _flat_axis_index(tp_axes, sizes)
    perm = _ring_perm(n)
    acc = None
    for step in range(n):
        dest = jnp.mod(idx - 1 - step, n)
        x_blk = jax.lax.dynamic_slice_in_dim(x, dest * s, s, 1)
        part = jnp.einsum("bsf,fh->bsh", x_blk, w)
        if acc is None:
            acc = part
        elif quant is None or quant[0] == "none":
            acc = jax.lax.ppermute(acc, tp_axes, perm) + part
        else:
            acc = _wire_hop(acc, tp_axes, perm, quant[0], quant[1]).astype(
                part.dtype) + part
    return acc


def _row_matmul_dense(x, w, *, tp_axes, n, sizes, quant=None):
    # psum_scatter reduces inside the collective — there is no payload seam
    # to quantize, so the 'shard_map' mode's row matmul stays full-precision
    # (the linter documents this asymmetry; 'overlap' quantizes both rings)
    del n, sizes, quant
    part = jnp.einsum("bsf,fh->bsh", x, w)
    return jax.lax.psum_scatter(part, tp_axes, scatter_dimension=1, tiled=True)


def _row_bwd_chunks(x, w, g, *, tp_axes, n, sizes):
    """Row backward = the column forward's mirror: the seq-sharded cotangent
    g (B, s, H) ring-all-gathers while each arriving block immediately
    feeds its chunk of dx = g_full @ w^T (placed at the block's seq offset)
    and of dw = x^T @ g_full."""
    b, s = g.shape[0], g.shape[1]
    f = x.shape[2]
    idx = _flat_axis_index(tp_axes, sizes)
    perm = _ring_perm(n)
    dx = jnp.zeros((b, n * s, f), x.dtype)
    dw = jnp.zeros_like(w)
    g_cur = g
    for step in range(n):
        src = jnp.mod(idx - step, n)
        part = jnp.einsum("bsh,fh->bsf", g_cur, w)
        dx = jax.lax.dynamic_update_slice(
            dx, part, (jnp.int32(0), src * s, jnp.int32(0)))
        x_src = jax.lax.dynamic_slice_in_dim(x, src * s, s, 1)
        dw = dw + jnp.einsum("bsf,bsh->fh", x_src, g_cur)
        if step < n - 1:
            g_cur = jax.lax.ppermute(g_cur, tp_axes, perm)
    return dx, dw


def _vary_like(w, x):
    """Cast the weight shard to vary over every manual axis the activation
    varies over (the dp axes: the kernel enters the region replicated over
    them). A custom_vjp's bwd rule must hand back cotangents of its inputs'
    own type, and dw = x^T g varies wherever x does; with the cast made
    OUTSIDE the custom_vjp, its transpose is the psum of dw over those axes —
    the data-parallel gradient sum, placed by autodiff where the typing says
    it belongs."""
    missing = tuple(sorted(jax.typeof(x).vma - jax.typeof(w).vma))
    return jax.lax.pcast(w, missing, to="varying") if missing else w


def make_col_matmul(tp_axes: Tuple[str, ...], n: int, sizes: Tuple[int, ...], *,
                    mode: str, use_custom_vjp: bool = True, quant=None):
    """(x_shard (B,s,H), w_shard (H,...)) -> (B,S,...). With `use_custom_vjp`
    the overlap mode attaches the hand-scheduled ring backward; the autodiff
    fallback (the tests' parity oracle, as in ring_attention) differentiates
    the unrolled forward. ``quant`` = (wire dtype, block) quantizes the
    FORWARD ring payload (tp_comm_quant); the hand-scheduled backward keeps
    full-precision cotangent rings — the straight-through convention, so
    gradients are taken as if the forward wire were exact."""
    kw = dict(tp_axes=tuple(tp_axes), n=n, sizes=tuple(sizes), quant=quant)
    bkw = dict(tp_axes=tuple(tp_axes), n=n, sizes=tuple(sizes))
    fwd_impl = _col_matmul_dense if mode == "shard_map" else _col_matmul_chunks
    if mode == "shard_map" or not use_custom_vjp:
        return partial(fwd_impl, **kw)

    @jax.custom_vjp
    def col(x, w):
        return _col_matmul_chunks(x, w, **kw)

    col.defvjp(lambda x, w: (_col_matmul_chunks(x, w, **kw), (x, w)),
               lambda res, g: _col_bwd_chunks(*res, g, **bkw))
    return lambda x, w: col(x, _vary_like(w, x))


def make_row_matmul(tp_axes: Tuple[str, ...], n: int, sizes: Tuple[int, ...], *,
                    mode: str, use_custom_vjp: bool = True, quant=None):
    """(x (B,S,f), w (f,H)) -> (B,s,H); see make_col_matmul."""
    kw = dict(tp_axes=tuple(tp_axes), n=n, sizes=tuple(sizes), quant=quant)
    bkw = dict(tp_axes=tuple(tp_axes), n=n, sizes=tuple(sizes))
    fwd_impl = _row_matmul_dense if mode == "shard_map" else _row_matmul_chunks
    if mode == "shard_map" or not use_custom_vjp:
        return partial(fwd_impl, **kw)

    @jax.custom_vjp
    def row(x, w):
        return _row_matmul_chunks(x, w, **kw)

    row.defvjp(lambda x, w: (_row_matmul_chunks(x, w, **kw), (x, w)),
               lambda res, g: _row_bwd_chunks(*res, g, **bkw))
    return lambda x, w: row(x, _vary_like(w, x))


# -------------------------------------------------------------- layer body
def manual_param_specs(cfg, axes: LayerAxes) -> Params:
    """The manual region's in_specs for one layer's params: the GSPMD specs
    (models/base.layer_param_specs) with every non-tp mesh axis dropped —
    zero3 dims enter gathered (shard_map inserts the boundary all-gather,
    exactly the ZeRO-3 gather GSPMD would emit) and the transpose
    reduce-scatters the cotangent back outside."""
    from galvatron_tpu.models.base import layer_param_specs

    tp_set = set(axes.tp)

    def keep_tp(sp: P) -> P:
        entries = []
        for e in sp:
            kept = tuple(a for a in S._entry_axes(e) if a in tp_set)
            entries.append(S._ax(kept))
        return P(*entries)

    return jax.tree.map(keep_tp, layer_param_specs(cfg, axes),
                        is_leaf=lambda t: isinstance(t, P))


def manual_layer_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg,
    *,
    mesh: Mesh,
    axes: LayerAxes,
    hp: Optional[HybridParallelConfig] = None,
    attn_bias: Optional[jax.Array] = None,
    mode: str = "overlap",
    use_custom_vjp: bool = True,
) -> jax.Array:
    """One transformer block with manual TP collectives, drop-in signature-
    compatible with models/base.layer_forward for run_layers' scan and
    unrolled bodies. `x` is the (B, S, H) global activation carrying the
    inter-layer act_spec sharding (batch over dp, seq over tp — megatron-sp);
    the whole block runs under one shard_map over dp+tp with qkv/mlp-in as
    overlapped column matmuls, attention local on the head shard, and
    attn-out/mlp-out as overlapped row matmuls."""
    if mode not in ("shard_map", "overlap"):
        raise ValueError("manual_layer_forward mode must be 'shard_map' or "
                         "'overlap', got %r" % mode)
    # tp_comm_quant: wire-encode the ring payloads (ROADMAP item 2 /
    # EQuARX); fp8 without runtime support refuses loudly (GLS013), the
    # never-silently-differ contract
    quant = None
    tp_quant = getattr(hp, "tp_comm_quant", "none") if hp is not None else "none"
    if tp_quant != "none":
        from galvatron_tpu.parallel import quant_collectives as QC

        if tp_quant == "fp8_e4m3" and not QC.fp8_supported():
            from galvatron_tpu.analysis import diagnostics as D

            raise D.DiagnosticError([D.make(
                "GLS013", "tp_comm_quant='fp8_e4m3' needs "
                "jax.numpy.float8_e4m3fn, which this jax does not provide",
                key="tp_comm_quant",
            )])
        quant = (tp_quant, int(getattr(hp, "comm_quant_block", 64)))
    tp_axes = tuple(axes.tp)
    n = mesh_axis_size(mesh, tp_axes)
    sizes = tuple(mesh.shape[a] for a in tp_axes)
    bd = S._ax(axes.batch_axes)
    x_spec = P(bd, S._ax(axes.seq_axes), None)
    p_specs = manual_param_specs(cfg, axes)
    has_bias = attn_bias is not None
    dtype = cfg.compute_dtype

    def body(lp, xs, pos, bias):
        col = make_col_matmul(tp_axes, n, sizes, mode=mode,
                              use_custom_vjp=use_custom_vjp, quant=quant)
        row = make_row_matmul(tp_axes, n, sizes, mode=mode,
                              use_custom_vjp=use_custom_vjp, quant=quant)

        from galvatron_tpu.models.parts.common import _activation, _norm
        from galvatron_tpu.ops.attention import core_attention
        from galvatron_tpu.ops.rope import apply_rotary

        def col_proj(pk, y):
            out = col(y, pk["kernel"].astype(dtype))
            if "bias" in pk:
                out = out + pk["bias"].astype(dtype)
            return out

        residual = xs
        y = _norm(xs, lp["ln1"], cfg) if cfg.pre_norm else xs
        if cfg.fused_qkv:
            qkv = col_proj(lp["wqkv"], y)  # (B, S, 3, nh_loc, hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            q = col_proj(lp["wq"], y)
            kv = col_proj(lp["wkv"], y)  # (B, S, 2, nkv_loc, hd)
            k, v = kv[:, :, 0], kv[:, :, 1]
        if cfg.position_type == "rope":
            q = apply_rotary(q, pos, cfg.rope_theta)
            k = apply_rotary(k, pos, cfg.rope_theta)
        # attention is LOCAL on the head shard: q/k/v are full-sequence
        attn = core_attention(q, k, v, causal=cfg.causal, bias=bias,
                              impl=cfg.attn_impl, bias_type="key_padding")
        attn = attn.reshape(attn.shape[0], attn.shape[1], -1)
        o = row(attn, lp["wo"]["kernel"].astype(dtype))
        if "bias" in lp["wo"]:
            o = o + lp["wo"]["bias"].astype(dtype)
        xs = residual + o
        if not cfg.pre_norm:
            xs = _norm(xs, lp["ln1"], cfg)

        residual = xs
        y = _norm(xs, lp["ln2"], cfg) if cfg.pre_norm else xs
        wi_out = col_proj(lp["wi"], y)
        if cfg.activation == "swiglu":
            hmid = jax.nn.silu(wi_out[:, :, 0]) * wi_out[:, :, 1]
        else:
            hmid = _activation(wi_out, cfg)
        out = row(hmid, lp["wo_mlp"]["kernel"].astype(dtype))
        if "bias" in lp["wo_mlp"]:
            out = out + lp["wo_mlp"]["bias"].astype(dtype)
        xs = residual + out
        if not cfg.pre_norm:
            xs = _norm(xs, lp["ln2"], cfg)
        return xs

    in_specs = (p_specs, x_spec, P(bd, None), P(bd, None, None, None))
    if not has_bias:
        # consistent arity (as in ring_attention): a zero operand the body
        # feeds to core_attention as bias=None would change the program, so
        # pass None through a closure instead
        body_fn = lambda lp, xs, pos: body(lp, xs, pos, None)  # noqa: E731
        in_specs = in_specs[:3]
        operands = (p, x, positions)
    else:
        body_fn = body
        operands = (p, x, positions, attn_bias)
    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = mesh if ctx.empty else ctx
    # manual over EVERY mesh axis, not dp+tp alone: what is left is 'pp',
    # which has size 1 wherever this path runs (run_layers, pp=1). An axis
    # left auto makes jax annotate shardings inside the psum reduction
    # bodies, which XLA:CPU's bf16 all-reduce promotion aborts on.
    return jax.shard_map(
        body_fn,
        mesh=use_mesh,
        in_specs=in_specs,
        out_specs=x_spec,
        axis_names=set(use_mesh.axis_names) - set(use_mesh.manual_axes),
    )(*operands)


# ----------------------------------------------------- overlap measurement
def measure_comm_hidden(
    cfg,
    hp: HybridParallelConfig,
    mesh: Mesh,
    *,
    batch_size: Optional[int] = None,
    iters: int = 3,
    warmup: int = 1,
) -> List[Dict[str, Any]]:
    """Measured communication time hidden by the decomposed path, per TP
    LayerRun: wall-clock of ONE representative layer (fwd+bwd, scaled by
    the run's length) under ``overlap`` vs the serialized manual mode
    (``shard_map`` — same collectives, no interleaving).
    ``comm_hidden_ms = max(serial - overlap, 0)`` is the comm the chunked
    schedule moved off the critical path. One small jitted program per
    (run, mode) on synthetic activations — a profiling helper (driver
    --profile / --telemetry), never on the training hot path."""
    import time as _time

    bsz = batch_size or hp.global_bsz
    seq = cfg.max_seq_len
    key = jax.random.PRNGKey(0)
    out: List[Dict[str, Any]] = []
    for ridx, run in enumerate(layer_runs(hp)):
        ax = layer_axes(hp, run.start)
        if len(ax.tp) == 0 or manual_tp_reason(cfg, hp, run.strategy) is not None:
            continue
        from galvatron_tpu.models.base import init_layer_params

        lp = init_layer_params(key, cfg)
        x = jax.random.normal(key, (bsz, seq, cfg.hidden_size), jnp.float32)
        x = x.astype(cfg.compute_dtype)
        positions = jnp.broadcast_to(jnp.arange(seq), (bsz, seq))

        def timed(mode):
            def loss(p_, x_):
                y = manual_layer_forward(
                    p_, x_, positions, cfg, mesh=mesh, axes=ax, hp=hp,
                    mode=mode)
                return jnp.mean(y.astype(jnp.float32) ** 2)

            f = jax.jit(jax.value_and_grad(loss))
            for _ in range(max(warmup, 1)):
                jax.block_until_ready(f(lp, x))  # galv-lint: ignore[GLC005] -- timing harness: the sync IS the measurement
            ts = []
            for _ in range(max(iters, 1)):
                t0 = _time.perf_counter()
                jax.block_until_ready(f(lp, x))  # galv-lint: ignore[GLC005] -- timing harness: the sync IS the measurement
                ts.append(_time.perf_counter() - t0)
            return min(ts) * 1e3

        overlap_ms = timed("overlap")
        serial_ms = timed("shard_map")
        out.append({
            "run": ridx,
            "start": run.start,
            "stop": run.stop,
            "overlap_ms": round(overlap_ms * run.length, 4),
            "serial_ms": round(serial_ms * run.length, 4),
            "comm_hidden_ms": round(max(serial_ms - overlap_ms, 0.0) * run.length, 4),
        })
    return out
