"""Numerics suite for the quantized collectives (ISSUE 9).

Layers of the pyramid, cheapest first: pure quant/dequant kernel properties
(no mesh), the quantized rings vs their exact native collectives under a
shard_map harness, the explicit quantized grad-sync train step vs the fp32
GSPMD step (shared reference via a module-scoped memo), and the quantized
TP ring payloads vs the unquantized manual path. The full dtype x layout
cross-product is marked ``slow`` — tier-1 keeps one representative of each
mechanism (budget: the whole file well under the 40s addition cap)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.config.strategy import COMM_DTYPES, HybridParallelConfig
from galvatron_tpu.parallel import quant_collectives as QC

# full train-step programs compile >1s via PLAIN jit here and can recur
# identically across the session (the fp32 references) — keep them out of
# the session's persistent compile cache: a second identical compile would
# execute a DESERIALIZED XLA:CPU executable, the known jaxlib 0.4.37 heap
# corruption (tests/conftest.py hazard; test_migration's precedent)
pytestmark = [pytest.mark.parallel,
              pytest.mark.usefixtures("disable_persistent_compile_cache")]

QUANT = ("int8", "fp8_e4m3")
# relative-to-blockmax error of one quantize/dequantize pass: int8 rounds to
# 1/127 steps (half-step max error); fp8-e4m3 has 3 mantissa bits (2^-4
# relative half-spacing) but subnormal tails are coarser — bound loosely
REL_ERR = {"int8": 0.5 / 127.0 + 1e-6, "fp8_e4m3": 0.07}


def _rng(seed=0):
    return np.random.default_rng(seed)


# ============================================================ quant kernels
@pytest.mark.parametrize("dtype", QUANT)
@pytest.mark.parametrize("block", [16, 64, 256])
def test_roundtrip_error_bound_per_block(dtype, block):
    x = jnp.asarray(_rng(1).normal(size=(997,)) * 3.0, jnp.float32)  # odd: pads
    payload, scales = QC.quantize_blockwise(x, dtype, block)
    dq = QC.dequantize_blockwise(payload, scales, x.shape)
    assert dq.shape == x.shape
    # per-block bound: |x - dq| <= rel * blockmax for every element
    pad = (-x.shape[0]) % block
    xp = np.concatenate([np.asarray(x), np.zeros(pad, np.float32)]).reshape(-1, block)
    err = np.abs(np.concatenate(
        [np.asarray(dq), np.zeros(pad, np.float32)]).reshape(-1, block) - xp)
    bound = REL_ERR[dtype] * np.abs(xp).max(axis=1, keepdims=True)
    assert (err <= bound + 1e-7).all(), float((err - bound).max())


@pytest.mark.parametrize("dtype", QUANT)
def test_per_block_scales_are_absmax_over_qmax(dtype):
    block = 8
    x = jnp.asarray(_rng(2).normal(size=(4, block)).reshape(-1), jnp.float32)
    _, scales = QC.quantize_blockwise(x, dtype, block)
    qmax = {"int8": 127.0, "fp8_e4m3": 448.0}[dtype]
    expect = np.abs(np.asarray(x).reshape(-1, block)).max(axis=1) / qmax
    np.testing.assert_allclose(np.asarray(scales), expect, rtol=1e-6)


@pytest.mark.parametrize("dtype", QUANT)
def test_saturation_and_payload_range(dtype):
    x = jnp.asarray([-7.0, 7.0, 3.5, -3.5, 0.0, 1e-30, 1e4, -1e4], jnp.float32)
    payload, scales = QC.quantize_blockwise(x, dtype, 8)
    p = np.asarray(payload, np.float32)
    assert np.isfinite(p).all()
    assert (np.abs(p) <= {"int8": 127, "fp8_e4m3": 448}[dtype]).all()
    # the block absmax maps exactly to +/- qmax
    dq = np.asarray(QC.dequantize_blockwise(payload, scales, x.shape))
    np.testing.assert_allclose(dq[6], 1e4, rtol=1e-6)


def test_all_zero_block_is_exact():
    x = jnp.zeros((64,), jnp.float32)
    payload, scales = QC.quantize_blockwise(x, "int8", 16)
    assert (np.asarray(payload) == 0).all()
    assert (np.asarray(scales) == 1.0).all()  # no div-by-zero scale
    assert (np.asarray(QC.dequantize_blockwise(payload, scales, x.shape)) == 0).all()


def test_quantization_is_deterministic():
    x = jnp.asarray(_rng(3).normal(size=(513,)), jnp.float32)
    a = QC.quantize_blockwise(x, "int8", 32)
    b = QC.quantize_blockwise(x, "int8", 32)
    assert (np.asarray(a[0]) == np.asarray(b[0])).all()
    assert (np.asarray(a[1]) == np.asarray(b[1])).all()


def test_wire_bytes_per_element():
    assert QC.wire_bytes_per_element("none", 64) == 4.0
    assert QC.wire_bytes_per_element("none", 64, full_bytes=2.0) == 2.0
    assert QC.wire_bytes_per_element("bf16", 64) == 2.0
    assert QC.wire_bytes_per_element("int8", 64) == 1.0 + 4.0 / 64
    assert QC.wire_bytes_per_element("fp8_e4m3", 16) == 1.25


# ========================================================== quantized rings
def _ring_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("dp",))


def _run_manual(fn, mesh, in_specs, out_specs):
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names={"dp"}, check_vma=False))


@pytest.mark.parametrize("dtype,block", [("int8", 16), ("int8", 64),
                                         ("fp8_e4m3", 64)])
def test_ring_all_reduce_matches_psum_within_bound(dtype, block):
    mesh = _ring_mesh()
    x = jnp.asarray(_rng(4).normal(size=(4, 300)), jnp.float32)

    ring = _run_manual(
        lambda v: QC.ring_all_reduce(v[0], ("dp",), (4,), dtype=dtype,
                                     block=block),
        mesh, P("dp"), P())
    exact = np.asarray(x).sum(axis=0)
    got = np.asarray(ring(x))
    # n-1 quantized wire hops on the reduce-scatter + 1 on the gather, each
    # bounded by rel x the running partial's block magnitude (<= n x the
    # input's absmax): hops x rel x n x absmax
    bound = 5 * REL_ERR[dtype] * 4 * float(np.abs(np.asarray(x)).max()) + 1e-5
    assert (np.abs(got - exact) <= bound).all(), np.abs(got - exact).max()


def test_ring_all_reduce_error_scales_with_wire_precision():
    """int8 (rel ~4e-3) beats fp8-e4m3 (rel ~7e-2) on the same data — the
    error ordering the accuracy-budget semantics rest on."""
    mesh = _ring_mesh()
    x = jnp.asarray(_rng(4).normal(size=(4, 300)) * 3.0, jnp.float32)
    exact = np.asarray(x).sum(axis=0)

    def err(dtype):
        ring = _run_manual(
            lambda v: QC.ring_all_reduce(v[0], ("dp",), (4,), dtype=dtype,
                                         block=64),
            mesh, P("dp"), P())
        return float(np.abs(np.asarray(ring(x)) - exact).max())

    assert err("int8") < err("fp8_e4m3")


def test_ring_all_reduce_none_is_exact_psum():
    mesh = _ring_mesh()
    x = jnp.asarray(_rng(5).normal(size=(4, 64)), jnp.float32)
    ring = _run_manual(
        lambda v: QC.ring_all_reduce(v[0], ("dp",), (4,), dtype="none"),
        mesh, P("dp"), P())
    np.testing.assert_array_equal(np.asarray(ring(x)),
                                  np.asarray(jnp.sum(x, axis=0)))


def test_ring_all_gather_bf16_passthrough_is_bitwise():
    """bf16 payloads are a pure cast chain: gathering a bf16 shard moves it
    bit-exactly (no scales, no rounding beyond the cast, which is identity
    on bf16 input)."""
    mesh = _ring_mesh()
    x = jnp.asarray(_rng(6).normal(size=(8, 16)), jnp.bfloat16)
    ring = _run_manual(
        lambda v: QC.ring_all_gather(v, ("dp",), (4,), axis=0, dtype="bf16"),
        mesh, P("dp"), P())
    native = _run_manual(
        lambda v: jax.lax.all_gather(v, ("dp",), axis=0, tiled=True),
        mesh, P("dp"), P())
    assert (np.asarray(ring(x).view(jnp.uint16))
            == np.asarray(native(x).view(jnp.uint16))).all()


@pytest.mark.parametrize("axis", [0, 1])
def test_ring_all_gather_int8_places_blocks_correctly(axis):
    mesh = _ring_mesh()
    shape = (8, 6) if axis == 0 else (6, 8)
    x = jnp.asarray(_rng(7).normal(size=shape), jnp.float32)
    ring = _run_manual(
        lambda v: QC.ring_all_gather(v, ("dp",), (4,), axis=axis,
                                     dtype="int8", block=16),
        mesh, P(*(("dp",) if axis == 0 else (None, "dp"))), P())
    got = np.asarray(ring(x))
    assert got.shape == np.asarray(x).shape
    # every source block lands in ITS slot, within one quant pass's error
    err = np.abs(got - np.asarray(x))
    assert err.max() <= REL_ERR["int8"] * np.abs(np.asarray(x)).max() + 1e-6


def test_ring_reduce_scatter_int8_matches_psum_scatter():
    mesh = _ring_mesh()
    x = jnp.asarray(_rng(8).normal(size=(4, 8, 10)), jnp.float32)
    ring = _run_manual(
        lambda v: QC.ring_reduce_scatter(v[0], ("dp",), (4,), axis=0,
                                         dtype="int8", block=16),
        mesh, P("dp"), P("dp"))
    exact = np.asarray(x).sum(axis=0)
    got = np.asarray(ring(x)).reshape(8, 10)
    bound = 4 * REL_ERR["int8"] * np.abs(np.asarray(x)).sum(axis=0) + 1e-5
    assert (np.abs(got - exact) <= bound).all()


# =============================================== quantized grad-sync step
from galvatron_tpu.models import base as M  # noqa: E402
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.runtime.dataloader import get_train_iterator  # noqa: E402
from galvatron_tpu.runtime.model_api import (  # noqa: E402
    construct_hybrid_parallel_model,
)

CFG = TransformerConfig(
    hidden_size=32, num_heads=4, num_layers=2, vocab_size=64, max_seq_len=16,
    compute_dtype=jnp.float32, param_dtype=jnp.float32,
)
STEPS = 4
_TRAJ = {}


def _trajectory(gcd="none", pcd="none", sdp=0, chunks=1):
    """Losses of a short run under one comm-precision config (memoized: the
    fp32 references are shared across the parametrized comparisons)."""
    key = (gcd, pcd, sdp, chunks)
    if key in _TRAJ:
        return _TRAJ[key]
    import optax

    hp = HybridParallelConfig.uniform(
        4, CFG.num_layers, tp=1, sdp=sdp, global_bsz=8, chunks=chunks,
        grad_comm_dtype=gcd, param_comm_dtype=pcd, mixed_precision="fp32")
    model = construct_hybrid_parallel_model(CFG, hp)
    tx = optax.adam(1e-2)
    params = model.init_params(jax.random.PRNGKey(0))
    opt_state = model.init_opt_state(tx, params)
    step = model.make_train_step(tx, donate=False)
    it = get_train_iterator(hp, CFG.vocab_size, CFG.max_seq_len, seed=1)
    losses = []
    for _ in range(STEPS):
        params, opt_state, m = step(params, opt_state, model.shard_batch(next(it)))
        losses.append(float(m["loss"]))
    _TRAJ[key] = losses
    return losses


def test_int8_grad_sync_trains_close_to_fp32():
    """The acceptance-criteria trajectory test: quantized ddp grad sync
    tracks the fp32 GSPMD step within tolerance over a short run."""
    ref = _trajectory()
    q = _trajectory(gcd="int8")
    assert max(abs(a - b) for a, b in zip(ref, q)) < 5e-3, (ref, q)
    # the trajectory moved (params actually updated through the quant ring)
    assert q[0] != q[-1]


@pytest.mark.slow
def test_bf16_wire_is_tighter_than_int8():
    ref = _trajectory()
    bf = max(abs(a - b) for a, b in zip(ref, _trajectory(gcd="bf16")))
    assert bf < 2e-3


def test_zero3_quantized_gather_and_sync_trains():
    ref = _trajectory(sdp=1)
    q = _trajectory(gcd="int8", pcd="int8", sdp=1)
    assert max(abs(a - b) for a, b in zip(ref, q)) < 5e-3, (ref, q)


@pytest.mark.slow
def test_grad_sync_is_deterministic():
    # rebuild from scratch (bypassing the memo) and compare bitwise: the
    # quantized ring has no RNG and a fixed rotation order
    a = list(_trajectory(gcd="int8"))
    _TRAJ.pop(("int8", "none", 0, 1))
    c = _trajectory(gcd="int8")
    assert a == c


@pytest.mark.slow
@pytest.mark.parametrize("gcd", ["bf16", "int8", "fp8_e4m3"])
@pytest.mark.parametrize("sdp,chunks", [(0, 1), (0, 2), (1, 1)])
def test_quant_cross_product_slow(gcd, sdp, chunks):
    pcd = gcd if sdp else "none"
    ref = _trajectory(sdp=sdp, chunks=chunks)
    q = _trajectory(gcd=gcd, pcd=pcd, sdp=sdp, chunks=chunks)
    tol = 2e-3 if gcd == "bf16" else 8e-3
    assert max(abs(a - b) for a, b in zip(ref, q)) < tol, (gcd, ref, q)


# ------------------------------------------------------------- refusals
def test_guard_composition_refuses_gls013():
    import optax

    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    hp = HybridParallelConfig.uniform(4, 2, tp=1, global_bsz=8,
                                      grad_comm_dtype="int8",
                                      mixed_precision="fp32")
    model = construct_hybrid_parallel_model(CFG, hp)
    with pytest.raises(DiagnosticError, match="GLS013"):
        model.make_train_step(optax.adam(1e-2), guard_anomalies=True)


def test_non_pure_dp_refuses_gls013():
    import optax

    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    hp = HybridParallelConfig.uniform(4, 2, tp=2, global_bsz=8,
                                      grad_comm_dtype="int8",
                                      mixed_precision="fp32")
    model = construct_hybrid_parallel_model(CFG, hp)
    with pytest.raises(DiagnosticError, match="GLS013"):
        model.make_train_step(optax.adam(1e-2))


def test_custom_loss_refuses_gls013():
    import optax

    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    hp = HybridParallelConfig.uniform(4, 2, tp=1, global_bsz=8,
                                      grad_comm_dtype="int8",
                                      mixed_precision="fp32")
    model = construct_hybrid_parallel_model(
        CFG, hp, loss_fn=lambda p, b: jnp.float32(0.0))
    with pytest.raises(DiagnosticError, match="GLS013"):
        model.make_train_step(optax.adam(1e-2))


def test_dp1_is_inert_not_refused():
    """world=1 has no dp group: the knob is inert (GLS103 at lint time) and
    the step builds through the ordinary GSPMD path."""
    import optax

    hp = HybridParallelConfig.uniform(1, 2, tp=1, global_bsz=4,
                                      grad_comm_dtype="int8",
                                      mixed_precision="fp32")
    assert not QC.wants_quant_comm(hp)
    model = construct_hybrid_parallel_model(CFG, hp)
    model.make_train_step(optax.adam(1e-2))  # must not raise


# ----------------------------------------------------- quantized TP rings
def _tp_loss_and_grads(quant, mode="overlap"):
    B_, S_, H_, NL = 4, 32, 32, 2
    cfg = TransformerConfig(
        hidden_size=H_, num_heads=4, num_layers=NL, vocab_size=64,
        max_seq_len=S_, compute_dtype=jnp.float32, param_dtype=jnp.float32)
    params = {"layers": [
        M.init_layer_params(k, cfg)
        for k in jax.random.split(jax.random.PRNGKey(0), NL)]}
    x = 0.05 * jax.random.normal(jax.random.PRNGKey(1), (B_, S_, H_), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S_), (B_, S_))
    from galvatron_tpu.parallel.mesh import build_mesh

    hp = HybridParallelConfig.uniform(4, NL, tp=2, global_bsz=B_,
                                      tp_comm_mode=mode, tp_comm_quant=quant,
                                      mixed_precision="fp32")
    mesh = build_mesh(hp)

    def loss(p):
        y = M.run_layers(p, x, positions, cfg, hp, mesh)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss))(params)


def test_tp_ring_int8_payloads_stay_close():
    l_ref, g_ref = _tp_loss_and_grads("none")
    l_q, g_q = _tp_loss_and_grads("int8")
    assert abs(float(l_ref) - float(l_q)) < 1e-4
    gd = max(float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(g_q), jax.tree.leaves(g_ref)))
    assert gd < 1e-3, gd


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["shard_map", "overlap"])
@pytest.mark.parametrize("quant", ["bf16", "int8", "fp8_e4m3"])
def test_tp_ring_quant_cross_product_slow(mode, quant):
    l_ref, g_ref = _tp_loss_and_grads("none", mode)
    l_q, g_q = _tp_loss_and_grads(quant, mode)
    assert abs(float(l_ref) - float(l_q)) < 5e-4
    gd = max(float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(g_q), jax.tree.leaves(g_ref)))
    # shard_map mode's dense path differentiates THROUGH the quantizer
    # (no custom_vjp): grads drift further than overlap's straight-through
    assert gd < (5e-3 if mode == "shard_map" else 1e-4), (mode, quant, gd)


def test_tp_comm_quant_under_gspmd_refuses_at_construction():
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    with pytest.raises(DiagnosticError, match="GLS013"):
        HybridParallelConfig.uniform(4, 2, tp=2, global_bsz=4,
                                     tp_comm_quant="int8")


def test_comm_dtype_enum_rejected():
    with pytest.raises(ValueError, match="grad_comm_dtype"):
        HybridParallelConfig.uniform(4, 2, tp=1, global_bsz=4,
                                     grad_comm_dtype="int4")
    assert set(QUANT) <= set(COMM_DTYPES)
