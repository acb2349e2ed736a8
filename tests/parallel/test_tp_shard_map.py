"""Unit tests for the manual shard_map TP primitives (ISSUE 8,
parallel/tp_shard_map.py): the decomposed ppermute ring matmuls against
their dense references, the hand-written ring VJP against the autodiff
oracle, the support checker's refusal taxonomy, and the in_spec derivation
that gathers ZeRO-3 dims at the region boundary. Full-layer parity against
GSPMD lives in tests/models/test_tp_comm_mode.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.base import layer_param_specs
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.parallel import tp_shard_map as T
from galvatron_tpu.parallel.mesh import build_mesh, layer_axes
from jax.sharding import PartitionSpec as P

# the ring-primitive programs here are small (<1s compiles), but the module
# shares the session with the full parity matrix; keep its plain-jit
# compiles out of the persistent cache (deserialized-executable hazard,
# tests/conftest.py)
pytestmark = pytest.mark.usefixtures("disable_persistent_compile_cache")

B, S, H, F = 4, 16, 8, 12


def tp_mesh(devices8, tp):
    """A mesh whose minor axes realise tp (the run_layers geometry). The
    hp only supplies mesh/axes geometry; its global_bsz is independent of
    the unit tests' array batch."""
    hp = HybridParallelConfig.uniform(8, 1, tp=tp, global_bsz=8)
    return build_mesh(hp, devices8), layer_axes(hp, 0)


def shard_mapped(mesh, ax, fn, in_specs, out_spec):
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        axis_names=set(ax.dp) | set(ax.tp),
    ))


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("mode", ["shard_map", "overlap"])
def test_col_matmul_matches_dense(devices8, tp, mode):
    """Ring all-gather+matmul == gather-then-matmul, with a 3-d kernel tail
    (the head-major qkv layout)."""
    mesh, ax = tp_mesh(devices8, tp)
    n = tp
    sizes = tuple(mesh.shape[a] for a in ax.tp)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (H, 4, F), jnp.float32)

    def body(xs, ws):
        col = T.make_col_matmul(tuple(ax.tp), n, sizes, mode=mode)
        return col(xs, ws)

    got = shard_mapped(
        mesh, ax, body,
        (P(T.S._ax(ax.dp), T.S._ax(ax.tp), None), P(None, None, T.S._ax(ax.tp))),
        P(T.S._ax(ax.dp), None, None, T.S._ax(ax.tp)),
    )(x, w)
    ref = jnp.einsum("bsh,hcf->bscf", x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("tp", [2, 4])
@pytest.mark.parametrize("mode", ["shard_map", "overlap"])
def test_row_matmul_matches_dense(devices8, tp, mode):
    mesh, ax = tp_mesh(devices8, tp)
    n = tp
    sizes = tuple(mesh.shape[a] for a in ax.tp)
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, F), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (F, H), jnp.float32)

    def body(xs, ws):
        row = T.make_row_matmul(tuple(ax.tp), n, sizes, mode=mode)
        return row(xs, ws)

    got = shard_mapped(
        mesh, ax, body,
        (P(T.S._ax(ax.dp), None, T.S._ax(ax.tp)), P(T.S._ax(ax.tp), None)),
        P(T.S._ax(ax.dp), T.S._ax(ax.tp), None),
    )(x, w)
    ref = jnp.einsum("bsf,fh->bsh", x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("which", ["col", "row"])
def test_ring_custom_vjp_matches_autodiff_oracle(devices8, which):
    """The hand-scheduled ring backward == plain autodiff through the
    unrolled ring forward (ring_attention's oracle discipline)."""
    tp = 2
    mesh, ax = tp_mesh(devices8, tp)
    sizes = tuple(mesh.shape[a] for a in ax.tp)
    if which == "col":
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (H, F), jnp.float32)
        in_specs = (P(T.S._ax(ax.dp), T.S._ax(ax.tp), None),
                    P(None, T.S._ax(ax.tp)))
        maker = T.make_col_matmul
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (B, S, F), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (F, H), jnp.float32)
        in_specs = (P(T.S._ax(ax.dp), None, T.S._ax(ax.tp)),
                    P(T.S._ax(ax.tp), None))
        maker = T.make_row_matmul

    def loss_fn(use_custom):
        def body(xs, ws):
            op = maker(tuple(ax.tp), tp, sizes, mode="overlap",
                       use_custom_vjp=use_custom)
            # the GLOBAL loss: out_specs=P() promises a value every device
            # agrees on, which the per-shard partial sum is not
            return jax.lax.psum(jnp.sum(op(xs, ws).astype(jnp.float32) ** 2),
                                tuple(ax.dp) + tuple(ax.tp))

        f = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=P(),
            axis_names=set(ax.dp) | set(ax.tp))
        return jax.jit(jax.value_and_grad(lambda a, b: f(a, b), argnums=(0, 1)))

    ref, (rx, rw) = loss_fn(False)(x, w)
    got, (gx, gw) = loss_fn(True)(x, w)
    # fp32, sums taken in another order (the loss and dw are now summed over
    # every shard): ~10 ulp of the largest term, which bounds what
    # cancellation can leave in a small element
    np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
    for g, r in ((gx, rx), (gw, rw)):
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(g), r, rtol=1e-5,
                                   atol=1e-6 * np.abs(r).max())


# ------------------------------------------------------------------ support
def tiny_cfg(**kw):
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_seq_len", 16)
    return TransformerConfig(**kw)


class TestSupportChecker:
    def test_supported(self):
        hp = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8)
        assert T.manual_tp_reason(tiny_cfg(), hp, hp.layers[0]) is None

    def test_tp1_trivially_supported(self):
        hp = HybridParallelConfig.uniform(8, 2, global_bsz=8)
        assert T.manual_tp_reason(tiny_cfg(), hp, hp.layers[0]) is None

    @pytest.mark.parametrize("kw,frag", [
        (dict(tp=2, sp=1), "ulysses"),
        (dict(tp=2, cp=2), "context parallelism"),
        (dict(tp=2, sequence_parallel=False), "megatron-sp"),
    ])
    def test_structural_refusals(self, kw, frag):
        hp = HybridParallelConfig.uniform(8, 2, global_bsz=8, **kw)
        reason = T.manual_tp_reason(tiny_cfg(), hp, hp.layers[0])
        assert reason is not None and frag in reason

    @pytest.mark.parametrize("cfg_kw,frag", [
        (dict(num_heads=6), "num_heads"),
        (dict(num_heads=4, num_kv_heads=2), "num_kv_heads"),
        (dict(ffn_hidden=130), "ffn_hidden"),
        (dict(max_seq_len=18), "max_seq_len"),
    ])
    def test_model_shape_refusals(self, cfg_kw, frag):
        hp = HybridParallelConfig.uniform(8, 2, tp=4, global_bsz=8)
        reason = T.manual_tp_reason(tiny_cfg(**cfg_kw), hp, hp.layers[0])
        assert reason is not None and frag in reason, reason

    def test_no_model_cfg_checks_structure_only(self):
        hp = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8)
        assert T.manual_tp_reason(None, hp, hp.layers[0]) is None
        hp_sp = HybridParallelConfig.uniform(8, 2, tp=2, sp=1, global_bsz=8)
        assert T.manual_tp_reason(None, hp_sp, hp_sp.layers[0]) is not None

    def test_assert_raises_gls012(self):
        from galvatron_tpu.analysis.diagnostics import DiagnosticError

        hp = HybridParallelConfig.uniform(8, 2, tp=2, sp=1, global_bsz=8,
                                          tp_comm_mode="overlap")
        with pytest.raises(DiagnosticError, match="GLS012"):
            T.assert_manual_tp_supported(tiny_cfg(), hp, hp.layers[0])

    def test_wants_manual_tp(self):
        hp2 = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8,
                                           tp_comm_mode="overlap")
        hp1 = HybridParallelConfig.uniform(8, 2, global_bsz=8,
                                           tp_comm_mode="overlap")
        hpg = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8)
        assert T.wants_manual_tp(hp2, layer_axes(hp2, 0))
        assert not T.wants_manual_tp(hp1, layer_axes(hp1, 0))  # tp=1: inert
        assert not T.wants_manual_tp(hpg, layer_axes(hpg, 0))  # gspmd
        assert not T.wants_manual_tp(None, None)


# ------------------------------------------------------------------- specs
def test_manual_param_specs_drop_non_tp_axes():
    """The manual in_specs keep tp shardings and gather everything else:
    ZeRO-3 dp dims enter replicated (boundary all-gather)."""
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 2, tp=2, sdp=1, global_bsz=8)
    ax = layer_axes(hp, 0)
    manual = T.manual_param_specs(cfg, ax)
    ref = layer_param_specs(cfg, ax)
    tp_set = set(ax.tp)
    flat_m = jax.tree.leaves(manual, is_leaf=lambda t: isinstance(t, P))
    flat_r = jax.tree.leaves(ref, is_leaf=lambda t: isinstance(t, P))
    assert len(flat_m) == len(flat_r)
    saw_tp = saw_dropped_dp = False
    for m, r in zip(flat_m, flat_r):
        for em, er in zip(m, r):
            m_ax, r_ax = set(T.S._entry_axes(em)), set(T.S._entry_axes(er))
            assert m_ax == r_ax & tp_set
            saw_tp |= bool(m_ax)
            saw_dropped_dp |= bool(r_ax - tp_set)
    assert saw_tp and saw_dropped_dp


def test_measure_comm_hidden_reports_tp_runs(devices8):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8,
                                      tp_comm_mode="overlap")
    rows = T.measure_comm_hidden(cfg, hp, build_mesh(hp, devices8),
                                 batch_size=4, iters=1, warmup=1)
    assert len(rows) == 1
    row = rows[0]
    assert row["run"] == 0 and (row["start"], row["stop"]) == (0, 2)
    assert row["overlap_ms"] > 0 and row["serial_ms"] > 0
    assert row["comm_hidden_ms"] >= 0


def test_measure_comm_hidden_skips_non_tp_runs(devices8):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 2, global_bsz=8,
                                      tp_comm_mode="overlap")
    assert T.measure_comm_hidden(cfg, hp, build_mesh(hp, devices8),
                                 batch_size=4) == []
