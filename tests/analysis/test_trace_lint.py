"""Traced-program linter (analysis/trace_lint.py, GLT codes).

Golden repros: minimal crafted programs reproducing each pinned jax-0.4.37
GSPMD miscompile class, asserting trace-lint flags them — and stays silent
on the fixed equivalents the shipped code uses. The three `_flagged` test
names are load-bearing: the WA004/WA005/WA006 entries of the workaround
inventory (utils/jax_compat.py) name them as pinning tests.

Everything here is abstract tracing — no compiles, no buffers — so the
whole module stays cheap on the single-core CI box.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from galvatron_tpu.analysis import trace_lint as TL
from galvatron_tpu.config.strategy import HybridParallelConfig


@pytest.fixture(scope="module")
def mesh(devices8):
    return Mesh(np.array(devices8).reshape(4, 2), ("dp", "tp"))


def _wsc(mesh, x, spec):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _sds(shape, dtype="float32"):
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype))


def _codes(closed):
    res = TL.lint_closed_jaxpr(closed)
    return set(res.report.codes()), res


# ------------------------------------------------- GLT001 (stack_layer_run)
def test_glt001_sharded_reshape_in_scan_flagged(mesh):
    def bad_scan(x):
        def body(c, _):
            c = _wsc(mesh, c, P("tp", None))
            c2 = c.reshape(4, 2, 8)  # splits dim0, which tp shards
            return c2.reshape(8, 8) * 1.5, None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    codes, res = _codes(jax.make_jaxpr(jax.jit(bad_scan))(_sds((8, 8))))
    assert "GLT001" in codes, res.report.render()
    d = next(d for d in res.report.diagnostics if d.code == "GLT001")
    assert d.severity == "error"
    assert d.file and d.file.endswith(".py") and d.line  # source-mapped


def test_glt001_unsharded_reshape_in_scan_clean(mesh):
    def good_scan(x):
        def body(c, _):
            c = _wsc(mesh, c, P("tp", None))
            c2 = c[:, None, :] * jnp.ones((8, 2, 8), np.float32)
            return c2.sum(axis=1) * 0.5, None

        y, _ = jax.lax.scan(body, x, None, length=3)
        return y

    codes, res = _codes(jax.make_jaxpr(jax.jit(good_scan))(_sds((8, 8))))
    assert not res.report.errors, res.report.render()


# --------------------------------------------- GLT002 (make_pipelined_loss)
def test_glt002_unconstrained_microbatch_split_flagged(mesh):
    def bad_split(x):
        x = _wsc(mesh, x, P("dp", None))
        mbs = x.reshape(4, 2, 16)  # splits the dp-sharded batch dim

        def tick(c, mb):
            return c + mb.sum(), None

        c, _ = jax.lax.scan(tick, jnp.float32(0.0), mbs)
        return c

    codes, res = _codes(jax.make_jaxpr(jax.jit(bad_split))(_sds((8, 16))))
    assert "GLT002" in codes, res.report.render()


def test_glt002_constrained_split_clean(mesh):
    def good_split(x):
        x = _wsc(mesh, x, P("dp", None))
        mbs = x.reshape(4, 2, 16)
        # the shipped parallel/pipeline.py split() pattern: re-constrain
        mbs = _wsc(mesh, mbs, P(None, "dp", None))

        def tick(c, mb):
            return c + mb.sum(), None

        c, _ = jax.lax.scan(tick, jnp.float32(0.0), mbs)
        return c

    codes, res = _codes(jax.make_jaxpr(jax.jit(good_split))(_sds((8, 16))))
    assert not res.report.errors, res.report.render()


# -------------------------------------------------- GLT003 (init_params pp)
def _stacked_init(r):
    ws = [jax.random.normal(jax.random.fold_in(r, i), (4, 4))
          for i in range(4)]
    return jnp.stack(ws)


def test_glt003_stacked_init_under_out_shardings_flagged(mesh):
    r = _sds((2,), "uint32")
    closed = jax.make_jaxpr(jax.jit(
        _stacked_init,
        out_shardings=NamedSharding(mesh, P("dp", None, None))))(r)
    codes, res = _codes(closed)
    assert "GLT003" in codes, res.report.render()


def test_glt003_clean_variants(mesh):
    r = _sds((2,), "uint32")
    # no out_shardings at all: the WA006 host-side-stack workaround's shape
    codes, res = _codes(jax.make_jaxpr(jax.jit(_stacked_init))(r))
    assert not res.report.errors, res.report.render()
    # out_shardings that leave the stacked dim unsharded are fine too
    codes, res = _codes(jax.make_jaxpr(jax.jit(
        _stacked_init,
        out_shardings=NamedSharding(mesh, P(None, "tp", None))))(r))
    assert not res.report.errors, res.report.render()


# ------------------------------------------------- GLT004 (donation waste)
def test_glt004_donated_without_alias_flagged():
    def step(p, b):
        return (p * b).sum()  # scalar out: nothing to alias p into

    codes, res = _codes(jax.make_jaxpr(
        jax.jit(step, donate_argnums=(0,)))(_sds((8, 8)), _sds((8, 8))))
    assert "GLT004" in codes, res.report.render()
    assert not res.report.errors  # warning, not error


def test_glt004_matched_donation_clean():
    def step(p, b):
        return p + b

    codes, res = _codes(jax.make_jaxpr(
        jax.jit(step, donate_argnums=(0,)))(_sds((8, 8)), _sds((8, 8))))
    assert "GLT004" not in codes, res.report.render()


# ------------------------------------- GLT005 (manual-region vjp closure)
def _ring_region(mesh, close_over):
    from jax import shard_map

    def outer(x):
        def body(xb):
            @jax.custom_vjp
            def f(v):
                return v * 2.0

            def fwd(v):
                return f(v), v

            if close_over:
                # traced in the region scope, read only by the bwd closure:
                # the hazard — its eqn dangles in the body jaxpr
                idx = jax.lax.axis_index("tp")

                def bwd(res, g):
                    return (g * (idx + 1).astype(g.dtype),)
            else:
                def bwd(res, g):
                    i = jax.lax.axis_index("tp")
                    return (g * (i + 1).astype(g.dtype),)

            f.defvjp(fwd, bwd)
            return f(xb)

        sm = shard_map(body, mesh=mesh, in_specs=P(None, "tp"),
                       out_specs=P(None, "tp"), check_vma=False)
        return jax.grad(lambda v: sm(v).sum())(x)

    return jax.make_jaxpr(jax.jit(outer))(_sds((8, 8)))


def test_glt005_vjp_closure_over_axis_index_flagged(mesh):
    codes, res = _codes(_ring_region(mesh, close_over=True))
    assert "GLT005" in codes, res.report.render()


def test_glt005_axis_index_inside_bwd_clean(mesh):
    codes, res = _codes(_ring_region(mesh, close_over=False))
    assert "GLT005" not in codes, res.report.render()


# --------------------------------------------- shipped package stays clean
def test_shipped_dp8_traces_clean(gpt_cfg, devices8):
    hp = HybridParallelConfig.uniform(8, gpt_cfg.num_layers)
    res = TL.lint_model(gpt_cfg, hp, devices8)
    assert not res.report.errors, res.report.render()


def test_shipped_pp2_tp2_traces_clean(gpt_cfg, devices8):
    hp = HybridParallelConfig.uniform(
        8, gpt_cfg.num_layers, pp=2, tp=2, chunks=2)
    res = TL.lint_model(gpt_cfg, hp, devices8)
    assert not res.report.errors, res.report.render()


def test_shipped_manual_tp_traces_clean_with_collectives(gpt_cfg, devices8):
    """tp_comm_mode=shard_map: the manual TP ring's collectives are visible
    at trace level — the audit must see them (no GLT101 drift) and every
    one must carry source file:line attribution."""
    hp = HybridParallelConfig.uniform(
        8, gpt_cfg.num_layers, tp=2, tp_comm_mode="shard_map")
    res = TL.lint_model(gpt_cfg, hp, devices8)
    assert not res.report.errors, res.report.render()
    assert "GLT101" not in res.report.codes(), res.report.render()
    assert res.collectives, "manual TP traced no collectives"
    assert all(c["file"] and c["line"] for c in res.collectives)


def test_trace_result_renders_audit(gpt_cfg, devices8):
    hp = HybridParallelConfig.uniform(
        8, gpt_cfg.num_layers, tp=2, tp_comm_mode="shard_map")
    res = TL.lint_model(gpt_cfg, hp, devices8)
    out = res.render_audit()
    assert "traced collectives" in out
    assert "psum" in out or "ppermute" in out
