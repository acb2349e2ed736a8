"""The native jax surface the codebase stands on: `jax.shard_map` with
``axis_names=``/``check_vma=`` (full and partial manual), the context abstract
mesh, and the varying-axes typing the manual regions are written against.
Nothing is patched in; these pin what the installed jax itself provides."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.utils import jax_compat


def test_surface_is_jaxs_own():
    for fn in (jax.shard_map, jax.sharding.get_abstract_mesh):
        assert fn.__module__.startswith("jax."), fn.__module__
    # no shim, install hook or out-of-process probe is left to apply one
    for name in ("install", "supports_partial_manual_shard_map"):
        assert not hasattr(jax_compat, name), name


def test_package_import_patches_nothing():
    import galvatron_tpu

    before = (jax.shard_map, jax.sharding.get_abstract_mesh)
    importlib.reload(galvatron_tpu)
    assert (jax.shard_map, jax.sharding.get_abstract_mesh) == before


def test_get_abstract_mesh_contract(devices8):
    """Outside any context the abstract mesh is EMPTY (call sites then use
    their concrete mesh); inside a manual region it names the manual axes —
    what a nested region (ring attention, the flash kernel) keys on."""
    assert jax.sharding.get_abstract_mesh().empty
    seen = []

    def body(x):
        ctx = jax.sharding.get_abstract_mesh()
        seen.append((ctx.empty, set(ctx.manual_axes)))
        return x

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("pp", "tp"))
    jax.make_jaxpr(jax.shard_map(body, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
                                 axis_names={"pp"}))(jnp.zeros((4, 4)))
    assert seen == [(False, {"pp"})]


def test_shard_map_full_manual_runs(devices8):
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("pp", "tp"))
    f = jax.shard_map(
        lambda x: jax.lax.psum(x, "tp"),
        mesh=mesh, in_specs=P("pp", "tp"), out_specs=P("pp", None),
        axis_names={"pp", "tp"}, check_vma=False,
    )
    x = jnp.arange(8.0).reshape(2, 4)
    out = jax.jit(f)(x)
    np.testing.assert_allclose(np.asarray(out), [[6.0], [22.0]])


def test_shard_map_partial_manual_compiles_and_runs(devices8):
    """Manual over 'pp' only, GSPMD-auto over the rest, with a collective
    inside — the 1F1B engines' shape. The body sees the per-'pp' block
    (4/2 x 4, NOT 4/8), and the program compiles and runs in-process."""
    mesh = Mesh(np.array(devices8[:4]).reshape(2, 2), ("pp", "dp"))
    shapes = []

    def body(x):
        shapes.append(x.shape)
        return jax.lax.ppermute(x, "pp", [(0, 1), (1, 0)])

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pp"), out_specs=P("pp"),
                              axis_names={"pp"}, check_vma=False))
    x = jnp.arange(16.0).reshape(4, 4)
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(jnp.roll(x, 2, axis=0)))
    assert shapes == [(2, 4)]


def test_varying_axes_typing_demands_the_reduction(devices8):
    """What parallel/tp_shard_map.py's gradients are derived against: a
    per-shard partial sum is typed VARYING and cannot leave through a
    replicated out_spec; the psum makes it invariant. A replicated operand
    meeting a varying one is cast for you, and the cast's transpose is the
    psum of the cotangent."""
    mesh = Mesh(np.array(devices8), ("dp",))
    x = jnp.arange(16.0).reshape(8, 2)
    w = jnp.ones((2,))

    def partial_sum(xs, ws):
        return jnp.sum(xs * ws)

    def total(xs, ws):
        return jax.lax.psum(partial_sum(xs, ws), "dp")

    def smap(fn):
        return jax.shard_map(fn, mesh=mesh, in_specs=(P("dp"), P()), out_specs=P())

    with pytest.raises(ValueError, match="require replication"):
        jax.jit(smap(partial_sum))(x, w)
    assert float(jax.jit(smap(total))(x, w)) == float(x.sum())
    # dw is summed over every shard although the body never psums it
    dw = jax.jit(jax.grad(smap(total), argnums=1))(x, w)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(x.sum(0)))


def test_ring_attention_imports_without_attributeerror():
    """The modules that hang off those names import cleanly."""
    import galvatron_tpu.ops.ring_attention  # noqa: F401
    import galvatron_tpu.parallel.pipeline_1f1b  # noqa: F401
    import galvatron_tpu.parallel.pipeline_1f1b_encdec  # noqa: F401
    import galvatron_tpu.parallel.pipeline_1f1b_swin  # noqa: F401
    import galvatron_tpu.profiler.hardware  # noqa: F401
