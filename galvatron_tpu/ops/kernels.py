"""Where a Mosaic kernel may run, said once for the kernel files of `ops/`:
how a layer's operands lie over a mesh (`KernelSharding`), whether they lie on
TPUs (`lies_on_tpu`), whether a call may take its kernels (`on_kernels`), the manual
region a device runs them in on its own rows of the batch (`rows_a_device`),
and the jit a caller of kernels is traced once through (`traced_once`). Which
SHAPES a kernel takes is each kernel file's to say, beside the kernel whose
bounds were measured (`fits`, `rows_form`, `window_takes_kernels`).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

TILE = 128  # the vector unit's lanes: what the kernels cut tokens and channels into
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))  # a product's contracted axes


class KernelSharding(NamedTuple):
    """How attention's (B, S, nh, hd) operands are laid out over a mesh:
    batch over ``batch_axes``, heads over ``head_axes``, sequence and head_dim
    whole on every device. GSPMD cannot partition a Mosaic kernel, so on a
    mesh of more than one device the flash kernel runs inside a
    `jax.shard_map` — each device runs the kernel on its own batch rows and
    heads, with no collective. The region is manual over EVERY mesh axis
    (Mosaic refuses a kernel while any axis is left auto): the remaining
    axis is 'pp', which is either size 1, already manual in the enclosing
    1F1B schedule, or the stage dim the GPipe engine maps with
    ``jax.vmap(spmd_axis_name='pp')``, which puts it into these specs."""

    mesh: Mesh
    batch_axes: Tuple[str, ...] = ()
    head_axes: Tuple[str, ...] = ()

    @classmethod
    def for_layer(cls, mesh: Mesh, axes) -> "KernelSharding":
        """The layout of one layer's attention operands (axes: LayerAxes):
        batch over its dp axes, heads over its tp axes."""
        return cls(mesh, tuple(axes.batch_axes), tuple(axes.tp))

    @property
    def on_tpu(self) -> bool:
        return self.mesh.devices.flat[0].platform == "tpu"

    def divides(self, batch: int, heads: int) -> bool:
        shape = self.mesh.shape
        return (batch % math.prod(shape[a] for a in self.batch_axes) == 0
                and heads % math.prod(shape[a] for a in self.head_axes) == 0)


def lies_on_tpu(sharding: Optional[KernelSharding]) -> bool:
    """Whether a call's operands lie on TPUs: the mesh's devices say so (a
    compile for a described TPU takes the chip's branch); with no `sharding`,
    the default backend."""
    return sharding.on_tpu if sharding is not None else jax.default_backend() == "tpu"


def on_kernels(sharding: Optional[KernelSharding], batch: int, fits: bool):
    """(whether the call takes its kernels, the sharding a manual region needs
    or None): the kernels where the operands lie on TPUs, the shapes fit and the
    call sits on one device or, with `sharding`, on whole rows of the batch a
    device with all heads on each."""
    tpu = lies_on_tpu(sharding)
    if sharding is not None and sharding.mesh.size == 1:
        sharding = None  # one device: the kernels need no manual region
    return tpu and fits and (sharding is None or sharding.divides(batch, 1)), sharding


def rows_a_device(rule, sharding: Optional[KernelSharding], operands, weights, out_ranks):
    """`rule(*operands)` a device on its own rows of the batch, under a manual
    region (GSPMD cannot partition a Mosaic kernel; see `KernelSharding` and
    `ops/attention._sharded_kernel`, whose pattern this is); as it is
    where `on_kernels` found one device (`sharding` None). `weights`: which
    operands lie whole on every device; `out_ranks`: the results' ranks, each
    over the batch."""
    if sharding is None:
        return rule(*operands)
    rows = sharding.batch_axes or None
    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = sharding.mesh if ctx.empty else ctx
    return jax.shard_map(
        rule, mesh=use_mesh,
        in_specs=tuple(P(*(None,) * x.ndim) if i in weights else P(rows, *(None,) * (x.ndim - 1))
                       for i, x in enumerate(operands)),
        out_specs=tuple(P(rows, *(None,) * (rank - 1)) for rank in out_ranks),
        axis_names=set(use_mesh.axis_names) - set(use_mesh.manual_axes), check_vma=False,
    )(*operands)


def traced_once(*static, sizes=tuple):
    """A caller of kernels -> one traced, and lowered, once for each value of
    its `static` arguments, its operands' shapes and `sizes()` (what else of
    the module the trace reads: tile sizes a sweep sets). A step calls it with
    the same shapes in every run of layers, in the first forward and in the
    recomputation, and tracing a kernel's unrolled body again each time is
    what a start pays for the kernels: 0.6 s a call on the chip's host, 28 s a
    start of the Kimi cell (PERF.md section 6, PR 44)."""
    def wrap(fn):
        def once(sizes_, *args):
            return fn(*args)

        once.__name__ = fn.__name__
        jitted = jax.jit(once, static_argnums=(0,) + tuple(i + 1 for i in static))
        return functools.wraps(fn)(lambda *args: jitted(sizes(), *args))
    return wrap


def dot(a, b, dims):
    """A product accumulated in float32, over a leading batch axis where the
    operands have one; float32 operands are multiplied as float32 (Mosaic's
    `contract_precision<fp32>`). `dims`: the contracted axes of a matrix, `NN`,
    `NT` or `TN`."""
    exact = a.dtype == jnp.float32 or b.dtype == jnp.float32
    batched = a.ndim == 3
    contract = tuple((axis + batched,) for (axis,) in dims)
    return jax.lax.dot_general(a, b, (contract, (((0,), (0,)) if batched else ((), ()))),
                               preferred_element_type=jnp.float32,
                               precision=jax.lax.Precision.HIGHEST if exact else None)
