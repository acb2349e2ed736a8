"""Device mesh construction and per-layer axis assignment.

TPU-native replacement for the reference's NCCL communication-group builder
(reference: galvatron/core/runtime/comm_groups.py:416-569). Where the reference
materialises one `torch.distributed` group per (layer, role) — TP consecutive
(comm_groups.py:71), CP strided (:94), DP strided (:121), SP (:146), PP (:180),
embedding (:199), plus explicit redistribution groups (:315) — we build ONE
`jax.sharding.Mesh` whose per-stage device block is factored into binary
sub-axes ``m0 .. m{k-1}`` (major -> minor), and express every layer's strategy
as an *assignment of sub-axes to roles*:

    minor sub-axes -> tp (or ulysses-sp), next -> cp, major remainder -> dp

matching the reference's rank order DP(outer) -> CP -> TP(inner, consecutive)
(comm_groups.py:94-145). ``tp_consec=0`` flips the assignment so tp occupies
the *major* sub-axes — the TPU analogue of non-consecutive (cross-node) TP
groups: on a real slice the minor mesh dims ride contiguous ICI rings while
major dims may span DCN.

All collectives (grad all-reduce over dp, TP all-reduce/all-gather, Ulysses
all-to-all, ring ppermute, inter-layer redistribution) are then *derived by
XLA* from `PartitionSpec`s over these axes — there is no group bookkeeping to
keep in sync.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy

PP_AXIS = "pp"


def subaxis_sizes(per_stage: int) -> Tuple[int, ...]:
    """Factor the per-pipeline-stage device count into binary sub-axes
    (major -> minor), with any odd remainder as a single leading axis.

    Powers of two cover every degree in the reference search space
    (search_engine.py:783-914 enumerates pow2 tp/cp/pp)."""
    sizes = []
    n = per_stage
    while n % 2 == 0 and n > 1:
        sizes.append(2)
        n //= 2
    if n > 1:
        sizes.insert(0, n)
    return tuple(sizes)


def subaxis_names(per_stage: int) -> Tuple[str, ...]:
    return tuple("m%d" % i for i in range(len(subaxis_sizes(per_stage))))


def build_mesh(
    config: HybridParallelConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Mesh with axes ``("pp", "m0", ..., "m{k-1}")``.

    `mesh_utils.create_device_mesh` maps minor axes to contiguous ICI. Only
    single-host CPU (test) devices, which have no topology to respect, fall
    back to a plain reshape when it cannot place a shape; on TPU, and across
    hosts, its refusal is raised."""
    if devices is None:
        devices = jax.devices()
    if len(devices) < config.world_size:
        raise ValueError(
            "need %d devices for this config, have %d" % (config.world_size, len(devices))
        )
    devices = list(devices)[: config.world_size]
    shape = (config.pp,) + subaxis_sizes(config.per_stage_devices)
    names = (PP_AXIS,) + subaxis_names(config.per_stage_devices)
    # multi-host: hybrid ICI/DCN placement (pp + major-dp span hosts, tp/cp
    # stay on intra-host ICI — runtime/distributed.py)
    from galvatron_tpu.runtime.distributed import dcn_granule_count, device_mesh_for

    try:
        dev_array = device_mesh_for(shape, devices)
    except Exception:
        if devices[0].platform != "cpu" or dcn_granule_count(devices) > 1:
            # never silently downgrade to a locality-blind reshape: tp/cp
            # would leave their ICI rings (or span DCN) and cripple every
            # collective
            raise
        dev_array = np.array(devices).reshape(shape)
    return Mesh(dev_array, names)


@dataclass(frozen=True)
class LayerAxes:
    """The mesh-axis assignment realising one layer's strategy.

    ``dp``/``cp``/``tp`` are tuples of mesh-axis names (major -> minor).
    When ``ulysses`` is set the ``tp`` axes carry Ulysses sequence parallelism
    (attention-head scatter / sequence gather all-to-all) instead of Megatron
    tensor parallelism. ``megatron_sp`` marks Megatron-SP activation sharding
    (activations sharded over the tp axes outside attention/mlp)."""

    dp: Tuple[str, ...]
    cp: Tuple[str, ...]
    tp: Tuple[str, ...]
    ulysses: bool = False
    megatron_sp: bool = False
    zero3: bool = False
    zero_opt: bool = False  # optimizer state sharded over dp (zero1/2/3)

    @property
    def seq_axes(self) -> Tuple[str, ...]:
        """Axes sharding the sequence dim of activations *between* layers:
        cp always; plus tp when this layer does ulysses or megatron-sp."""
        ax = tuple(self.cp)
        if self.ulysses or self.megatron_sp:
            ax += tuple(self.tp)
        return ax

    @property
    def batch_axes(self) -> Tuple[str, ...]:
        return self.dp


def _assign(
    names: Tuple[str, ...],
    sizes: Tuple[int, ...],
    tp: int,
    cp: int,
    tp_consec: bool,
) -> Tuple[Tuple[str, ...], Tuple[str, ...], Tuple[str, ...]]:
    """Split sub-axes into (dp, cp, tp) groups by degree products."""

    def take_minor(names_left, sizes_left, degree, what):
        taken = []
        prod = 1
        while prod < degree:
            if not names_left:
                raise ValueError("cannot realise %s degree %d from sub-axes %s" % (what, degree, sizes))
            taken.insert(0, names_left[-1])
            prod *= sizes_left[-1]
            names_left, sizes_left = names_left[:-1], sizes_left[:-1]
        if prod != degree:
            raise ValueError("%s degree %d not a product of minor sub-axes %s" % (what, degree, sizes))
        return names_left, sizes_left, tuple(taken)

    if not tp_consec and tp > 1:
        # tp on the MAJOR axes: reverse, assign, un-reverse.
        rn, rs = tuple(reversed(names)), tuple(reversed(sizes))
        rn_left, rs_left, tp_ax = take_minor(rn, rs, tp, "tp")
        rn_left, rs_left, cp_ax = take_minor(rn_left, rs_left, cp, "cp")
        dp_ax = tuple(reversed(rn_left))
        return dp_ax, tuple(reversed(cp_ax)), tuple(reversed(tp_ax))
    names_left, sizes_left, tp_ax = take_minor(names, sizes, tp, "tp")
    names_left, sizes_left, cp_ax = take_minor(names_left, sizes_left, cp, "cp")
    return tuple(names_left), cp_ax, tp_ax


def layer_axes(config: HybridParallelConfig, layer_idx: int) -> LayerAxes:
    s = config.layers[layer_idx]
    return _axes_from_strategy(config, s.tp, s.cp, bool(s.sp), bool(s.tp_consec), bool(s.fsdp))


def vocab_axes(config: HybridParallelConfig) -> LayerAxes:
    """Axes for embedding / lm-head / loss layers (vocab_tp/vocab_sp/vocab_cp,
    reference hybrid_parallel_config.py:90,105 and dp_core.cpp:78-117)."""
    return _axes_from_strategy(
        config,
        config.vocab_tp,
        config.vocab_cp,
        bool(config.vocab_sp),
        True,
        bool(config.embed_sdp),
    )


def pipeline_vocab_axes(config: HybridParallelConfig) -> LayerAxes:
    """The axes the vocabulary layers are stored and computed under where
    stages share them: `vocab_axes` with the pp axis leading its tp axes, so
    the table's rows and the head's columns are split over ``('pp',) + tp``
    and each chip owns its rows (Megatron's vocabulary parallelism over
    pipeline stages, arXiv:2411.05288). The pp axis is idle for these layers
    otherwise: every stage would hold and compute a whole tp-share. Unchanged
    at pp = 1, and under vocab-SP, where the tp axes carry the sequence and
    the vocabulary is dense. Activations BETWEEN layers keep `vocab_axes`'
    layout (`seq_axes` of this one would put pp on a Megatron-SP sequence)."""
    vax = vocab_axes(config)
    if config.pp > 1 and not vax.ulysses:
        vax = replace(vax, tp=(PP_AXIS,) + tuple(vax.tp))
    return vax


def _axes_from_strategy(
    config: HybridParallelConfig,
    tp: int,
    cp: int,
    ulysses: bool,
    tp_consec: bool,
    fsdp: bool,
) -> LayerAxes:
    names = subaxis_names(config.per_stage_devices)
    sizes = subaxis_sizes(config.per_stage_devices)
    dp_ax, cp_ax, tp_ax = _assign(names, sizes, tp, cp, tp_consec)
    dp_type = "zero3" if fsdp else config.default_dp_type
    return LayerAxes(
        dp=dp_ax,
        cp=cp_ax,
        tp=tp_ax,
        ulysses=ulysses and tp > 1,
        megatron_sp=config.sequence_parallel and tp > 1 and not ulysses,
        zero3=dp_type == "zero3",
        zero_opt=dp_type in ("zero2", "zero3"),
    )


def mesh_axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
