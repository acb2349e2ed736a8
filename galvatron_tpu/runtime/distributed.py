"""Multi-host bootstrap and hybrid ICI/DCN mesh shapes.

TPU-native replacement for the reference's process bootstrap — one process
per GPU via ``torch.distributed.launch`` with MASTER_ADDR/PORT env:// init
(reference scripts/train_dist.sh:9-15, core/arguments.py:8-30) and MPI for
multi-node nccl-tests (hardware_profiler.py:361-369). On TPU pods the unit
is one process per HOST, each owning its local chips:

- `initialize_distributed` wires `jax.distributed.initialize` from flags or
  the standard env vars. On TPU pod slices JAX discovers the topology from
  the runtime with zero configuration, so every knob is optional; on
  CPU/GPU clusters pass coordinator/num_processes/process_id explicitly.
- `hybrid_mesh_shapes` splits a logical mesh shape into (ici, dcn) factors
  for `mesh_utils.create_hybrid_device_mesh`: cross-host (DCN) factors are
  taken from the MAJOR axes first — pp and major-dp ride DCN while tp/cp
  stay on the minor axes' contiguous ICI, the same major->minor convention
  as parallel/mesh.py's tp_consec assignment.

Launch procedure (documented for operators):

    # TPU pod slice (one process per host, auto-discovery):
    $ python -m galvatron_tpu train --model_type llama ...   # on every host

    # CPU/GPU cluster (explicit bootstrap, the env:// analogue):
    $ GALVATRON_COORDINATOR=host0:8476 GALVATRON_NUM_PROCESSES=4 \
      GALVATRON_PROCESS_ID=$RANK python -m galvatron_tpu train ...
"""

from __future__ import annotations

import os
from math import gcd
from typing import Optional, Sequence, Tuple

import numpy as np

import jax


def initialize_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Bootstrap `jax.distributed` for multi-host runs. Returns True when a
    multi-process runtime is (now) active.

    Resolution order per knob: explicit argument > GALVATRON_* env var >
    JAX auto-discovery (TPU pod runtime / cluster plugins). Single-process
    runs (no coordinator resolvable, or num_processes == 1) are a no-op.
    Safe to call twice — a live distributed runtime short-circuits. The
    short-circuit must NOT touch jax.process_count()/jax.devices(): those
    initialize the local backend, after which jax.distributed.initialize
    raises — the bootstrap must run before any backend exists."""
    if jax.distributed.is_initialized():
        return jax.process_count() > 1
    coordinator_address = coordinator_address or os.environ.get("GALVATRON_COORDINATOR")
    env_np = os.environ.get("GALVATRON_NUM_PROCESSES")
    env_pid = os.environ.get("GALVATRON_PROCESS_ID")
    num_processes = num_processes if num_processes is not None else (
        int(env_np) if env_np else None
    )
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    if num_processes is not None and num_processes <= 1:
        return False
    if coordinator_address is None and num_processes is None:
        # no explicit bootstrap requested; TPU pod runtimes self-initialize
        # via jax.distributed only when the operator opts in
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return jax.process_count() > 1


def hybrid_mesh_shapes(
    shape: Sequence[int], num_hosts: int
) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """Split a logical mesh shape into (ici_shape, dcn_shape) with
    prod(dcn) == num_hosts, taking DCN factors from the MAJOR (leading)
    axes first so pp / major-dp span hosts while minor axes (tp/cp) stay on
    intra-host ICI. Raises when the host count does not factor into the
    leading axes (e.g. 3 hosts over a pow2 mesh)."""
    rem = num_hosts
    dcn = []
    for s in shape:
        g = gcd(s, rem)
        dcn.append(g)
        rem //= g
    if rem != 1:
        raise ValueError(
            "cannot factor %d hosts into mesh shape %s (leading-axis split)"
            % (num_hosts, tuple(shape))
        )
    ici = tuple(s // d for s, d in zip(shape, dcn))
    # DCN factors must form a contiguous LEADING block: every axis before the
    # last DCN-carrying axis must be fully DCN. Otherwise a minor (tp/cp)
    # axis silently absorbs host factors — e.g. shape (3, 4) on 2 hosts would
    # put tp across DCN — the exact silent-cripple build_mesh refuses.
    last_dcn = max((i for i, d in enumerate(dcn) if d > 1), default=-1)
    if any(ici[i] > 1 for i in range(last_dcn)):
        raise ValueError(
            "host count %d does not factor into the LEADING axes of mesh "
            "shape %s (dcn=%s would put a minor axis across DCN)"
            % (num_hosts, tuple(shape), tuple(dcn))
        )
    return ici, tuple(dcn)


def dcn_granule_count(devices: Sequence[jax.Device]) -> int:
    """Number of DCN-separated device groups (slices on TPU, processes
    elsewhere); 1 means every device pair rides ICI."""
    if hasattr(devices[0], "slice_index"):
        return len({d.slice_index for d in devices})
    return len({getattr(d, "process_index", 0) for d in devices})


def device_mesh_for(
    shape: Sequence[int], devices: Sequence[jax.Device]
) -> np.ndarray:
    """Device array for a logical mesh shape: hybrid ICI/DCN placement when
    the devices span multiple DCN granules, plain ICI-aware placement
    otherwise (reference analogue: hostfile + MPI rank layout).

    The DCN granule is a TPU *slice* when the runtime reports `slice_index`
    (a multi-host pod slice is fully ICI-connected — only multislice crosses
    DCN); otherwise a *process* (CPU/GPU clusters, mocked tests)."""
    from jax.experimental import mesh_utils

    process_is_granule = not hasattr(devices[0], "slice_index")
    n_granules = dcn_granule_count(devices)
    if n_granules > 1:
        ici, dcn = hybrid_mesh_shapes(shape, n_granules)
        return mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=list(devices), process_is_granule=process_is_granule
        )
    return mesh_utils.create_device_mesh(tuple(shape), devices=list(devices))
