"""galvatron_tpu — a TPU-native automatic hybrid-parallel training framework.

A from-scratch JAX/XLA/Pallas re-design of the capabilities of Hetu-Galvatron
(reference: /root/reference):

1. ``galvatron_tpu.profiler``  — hardware (ICI/DCN collective) + model (per-layer
   time/memory by layer differencing) profilers writing JSON configs.
2. ``galvatron_tpu.search``    — cost-model-driven dynamic-programming search over
   per-layer hybrid strategies (PP x TP x DP/ZeRO x SP x CP x ckpt) under an HBM
   budget (C++ DP core, reference: csrc/dp_core.cpp).
3. ``galvatron_tpu.runtime`` / ``galvatron_tpu.parallel`` — executes the searched
   layer-wise strategy on a named ``jax.sharding.Mesh``: per-layer PartitionSpecs,
   XLA collectives instead of NCCL groups, scan/ppermute pipeline schedules,
   Ulysses all-to-all and zigzag ring attention for long context.

The reference loop `profile -> search -> train` is preserved:
``profile_hardware`` + ``profile_model`` -> ``search`` (emits strategy JSON) ->
``train --galvatron_config_path <json>``.
"""

__version__ = "0.1.0"

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy

__all__ = ["HybridParallelConfig", "LayerStrategy", "__version__"]
