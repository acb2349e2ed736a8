"""Runtime observability: structured telemetry, MFU accounting, attribution.

The paper's premise is a closed profile -> search -> train loop; this package
is the measurement substrate that closes it at runtime:

- ``obs.telemetry``   — a schema-versioned JSONL event stream (per-step and
  lifecycle events), buffered off the critical path like runtime/prefetch.py.
- ``obs.flops``       — analytic model-FLOPs accounting + a per-device-kind
  peak-FLOPs registry, so every timing surface (profiler summary, telemetry,
  ``cli report``) can report MFU and model-FLOPs/s.
- ``obs.attribution`` — the predicted-vs-measured divergence table: the
  search engine's TimeCostModel/MemoryCostModel prediction per LayerRun next
  to measured steady-state step time and compiled-step memory.
- ``obs.launch``      — where a start went: the program's import by package,
  the phases of ``cli/train._train`` up to the first drained step, and jax's
  trace / lower / compilation-cache counters on the way (the ``launch`` event,
  the summary's ``launch_ms`` / ``launch_imports`` / ``launch_jit``; beside them
  ``checkpoint_import``, how the run came by ``runtime/checkpoint``: cli/train.py).
- ``obs.compiled``    — what the compiler made of the step, read off its text
  once: every collective by kind, mesh axes, role, wire bytes, scope and the
  instruction that runs it, the fused and the hidden ones among them (the
  ``compile`` event's ``collectives`` and ``dp_grad_*_mb``, the summary's
  ``step_collectives``; ``cli report`` prints the table).
- ``obs.report``      — offline analysis of a telemetry JSONL
  (``python -m galvatron_tpu.cli report``): steady-state detection, MFU,
  lifecycle timeline, divergence table.

Import-light on purpose: ``telemetry``/``flops``/``report``/``launch`` are stdlib-only
at module scope (jax is touched only inside functions that receive jax
objects), so the offline report path never initialises an accelerator
backend.
"""
