"""One run of one cell: set-up, the measured window, the checks, the result.

The harness drives `galvatron_tpu.cli.train.train(args)` in its own process,
the code `python -m galvatron_tpu.cli train` runs, with `args` from the
program's own parser. From the program it takes the system under test, its
summary, its telemetry events and its compiled step; the clock, the
estimator, the FLOPs, the peaks, the trace reduction, the plain reference and
the comparison that decides `correct` are the benchmark's own.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import threading
import time
import types
from typing import Any, Callable, Dict, List, Mapping, Optional

from benchmarks import cells, flops, stages, trace as trace_mod, window

STEP_NAMES = ("plain_step", "train_step")  # the jitted step's names in the program
TRACED_STEPS = 3
# after stop_trace the host has lost its two-step lead: three iterations
# refill it, and the rest is margin before the window's first stamp
SETTLE_AFTER_TRACE = 6
# --trace 2: the traced tail after the window fills about this long, and holds
# at least this many steps (the reduction drops the first and the last run of
# the step, tracing_on_slowdown_pct the first three intervals)
TAIL_SECONDS = 3.0
TAIL_MIN_STEPS = 6
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class DeviceStart:
    """`jax.devices()` on a thread of its own, started at once."""

    def __init__(self):
        self.seconds: Optional[float] = None
        self._devices = None
        self._error: Optional[Exception] = None
        self._thread = threading.Thread(target=self._run, name="device-start")
        self._thread.start()

    def _run(self):
        import jax

        t = time.perf_counter()
        try:
            self._devices = jax.devices()
        except Exception as e:  # handed to the thread that asks
            self._error = e
        self.seconds = time.perf_counter() - t

    def wait(self, timeout: float) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def devices(self):
        self._thread.join()
        if self._error is not None:
            raise self._error
        return self._devices


def import_program():
    """The program's modules a run needs, imported here so that the caller
    can time them (and start the chip beside them)."""
    import galvatron_tpu.cli.arguments  # noqa: F401
    import galvatron_tpu.cli.train  # noqa: F401
    import galvatron_tpu.obs.telemetry  # noqa: F401
    import galvatron_tpu.runtime.model_api  # noqa: F401


def refusal(devices, chips: int, peaks: Mapping[str, Any]) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    kinds = sorted({d.device_kind for d in devices})
    if any(d.platform != "tpu" for d in devices) or any(k not in peaks for k in kinds):
        return ("the benchmark needs TPUs whose device_kind is in benchmarks/peaks.json "
                "(%s); found platform=%s kind=%s" % (
                    ", ".join(sorted(peaks)), sorted({d.platform for d in devices}), kinds))
    if len(devices) != chips:
        return "the cell asks for %d chip(s), jax finds %d" % (chips, len(devices))
    return None


class CompileLog:
    """Every backend compilation (or persistent-cache read) jax reports, with
    the host time it ended at."""

    def __init__(self):
        self.events: List[tuple] = []

    def __call__(self, event, duration, fun_name=None, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), fun_name, duration))

    def of_step(self) -> int:
        return sum(1 for _, name, _ in self.events
                   if any(s in (name or "") for s in STEP_NAMES))

    def inside(self, start: float, end: float) -> List[str]:
        return [str(name) for t, name, _ in self.events if start < t <= end]


def out_dir_for(root: str, cell_name: str, seed: int, traced: int) -> str:
    base = os.path.join(root, "chiprun_out", "benchmarks", cell_name)
    n = 0
    while True:
        path = os.path.join(base, "s%d-t%d-%d" % (seed, int(traced), n))
        try:
            os.makedirs(path)
            return path
        except FileExistsError:
            n += 1


def reference_loss(cell: cells.Cell, args) -> float:
    """The plain reference's loss of the first batch on the seed's untrained
    weights, made and dropped before the trainer puts its state on the device."""
    import jax

    from galvatron_tpu.cli.arguments import hp_config_from_args, model_config_from_args
    from galvatron_tpu.cli.train import build_data_iterator
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    ref = cells.load_module(cell.root, "benchmarks/references/%s.py" % cell.config["reference"])
    fam, cfg = model_config_from_args(args)
    hp = hp_config_from_args(args, cfg.num_layers, args.world_size or len(jax.devices()))
    model = construct_hybrid_parallel_model(cfg, hp)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    batch = model.shard_batch(next(build_data_iterator(args, fam, cfg, hp)))
    # the architecture's switches as the program holds them (norm, activation,
    # positions, tying, eps, rope base); the tree says where biases are
    fields = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    # a pipelined model keeps its layers stacked by stage: the reference gets
    # the per-layer tree (benchmarks/stages.py)
    division = tuple(hp.pp_division)
    return float(jax.jit(lambda p, b: ref.loss(
        stages.per_layer_tree(p, division), b, fields))(params, batch))


def expected_first_loss(cell: cells.Cell) -> float:
    """ln V + hidden x init_std^2 / 2: the final norm hands the head unit-RMS
    rows and the head is N(0, init_std^2), so the untrained logits are
    N(0, hidden x init_std^2) and E[CE] = ln V + sigma^2 / 2. Plus what the
    configuration's objective adds to the cross entropy at initialisation
    (`checks.first_loss.plus`: router losses, derived in its `plus_why`)."""
    f = cell.fields
    cross_entropy = math.log(f["vocab_size"]) + f["hidden_size"] * f["init_std"] ** 2 / 2
    return cross_entropy + cell.config["checks"]["first_loss"].get("plus", 0.0)


def step_memory(compiled) -> Dict[str, float]:
    ma = compiled.memory_analysis()
    gib = 2.0 ** 30
    return {
        "args_gib": ma.argument_size_in_bytes / gib,
        "temp_gib": ma.temp_size_in_bytes / gib,
        "output_gib": ma.output_size_in_bytes / gib,
        "aliased_gib": ma.alias_size_in_bytes / gib,
        "step_hbm_gib": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                         + ma.output_size_in_bytes - ma.alias_size_in_bytes) / gib,
    }


def read_trace(trace_dir: str, hlo: str, out_dir: str) -> Dict[str, Any]:
    """The traced steps reduced (benchmarks/trace.py); their events are kept
    beside run.json and the raw trace, tens of MiB, is dropped."""
    reduced = None
    xplane = trace_mod.find_xplane(trace_dir)
    if xplane is not None:
        loaded = trace_mod.load(xplane, trace_mod.origins_from_hlo(hlo))
        reduced = trace_mod.reduce(loaded, STEP_NAMES)
        trace_mod.save_events(loaded, os.path.join(out_dir, "trace_events.json.gz"), STEP_NAMES)
    shutil.rmtree(trace_dir, ignore_errors=True)
    if reduced is None:
        raise RuntimeError("the traced run holds no whole step of the device")
    return reduced


def per_layer_values(cell: cells.Cell, run: Mapping[str, Any]) -> Dict[str, float]:
    """Each of the cell's per-layer metrics through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    values = {}
    for metric in cell.metrics("per_layer"):
        reader = cells.load_module(cell.root, "benchmarks/layer_metrics/%s.py" % metric["name"])
        value = reader.read(run)
        if value is not None:
            values[metric["name"]] = float(value)
    return values


class TracedTail:
    """`--trace 2`: what happens at the window's closing stamp, inside
    `on_step`, once the window's numbers are fixed. A telemetry sink goes
    in, the profiler is started and stopped once and that trace thrown away
    (so that the cost of its first start falls into no number), and the
    trainer's own trace control is asked for the next n steps, which the
    run is extended by."""

    def __init__(self, args, clock: window.WindowClock, trace_dir: str):
        self.args, self.clock, self.trace_dir = args, clock, trace_dir
        self.sink = None
        self.steps = 0
        self.first_start_s = 0.0

    def __call__(self, it: int) -> int:
        import jax

        from galvatron_tpu.obs import telemetry

        median = window.estimate(self.clock.window_stamps(), 1.0)["median_step_s"]
        self.steps = max(TAIL_MIN_STEPS, math.ceil(TAIL_SECONDS / median))
        self.sink = telemetry.install(telemetry.MemorySink())
        first, t = self.trace_dir + ".first", time.perf_counter()
        jax.profiler.start_trace(first)
        jax.profiler.stop_trace()
        shutil.rmtree(first, ignore_errors=True)
        self.first_start_s = time.perf_counter() - t
        if not self.args.trace_control.request(self.trace_dir, it, it + self.steps - 1):
            raise RuntimeError("the trainer's trace control is busy at the window's end")
        self.args.train_iters = it + self.steps
        return self.steps

    def close(self) -> None:
        if self.sink is not None:
            from galvatron_tpu.obs import telemetry

            telemetry.uninstall(self.sink)


def run_cell(cell: cells.Cell, *, seed: int, seconds: float, traced: int,
             peaks: Mapping[str, Any], t0: float, out_dir: str,
             say: Callable[..., None], marks: Optional[Mapping[str, float]] = None,
             chip_start_s: Optional[float] = None) -> Dict[str, Any]:
    """Runs the cell once and returns the result object of the last line.
    `say(**obj)` prints an earlier line; `t0` is the process's start on
    `time.perf_counter`, `marks` the consecutive parts of set-up the caller
    has timed, `chip_start_s` what `jax.devices()` took beside them.
    `traced` is `--trace`: 0 the measured window alone, 1 a traced run of its
    own, 2 the measured window of 0 followed by a traced tail."""
    import jax

    from galvatron_tpu.cli import train as T
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.obs import telemetry

    parts = dict(marks or {})
    devices = jax.devices()
    peak = peaks[devices[0].device_kind]
    traffic, tol = cell.traffic, cell.config["checks"]
    warmup = int(traffic["warmup_steps"])
    trace_dir = os.path.join(out_dir, "xla_trace") if traced else None
    trace_steps = None
    if traced == 1:
        trace_steps = (warmup, warmup + TRACED_STEPS - 1)
        warmup = trace_steps[1] + 1 + SETTLE_AFTER_TRACE
    cells.register_family(cell)
    args = initialize_galvatron(
        mode="train_dist",
        argv=cells.train_argv(cell, seed, trace_dir if traced == 1 else None, trace_steps))

    # the plain reference is the benchmark's own work, not the program's
    # set-up: it is timed apart and taken out of setup_s
    t = time.perf_counter()
    ref_loss = reference_loss(cell, args)
    gc.collect()
    reference_s = time.perf_counter() - t

    clock = window.WindowClock(
        seconds, warmup, end_run=lambda it: setattr(args, "train_iters", it))
    tail = None
    if traced == 2:
        tail = clock.end_run = TracedTail(args, clock, trace_dir)
    # the trainer's per-step observation seam; the step itself is untouched
    args.fault_hooks = types.SimpleNamespace(
        on_step=clock.on_step, wrap_step_fn=None, wrap_data_iter=None)
    compiles = CompileLog()
    sink = telemetry.MemorySink() if traced == 1 else None
    before = set(T._STEP_EXECUTABLES)
    jax.monitoring.register_event_duration_secs_listener(compiles)
    if sink is not None:
        telemetry.install(sink)
    t_train = time.perf_counter()
    try:
        with contextlib.redirect_stdout(sys.stderr):
            summary = T.train(args)
    finally:
        t_trained = time.perf_counter()
        if sink is not None:
            telemetry.uninstall(sink)
        if tail is not None:
            tail.close()
        jax.monitoring.unregister_event_duration_listener(compiles)
    stamps = clock.window_stamps()
    window_steps = (clock.warmup, clock.last)
    if tail is not None:
        # no sink stood in the window: the readers of telemetry get the tail's
        sink, window_steps = tail.sink, (clock.last, clock.last + tail.steps)
    parts["to_first_step_s"] = clock.stamps[1] - t_train
    parts["warmup_s"] = stamps[0] - clock.stamps[1]
    setup_s = stamps[0] - t0 - reference_s
    parts["other_s"] = setup_s - sum(parts.values())

    est = window.estimate(stamps, cell.tokens_a_step)
    tokens_per_s_chip = est["rate"] / cell.chips
    flops_a_token = cells.flops_a_token(cell)
    new = [k for k in T._STEP_EXECUTABLES if k not in before]
    compiled = T._STEP_EXECUTABLES[new[0]] if len(new) == 1 else None
    memory = step_memory(compiled) if compiled is not None else {}
    hlo = compiled.as_text() if compiled is not None else ""
    losses = summary["losses"]
    window_losses = losses[clock.warmup:clock.last]
    failed = sum(1 for x in window_losses if not math.isfinite(x))
    in_window = compiles.inside(stamps[0], stamps[-1])

    expected = expected_first_loss(cell)
    checks = {
        "losses_finite": bool(losses) and all(math.isfinite(x) for x in losses),
        "first_loss": abs(losses[0] - expected) <= tol["first_loss"]["abs"],
        "reference_loss": abs(losses[0] - ref_loss) <= tol["reference_loss"]["abs"],
        "one_step_compilation": compiles.of_step() == 1 and compiled is not None,
        "no_compilation_in_window": not in_window,
        "kernel_in_step": "tpu_custom_call" in hlo,
    }
    if cell.chips > 1:
        leaves = jax.tree.leaves(compiled.input_shardings[0][0]) if compiled is not None else []
        checks["params_span_all_chips"] = bool(leaves) and all(
            len(s.device_set) == cell.chips for s in leaves)
        checks["layout_collectives"] = all(c in hlo for c in cell.collectives)

    stats = [d.memory_stats() or {} for d in devices]
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0)) for s in stats),
    }
    run = {
        "cell": cell, "peak": peak, "summary": summary, "window": est,
        "memory": memory, "device": device, "flops_a_token": flops_a_token,
        "events": sink.events if sink is not None else [],
        "window_steps": window_steps, "trace": None,
        "setup_parts_s": parts, "chip_start_s": chip_start_s,
        # the stamp intervals of the traced tail (--trace 2), else None
        "tail_intervals_s": None,
    }
    if tail is not None:
        after = clock.tail_stamps()
        run["tail_intervals_s"] = [b - a for a, b in zip(after, after[1:])]
    values = {
        "tokens_per_s_chip": tokens_per_s_chip,
        "mfu": flops.mfu_pct(tokens_per_s_chip, flops_a_token, peak["bf16_flops_per_s"]),
        "step_hbm_gib": memory.get("step_hbm_gib"),
        "setup_s": setup_s,
    }
    breakdown = None
    if traced:
        t = time.perf_counter()
        run["trace"] = read_trace(trace_dir, hlo, out_dir)
        reduce_s = time.perf_counter() - t
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
        breakdown = {"device_ops": run["trace"]["device_ops"],
                     "idle_gaps": run["trace"]["idle_gaps"]}
        layers = per_layer_values(cell, run)
        values = {**values, **layers} if traced == 2 else layers

    units = {m["name"]: m["unit"] for g in ("end_to_end", "per_layer")
             for m in cell.manifest[g]}
    result = {
        "correct": all(checks.values()),
        "attempted": len(window_losses),
        "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]} for k, v in values.items()
                    if v is not None and k in units},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    detail = {
        "workload": cell.name, "seed": seed, "seconds": seconds, "traced": bool(traced),
        "checks": checks, "compilations_in_window": in_window,
        "first_loss": losses[0], "expected_first_loss": expected,
        "reference_loss": ref_loss, "last_loss": losses[-1],
        "setup_parts_s": parts, "setup_s": setup_s, "reference_s": reference_s,
        "chip_start_s": chip_start_s,
        "step_trace_s": summary.get("trace_ms", 0.0) / 1e3,
        "step_compile_s": summary.get("compile_ms", 0.0) / 1e3,
        "step_cache_hit": summary.get("compile_cache_hit"),
        "window": {k: v for k, v in est.items() if k != "intervals_s"},
        "trainer_wall_ms_per_iter": summary.get("wall_ms_per_iter"),
        "trainer_dispatch_ms": summary.get("dispatch_ms"),
        "memory": memory, "flops_a_token": flops_a_token,
    }
    if tail is not None:
        # what a --trace 2 run spends after its window: the profiler's first
        # start and stop, the traced steps up to the trainer's return (the
        # trace is stopped and written in there), and reducing the trace
        detail["tail"] = {
            "steps": tail.steps, "first_profiler_start_s": tail.first_start_s,
            "window_end_to_return_s": t_trained - stamps[-1], "reduce_s": reduce_s}
    say(**detail)
    with open(os.path.join(out_dir, "run.json"), "w") as f:
        json.dump({**detail, "intervals_s": est["intervals_s"],
                   "warmup_intervals_s": [b - a for a, b in zip(
                       clock.stamps[:clock.warmup], clock.stamps[1:clock.warmup + 1])],
                   "tail_intervals_s": run["tail_intervals_s"],
                   "losses": losses, "result": result,
                   "trace": run["trace"]}, f)
    return result
