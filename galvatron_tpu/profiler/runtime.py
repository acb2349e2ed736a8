"""Runtime (in-training) profiler: iteration timing, throughput, memory.

TPU-native counterpart of the reference RuntimeProfiler
(galvatron/core/profiler/runtime_profiler.py:10-339): CUDA-event timing with a
warmup window (:189-300) becomes `block_until_ready` walltime around the
jitted train step (one step = one XLA program, so walltime IS device time
after the first dispatch); stage-tagged peak-memory snapshots via
`torch.cuda.max_memory_allocated` (:99-126) become `device.memory_stats()`
(live TPU HBM: bytes_in_use / peak_bytes_in_use) plus the compiler-reported
working set of the compiled step, which is the number the search engine's
memory constraint is checked against.

Results persist into the same JSON files the search engine reads
(reference profiler/utils.py save_profiled_time:57 / save_profiled_memory:22).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

import jax

from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import telemetry
from galvatron_tpu.utils.jsonio import read_json_config, write_json_config


def device_memory_stats(device=None) -> Dict[str, float]:
    """Current/peak HBM bytes for one device; zeros when the backend does not
    report (CPU test meshes)."""
    device = device or jax.local_devices()[0]
    try:
        stats = device.memory_stats() or {}
    except Exception:
        stats = {}
    return {
        "bytes_in_use": float(stats.get("bytes_in_use", 0.0)),
        "peak_bytes_in_use": float(stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0.0))),
        "bytes_limit": float(stats.get("bytes_limit", 0.0)),
    }


def compiled_step_memory_mb(compiled) -> float:
    """HBM working set of a compiled train step (args + temps + outputs),
    the quantity MemoryCostModel predicts."""
    stats = compiled.memory_analysis()
    if stats is None:
        return 0.0
    total = (
        stats.temp_size_in_bytes
        + stats.argument_size_in_bytes
        + stats.output_size_in_bytes
        - getattr(stats, "alias_size_in_bytes", 0)
    )
    return float(total) / 2**20


@dataclass
class RuntimeProfiler:
    """Wrap a train loop: `start(it)` / `end(it, n_samples)` around each step.

    Iterations inside the warmup window are timed but excluded from the
    summary (reference profile_time_start/end warmup handling,
    runtime_profiler.py:189-300)."""

    warmup: int = 2
    rank: int = 0
    save_path: Optional[str] = None
    model_name: str = "model"
    log_dir: Optional[str] = None  # tee iteration stats to
    # <log_dir>/train_<model_name>.log (the search engine's per-task log
    # discipline applied to training; reference logs rank-0 prints only)
    _t0: float = 0.0
    # per-iteration start stamps keyed by iteration: the dispatch-ahead loop
    # keeps a window of steps in flight, so start(N+2) can precede end(N)
    _t0s: Dict[int, float] = field(default_factory=dict)
    _wall_t0: Optional[float] = None  # start of the fenced post-warmup window:
    # the moment the last warmup step DRAINED (first post-warmup start when
    # there is no warmup). The first post-warmup dispatch would be too early
    # under dispatch-ahead: warmup steps still in flight then would be
    # executed inside the window without being counted in it
    _started: int = 0  # post-warmup dispatches (rollback replays count)
    iter_times_ms: List[float] = field(default_factory=list)
    all_times_ms: List[float] = field(default_factory=list)
    samples: List[int] = field(default_factory=list)
    dispatch_ms: List[float] = field(default_factory=list)  # start -> step
    # call returned (host enqueue cost; the device may still be running)
    host_blocked_ms: List[float] = field(default_factory=list)  # time the
    # host spent blocked on the device inside end()'s block_until_ready —
    # the number the dispatch-ahead loop exists to drive to ~zero
    loop_wall_ms: Optional[float] = None  # fence-to-fence post-warmup wall
    memory_snapshots: Dict[str, Dict[str, float]] = field(default_factory=dict)
    resilience_counters: Optional[Dict[str, int]] = None  # set by the train
    # driver (runtime/resilience.py ResilienceCounters.as_dict()): anomalies
    # skipped, rollbacks, I/O retries, emergency saves, torn checkpoints
    trace_ms: Optional[float] = None  # step-fn trace (lower) walltime
    compile_ms: Optional[float] = None  # XLA compile walltime of the step
    compile_cache_hit: Optional[bool] = None  # step answered from the
    # persistent compilation cache (utils/compile_cache.py)
    # where the start went (obs/launch.Launch.fields(), set by the driver once
    # the first step has drained): `launch_ms` by phase, `launch_imports` by
    # package, `launch_jit`'s counters; the summary carries the three keys
    launch: Optional[Dict[str, object]] = None
    # MFU accounting (obs/flops.py): the driver sets the per-step model
    # FLOPs and the chip's peak so the summary can report MFU and
    # model-FLOPs/s next to every timing number
    model_flops: Optional[float] = None  # model FLOPs per optimizer step
    peak_flops: Optional[float] = None  # device peak FLOP/s (registry)
    compiled_memory_mb: Optional[float] = None  # compiled-step working set
    # decomposed-TP overlap accounting (parallel/tp_shard_map): per-LayerRun
    # measured comm hidden behind the chunked matmul schedule; the summary
    # reports the per-step total next to host_blocked_ms — one is the comm
    # the overlap path hid on-device, the other the host-side stall the
    # dispatch-ahead loop hides
    comm_hidden_ms: Dict[int, float] = field(default_factory=dict)
    _iter: int = 0
    _log_fh = None  # one appending handle for the whole run (close() closes)

    # ------------------------------------------------------------------ timing
    def start(self, iteration: int):
        self._iter = iteration
        self._t0 = time.perf_counter()
        self._t0s[iteration] = self._t0
        if iteration >= self.warmup:
            if self._wall_t0 is None:
                self._wall_t0 = self._t0
            self._started += 1

    def dispatched(self, iteration: int):
        """Call right after the (async) step call returns: records the host
        dispatch cost of this iteration — how long the host held the critical
        path before handing the program to the device."""
        t0 = self._t0s.get(iteration, self._t0)
        dt = (time.perf_counter() - t0) * 1e3
        if iteration >= self.warmup:
            self.dispatch_ms.append(dt)
        return dt

    def end(self, iteration: int, n_samples: int = 0, outputs=None):
        """Call with the step outputs so the timer blocks until the device
        finishes (outputs=None times dispatch only). Under the dispatch-ahead
        loop this runs at drain time, possibly several iterations after
        start(); the blocked interval inside block_until_ready is recorded
        separately as host_blocked_ms."""
        tb = time.perf_counter()
        if outputs is not None:
            jax.block_until_ready(outputs)
        now = time.perf_counter()
        dt = (now - self._t0s.pop(iteration, self._t0)) * 1e3
        self.all_times_ms.append(dt)
        if iteration >= self.warmup:
            self.iter_times_ms.append(dt)
            self.samples.append(n_samples)
            self.host_blocked_ms.append((now - tb) * 1e3)
        elif iteration == self.warmup - 1 and not self.iter_times_ms:
            self._wall_t0 = now  # (a rollback replay must not move it)
        return dt

    def loop_fence(self, outputs=None):
        """End-of-run fence: block until the device has fully drained, then
        record the post-warmup loop wall time. Without this fence the
        dispatch-ahead loop's steady-state numbers would credit work the
        device has not finished."""
        if outputs is not None:
            jax.block_until_ready(outputs)
        if self._wall_t0 is not None and self._started > 0:
            self.loop_wall_ms = (time.perf_counter() - self._wall_t0) * 1e3

    def record_comm_hidden(self, run: int, hidden_ms: float):
        """Record the measured communication time (ms per step) the
        decomposed TP path hid behind chunked compute for one LayerRun
        (tp_shard_map.measure_comm_hidden; driver --profile under
        tp_comm_mode=overlap)."""
        self.comm_hidden_ms[int(run)] = float(hidden_ms)

    def record_compile(self, trace_ms: Optional[float] = None,
                       compile_ms: Optional[float] = None,
                       cache_hit: Optional[bool] = None):
        """Record the one-off trace/compile cost of the jitted train step
        (cli/train.py AOT-lowers and compiles the step explicitly), so the
        summary separates program-build cost from steady-state step time —
        under scan-over-layer-runs the former is depth-constant and this is
        where the win shows up."""
        if trace_ms is not None:
            self.trace_ms = float(trace_ms)
        if compile_ms is not None:
            self.compile_ms = float(compile_ms)
        if cache_hit is not None:
            self.compile_cache_hit = bool(cache_hit)

    # ------------------------------------------------------------------ memory
    def profile_memory(self, iteration: int, stage: str = ""):
        """Stage-tagged snapshot (reference profile_memory/post_profile_memory,
        runtime_profiler.py:99-128)."""
        key = "iter_%d_%s" % (iteration, stage or "snap")
        self.memory_snapshots[key] = device_memory_stats()
        return self.memory_snapshots[key]

    # ----------------------------------------------------------------- summary
    def summary(self) -> Dict[str, float]:
        if not self.iter_times_ms:
            out = {"avg_iter_ms": 0.0, "samples_per_s": 0.0, "iters": 0}
        else:
            avg = float(np.mean(self.iter_times_ms))
            tput = (
                float(np.sum(self.samples)) / (float(np.sum(self.iter_times_ms)) / 1e3)
                if np.sum(self.iter_times_ms) > 0
                else 0.0
            )
            peak = max((m["peak_bytes_in_use"] for m in self.memory_snapshots.values()), default=0.0)
            out = {
                "avg_iter_ms": avg,
                "p50_iter_ms": float(np.percentile(self.iter_times_ms, 50)),
                # alias: the steady-state step time, to read alongside the
                # one-off trace_ms/compile_ms program-build costs
                "steady_step_ms": float(np.percentile(self.iter_times_ms, 50)),
                "samples_per_s": tput,
                "peak_hbm_mb": peak / 2**20,
                "iters": len(self.iter_times_ms),
            }
        if self.dispatch_ms:
            out["dispatch_ms"] = float(np.mean(self.dispatch_ms))
        if self.host_blocked_ms:
            out["host_blocked_ms"] = float(np.mean(self.host_blocked_ms))
            out["host_blocked_ms_total"] = float(np.sum(self.host_blocked_ms))
        if self.loop_wall_ms is not None and self._started > 0:
            # the honest steady-state throughput: post-warmup dispatches over
            # fenced wall time (iter_times_ms measures dispatch->drain
            # latency, which overlaps across iterations under dispatch-ahead)
            out["loop_wall_ms"] = self.loop_wall_ms
            out["wall_ms_per_iter"] = self.loop_wall_ms / self._started
            if self.loop_wall_ms > 0:
                out["steps_per_s"] = self._started / (self.loop_wall_ms / 1e3)
        if self.comm_hidden_ms:
            out["comm_hidden_ms"] = float(sum(self.comm_hidden_ms.values()))
        if self.trace_ms is not None:
            out["trace_ms"] = self.trace_ms
        if self.compile_ms is not None:
            out["compile_ms"] = self.compile_ms
        if self.compile_cache_hit is not None:
            out["compile_cache_hit"] = self.compile_cache_hit
        if self.compiled_memory_mb is not None:
            out["compiled_step_memory_mb"] = self.compiled_memory_mb
        if self.launch is not None:
            out.update(self.launch)
        if self.model_flops:
            # MFU from the honest steady-state rate: fenced wall time per
            # post-warmup dispatch when available (iter_ms latencies overlap
            # under the dispatch-ahead loop), else the mean iteration time
            out["model_flops_per_step"] = self.model_flops
            step_ms = out.get("wall_ms_per_iter") or out.get("avg_iter_ms")
            fps = obs_flops.flops_per_s(self.model_flops, step_ms)
            if fps is not None:
                out["model_flops_per_s"] = fps
            util = obs_flops.mfu(self.model_flops, step_ms, self.peak_flops)
            if util is not None:
                out["mfu"] = util
        if self.resilience_counters is not None:
            out["resilience"] = dict(self.resilience_counters)
        return out

    def log_iteration(self, iteration: int, metrics: Optional[dict] = None, print_fn=print):
        """reference _log_iteration_stats (runtime_profiler.py:303). The
        per-task log file is opened ONCE (appending) and held until
        :meth:`close` — the old open-per-iteration cost a filesystem round
        trip on the logging path every step — and the same line is mirrored
        into the telemetry stream when a sink is active."""
        if self.rank != 0 or not self.all_times_ms:
            return
        extra = ""
        if metrics:
            extra = " " + " ".join(
                "%s=%.4g" % (k, float(v)) for k, v in metrics.items() if np.isscalar(v) or getattr(v, "ndim", 1) == 0
            )
        line = "iter %4d | %8.2f ms%s" % (iteration, self.all_times_ms[-1], extra)
        print_fn(line)
        telemetry.emit("log", message=line)
        if self.log_dir:
            if self._log_fh is None:
                os.makedirs(self.log_dir, exist_ok=True)
                path = os.path.join(self.log_dir, "train_%s.log" % self.model_name)
                self._log_fh = open(path, "a")  # galv-lint: ignore[GLC006] -- the one sanctioned open, held for the run
            self._log_fh.write(line + "\n")

    def close(self):
        """Release the iteration-log handle (the train driver calls this in
        its ``finally``); safe to call repeatedly, flushes on close."""
        if self._log_fh is not None:
            try:
                self._log_fh.close()
            finally:
                self._log_fh = None

    # -------------------------------------------------------------------- save
    def save(self, path: Optional[str] = None):
        """Merge this run's summary into a profiling JSON keyed by model
        (reference profiler/utils.py:22-90 merges into shared config files)."""
        path = path or self.save_path
        if not path:
            return
        existing = read_json_config(path) if os.path.exists(path) else {}
        existing[self.model_name] = self.summary()
        write_json_config(existing, path)
