"""Shared subprocess harness for the standalone benchmark orchestrators
(bench.py, scripts/sweep_flash_bwd.py).

A chip belongs to one process at a time, and a compile can hang, so both
orchestrators stay off jax themselves and run every measurement in a fresh
child process, agreeing on these rules:

  - children run in their OWN process group and are SIGKILLed as a unit on
    timeout, so a wedged child cannot squat the chip;
  - a child that printed its result JSON but died in teardown still counts
    as success;
  - off-TPU smoke runs execute pallas kernels in interpret mode (the path is
    exercised; the timings mean nothing).

stdlib-only on the orchestrator side: importing this module must never touch
jax (a parent that has touched jax holds the chip its children need)."""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
from typing import Optional, Tuple


def extract_json(stdout: Optional[str]) -> Optional[dict]:
    """Last parseable {...} line of a child's stdout, else None."""
    for line in reversed((stdout or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                return None
    return None


def run_isolated(argv, env, timeout_s: float,
                 on_spawn=None) -> Tuple[Optional[dict], Optional[int], str]:
    """Run `argv` in its own process group with a hard timeout.

    Returns (payload, returncode, stderr_tail): payload is the child's last
    JSON stdout line (accepted EVEN IF the child exited non-zero — a crash
    in teardown must not discard a finished measurement); returncode
    is None on timeout (the whole process group is SIGKILLed). `on_spawn`
    receives the live Popen so a caller's watchdog can kill_group() it from
    a signal handler."""
    p = subprocess.Popen(
        argv, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, start_new_session=True,
    )
    if on_spawn is not None:
        on_spawn(p)
    try:
        out, err = p.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        kill_group(p)
        try:
            out, err = p.communicate(timeout=10.0)
        except subprocess.TimeoutExpired:
            out, err = "", ""
        return extract_json(out), None, (err or "").strip()[-200:]
    return extract_json(out), p.returncode, (err or "").strip()[-200:]


def kill_group(p: subprocess.Popen) -> None:
    """SIGKILL a child and its whole process group."""
    if p.poll() is None:
        try:
            os.killpg(p.pid, signal.SIGKILL)
        except (OSError, ProcessLookupError):
            p.kill()


def _ancestor_pids() -> set:
    """This process's full ancestor pid chain via /proc (linux). The bench
    is routinely launched through wrapper shells/timeout whose own command
    lines contain the word "bench" — excluding only pid/ppid still flags
    the grandparent shell as a concurrent bench. Falls back to {self,
    parent} where /proc is unavailable."""
    pids = {str(os.getpid()), str(os.getppid())}
    pid = os.getpid()
    for _ in range(64):
        try:
            with open("/proc/%d/stat" % pid) as f:
                # field 4 (after the parenthesised, space-tolerant comm)
                pid = int(f.read().rsplit(")", 1)[-1].split()[1])
        except (OSError, ValueError, IndexError):
            break
        pids.add(str(pid))
        if pid <= 1:
            break
    return pids


def concurrent_bench_processes():
    """`pgrep -af bench` minus this process's ancestor chain: the timing
    discipline run before any section is measured. Another bench round (or
    a stray wedged measurement child) sharing the host corrupts every
    number, so the orchestrator records what it saw and the payload carries
    the hazard instead of shipping silently-noisy timings. Best-effort: no
    pgrep (or a hung one) yields an empty list, never an exception."""
    try:
        p = subprocess.run(["pgrep", "-af", "bench"], capture_output=True,
                           text=True, timeout=10)
    except (OSError, subprocess.SubprocessError):
        return []
    own = _ancestor_pids()
    hits = []
    for line in (p.stdout or "").strip().splitlines():
        parts = line.strip().split(None, 1)
        if not parts or parts[0] in own:
            continue
        hits.append(line.strip()[:200])
    return hits


def interpret_ctx_factory():
    """Context-manager factory for pallas kernels: native on TPU, interpret
    mode elsewhere (CPU smoke runs — timings meaningless, path exercised).
    Call once per timed region; generator-based contexts are single-use."""
    import contextlib

    import jax

    if jax.default_backend() == "tpu":
        return contextlib.nullcontext
    import jax.experimental.pallas.tpu as pltpu

    return pltpu.force_tpu_interpret_mode


def child_pythonpath(env: dict, repo_root: str) -> str:
    """PYTHONPATH for measurement children: the repo (so galvatron_tpu
    imports) ahead of whatever the caller already had."""
    return ":".join(p for p in (repo_root, env.get("PYTHONPATH", "")) if p)


if sys.version_info < (3, 9):  # pragma: no cover
    raise RuntimeError("python >= 3.9 required")


# ===================================================================== gate
# MFU-regression gate (ROADMAP item 1): compare a bench payload against the
# most recent non-empty baseline round so the perf trajectory cannot silently
# decay (two rounds once shipped zero numbers and nobody noticed until
# re-anchor). stdlib-only: runs in the orchestrator.

def _get_path(d, dotted):
    for part in dotted.split("."):
        if not isinstance(d, dict) or part not in d:
            return None
        d = d[part]
    return d


# (dotted path under the payload, higher_is_better). The headline value is
# keyed by its metric name so a SMOKE payload never compares against a
# full-shape baseline.
GATE_METRICS = (
    ("extra.train_step.mfu", True),
    ("extra.train_step.tokens_per_sec_per_chip", True),
    ("extra.train_loop.dispatch_ahead.steps_per_s", True),
    # TP execution paths (ISSUE 8): the regression gate covers both the
    # GSPMD baseline and the decomposed overlapped path
    ("extra.tp_overlap.gspmd.step_ms", False),
    ("extra.tp_overlap.overlap.step_ms", False),
    # Quantized collectives (ISSUE 9): the gate pins both the fp32 baseline
    # and the int8 grad-sync step so neither path silently decays — and the
    # loss delta so quantization error cannot silently grow either
    ("extra.quant_comm.fp32.step_ms", False),
    ("extra.quant_comm.int8.step_ms", False),
    ("extra.quant_comm.loss_delta_int8", False),
    # Serving (ISSUE 11): the gate pins warm-path throughput for both the
    # gspmd baseline and the searched layout, plus the searched layout's
    # decode step and TTFT tail, so the inference engine cannot silently
    # decay between rounds
    ("extra.serve.gspmd.tokens_per_s_per_chip", True),
    ("extra.serve.searched.tokens_per_s_per_chip", True),
    ("extra.serve.searched.decode_step_ms", False),
    ("extra.serve.searched.ttft_ms_p99", False),
    # Silent-corruption sentinel (ISSUE 13): the gate pins all three
    # sentinel modes' step time — digest must stay within its <= 2%
    # budget and the vote's shard_map digest cannot silently bloat
    ("extra.sdc_overhead.off.step_ms", False),
    ("extra.sdc_overhead.digest.step_ms", False),
    ("extra.sdc_overhead.vote.step_ms", False),
    # Per-layer remat search (ISSUE 15): the gate pins all three remat
    # plans' step time — the searched-mixed plan must keep beating the
    # all-full plan it exists to improve on — and the searched plan's
    # compiled memory footprint so the mix cannot silently drift toward
    # holding everything resident
    ("extra.remat.none.step_ms", False),
    ("extra.remat.full.step_ms", False),
    ("extra.remat.searched.step_ms", False),
    ("extra.remat.searched.peak_mb", False),
    # Online autotuner (ISSUE 14): the gate pins throughput on both sides
    # of the mid-run hot-swap — the mis-specified start (detector + planner
    # riding along) and the converged post-swap strategy — so neither the
    # tuner's overhead nor the swapped-to layout can silently decay
    ("extra.autotune.misspecified.steps_per_s", True),
    ("extra.autotune.converged.steps_per_s", True),
)


def perf_metrics(payload):
    """name -> (value, higher_is_better) for every comparable number the
    payload carries. Absent/None entries are simply not in the dict, so
    absent-numbers rounds contribute nothing."""
    out = {}
    if isinstance(payload.get("value"), (int, float)) and payload.get("metric"):
        out["value[%s]" % payload["metric"]] = (float(payload["value"]), False)
    for path, higher in GATE_METRICS:
        v = _get_path(payload, path)
        if isinstance(v, (int, float)):
            out[path] = (float(v), higher)
    return out


def perf_regressions(current_payload, baseline_payload, tolerance=0.1):
    """Regression report lines, empty when every shared metric is within
    `tolerance` of the baseline (relative decay for higher-is-better
    metrics, relative growth for lower-is-better)."""
    cur = perf_metrics(current_payload or {})
    base = perf_metrics(baseline_payload or {})
    out = []
    for name in sorted(set(cur) & set(base)):
        c, higher = cur[name]
        b, _ = base[name]
        if b <= 0:
            continue
        if higher and c < b * (1.0 - tolerance):
            out.append("%s: %.6g -> %.6g (-%.1f%%, tolerance %.0f%%)"
                       % (name, b, c, (1.0 - c / b) * 100.0, tolerance * 100.0))
        elif not higher and c > b * (1.0 + tolerance):
            out.append("%s: %.6g -> %.6g (+%.1f%%, tolerance %.0f%%)"
                       % (name, b, c, (c / b - 1.0) * 100.0, tolerance * 100.0))
    return out


def load_latest_baseline(glob_pattern):
    """(path, payload) of the newest baseline round that actually carries
    numbers, else None. Accepts both the raw bench JSON-line shape and the
    perf driver's wrapper ({"n": round, "parsed": {...}}); rounds whose
    parsed payload is null or number-free (rounds that measured nothing) are
    tolerated and skipped."""
    import glob as _glob

    candidates = []
    for path in _glob.glob(glob_pattern):
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        payload = doc.get("parsed", doc) if isinstance(doc, dict) else None
        if not isinstance(payload, dict) or not perf_metrics(payload):
            continue
        order = doc.get("n") if isinstance(doc.get("n"), (int, float)) else None
        candidates.append(((order is None, order if order is not None else path), path, payload))
    if not candidates:
        return None
    candidates.sort(key=lambda t: t[0])
    _, path, payload = candidates[-1]
    return path, payload
