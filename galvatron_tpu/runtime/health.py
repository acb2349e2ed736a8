"""Training watchdog + mesh-health probing: detect a wedged or degraded run.

PR 5 made runs *resumable* after hardware trouble (elastic degraded-mesh
resume), but the detection side was still an operator staring at a stalled
log: a hung collective, a wedged input pipeline, or a quietly shrunken
device set all present as "the process stopped printing". This module is
the runtime's own failure detector, the missing half of the self-healing
story (ROADMAP item 5; the recovery half is in-memory migration in
runtime/elastic.py):

- :class:`Watchdog` — a monitor thread armed around every dispatched step.
  The deadline is *learned* from the run itself: ``factor * median(steady
  step time) + floor`` once enough post-warmup steps have drained, a
  generous startup deadline before that (first-step compiles legitimately
  take minutes). A missed deadline escalates in two stages: **fire**
  (emit a ``watchdog`` telemetry event with a full diagnostic dump —
  in-flight window depth, last drained step, per-thread stacks via
  :mod:`faulthandler` — and request a drain-and-retry from the driver),
  then **escalate** (request an emergency save + clean exit with
  :data:`WATCHDOG_EXIT_CODE`) when a further deadline passes with no
  progress. All decision logic lives in the pure :meth:`Watchdog.check`
  so tests drive it with a fake clock; the thread is just a pump.
- :func:`classify_world` / :class:`MeshHealthMonitor` — a cheap periodic
  mesh-health probe: a device-enumeration diff against the strategy's
  provenance plus a tiny jitted collective run under a bounded timeout,
  classifying the live world as healthy / degraded / grown / wedged. The
  driver's ``--migrate_on_degrade`` turns a degraded verdict into an
  in-memory strategy migration instead of a crash-and-resume round trip.

The watchdog cannot *unwedge* a hard-stuck XLA call — nothing in-process
can — but it turns "silent hang" into a structured, machine-readable event
stream entry with thread stacks, and turns transient stalls (a long GC
pause, a flaky interconnect retry, an injected sleeping callback in the
fault sim) into a drained-and-retried step or a clean, resumable exit.
"""

from __future__ import annotations

import faulthandler
import statistics
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

from galvatron_tpu.obs import telemetry

__all__ = [
    "WATCHDOG_EXIT_CODE",
    "Watchdog",
    "WatchdogConfig",
    "classify_world",
    "probe_collective",
    "MeshHealthMonitor",
    "thread_stack_dump",
]

# The driver's exit code when the watchdog escalated and forced the
# emergency-save path: distinct from 0 (clean), 1 (ordinary failure), and 2
# (the GLS2xx elastic-refusal contract), so a supervisor can tell "the run
# wedged and self-evacuated" from "needs operator input".
WATCHDOG_EXIT_CODE = 3


def thread_stack_dump(max_chars: int = 8000) -> str:
    """Every thread's current Python stack, via faulthandler (which can dump
    even threads blocked in C calls — exactly the ones a hang diagnostic
    cares about). Truncated to keep the telemetry event bounded."""
    try:
        with tempfile.TemporaryFile(mode="w+") as fh:
            faulthandler.dump_traceback(file=fh, all_threads=True)
            fh.seek(0)
            text = fh.read()
    except Exception as e:  # faulthandler needs a real fd; degrade gracefully
        return "<stack dump unavailable: %s>" % e
    if len(text) > max_chars:
        text = text[:max_chars] + "\n<truncated>"
    return text


# ------------------------------------------------------------------ watchdog
@dataclass
class WatchdogConfig:
    """Deadline learning + escalation knobs (driver flags ``--watchdog`` /
    ``--watchdog_factor`` map onto floor_s / factor)."""

    floor_s: float = 30.0  # additive floor under the learned deadline
    factor: float = 4.0  # k in k * median(step time) + floor
    min_history: int = 3  # drained steps before the deadline arms
    startup_deadline_s: float = 600.0  # pre-history deadline (covers compile)
    escalation_grace: float = 1.0  # extra deadlines after fire before escalate
    poll_interval_s: float = 0.25  # monitor-thread cadence
    history: int = 64  # step-time samples kept for the median


class Watchdog:
    """Per-step liveness monitor with a two-stage escalation ladder.

    The driver arms the watchdog at the top of each loop body (covering
    batch fetch + dispatch + the in-flight window) and reports progress at
    every drain; `disarm()` brackets legitimately slow sections (eval,
    checkpoint saves). The monitor thread periodically calls :meth:`check`;
    tests call it directly with a fake clock.

    Escalation contract (the driver polls the request flags at the loop
    top, where params/opt_state are consistent):

    - ``fire``  -> `retry_requested`: drain the in-flight window and keep
      going (a transient stall should not kill a multi-day run).
    - ``escalate`` -> `abort_requested`: emergency-save + clean exit with
      :data:`WATCHDOG_EXIT_CODE`.
    """

    def __init__(
        self,
        cfg: Optional[WatchdogConfig] = None,
        time_fn: Callable[[], float] = time.monotonic,
        on_fire: Optional[Callable[[Dict[str, Any]], None]] = None,
        on_escalate: Optional[Callable[[Dict[str, Any]], None]] = None,
    ):
        self.cfg = cfg or WatchdogConfig()
        self._time = time_fn
        self._on_fire = on_fire
        self._on_escalate = on_escalate
        self._lock = threading.Lock()
        self._step_times_ms: deque = deque(maxlen=max(self.cfg.history, 1))
        # armed interval state
        self._armed = False
        self._armed_at: Optional[float] = None
        self._phase = ""
        self._iteration: Optional[int] = None
        self._inflight_depth = 0
        self._last_drained: Optional[int] = None
        # escalation state
        self._fired_at: Optional[float] = None
        self.fires = 0
        self.escalated = False
        self.retry_requested = False
        self.abort_requested = False
        self.events: List[Dict[str, Any]] = []  # local record (summary dict)
        # monitor thread
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- learning
    def observe_step_time(self, ms: float) -> None:
        with self._lock:
            self._step_times_ms.append(float(ms))

    def deadline_s(self) -> float:
        """The current no-progress budget: learned once `min_history` steps
        have drained, the generous startup deadline before that."""
        with self._lock:
            times = list(self._step_times_ms)
        if len(times) < max(self.cfg.min_history, 1):
            return float(self.cfg.startup_deadline_s)
        med_s = statistics.median(times) / 1e3
        return self.cfg.factor * med_s + self.cfg.floor_s

    # ------------------------------------------------------------ arm/disarm
    def arm(self, iteration: int, phase: str = "step", inflight: int = 0) -> None:
        """Start (or refresh) the armed interval: the deadline clock runs
        from now. Called at the top of each loop body and after dispatch."""
        now = self._time()
        with self._lock:
            self._armed = True
            self._armed_at = now
            self._phase = phase
            self._iteration = int(iteration)
            self._inflight_depth = int(inflight)
            self._fired_at = None  # new interval: the ladder restarts

    def progress(self, drained_iteration: Optional[int] = None,
                 inflight: Optional[int] = None) -> None:
        """Report liveness without restarting the escalation ladder's armed
        flag semantics: refreshes the deadline clock and clears a pending
        fire (the run recovered on its own)."""
        now = self._time()
        with self._lock:
            if drained_iteration is not None:
                self._last_drained = int(drained_iteration)
            if inflight is not None:
                self._inflight_depth = int(inflight)
            if self._armed:
                self._armed_at = now
                self._fired_at = None

    def disarm(self) -> None:
        """Suspend monitoring (eval passes, checkpoint saves, migration —
        long-running by design, with their own containment)."""
        with self._lock:
            self._armed = False
            self._armed_at = None
            self._fired_at = None

    # -------------------------------------------------------------- decision
    def check(self, now: Optional[float] = None) -> Optional[str]:
        """The pure escalation decision: None | "fire" | "escalate".

        fire     — armed, no progress for a full deadline, not yet fired in
                   this interval.
        escalate — fired, and a further `escalation_grace` deadlines passed
                   with still no progress.
        """
        now = self._time() if now is None else now
        deadline = self.deadline_s()
        with self._lock:
            if not self._armed or self._armed_at is None or self.escalated:
                return None
            if self._fired_at is None:
                if now - self._armed_at <= deadline:
                    return None
                self._fired_at = now
                self.fires += 1
                self.retry_requested = True
                action = "fire"
            else:
                if now - self._fired_at <= deadline * max(self.cfg.escalation_grace, 0.0):
                    return None
                self.escalated = True
                self.abort_requested = True
                action = "escalate"
            elapsed = now - self._armed_at
        self._report(action, elapsed, deadline)
        return action

    def take_retry_request(self) -> bool:
        """Consume a pending drain-and-retry request (driver loop top)."""
        with self._lock:
            req, self.retry_requested = self.retry_requested, False
            return req

    # ------------------------------------------------------------ diagnostics
    def diagnostics(self, include_stacks: bool = True) -> Dict[str, Any]:
        with self._lock:
            times = list(self._step_times_ms)
            diag: Dict[str, Any] = {
                "iter": self._iteration,
                "phase": self._phase,
                "inflight_depth": self._inflight_depth,
                "last_drained": self._last_drained,
                "fires": self.fires,
                "steps_observed": len(times),
            }
        if times:
            diag["median_step_ms"] = float(statistics.median(times))
        if include_stacks:
            diag["stacks"] = thread_stack_dump()
        return diag

    def _report(self, action: str, elapsed: float, deadline: float) -> None:
        diag = self.diagnostics()
        diag.update(action=action, elapsed_s=elapsed, deadline_s=deadline)
        self.events.append({k: v for k, v in diag.items() if k != "stacks"})
        telemetry.emit(
            "watchdog", action=action, iter=diag.get("iter"),
            phase=diag.get("phase"), elapsed_s=elapsed, deadline_s=deadline,
            inflight_depth=diag.get("inflight_depth"),
            last_drained=diag.get("last_drained"), fires=diag.get("fires"),
            stacks=diag.get("stacks"),
        )
        telemetry.runtime_log(
            "watchdog %s: no progress for %.1fs (deadline %.1fs) at iter %s "
            "phase %r, %s step(s) in flight, last drained %s"
            % (action, elapsed, deadline, diag.get("iter"), diag.get("phase"),
               diag.get("inflight_depth"), diag.get("last_drained"))
        )
        cb = self._on_fire if action == "fire" else self._on_escalate
        if cb is not None:
            cb(diag)

    def summary(self) -> Dict[str, Any]:
        return {
            "fires": self.fires,
            "escalated": self.escalated,
            "deadline_s": self.deadline_s(),
            "events": list(self.events),
        }

    # ---------------------------------------------------------------- thread
    def start(self) -> "Watchdog":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._monitor, name="galvatron-watchdog", daemon=True
            )
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(self.cfg.poll_interval_s * 4, 1.0))
            self._thread = None

    def _monitor(self) -> None:
        while not self._stop.wait(self.cfg.poll_interval_s):
            try:
                self.check()
            except Exception as e:  # the monitor must never kill the run
                telemetry.runtime_log("watchdog monitor error: %s" % e)

    def __enter__(self) -> "Watchdog":
        return self.start()

    def __exit__(self, *exc_info):
        self.stop()
        return False


# ------------------------------------------------------------- mesh health
def classify_world(expected_ids: Sequence[int], live_devices: Sequence[Any]) -> Dict[str, Any]:
    """Device-enumeration diff: the live platform's device ids against the
    ids the running strategy was planned for (its mesh / the checkpoint
    provenance's device_count). Pure bookkeeping — no device work."""
    expected = sorted(int(i) for i in expected_ids)
    live = sorted(int(getattr(d, "id", d)) for d in live_devices)
    missing = sorted(set(expected) - set(live))
    added = sorted(set(live) - set(expected))
    if missing:
        status = "degraded"
    elif added:
        status = "grown"
    else:
        status = "healthy"
    return {
        "status": status,
        "expected": len(expected),
        "live": len(live),
        "missing_ids": missing,
        "added_ids": added,
    }


def _sum_of_ones(mesh) -> float:
    """One float per device, sharded over all mesh axes, summed to a
    replicated scalar (an all-reduce on any multi-device mesh)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec

    x = jax.device_put(
        np.ones((mesh.devices.size,), np.float32),
        NamedSharding(mesh, PartitionSpec(tuple(mesh.shape.keys()))))
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, PartitionSpec()))(x)
    return float(jax.device_get(total))


def probe_collective(mesh, timeout_s: float = 5.0,
                     collective: Callable[[Any], float] = _sum_of_ones) -> Dict[str, Any]:
    """`collective` (a tiny jitted sum across every device of `mesh`, which
    answers the device count; a test passes one that blocks) run under a
    bounded timeout. A healthy mesh answers in milliseconds; a wedged
    interconnect leaves the worker blocked and the probe reports ``ok=False``
    with ``timed_out=True`` instead of hanging the driver."""
    result: Dict[str, Any] = {"ok": False, "timed_out": False, "elapsed_s": None}
    n = int(mesh.devices.size)

    def run():
        try:
            t0 = time.perf_counter()
            value = collective(mesh)
            result["elapsed_s"] = time.perf_counter() - t0
            result["ok"] = value == float(n)
            if not result["ok"]:
                result["error"] = "collective returned %r, expected %d" % (value, n)
        except Exception as e:  # noqa: BLE001 — reported, not raised
            result["error"] = "%s: %s" % (type(e).__name__, e)

    worker = threading.Thread(target=run, name="galvatron-mesh-probe", daemon=True)
    worker.start()
    worker.join(timeout=max(timeout_s, 0.0))
    if worker.is_alive():
        result["timed_out"] = True
        result["error"] = "collective did not complete within %.1fs" % timeout_s
    return result


@dataclass
class MeshHealthMonitor:
    """Periodic mesh-health probe driven from the train loop's step
    boundaries (no extra thread: a probe only runs when the loop is live,
    which is exactly when its verdict can be acted on).

    `expected_ids` come from the running strategy's mesh; `devices_fn` is
    injectable so tests can simulate device loss without killing real
    devices.

    `quarantined_ids` holds devices other subsystems have convicted (the
    silent-corruption voter in runtime/sdc.py): a quarantined device is
    treated as missing even though enumeration still lists it — the lie is
    in its arithmetic, not its liveness — so every later probe keeps
    reporting the world degraded until the run migrates off it."""

    mesh: Any
    interval_s: float = 60.0
    timeout_s: float = 5.0
    devices_fn: Callable[[], Sequence[Any]] = None  # default: jax.devices
    time_fn: Callable[[], float] = time.monotonic
    collective: bool = True  # enumeration diff only when False (cheaper)
    _next_due: Optional[float] = field(default=None, repr=False)
    expected_ids: Sequence[int] = ()
    quarantined_ids: set = field(default_factory=set)

    def __post_init__(self):
        if self.devices_fn is None:
            import jax

            self.devices_fn = jax.devices
        if not self.expected_ids:
            self.expected_ids = [int(d.id) for d in self.mesh.devices.flat]

    def maybe_probe(self, now: Optional[float] = None) -> Optional[Dict[str, Any]]:
        """Run the probe when due (every `interval_s`); None otherwise."""
        now = self.time_fn() if now is None else now
        if self._next_due is None:
            self._next_due = now + self.interval_s
            return None
        if now < self._next_due:
            return None
        self._next_due = now + self.interval_s
        return self.probe()

    def quarantine(self, device_ids: Sequence[int]) -> Dict[str, Any]:
        """Convict `device_ids` and return the immediate (degraded) verdict
        the caller can feed straight into its migrate-on-degrade handler —
        no need to wait for the next scheduled probe."""
        self.quarantined_ids.update(int(i) for i in device_ids)
        return self.probe()

    def probe(self) -> Dict[str, Any]:
        live = [d for d in self.devices_fn()
                if int(getattr(d, "id", d)) not in self.quarantined_ids]
        verdict = classify_world(self.expected_ids, live)
        if self.quarantined_ids:
            verdict["quarantined_ids"] = sorted(self.quarantined_ids)
        if self.collective and verdict["status"] == "healthy":
            coll = probe_collective(self.mesh, timeout_s=self.timeout_s)
            verdict["collective_ok"] = coll["ok"]
            if coll.get("elapsed_s") is not None:
                verdict["collective_elapsed_s"] = coll["elapsed_s"]
            if not coll["ok"]:
                verdict["status"] = "wedged"
                verdict["error"] = coll.get("error")
        return verdict
