"""Watchdog + mesh-health unit tests (runtime/health.py).

The escalation ladder and deadline learning run against a FAKE clock — the
monitor thread is just a pump around the pure `check()`, so tier-1 pays no
wall-clock sleeps for the interesting logic. One short real-thread smoke
test and one real (tiny) mesh probe keep the glue honest."""

import threading
import time

import pytest

from galvatron_tpu.obs import telemetry as T
from galvatron_tpu.runtime import health as H


class FakeClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt
        return self.t


def make_wd(clock, **cfg_kw):
    cfg_kw.setdefault("floor_s", 1.0)
    cfg_kw.setdefault("factor", 2.0)
    cfg_kw.setdefault("min_history", 3)
    cfg_kw.setdefault("startup_deadline_s", 100.0)
    return H.Watchdog(H.WatchdogConfig(**cfg_kw), time_fn=clock)


# ------------------------------------------------------------ deadline learning
def test_deadline_is_startup_until_history_then_learned():
    clock = FakeClock()
    wd = make_wd(clock)
    assert wd.deadline_s() == 100.0
    wd.observe_step_time(500.0)
    wd.observe_step_time(1000.0)
    assert wd.deadline_s() == 100.0  # 2 < min_history
    wd.observe_step_time(1500.0)
    # factor * median(0.5, 1.0, 1.5)s + floor = 2 * 1.0 + 1.0
    assert wd.deadline_s() == pytest.approx(3.0)


def test_deadline_tracks_median_not_outliers():
    wd = make_wd(FakeClock())
    for ms in (100.0, 100.0, 100.0, 100.0, 60000.0):  # one straggler
        wd.observe_step_time(ms)
    assert wd.deadline_s() == pytest.approx(2.0 * 0.1 + 1.0)


# ---------------------------------------------------------- escalation ladder
def test_fire_then_escalate_ladder():
    clock = FakeClock()
    wd = make_wd(clock, startup_deadline_s=10.0)
    wd.arm(0, "fetch")
    assert wd.check(clock.advance(9.0)) is None
    assert wd.check(clock.advance(2.0)) == "fire"  # 11s > 10s deadline
    assert wd.fires == 1 and wd.retry_requested and not wd.escalated
    # within the post-fire grace: no escalation yet
    assert wd.check(clock.advance(9.0)) is None
    assert wd.check(clock.advance(2.0)) == "escalate"
    assert wd.escalated and wd.abort_requested
    # terminal: no further actions
    assert wd.check(clock.advance(100.0)) is None
    s = wd.summary()
    assert s["escalated"] and s["fires"] == 1
    assert [e["action"] for e in s["events"]] == ["fire", "escalate"]


def test_progress_resets_ladder_and_records_drain():
    clock = FakeClock()
    wd = make_wd(clock, startup_deadline_s=10.0)
    wd.arm(3, "inflight", inflight=2)
    assert wd.check(clock.advance(11.0)) == "fire"
    wd.progress(drained_iteration=3, inflight=1)  # the run recovered
    assert wd.check(clock.advance(9.0)) is None  # ladder restarted
    assert wd.check(clock.advance(2.0)) == "fire"  # a NEW stall fires again
    assert wd.fires == 2
    assert wd.diagnostics(include_stacks=False)["last_drained"] == 3


def test_disarm_and_rearm():
    clock = FakeClock()
    wd = make_wd(clock, startup_deadline_s=10.0)
    wd.arm(0)
    wd.disarm()  # eval/save boundary
    assert wd.check(clock.advance(1000.0)) is None
    wd.arm(1)
    assert wd.check(clock.advance(11.0)) == "fire"


def test_retry_request_is_consumed_once():
    clock = FakeClock()
    wd = make_wd(clock, startup_deadline_s=10.0)
    wd.arm(0)
    wd.check(clock.advance(11.0))
    assert wd.take_retry_request() is True
    assert wd.take_retry_request() is False


def test_arm_restarts_interval():
    clock = FakeClock()
    wd = make_wd(clock, startup_deadline_s=10.0)
    wd.arm(0)
    clock.advance(9.0)
    wd.arm(1)  # next loop body: the deadline clock restarts
    assert wd.check(clock.advance(9.0)) is None
    assert wd.check(clock.advance(2.0)) == "fire"


def test_fire_emits_schema_valid_watchdog_event_with_stacks():
    sink = T.MemorySink()
    T.install(sink)
    try:
        clock = FakeClock()
        wd = make_wd(clock, startup_deadline_s=10.0)
        wd.observe_step_time(100.0)
        wd.arm(7, "inflight", inflight=2)
        wd.check(clock.advance(11.0))
    finally:
        T.uninstall(sink)
    events = [e for e in sink.events if e["type"] == "watchdog"]
    assert len(events) == 1
    ev = events[0]
    assert ev["action"] == "fire" and ev["iter"] == 7 and ev["phase"] == "inflight"
    assert ev["inflight_depth"] == 2 and ev["deadline_s"] == 10.0
    # the diagnostic dump includes THIS thread's stack via faulthandler
    assert "test_health" in ev["stacks"] or "Thread" in ev["stacks"]


def test_monitor_thread_fires_in_real_time():
    """Thread-pump smoke test: a real armed interval with a 50ms deadline
    fires within a second of wall time."""
    fired = threading.Event()
    wd = H.Watchdog(
        H.WatchdogConfig(startup_deadline_s=0.05, poll_interval_s=0.01,
                         min_history=99),
        on_fire=lambda diag: fired.set(),
    )
    with wd:
        wd.arm(0, "fetch")
        assert fired.wait(timeout=2.0)
    assert wd.fires == 1 and wd.retry_requested


# --------------------------------------------------------------- mesh health
class _Dev:
    def __init__(self, i):
        self.id = i


def test_classify_world_verdicts():
    assert H.classify_world([0, 1, 2, 3], [_Dev(i) for i in range(4)]) == {
        "status": "healthy", "expected": 4, "live": 4,
        "missing_ids": [], "added_ids": [],
    }
    degraded = H.classify_world([0, 1, 2, 3], [_Dev(0), _Dev(2)])
    assert degraded["status"] == "degraded" and degraded["missing_ids"] == [1, 3]
    grown = H.classify_world([0, 1], [_Dev(i) for i in range(4)])
    assert grown["status"] == "grown" and grown["added_ids"] == [2, 3]


def test_probe_collective_on_live_mesh(devices8):
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices8[:2]).reshape(2), ("dp",))
    out = H.probe_collective(mesh, timeout_s=30.0)
    assert out["ok"] is True and out["timed_out"] is False
    assert out["elapsed_s"] is not None


def test_probe_collective_zero_timeout_reports_timed_out(devices8):
    """A collective that does not answer within the timeout: the probe
    must report a (non-hanging) timeout instead of blocking the caller. The
    collective blocks on an event until the assertion is made, so no warm
    jit can beat a zero-second join."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices8[:2]).reshape(2), ("dp",))
    release = threading.Event()

    def wedged(mesh):
        release.wait(timeout=60.0)
        return float(mesh.devices.size)

    try:
        out = H.probe_collective(mesh, timeout_s=0.0, collective=wedged)
        assert out["timed_out"] is True and out["ok"] is False
    finally:
        release.set()


def test_mesh_monitor_interval_and_simulated_device_loss(devices8):
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(devices8[:4]).reshape(4), ("dp",))
    clock = FakeClock()
    live = {"devices": list(devices8[:4])}
    mon = H.MeshHealthMonitor(
        mesh, interval_s=60.0, devices_fn=lambda: live["devices"],
        time_fn=clock, collective=False,
    )
    assert mon.maybe_probe() is None  # first call only schedules
    assert mon.maybe_probe(clock.advance(30.0)) is None  # not due yet
    v = mon.maybe_probe(clock.advance(31.0))
    assert v is not None and v["status"] == "healthy"
    live["devices"] = list(devices8[:2])  # simulate losing half the mesh
    assert mon.maybe_probe(clock.advance(10.0)) is None  # respects interval
    v = mon.maybe_probe(clock.advance(51.0))
    assert v["status"] == "degraded" and v["live"] == 2 and len(v["missing_ids"]) == 2
