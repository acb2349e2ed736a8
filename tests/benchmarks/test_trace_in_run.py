"""`--trace 2`: the measured window of `--trace 0`, then a traced tail in the
same process. The clock's arithmetic with and without a tail, and one whole
run on the CPU at a tiny size (the trace's reduction stubbed: XLA:CPU has no
device plane)."""

import json
import os

import pytest

from benchmarks import cells, harness, trace, window

from .test_cell_from_files import NOT_ON_THE_CPU, REPO, root  # noqa: F401 -- the tiny cell
from .test_window import run_clock


def fake_run(seconds, step_s, tail=0, warmup=6):
    """The stamps of a run whose steps take `step_s` and whose tail's steps,
    under the profiler, take a tenth more."""
    now, ended = [0.0], []

    def end_run(it):
        ended.append(it)
        return tail

    clock = window.WindowClock(seconds, warmup, end_run, clock=lambda: now[0])
    it = 0
    while not ended or it < ended[0] + tail:
        clock.on_step(it)
        now[0] += 5.0 if it == 0 else step_s * (1.1 if ended else 1.0)
        it += 1
    return clock, ended


@pytest.mark.parametrize("seconds,step_s,tail", [(10, 0.287, 11), (10, 0.547, 6), (0.01, 0.75, 6)])
def test_a_tail_moves_neither_the_windows_stamps_nor_its_estimate(seconds, step_s, tail):
    plain, ended_plain = fake_run(seconds, step_s)
    tailed, ended_tailed = fake_run(seconds, step_s, tail)
    assert ended_plain == ended_tailed and plain.last == tailed.last
    assert tailed.window_stamps() == plain.window_stamps()
    assert window.estimate(tailed.window_stamps(), 8192) == \
        window.estimate(plain.window_stamps(), 8192)
    # the tail: the closing stamp and one more for each of its steps but the
    # last, which has no on_step after it
    assert plain.tail_stamps() == [plain.stamps[-1]]
    assert len(tailed.tail_stamps()) == tail
    assert tailed.tail_stamps()[0] == tailed.window_stamps()[-1]
    gaps = [b - a for a, b in zip(tailed.tail_stamps(), tailed.tail_stamps()[1:])]
    assert gaps == pytest.approx([step_s * 1.1] * (tail - 1))


def test_the_clock_refuses_a_step_past_the_tail_and_any_step_after_a_window_without_one():
    done, ended = run_clock(1.0, 0.3)  # test_window's clock: end_run returns None
    assert done.tail == 0
    with pytest.raises(RuntimeError):
        done.on_step(ended[0] + 1)
    tailed, ended = fake_run(1.0, 0.3, tail=6)
    assert len(tailed.stamps) == ended[0] + 6
    with pytest.raises(RuntimeError):
        tailed.on_step(ended[0] + 6)


def test_the_tail_readers():
    reader = lambda name: cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name)  # noqa: E731
    run = {"window": {"median_step_s": 0.25}, "tail_intervals_s": None}
    assert reader("tracing_on_slowdown_pct").read(run) is None
    # the first gap holds the profiler's start, the next two the refilling of
    # the loop's lead: they are left out
    run["tail_intervals_s"] = [2.0, 0.001, 0.24, 0.255, 0.255, 0.256, 0.255]
    assert reader("tracing_on_slowdown_pct").read(run) == pytest.approx(2.0)
    run["tail_intervals_s"] = [2.0, 0.001, 0.24]
    assert reader("tracing_on_slowdown_pct").read(run) is None
    events = [{"type": "step", "iter": i, "dispatch_ms": 2.0, "data_wait_ms": 0.1 * i}
              for i in range(40, 50)] + [{"type": "step", "iter": 50}]
    run = {"events": events, "window_steps": (44, 48)}
    assert reader("data_wait_ms").read(run) == pytest.approx(0.1 * (44 + 45 + 46 + 47) / 4)
    assert reader("host_dispatch_ms").read(run) == pytest.approx(2.0)
    assert reader("data_wait_ms").read({"events": [], "window_steps": (0, 9)}) is None


def recorded_reduction():
    return trace.reduce(trace.load_events(os.path.join(
        REPO, "benchmarks", "fixtures", "qwen7-c1-s2k-scoped.trace_events.json.gz")),
        harness.STEP_NAMES)


def test_a_run_that_measures_and_traces(root, tmp_path, monkeypatch):  # noqa: F811
    """Mode 2 on the CPU: one last line with the four end-to-end metrics and
    the per-layer ones, from one compilation of the step; the window is the
    one `--trace 0` would have measured, the telemetry is the tail's."""
    # a step program of this test's own: one that another test of this process
    # has compiled comes out of the trainer's memo and counts as no compilation
    traffic = os.path.join(root, "benchmarks", "traffic", "b2-s32.json")
    mix = json.load(open(traffic))
    mix["seq_length"] = 64
    json.dump(mix, open(traffic, "w"))
    cell = cells.load_cell(root, "tiny-cell")
    seen = {}

    def read_trace(trace_dir, hlo, out_dir):
        seen["xplane"] = trace.find_xplane(trace_dir)
        return recorded_reduction()

    monkeypatch.setattr(harness, "read_trace", read_trace)
    monkeypatch.setattr(harness, "TAIL_SECONDS", 0.3)  # a CPU step is tens of ms
    lines = []
    result = harness.run_cell(
        cell, seed=2**31 + 78, seconds=0.5, traced=2,
        peaks={"cpu": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}},
        t0=0.0, out_dir=str(tmp_path), say=lambda **o: lines.append(o))
    detail = lines[-1]
    assert seen["xplane"] is not None  # the trainer's control wrote the trace
    assert not os.path.exists(os.path.join(str(tmp_path), "xla_trace.first"))
    assert {k for k, ok in detail["checks"].items() if not ok} == NOT_ON_THE_CPU[cell.chips]
    assert detail["checks"]["one_step_compilation"] and detail["checks"]["no_compilation_in_window"]
    w = detail["window"]
    assert result["attempted"] == w["steps"] and result["failed"] == 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["tokens_per_s_chip"] == pytest.approx(
        w["steps"] * cell.tokens_a_step / w["window_s"] / cell.chips)
    per_layer = {m["name"] for m in cell.metrics("per_layer")}
    assert {"tokens_per_s_chip", "mfu", "step_hbm_gib", "setup_s"} <= set(metrics)
    # all but the sum of set-up's parts, which benchmarks/run.py times
    assert per_layer - set(metrics) == {"launch_serial_s"}
    assert metrics["median_step_ms"] == pytest.approx(w["median_step_s"] * 1e3)
    assert metrics["guard_select_ms"] > metrics["optimizer_ms"] > 0
    assert "tracing_on_slowdown_pct" in metrics and metrics["data_wait_ms"] >= 0
    assert result["device"]["busy_s"] > 0 and result["breakdown"]["device_ops"]
    saved = json.load(open(os.path.join(str(tmp_path), "run.json")))
    assert len(saved["intervals_s"]) == result["attempted"]
    # about three seconds of traced steps, and never fewer than the minimum
    tail = detail["tail"]["steps"]
    assert tail == max(harness.TAIL_MIN_STEPS, -(-harness.TAIL_SECONDS // w["median_step_s"]))
    assert len(saved["tail_intervals_s"]) == tail - 1
    # every loss of the run, the tail's too
    assert len(saved["losses"]) == 6 + w["steps"] + tail
