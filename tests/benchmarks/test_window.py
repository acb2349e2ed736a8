"""The window's arithmetic and the clock that opens and closes it."""

import pytest

from benchmarks import window

TOKENS = 8192
STEP = 0.287


def stamps_from(intervals):
    out = [100.0]
    for dt in intervals:
        out.append(out[-1] + dt)
    return out


def test_one_long_interval_moves_the_rate_and_stall_pct_and_not_the_median_step():
    """PR 22's bad run: one stall of 1.8 s in a window of 104 steps. The rate
    is all the work over all the time, so it loses what the stall took;
    `stall_pct` says how much that was, the median step stays."""
    n = 104
    calm = window.estimate(stamps_from([STEP] * n), TOKENS)
    intervals = [STEP] * n
    intervals[37] += 1.8
    stalled = window.estimate(stamps_from(intervals), TOKENS)
    assert calm["rate"] == pytest.approx(TOKENS / STEP, rel=1e-12)
    assert calm["stall_pct"] == pytest.approx(0.0, abs=1e-9)
    assert stalled["rate"] == pytest.approx(n * TOKENS / (n * STEP + 1.8), rel=1e-12)
    assert stalled["median_step_s"] == pytest.approx(calm["median_step_s"], rel=1e-12)
    assert stalled["stall_pct"] == pytest.approx(100 * 1.8 / (n * STEP + 1.8), rel=1e-9)
    assert 5.0 < stalled["stall_pct"] < 7.0


def test_a_host_pause_the_steps_in_flight_absorb_moves_nothing():
    """A pause shorter than the two steps in flight: one long gap, then a
    short one, the pair summing to two steps (PERF.md section 6)."""
    intervals = [STEP] * 34
    intervals[10], intervals[11] = STEP + 0.1, STEP - 0.1
    est = window.estimate(stamps_from(intervals), TOKENS)
    assert est["rate"] == pytest.approx(TOKENS / STEP, rel=1e-9)
    assert abs(est["stall_pct"]) < 1e-6


def test_a_uniformly_slower_run_moves_the_rate_and_no_stall_is_read():
    calm = window.estimate(stamps_from([STEP] * 34), TOKENS)
    slow = window.estimate(stamps_from([STEP * 1.06] * 34), TOKENS)
    assert slow["rate"] == pytest.approx(calm["rate"] / 1.06)
    assert slow["median_step_s"] == pytest.approx(calm["median_step_s"] * 1.06)
    assert abs(slow["stall_pct"]) < 1e-6


def test_a_window_needs_two_stamps():
    with pytest.raises(ValueError):
        window.estimate([1.0], TOKENS)


def run_clock(seconds, step_s, warmup=6, first_step_s=5.0):
    now, ended = [0.0], []
    clock = window.WindowClock(seconds=seconds, warmup=warmup, end_run=ended.append,
                               clock=lambda: now[0])
    it = 0
    while not ended:
        clock.on_step(it)
        now[0] += first_step_s if it == 0 else step_s  # the first step compiles
        it += 1
    return clock, ended


@pytest.mark.parametrize("seconds,step_s,steps", [
    (10, 0.287, 35),  # the one-chip cells: the first stamp at or past 10 s
    (10, 0.547, 19),  # the four-chip cell
    (10, 0.5, 20),  # a stamp that falls on the deadline closes the window
    (30, 0.287, 105),
    (0.01, 0.75, 1),  # never an empty window
])
def test_the_window_closes_at_the_first_stamp_past_its_seconds(seconds, step_s, steps):
    clock, ended = run_clock(seconds, step_s)
    assert ended == [6 + steps] and clock.last == 6 + steps
    est = window.estimate(clock.window_stamps(), 100)
    assert est["steps"] == steps
    assert est["window_s"] == pytest.approx(steps * step_s) and est["window_s"] >= seconds
    assert est["window_s"] - step_s < seconds
    assert est["rate"] == pytest.approx(100 / step_s)


def test_the_window_opens_after_warmup():
    clock, _ = run_clock(4.0, 0.125, warmup=9)
    stamps = clock.window_stamps()
    assert stamps[0] == pytest.approx(5.0 + 8 * 0.125) and len(stamps) == 33


def test_clock_refuses_a_short_warmup_a_replayed_iteration_and_a_step_after_the_end():
    with pytest.raises(ValueError):
        window.WindowClock(seconds=1.0, warmup=3, end_run=lambda it: None)
    clock = window.WindowClock(seconds=1.0, warmup=6, end_run=lambda it: None)
    clock.on_step(0)
    with pytest.raises(RuntimeError):
        clock.on_step(0)
    with pytest.raises(RuntimeError):
        clock.window_stamps()
    done, ended = run_clock(1.0, 0.3)
    with pytest.raises(RuntimeError):
        done.on_step(ended[0] + 1)


def test_the_entry_layers_readers():
    import os

    from benchmarks import cells

    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    reader = lambda name: cells.load_module(repo, "benchmarks/layer_metrics/%s.py" % name)
    intervals = [STEP] * 40
    intervals[7] += 1.0
    run = {"window": window.estimate(stamps_from(intervals), TOKENS)}
    assert reader("median_step_ms").read(run) == pytest.approx(287.0)
    assert reader("stall_pct").read(run) == pytest.approx(100 / (40 * STEP + 1.0))
    # the sum a CLI launch pays; nothing to read where the parts were not timed
    assert reader("launch_serial_s").read(run) is None
    run.update(chip_start_s=7.0, setup_parts_s={"import_jax_s": 3.0, "import_program_s": 15.0})
    assert reader("launch_serial_s").read(run) == pytest.approx(25.0)
