"""Dispatch-ahead training loop (ISSUE 4): bitwise loss parity against the
host-serialised loop (``--prefetch_batches 0 --inflight_steps 0``), deferred anomaly-guard decisions, forced drains at
save/eval/preemption boundaries, and the overlap metrics in the profiler
summary. The keep/skip select lives inside the jitted step, so the two loops
run the identical device program — only host bookkeeping timing differs,
which is why the parity assertions are exact equality, not tolerance."""

import numpy as np
import pytest

from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.runtime import checkpoint as ck
from tests.runtime import fault_injection as fi

# same tiny shapes as test_train_driver.TINY / test_resilience.TINY: every
# train() call pays a fresh XLA:CPU step compile, so shapes stay minimal
TINY = [
    "--model_type", "llama", "--set_model_config_manually", "1",
    "--hidden_size", "64", "--num_attention_heads", "4", "--num_layers", "2",
    "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "fp32",
    "--global_train_batch_size", "8", "--lr", "1e-3", "--world_size", "8",
]
RES_TINY = [
    "--model_type", "llama", "--set_model_config_manually", "1",
    "--hidden_size", "32", "--num_attention_heads", "2", "--num_layers", "2",
    "--vocab_size", "64", "--seq_length", "16", "--mixed_precision", "fp32",
    "--global_train_batch_size", "8", "--lr", "1e-2", "--world_size", "8",
]


# the host-serialised loop, the reference of every parity case below: no
# prefetch thread, every step's metrics drained before the next is dispatched
SYNC = ["--prefetch_batches", "0", "--inflight_steps", "0"]


def run(extra, hooks=None, base=TINY):
    args = initialize_galvatron(mode="train_dist", argv=base + extra)
    if hooks is not None:
        args.fault_hooks = hooks
    return train(args)


def test_dispatch_ahead_matches_sync_bitwise(devices8):
    """Same seed => the async loop (prefetch + deferred drains, the default)
    and the host-serialised loop produce bit-identical train/valid/test losses,
    including across the forced drain at every eval boundary."""
    common = ["--train_iters", "6", "--eval_interval", "3", "--eval_iters", "2"]
    a = run(common)
    b = run(common + SYNC)
    np.testing.assert_array_equal(a["losses"], b["losses"])
    assert a["valid_losses"] == b["valid_losses"]
    assert a["test_loss"] == b["test_loss"]
    # the overlap instrumentation is present in both modes
    for s in (a, b):
        assert s["iters"] == 4  # 6 iters - 2 warmup
        assert "host_blocked_ms" in s and "dispatch_ms" in s
        assert s["steps_per_s"] > 0 and s["loop_wall_ms"] > 0


def test_dispatch_ahead_parity_chunks_and_guard(devices8):
    """Parity holds with gradient-accumulation microbatching and the
    anomaly guard armed (the guarded step takes the host-fed spike_cap
    argument; with spike detection off the cap is +inf in both modes)."""
    common = ["--train_iters", "4", "--chunks", "2", "--anomaly_guard", "1"]
    a = run(common)
    b = run(common + SYNC)
    np.testing.assert_array_equal(a["losses"], b["losses"])


def test_deferred_guard_decisions_match_sync(devices8):
    """A NaN batch under deferred metrics: the skip decision (made in-jit)
    and the host-side strike accounting must match the synchronous loop
    exactly — same skipped count, same surviving losses, bit for bit."""
    common = ["--train_iters", "4"]
    hooks = fi.nan_batch_hooks([1])
    a = run(common, hooks=fi.nan_batch_hooks([1]), base=RES_TINY)
    b = run(common + SYNC, hooks=hooks, base=RES_TINY)
    for s in (a, b):
        assert s["resilience"]["anomalies_skipped"] == 1
        assert s["resilience"]["rollbacks"] == 0
        assert len(s["losses"]) == 3
        assert np.isfinite(s["losses"]).all()
    np.testing.assert_array_equal(a["losses"], b["losses"])


def test_forced_drain_before_emergency_save(devices8, tmp_path):
    """SIGTERM at a step boundary with steps still in flight: the loop must
    drain every dispatched step (losses 0..1 accounted), then emergency-save
    at the boundary — not save through a half-drained window."""
    d = str(tmp_path / "ck")
    s = run(["--train_iters", "5", "--save", d],
            hooks=fi.sigterm_hooks(2), base=RES_TINY)
    assert s["interrupted"] == "SIGTERM"
    assert s["resilience"]["emergency_saves"] == 1
    assert len(s["losses"]) == 2  # steps 0,1 dispatched AND drained
    assert ck.intact_iterations(d) == [2]


def test_prefetch_and_window_knobs(devices8):
    """--prefetch_batches 0 (no thread) and --inflight_steps 0 (drain every
    step) are independently valid points of the knob space; both together
    are the host-serialised loop."""
    a = run(["--train_iters", "3", "--prefetch_batches", "0"])
    b = run(["--train_iters", "3", "--inflight_steps", "0"])
    c = run(["--train_iters", "3"] + SYNC)
    np.testing.assert_array_equal(a["losses"], c["losses"])
    np.testing.assert_array_equal(b["losses"], c["losses"])


@pytest.mark.slow
def test_deferred_rollback_matches_sync(devices8, tmp_path):
    """Strike-rollback under deferred metrics: three consecutive NaN batches
    roll back to the last intact checkpoint, the in-flight window is
    discarded with the abandoned trajectory, and the replayed stream
    reproduces the synchronous loop's decisions and losses exactly."""
    results = {}
    for mode, extra in (("ahead", []), ("sync", SYNC)):
        d = str(tmp_path / ("ck_" + mode))
        results[mode] = run(
            ["--train_iters", "7", "--save", d, "--save_interval", "2",
             "--anomaly_max_strikes", "3", "--anomaly_reseed", "1000"] + extra,
            hooks=fi.nan_batch_hooks([3, 4, 5]), base=RES_TINY,
        )
    for s in results.values():
        assert s["resilience"]["anomalies_skipped"] == 3
        assert s["resilience"]["rollbacks"] == 1
        assert len(s["losses"]) == 6
        assert np.isfinite(s["losses"]).all()
    np.testing.assert_array_equal(results["ahead"]["losses"],
                                  results["sync"]["losses"])


@pytest.mark.slow
def test_dispatch_ahead_overlaps_input_latency(devices8):
    """The throughput property the loop exists for: with per-batch input
    latency (emulated I/O wait through the FaultHooks seam) the dispatch-
    ahead loop hides compute under the wait — strictly less host-blocked
    time and higher steps/s than the host-serialised loop."""
    import time

    from galvatron_tpu.runtime.resilience import FaultHooks

    def latency_hooks(ms):
        def wrap(data_iter, start_step):
            for b in data_iter:
                time.sleep(ms / 1e3)
                yield b

        return FaultHooks(wrap_data_iter=wrap)

    common = ["--train_iters", "8", "--world_size", "1", "--log_interval", "1000"]
    # calibrate: the emulated input wait must dominate the (machine- and
    # flag-dependent) step time for the overlap to be unambiguous
    probe = run(common + SYNC, base=RES_TINY)
    latency = max(3.0 * probe["steady_step_ms"], 50.0)
    a = run(common, hooks=latency_hooks(latency), base=RES_TINY)
    b = run(common + SYNC, hooks=latency_hooks(latency),
            base=RES_TINY)
    np.testing.assert_array_equal(a["losses"], b["losses"])
    # sync blocks ~a full step per iteration; dispatch-ahead hides the step
    # under the input wait, so its drains find finished results
    assert a["host_blocked_ms_total"] < 0.5 * b["host_blocked_ms_total"], (
        a["host_blocked_ms_total"], b["host_blocked_ms_total"])
    assert a["steps_per_s"] > b["steps_per_s"]
