"""The readers of the program's named scopes: their patterns against the
names the program defines, on labels worked out by hand, on the trace of a
scoped step recorded on the chip, and on the older recording of a step
without scopes, where there is nothing to read."""

import json
import os

import pytest

from benchmarks import cells, harness, scopes, trace
from galvatron_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
DEVICE_READERS = ("layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms", "embed_ms", "head_loss_ms",
                  "optimizer_ms", "guard_select_ms", "unscoped_pct")


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def label(instruction, op_name):
    """The label `trace.load` gives an op whose HLO instruction carries `op_name`."""
    return trace._label("%%%s = f32[8] fusion(...)" % instruction, {instruction: op_name})


def handmade():
    """One op a kind, labelled as the compiled step labels them: the
    program's own scope names under the transforms' wrappers."""
    r0, r1 = tracing.layers_scope(0), tracing.layers_scope(1)
    body = "/while/body/closed_call/"
    ops = {
        label("fusion.1", "jit(train_step)/jvp(%s)%sdot_general" % (r0, body)): 10e-3,
        # a `transpose` primitive in the forward is not the backward
        label("fusion.2", "jit(train_step)/jvp(%s)/transpose" % r1): 1e-3,
        label("fusion.3", "jit(train_step)/transpose(jvp(%s))%scheckpoint/rematted_computation/dot_general"
              % (r0, body)): 8e-3,
        label("fusion.4", "jit(train_step)/transpose(jvp(%s))%scheckpoint/dot_general" % (r0, body)): 20e-3,
        label("fusion.5", "jit(train_step)/transpose(jvp(%s))/dot_general" % r1): 2e-3,
        label("gather.6", "jit(train_step)/jvp(%s)/gather" % tracing.EMBED): 0.5e-3,
        label("scatter.7", "jit(train_step)/transpose(jvp(%s))/scatter-add" % tracing.EMBED): 4e-3,
        label("fusion.8", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): 3e-3,
        label("fusion.9", "jit(train_step)/transpose(jvp(%s))/%s/dot_general"
              % (tracing.HEAD_LOSS, tracing.HEAD_LOSS)): 6e-3,
        label("fusion.10", "jit(train_step)/%s/reduce_sum" % tracing.OPTIMIZER): 1.5e-3,
        label("fusion.11", "jit(train_step)/%s/jit(_where)/select_n" % tracing.GUARD): 7e-3,
        label("fusion.12", "jit(train_step)/%s/mul" % tracing.GRAD_ACCUM): 0.25e-3,
        "copy-done.13": 0.75e-3,
    }
    return {"trace": {"ops_a_step": {k: [v, 1.0] for k, v in ops.items()}}}


def test_the_patterns_read_the_programs_names():
    run = handmade()
    assert read("layers_fwd_ms", run) == pytest.approx(11.0)
    assert read("layers_remat_ms", run) == pytest.approx(8.0)
    assert read("layers_bwd_ms", run) == pytest.approx(22.0)
    assert read("embed_ms", run) == pytest.approx(4.5)
    assert read("head_loss_ms", run) == pytest.approx(9.0)
    assert read("optimizer_ms", run) == pytest.approx(1.5)
    assert read("guard_select_ms", run) == pytest.approx(7.0)
    assert read("unscoped_pct", run) == pytest.approx(100 * 0.75 / 64.0)
    # forward, recomputation and backward share the layers' time out between them
    assert scopes.ms_a_step(run, scopes.LAYERS) == pytest.approx(41.0)


def test_a_step_without_the_guard_reads_zero_and_a_program_without_scopes_nothing():
    run = handmade()
    run["trace"]["ops_a_step"] = {k: v for k, v in run["trace"]["ops_a_step"].items()
                                  if tracing.GUARD not in k}
    assert read("guard_select_ms", run) == 0.0
    assert read("optimizer_ms", run) == pytest.approx(1.5)
    for bare in ({"trace": None}, {"trace": {"ops_a_step": {"fusion.1:jvp__/while/body/dot_general": [1e-3, 1.0]}}}):
        assert [read(name, bare) for name in DEVICE_READERS] == [None] * len(DEVICE_READERS)


def test_the_older_recording_has_no_scopes_to_read():
    r = trace.reduce(trace.load_events(os.path.join(FIXTURES, "qwen7-c1-s2k.trace_events.json.gz")),
                     harness.STEP_NAMES)
    assert [read(name, {"trace": r}) for name in DEVICE_READERS] == [None] * len(DEVICE_READERS)


def test_the_readers_on_the_scoped_step_recorded_on_the_chip():
    """Device 0's events of the traced tail of one `--trace 2` run of
    qwen7-c1-s2k on a v5e; the expected numbers are what that run reported."""
    expected = json.load(open(os.path.join(FIXTURES, "qwen7-c1-s2k-scoped.expected.json")))
    r = trace.reduce(trace.load_events(
        os.path.join(FIXTURES, "qwen7-c1-s2k-scoped.trace_events.json.gz")), harness.STEP_NAMES)
    assert r["steps"] == expected["steps"]
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    run = {"trace": r}
    for name in DEVICE_READERS:
        assert read(name, run) == pytest.approx(expected[name], rel=1e-9), name
    # the scopes and what lies outside them are the whole of the device's
    # busy time (one chip: no op overlaps another)
    scoped = sum(read(name, run) for name in DEVICE_READERS if name.endswith("_ms"))
    busy_ms = 1e3 * r["busy_s"] / r["steps"]
    assert scoped + read("unscoped_pct", run) / 100 * busy_ms == pytest.approx(busy_ms, rel=1e-3)
    assert read("unscoped_pct", run) < 5
    # the guard's selects are the roots of the fusions that hold Adam's update
    assert read("guard_select_ms", run) > 5 * read("optimizer_ms", run)
    cell = cells.load_cell(REPO, "qwen7-c1-s2k")
    run.update(cell=cell, peak=cells.load_json(REPO, "benchmarks/peaks.json")["TPU v5 lite"])
    assert read("flash_ms", run) == pytest.approx(expected["flash_ms"])
    assert 0 < read("flash_roofline", run) < 100
