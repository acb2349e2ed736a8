"""`param_gather_ms`, the reader of the scope ZeRO-2's compute copy runs under
(`gt.param_gather`, galvatron_tpu/obs/tracing.py): its pattern against the
program's name, on labels as the compiled step carries them, and on programs
with nothing to read, which leave the metric out."""

import json
import os
import re

import pytest

from benchmarks import cells, harness, scopes, trace
from galvatron_tpu.obs import tracing
from tests.benchmarks.test_scopes import FIXTURES, REPO, handmade, label, read

READER = cells.load_module(REPO, "benchmarks/layer_metrics/param_gather_ms.py")


def test_the_pattern_is_the_programs_scope_and_a_scope_of_the_yardstick():
    assert re.fullmatch(READER.PARAM_GATHER, tracing.PARAM_GATHER)
    assert re.fullmatch(scopes.SCOPE, tracing.PARAM_GATHER)  # so `unscoped_pct` leaves it out


def test_it_reads_the_casts_and_the_gathers_of_the_copy():
    run = handmade()
    ops = run["trace"]["ops_a_step"]
    name = "jit(train_step)/%s/convert_element_type" % tracing.PARAM_GATHER
    ops[label("all-gather.40", name)] = [12e-3, 1.0]
    ops[label("convert_bitcast_fusion.41", name)] = [3e-3, 1.0]
    ops["all-gather.42"] = [5e-3, 1.0]  # the table's float32 gather after the update: no scope
    assert read("param_gather_ms", run) == pytest.approx(15.0)
    assert read("unscoped_pct", run) == pytest.approx(100 * (0.75 + 5.0) / (64.0 + 20.0))


@pytest.mark.parametrize("run", [
    {"trace": None}, {"trace": {"ops_a_step": {"fusion.1:jvp__/dot_general": [1e-3, 1.0]}}}, handmade()],
    ids=["untraced", "a_program_without_scopes", "scopes_but_no_copy"])
def test_nothing_to_read_leaves_the_metric_out(run):
    assert read("param_gather_ms", run) is None


def test_the_one_chip_recording_has_no_copy_and_the_manifest_lists_the_four_chip_cell():
    r = trace.reduce(trace.load_events(
        os.path.join(FIXTURES, "qwen7-c1-s2k-scoped.trace_events.json.gz")), harness.STEP_NAMES)
    assert read("param_gather_ms", {"trace": r}) is None
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    # by name, wherever it stands: new entries go last, so it is the last no more
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == "param_gather_ms"]
    # the four-chip cells that run ZeRO-2 over a dp axis (a pipeline's pp2 x tp2 has none)
    zero2_over_dp = [w["name"] for w in manifest["workloads"] if w["chips"] == 4 and
                     "zero2" in cells.load_cell(REPO, w["name"]).traffic["train_flags"]]
    assert entry["workloads"] == zero2_over_dp == ["qwen7-c4-tp2dp2"]
    assert entry["layer"] in {m["layer"] for m in manifest["per_layer"] if m is not entry}
