"""The twelve readers under `setup_s` that read where a start went from the
trainer's summary (`launch_ms`, `launch_imports`, `launch_jit`:
galvatron_tpu/obs/launch.py), each on a hand-written summary, and their entries
in the manifest. Every assertion is by NAME: none by a position in `per_layer`
nor by its length."""

import json
import os

import pytest

from benchmarks import cells

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SUMMARY = {
    "trace_ms": 13000.0, "compile_ms": 2600.0,
    "launch_ms": {
        "gt/launch/plan": 40.0, "gt/launch/build": 160.0, "gt/launch/init_state": 1500.0,
        "gt/launch/data": 300.0, "gt/compile/trace": 9000.0, "gt/compile/lower": 4000.0,
        "gt/compile/key": 600.0, "gt/compile/load": 2000.0, "gt/launch/first_run": 2000.0,
        "total": 20000.0},
    "launch_imports": {
        "total_s": 20.5, "modules": 1930, "checkpoint_s": 9.25,
        "by_package_s": {"jax": 0.0, "google": 8.9, "orbax": 1.1, "other": 10.5}},
    "launch_jit": {
        "jit_traces": 812, "top_traced": [{"fun_name": "train_step", "count": 1, "trace_s": 9.0}],
        "lowerings": 31, "lowering_s": 4.4, "cache_requests": 31, "cache_hits": 31,
        "cache_misses": 0, "cache_retrieval_s": 2.2, "backend_compile_s": 2.4},
}
# metric -> (its value on SUMMARY, the key of the summary it reads, layer, source, unit)
ENTRY = "entry: cli/train.py host loop"
RUNTIME = "runtime: runtime/model_api.py make_train_step"
COMPILE = "compile: compiled_step, utils/compile_cache.py"
READERS = {
    "launch_import_s": (20.5, "launch_imports", ENTRY, "program_span", "s"),
    "launch_import_ckpt_s": (9.25, "launch_imports", ENTRY, "program_span", "s"),
    "launch_build_s": (0.2, "launch_ms", RUNTIME, "program_span", "s"),
    "launch_init_state_s": (1.5, "launch_ms", RUNTIME, "program_span", "s"),
    "step_trace_s": (9.0, "launch_ms", COMPILE, "program_span", "s"),
    "step_lower_s": (4.0, "launch_ms", COMPILE, "program_span", "s"),
    "step_key_s": (0.6, "launch_ms", COMPILE, "program_span", "s"),
    "step_load_s": (2.0, "launch_ms", COMPILE, "program_span", "s"),
    "first_run_s": (2.0, "launch_ms", ENTRY, "program_span", "s"),
    "launch_jit_traces": (812.0, "launch_jit", COMPILE, "program_counter", "count"),
    "launch_cache_misses": (0.0, "launch_jit", COMPILE, "program_counter", "count"),
    # the phases add up to 19600 of 20000 ms
    "launch_unspanned_pct": (2.0, "launch_ms", ENTRY, "program_span", "%"),
}


def read(name, summary):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read({"summary": summary})


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_its_value_off_the_summary(name):
    value = read(name, SUMMARY)
    assert float(value) == pytest.approx(READERS[name][0], rel=1e-12)


@pytest.mark.parametrize("name", sorted(READERS))
def test_an_older_summary_leaves_the_metric_out_and_does_not_raise(name):
    older = {k: v for k, v in SUMMARY.items() if not k.startswith("launch_")}
    assert read(name, older) is None
    # and with the one key it reads gone, or there and empty, as well
    assert read(name, {k: v for k, v in SUMMARY.items() if k != READERS[name][1]}) is None
    assert read(name, {**SUMMARY, READERS[name][1]: None}) is None


def test_the_split_readers_add_up_to_the_lumps_they_split():
    assert read("step_trace_s", SUMMARY) + read("step_lower_s", SUMMARY) == pytest.approx(
        SUMMARY["trace_ms"] / 1e3)
    assert read("step_key_s", SUMMARY) + read("step_load_s", SUMMARY) == pytest.approx(
        SUMMARY["compile_ms"] / 1e3)
    assert read("first_step_s", SUMMARY) == pytest.approx(15.6)  # the lump stays what it was


def test_a_phase_missing_from_the_launch_is_no_zero():
    ms = {k: v for k, v in SUMMARY["launch_ms"].items() if k != "gt/launch/plan"}
    assert read("launch_build_s", {**SUMMARY, "launch_ms": ms}) is None
    assert read("launch_unspanned_pct", {**SUMMARY, "launch_ms": {"gt/launch/plan": 1.0}}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_manifest_holds_the_metric_by_name_under_setup_s_in_every_cell(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entries = [m for m in manifest["per_layer"] if m["name"] == name]
    assert len(entries) == 1
    _, _, layer, source, unit = READERS[name]
    assert entries[0] == {"name": name, "unit": unit, "better": "lower", "source": source,
                          "layer": layer, "moves": "setup_s"}  # and no `workloads`: every cell reports setup_s
    assert os.path.exists(os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py"))


def test_every_cell_lists_the_twelve_and_the_three_that_stay():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for workload in manifest["workloads"]:
        names = {m["name"] for m in cells.load_cell(REPO, workload["name"]).metrics("per_layer")}
        assert set(READERS) | {"launch_serial_s", "first_step_s", "step_cache_hit"} <= names, workload["name"]
