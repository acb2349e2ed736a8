"""The six `collective_*` readers (benchmarks/layer_metrics/, joined to the
trace by benchmarks/census.py) over the program's census of its step's
collectives (galvatron_tpu/obs/compiled.step_collectives): on a hand-made run,
on the fixture pairs recorded from PR 68's chip runs of the two four-chip cells
(`<cell>-census.trace_events.json.gz`, `<cell>-census.census.json`), through
the harness on the CPU, and against the manifest."""

import importlib.util
import json
import os

import pytest

from benchmarks import cells, census, harness, trace

from .test_cell_from_files import CPU_PEAK, REPO, root  # noqa: F401 -- the tiny cell
from .test_trace_in_run import recorded_reduction

FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
READERS = ("collective_fused_ms", "collective_dp_ms", "collective_tp_ms", "collective_pp_ms",
           "collective_wire_gib", "collective_hidden_pct")
LAYER = "layouts: parallel/mesh.py, spec.py"
BOTH = ["qwen7-c4-tp2dp2", "qwen7-c4-pp2tp2"]
# the cells a reader finds something to read in (ISSUE 68)
LISTS = {"collective_fused_ms": BOTH, "collective_dp_ms": BOTH[:1], "collective_tp_ms": BOTH,
         "collective_pp_ms": BOTH[1:], "collective_wire_gib": BOTH, "collective_hidden_pct": BOTH}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def row(instruction, kind="all-gather", form="plain", role="tp", wire=0.0, **more):
    return dict({"instruction": instruction, "kind": kind, "form": form, "group": 2, "axes": ["m1"], "role": role,
                 "operand_bytes": wire, "wire_bytes": wire, "scope": None, "phase": None}, **more)


def handmade():
    """A step of eight collectives as the program counts them and as the trace
    names them: seconds and calls a step of device 0."""
    rows = [
        row("all-gather.1", role="dp", wire=2.0 ** 30),            # ZeRO's parameter gather
        row("fusion.2", "reduce-scatter", "fused", "dp", 2.0 ** 28),   # a layer's gradients: a loop's body, 4 calls
        row("all-gather.3", wire=2.0 ** 27),                      # a tp gather the trace names
        row("all_to_all.4", "all-to-all", wire=2.0 ** 26),        # a shard_map's: no pattern of the trace's
        row("async-collective-start.5", form="start"),            # its bytes ride the matmul below
        row("fusion.6", form="hidden", wire=2.0 ** 29),
        row("async-collective-done.5", form="done"),
        row("all-reduce.7", "all-reduce", role="dp+tp", wire=64.0),
        row("collective-permute-start.8", "collective-permute", "start", "pp", 2.0 ** 25),
        row("collective-permute-done.8", "collective-permute", "done", "pp"),
        row("all-reduce.9", "all-reduce", role="tp", wire=8.0),  # a branch device 0 never ran
    ]
    ops = {
        "all-gather.1:gt.param_gather/convert_element_type": [10e-3, 1.0],
        "fusion.2:transpose_jvp_gt.layers.r0__/while/body/dot_general": [8e-3, 4.0],
        "all-gather.3:jvp_gt.layers.r0_/while/body/gt.mlp/dot_general": [3e-3, 4.0],
        "all_to_all.4:jvp_gt.embed_/shard_map/all_to_all": [2e-3, 1.0],
        "async-collective-start.5:jvp_gt.layers.r0_/while/body/gt.attn.proj/dot_general": [0.25e-3, 4.0],
        "fusion.6:jvp_gt.layers.r0_/while/body/gt.attn.proj/dot_general": [20e-3, 4.0],
        "async-collective-done.5:jvp_gt.layers.r0_/while/body/gt.attn.proj/dot_general": [0.5e-3, 4.0],
        "all-reduce.7:gt.optimizer/reduce_sum": [0.0625e-3, 1.0],
        "collective-permute-start.8:jvp_/while/body/closed_call/concatenate": [0.125e-3, 5.0],
        "collective-permute-done.8:jvp_/while/body/closed_call/concatenate": [1e-3, 5.0],
        "fusion.10:jvp_gt.layers.r0_/while/body/gt.mlp/dot_general": [50e-3, 4.0],  # a matmul: no row
    }
    named = sum(s for label, (s, _) in ops.items() if trace.COLLECTIVE.search(label))
    return {"summary": {"step_collectives": {"rows": rows, "census_ms": 1.0}}, "events": [],
            "trace": {"ops_a_step": ops, "collective_s_a_step": named}}


def test_the_readers_on_a_handmade_step():
    run = handmade()
    assert read("collective_dp_ms", run) == pytest.approx(18.0)
    assert read("collective_tp_ms", run) == pytest.approx(3.0 + 2.0 + 0.25 + 0.5)  # the hidden matmul's 20 left out
    assert read("collective_pp_ms", run) == pytest.approx(1.125)
    # what the trace's names miss: the fused sum, the shard_map's all-to-all, the async pair
    assert read("collective_fused_ms", run) == pytest.approx(8.0 + 2.0 + 0.25 + 0.5)
    sent = 2.0 ** 30 + 4 * 2.0 ** 28 + 4 * 2.0 ** 27 + 2.0 ** 26 + 4 * 2.0 ** 29 + 64.0 + 5 * 2.0 ** 25
    assert read("collective_wire_gib", run) == pytest.approx(sent / 2.0 ** 30)
    assert read("collective_hidden_pct", run) == pytest.approx(100 * 4 * 2.0 ** 29 / sent)
    # by role, the unions among them, all that is not hidden: what the trace names and what it does not
    by_role = census.ms_by_role(run)
    assert set(by_role) == {"dp", "tp", "pp", "dp+tp"}
    assert sum(by_role.values()) == pytest.approx(
        run["trace"]["collective_s_a_step"] * 1e3 + read("collective_fused_ms", run))
    assert [c["instruction"] for c, _, _, calls in census.timed(run) if not calls] == ["all-reduce.9"]


@pytest.mark.parametrize("run", [
    {"summary": {}, "events": [], "trace": {"ops_a_step": {"all-gather.1": [1e-3, 1.0]}}},
    {"summary": {"losses": [1.0]}, "events": [{"type": "compile", "trace_ms": 1.0}], "trace": None},
    dict(handmade(), trace=None)],
    ids=["one_chip_or_the_parent", "no_field_in_any_event", "untraced"])
def test_nothing_to_read_leaves_all_six_out(run):
    assert [read(name, run) for name in READERS] == [None] * 6


def test_the_compile_events_rows_serve_where_the_summary_has_none():
    run = handmade()
    rows = run["summary"].pop("step_collectives")["rows"]
    run["events"] = [{"type": "step", "iter": 0}, {"type": "compile", "collectives": rows}]
    assert read("collective_dp_ms", run) == pytest.approx(18.0)
    # and a step that sends nothing has no share to report
    run["events"][1]["collectives"] = [row("all-gather.1")]
    assert read("collective_wire_gib", run) == 0.0 and read("collective_hidden_pct", run) is None


def recorded(cell):
    """A fixture pair as the harness hands it to a reader."""
    reduced = trace.reduce(trace.load_events(os.path.join(FIXTURES, cell + "-census.trace_events.json.gz")),
                           harness.STEP_NAMES)
    counted = json.load(open(os.path.join(FIXTURES, cell + "-census.census.json")))
    return {"summary": {"step_collectives": counted}, "events": [], "trace": reduced}


@pytest.mark.parametrize("cell", BOTH)
def test_the_recorded_four_chip_steps_add_up_by_role(cell):
    """On the chip's own trace and the program's own census of the same run
    (PR 68): every row is an op of device 0's trace or a branch it did not
    take; the non-hidden rows, by role, add up to `collective_ms` and
    `collective_fused_ms`, so every op the trace names a collective is a row;
    no row is `other` or without axes; and each reader reads what PERF.md
    says of the run the pair was recorded from."""
    run = recorded(cell)
    rows = run["summary"]["step_collectives"]["rows"]
    joined = census.timed(run)
    assert sum(1 for _, _, _, calls in joined if calls) >= 0.95 * len(rows)
    counted = {r["instruction"] for r in rows}
    assert not [label for label in run["trace"]["ops_a_step"]
                if trace.COLLECTIVE.search(label) and label.split(":")[0] not in counted]
    by_role = census.ms_by_role(run)
    fused = read("collective_fused_ms", run)
    assert sum(by_role.values()) == pytest.approx(run["trace"]["collective_s_a_step"] * 1e3 + fused, abs=1e-6)
    for role in ("dp", "tp", "pp"):
        assert read("collective_%s_ms" % role, run) == pytest.approx(by_role.get(role, 0.0))
    assert not [r for r in rows if r["role"] == "other" or not r["axes"]]
    assert set(by_role) <= {"dp", "tp", "pp", "dp+tp", "tp+pp"}
    rest = sum(ms for role, ms in by_role.items() if "+" in role)
    assert rest < 0.1  # the scalar sums of the loss and of the gradient norm
    assert 0 < read("collective_hidden_pct", run) < 100 and read("collective_wire_gib", run) > 1.0
    expected = json.load(open(os.path.join(FIXTURES, cell + "-census.expected.json")))
    for name in READERS:
        assert read(name, run) == pytest.approx(expected[name], rel=1e-6), name
    unnamed = {}  # what `collective_fused_ms` holds, by (form, role)
    for counted_row, label, seconds, _ in joined:
        if counted_row["form"] != "hidden" and not census.named(label):
            key = (counted_row["form"], counted_row["role"])
            unnamed[key] = unnamed.get(key, 0.0) + seconds * 1e3
    if cell == "qwen7-c4-tp2dp2":
        # ISSUE 68 expected 18 to 26 (PERF.md's 21.7 = the dp sums 12.8 + 7.48 and two tp sums 1.47); the census
        # found the layers' seven fused tp reduce-scatters beside them, 3.0 ms each in five of them: 38.5
        assert unnamed[("fused", "dp")] == pytest.approx(20.56, abs=0.01)
        assert unnamed[("fused", "tp")] == pytest.approx(16.55, abs=0.01)
        assert unnamed[("plain", "dp")] == pytest.approx(1.32, abs=0.01)  # the shard_map's two all_to_all.N
        assert fused == pytest.approx(38.50, abs=0.01) and read("collective_pp_ms", run) == 0.0
        assert read("collective_dp_ms", run) > read("collective_tp_ms", run) > fused * 0.5
    else:
        # the fused tp reduce-scatters of the tick body (ten of 1.7 to 1.9 ms) and dx's 1.59; `psum_invariant.7`
        assert unnamed[("fused", "tp")] == pytest.approx(18.96, abs=0.01)
        assert unnamed[("plain", "tp")] == pytest.approx(1.99, abs=0.01) and fused == pytest.approx(21.81, abs=0.01)
        assert read("collective_dp_ms", run) == 0.0 and read("collective_pp_ms", run) > 2.5
        # the stage-to-stage sends: six permute pairs, all over the pp axis
        permutes = [r for r in rows if r["kind"] == "collective-permute"]
        assert len(permutes) == 12 and {(r["role"], tuple(r["axes"])) for r in permutes} == {("pp", ("pp",))}


def test_the_old_recordings_belong_to_programs_that_counted_nothing():
    for name in ("qwen7-c4-tp2dp2", "qwen7-c4-pp2tp2"):
        reduced = trace.reduce(trace.load_events(os.path.join(FIXTURES, name + ".trace_events.json.gz")),
                               harness.STEP_NAMES)
        assert [read(reader, {"summary": {}, "events": [], "trace": reduced}) for reader in READERS] == [None] * 6


def test_the_manifest_lists_the_six_or_has_no_room():
    """Each reader is a file the harness would load by the metric's name. Its
    entry (the layouts' layer, `tokens_per_s_chip`, the cells it reads in) is
    there, or `per_layer` is full: the driver refuses a manifest of more than
    128 per-layer metrics, and PR 68 found 128 (PERF.md section 7: a
    `benchmark` PR that merges the per-family copies of one reader makes room
    and appends the six)."""
    manifest = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert LAYER in {m["layer"] for m in manifest["per_layer"]}
    for name in READERS:
        assert callable(cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read)
        if name not in listed:
            assert len(manifest["per_layer"]) >= 128, name
            continue
        entry = listed[name]
        assert (entry["layer"], entry["moves"], entry["workloads"]) == (LAYER, "tokens_per_s_chip", LISTS[name])
        assert entry["unit"] == {"collective_wire_gib": "GiB", "collective_hidden_pct": "%"}.get(name, "ms")
        assert entry["source"] == ("program_counter" if entry["unit"] != "ms" else "device_trace")


def test_the_harness_hands_the_census_to_the_readers_and_the_script_writes_it(root, tmp_path, monkeypatch):  # noqa: F811
    """`--trace 2` on the CPU at a tiny size with the six entries appended to a
    temporary manifest: on four devices (tp 2 x dp 2) the summary that reaches
    the readers holds the census though the sink went in after the compile,
    and the line holds the readings (the trace is a one-chip recording, so
    no row finds its op: 0 ms, and no share of nothing); on one device the
    six are left out. `scripts/collective_census.py`'s seam writes
    `census.json` and its line beside the run."""
    manifest = json.load(open(os.path.join(root, "BENCHMARK.json")))
    manifest["per_layer"] += [
        {"name": name, "unit": "ms", "better": "lower", "source": "device_trace", "layer": LAYER,
         "moves": "tokens_per_s_chip", "workloads": ["tiny-cell"]} for name in READERS]
    json.dump(manifest, open(os.path.join(root, "BENCHMARK.json"), "w"))
    # a step program of this test's own: one that another test of this process has compiled (or will) comes out
    # of the trainer's memo and counts as no compilation there
    traffic = os.path.join(root, "benchmarks", "traffic", "b2-s32.json")
    mix = json.load(open(traffic))
    mix["seq_length"] = 96
    json.dump(mix, open(traffic, "w"))
    cell = cells.load_cell(root, "tiny-cell")
    monkeypatch.setattr(harness, "read_trace", lambda trace_dir, hlo, out_dir: recorded_reduction())
    monkeypatch.setattr(harness, "TAIL_SECONDS", 0.3)
    spec = importlib.util.spec_from_file_location(
        "collective_census", os.path.join(REPO, "scripts", "collective_census.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    said = []
    monkeypatch.setattr(harness, "per_layer_values", harness.per_layer_values)  # (put back after the seam)
    script.install(harness, said.extend)
    result = harness.run_cell(cell, seed=2**31 + 80, seconds=0.3, traced=2, peaks=CPU_PEAK, t0=0.0,
                              out_dir=str(tmp_path), say=lambda **o: None)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    line = json.loads(said[-1].split(" ", 1)[1])
    if cell.chips == 1:
        assert not set(READERS) & set(metrics) and line["rows"] == 0 and line["census_ms"] is None
        assert not os.path.exists(os.path.join(str(tmp_path), "census.json")) and len(said) == 1
        return
    assert {name: metrics[name] for name in READERS[:5]} == dict.fromkeys(READERS[:5], 0.0)
    assert "collective_hidden_pct" not in metrics
    written = json.load(open(os.path.join(str(tmp_path), "census.json")))
    assert len(written["rows"]) == line["rows"] > 20 and written["census_ms"] == line["census_ms"] > 0
    assert {"dp", "tp"} <= {r["role"] for r in written["rows"]} <= {"dp", "tp", "dp+tp"} and not line["other_or_no_axes"]
    assert {r["form"] for r in written["rows"]} == {"plain"}  # XLA:CPU fuses and hides none
    assert said[0].startswith("collectives of the compiled step: %d instructions" % line["rows"])
    assert said[-2].startswith("  hidden: 0 of ")
