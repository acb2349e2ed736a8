"""The reduction from trace events to metrics: on hand-made events whose
answers can be worked out on paper, on the small trace recorded on the chip
(benchmarks/fixtures/), and stage 1 on a trace taken here on the CPU."""

import os

import pytest

from benchmarks import trace

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
STEP = ("train_step",)


def test_self_times_take_the_children_out_of_a_while():
    events = [("while.1", 0, 100), ("fusion.a", 10, 30), ("fusion.b", 50, 40), ("copy.c", 120, 5)]
    got = {label: self_ns for label, _, _, self_ns in trace.self_times(events)}
    assert got == {"while.1": 30, "fusion.a": 30, "fusion.b": 40, "copy.c": 5}


def test_union_merges_overlaps():
    assert trace.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]


def handmade():
    """Four runs of the step, 1000 ns each with 100 ns between; the first and
    last are dropped as possibly cut. In each: a while (900) around a matmul
    fusion (400), an all-reduce (200) and a kernel (250), then 50 ns of
    nothing before the while ends."""
    ops, modules = [], []
    for i in range(4):
        t = 1100 * i
        modules.append(("jit_train_step(123)", t, 1000))
        ops += [("while.7:body", t, 900), ("fusion.1:dot_general", t, 400),
                ("all-reduce.3:psum", t + 400, 200),
                ("custom-call.9:_flash_attention_kernel", t + 600, 250),
                ("copy.2:tail", t + 900, 100)]
    modules.append(("jit_other(5)", 50, 10))
    host = [("$train.py:1251 compiled_step", 0, 5000), ("$_api.py:3108 try_to_block", 1900, 400)]
    return {"devices": {0: {"ops": ops, "modules": modules}}, "host": host}


def test_reduce_on_handmade_events():
    r = trace.reduce(handmade(), STEP)
    assert r["steps"] == 2 and r["devices"] == 1
    assert r["window_s"] == pytest.approx(2100e-9)
    # a step: 400 + 200 + 250 + 100 busy; the while's own 50 ns count as idle
    assert r["busy_s"] == pytest.approx(2 * 950e-9)
    assert r["collective_s_a_step"] == pytest.approx(200e-9)
    assert r["collective_exposed_s_a_step"] == pytest.approx(200e-9)  # one op at a time
    assert trace.ops_matching(r, r"_flash_attention_kernel") == (pytest.approx(250e-9), 1.0)
    assert r["device_ops"][0] == ["fusion.1:dot_general", pytest.approx(800e-9)]
    # the gap between the two steps falls under the host's block_until_ready
    assert r["idle_gaps"][0] == ["$_api.py:3108 try_to_block", pytest.approx(100e-9)]


def test_a_collective_under_compute_is_not_exposed():
    t = handmade()
    t["devices"][0]["ops"] += [("fusion.5:overlapping", 1100 * i + 500, 150) for i in range(4)]
    r = trace.reduce(t, STEP)
    assert r["collective_s_a_step"] == pytest.approx(200e-9)
    assert r["collective_exposed_s_a_step"] == pytest.approx(100e-9)


def quadratic_exposed(coll, other):
    """The pass as it was before the sweep: every collective interval against
    every other interval."""
    exposed = 0
    for a, b in coll:
        exposed += (b - a) - sum(max(0, min(b, d) - max(a, c)) for c, d in other)
    return exposed


def swept_exposed(coll, other):
    return sum(b - a for a, b in coll) - trace.overlap(coll, other)


def test_the_sweep_gives_the_double_loops_nanoseconds_on_thousands_of_intervals():
    import random

    rng = random.Random(26)

    def intervals(n):
        out, t = [], 0
        for _ in range(n):
            t += rng.randrange(0, 60)  # 0: one starts where the last began
            out.append((t, t + rng.randrange(1, 40)))
        return trace.union(out)

    coll, other = intervals(3000), intervals(4000)
    assert len(coll) > 1000 and len(other) > 1000
    assert swept_exposed(coll, other) == quadratic_exposed(coll, other)
    assert 0 < trace.overlap(coll, other) == trace.overlap(other, coll) < sum(b - a for a, b in coll)
    assert trace.overlap(coll, coll) == sum(b - a for a, b in coll)
    assert trace.overlap(coll, []) == trace.overlap([], other) == 0
    # touching ends share nothing
    assert trace.overlap([(0, 5), (9, 12)], [(5, 9), (12, 20)]) == 0


@pytest.mark.parametrize("fixture,stand_in", [
    ("qwen7-c1-s2k", r"flash|copy|select"), ("qwen7-c1-s2k-scoped", r"flash|copy|select"),
    ("qwen7-c4-tp2dp2", None)], ids=["one_chip", "one_chip_scoped", "four_chips"])
def test_the_sweep_gives_the_double_loops_nanoseconds_on_the_recorded_traces(
        fixture, stand_in, monkeypatch):
    """The old pass and the new on the same events, and `reduce` through the
    new. One chip has no collective, so there the kernels, the copies and the
    update's selects stand in for them; the four-chip trace has its own."""
    import re

    events = trace.load_events(os.path.join(
        REPO, "benchmarks", "fixtures", fixture + ".trace_events.json.gz"))
    if stand_in is not None:
        monkeypatch.setattr(trace, "COLLECTIVE", re.compile(stand_in))
    names = ("plain_step", "train_step")
    r = trace.reduce(events, names)
    lines = events["devices"]["0"]
    steps = trace.steps_of([tuple(e) for e in lines["modules"]], names)
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    leaves = [(label, start, dur) for label, start, dur, self_ns in trace.self_times(
        trace._clip([tuple(e) for e in lines["ops"]], lo, hi)) if self_ns == dur]
    coll = trace.union([(s, s + d) for label, s, d in leaves if trace.COLLECTIVE.search(label)])
    other = trace.union([(s, s + d) for label, s, d in leaves
                         if not trace.COLLECTIVE.search(label)])
    assert len(coll) > 50 and len(other) > 50
    old = quadratic_exposed(coll, other)
    assert swept_exposed(coll, other) == old > 0
    assert r["collective_exposed_s_a_step"] == old / 1e9 / len(steps)


def test_reduction_of_the_four_chip_trace_recorded_on_the_chip():
    """Device 0's events of five traced steps of qwen7-c4-tp2dp2 on a v5e
    2x2; the expected numbers are what that run itself reported."""
    import json

    from benchmarks import cells

    fixtures = os.path.join(REPO, "benchmarks", "fixtures")
    expected = json.load(open(os.path.join(fixtures, "qwen7-c4-tp2dp2.expected.json")))
    r = trace.reduce(trace.load_events(
        os.path.join(fixtures, "qwen7-c4-tp2dp2.trace_events.json.gz")), ("plain_step", "train_step"))
    assert r["steps"] == expected["steps"] == 5
    run = {"trace": r, "cell": cells.load_cell(REPO, "qwen7-c4-tp2dp2"),
           "peak": cells.load_json(REPO, "benchmarks/peaks.json")["TPU v5 lite"]}
    for name, value in expected.items():
        if name not in ("recorded", "steps"):
            reader = cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name)
            assert reader.read(run) == pytest.approx(value, rel=1e-9), name
    # nothing runs beside a collective in this step: all of it is exposed
    assert expected["collective_exposed_ms"] == expected["collective_ms"] > 90


def test_no_whole_step_reduces_to_nothing():
    assert trace.reduce({"devices": {0: {"ops": [], "modules": []}}, "host": []}, STEP) is None
    assert trace.reduce({"devices": {}, "host": []}, STEP) is None


def test_events_round_trip_through_the_fixture_format(tmp_path):
    path = str(tmp_path / "events.json.gz")
    trace.save_events(handmade(), path, STEP)
    again = trace.reduce(trace.load_events(path), STEP)
    direct = trace.reduce(handmade(), STEP)
    assert again == direct


def test_stage_one_reads_a_trace_taken_here(tmp_path):
    """`load` on a real `.xplane.pb`: a CPU trace has no TPU plane, and its
    host line is the thread that stopped the trace."""
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    jax.block_until_ready(jax.jit(lambda x: x @ x)(jnp.ones((64, 64))))
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    assert path is not None
    loaded = trace.load(path)
    assert loaded["devices"] == {}
    assert trace.reduce(loaded, STEP) is None


HLO = '''
%fused_computation.3 (param_0: bf16[8,16]) -> bf16[8,16] {
  %param_0 = bf16[8,16]{1,0} parameter(0)
  %dot.1 = bf16[8,16]{1,0} dot(%param_0, %param_0), metadata={op_name="jit(plain_step)/jvp()/while/body/dot_general" stack_frame_id=3}
  ROOT %convert.2 = bf16[8,16]{1,0} convert(%dot.1), metadata={op_name="jit(plain_step)/jvp()/while/body/convert_element_type"}
}

%bitcast_fusion.5.clone (bitcast_input.1: bf16[8,16]) -> bf16[16,8] {
  %bitcast_input.1 = bf16[8,16]{1,0} parameter(0)
  ROOT %bitcast.9 = bf16[16,8]{0,1} bitcast(%bitcast_input.1)
}

ENTRY %main.1 (p: bf16[8,16]) -> bf16[16,8] {
  %p = bf16[8,16]{1,0} parameter(0)
  %fusion.412 = bf16[8,16]{1,0} fusion(%p), kind=kOutput, calls=%fused_computation.3, metadata={}
  %flash_attention.16 = bf16[8,16]{1,0} custom-call(%fusion.412), custom_call_target="tpu_custom_call", metadata={op_name="jit(plain_step)/jvp()/jit(flash_attention)/pallas_call"}
  ROOT %fusion.387 = bf16[16,8]{0,1} fusion(%flash_attention.16), kind=kLoop, calls=%bitcast_fusion.5.clone
}
'''


def test_labels_put_the_jax_op_beside_the_instruction():
    origins = trace.origins_from_hlo(HLO)
    # a fusion without a name of its own takes its computation's matmul
    assert trace._label("%fusion.412 = bf16[8,16]{1,0} fusion(...)", origins) == \
        "fusion.412:jvp_/while/body/dot_general"
    assert trace._label("%flash_attention.16 = bf16[8,16] custom-call(...)", origins) == \
        "flash_attention.16:jvp_/jit_flash_attention_/pallas_call"
    assert trace._label("%fusion.387 = bf16[16,8] fusion(...)", origins) == \
        "fusion.387:bitcast_fusion.5.clone"
    assert trace._label("%copy-start.3 = (bf16[4]) copy-start(...)", origins) == "copy-start.3"


def test_reduction_of_the_trace_recorded_on_the_chip():
    """The fixture is device 0's events of four traced steps of qwen7-c1-s2k
    on a v5e; the expected numbers are what that run itself reported."""
    import json

    from benchmarks import cells

    fixtures = os.path.join(REPO, "benchmarks", "fixtures")
    expected = json.load(open(os.path.join(fixtures, "qwen7-c1-s2k.expected.json")))
    r = trace.reduce(trace.load_events(
        os.path.join(fixtures, "qwen7-c1-s2k.trace_events.json.gz")), ("plain_step", "train_step"))
    assert r["steps"] == expected["steps"] == 4
    assert r["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(expected["window_s"], rel=1e-9)
    assert r["step_s"] == pytest.approx(expected["step_s"])
    assert r["device_ops"][:3] == [[n, pytest.approx(s)] for n, s in expected["device_ops"]]
    assert r["idle_gaps"][0] == [expected["idle_gaps"][0][0],
                                 pytest.approx(expected["idle_gaps"][0][1])]
    assert r["collective_s_a_step"] == 0.0  # one chip
    # the per-layer readers on the same reduction
    cell = cells.load_cell(REPO, "qwen7-c1-s2k")
    run = {"trace": r, "cell": cell, "peak": cells.load_json(REPO, "benchmarks/peaks.json")[
        "TPU v5 lite"]}
    read = lambda name: cells.load_module(  # noqa: E731
        REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)
    assert read("flash_ms") == pytest.approx(expected["flash_ms"])
    assert read("flash_roofline") == pytest.approx(expected["flash_roofline"])
    assert 0 < read("flash_roofline") < 100
    assert read("device_idle_pct") == pytest.approx(expected["device_idle_pct"])
    # full recomputation: two forwards a layer, one of each backward kernel
    calls = {k: c for k, (_, c) in cells.load_module(
        REPO, "benchmarks/layer_metrics/flash_ms.py").per_kernel(run).items()}
    assert calls == {"fwd": 4.0, "dkv": 2.0, "dq": 2.0}


# ------------------------------------------- an instruction over several lines
def test_an_instruction_is_read_whole_so_a_kernel_given_metadata_falls_under_its_scope():
    """A `pallas_call` given `metadata=` (jax's splash kernels) prints its HLO
    instruction over three lines and its `op_name` stands on the last. The
    fixture is the compiled text of such a kernel's forward and backward for a
    described v5e (PR 53; the Mosaic bodies elided), under `gt.layers.r0` and
    `gt.attn.core`."""
    path = os.path.join(REPO, "benchmarks", "fixtures", "splash_mha-instructions.hlo.txt")
    text = open(path).read()
    first = [line for line in text.splitlines() if line.startswith("  %splash_mha_fwd_residuals.1 = ")]
    assert len(first) == 1 and "op_name" not in first[0]  # what the parent searched, and found nothing in
    origins = trace.origins_from_hlo(text)
    scope = "jit(f)/%s(gt.layers.r0%s/gt.attn.core/jit(_splash_attention)/%s/%s/pallas_call"
    for name, wrap, close in (("splash_mha_fwd_residuals", "jvp", ")"),
                              ("splash_mha_dkv_no_residuals", "transpose(jvp", "))"),
                              ("splash_mha_dq_no_residuals", "transpose(jvp", "))")):
        assert origins[name + ".1"] == scope % (wrap, close, name, name)
    # the instructions of one line beside them read as before
    assert origins["iota.1"].endswith("gt.attn.core/jit(_splash_attention)/broadcast_in_dim")
    assert "tuple.5" not in origins
    label = trace._label("%splash_mha_fwd_residuals.1 = (f32[128,128]{1,0}) custom-call(...)", origins)
    assert label == ("splash_mha_fwd_residuals.1:jvp_gt.layers.r0_/gt.attn.core/jit__splash_attention_/"
                     "splash_mha_fwd_residuals/splash_mha_fwd_residuals/pallas_call")
    # joining changes nothing where every instruction stands on one line
    one_line = "\n".join(line for line in trace._whole_instructions(text))
    assert trace.origins_from_hlo(one_line) == origins
    assert trace._whole_instructions(one_line) == one_line.splitlines()


def test_a_fusion_still_takes_its_computations_principal_op_after_the_join():
    text = "\n".join([
        "%fused_computation.1 (p: f32[8]) -> f32[8] {",
        '  %mul.1 = f32[8] multiply(%p, %p), metadata={op_name="jit(f)/gt.mlp/mul"}',
        '  ROOT %dot.2 = f32[8] dot(%mul.1, %p), frontend_attributes={a={',
        '"k":"v"',
        '}}, metadata={op_name="jit(f)/gt.mlp/dot_general"}',
        "}",
        "",
        "ENTRY %main (x: f32[8]) -> f32[8] {",
        "  %fusion.3 = f32[8] fusion(%x), kind=kOutput, calls=%fused_computation.1",
        "}"])
    origins = trace.origins_from_hlo(text)
    assert origins["dot.2"] == origins["fusion.3"] == "jit(f)/gt.mlp/dot_general"
