"""The layer body by part: the readers of `gt.mlp` and `gt.attn.proj` and of
the layer runs' self time, on labels worked out by hand, on a step recorded on
the chip with the two scopes in it (where the parts must add up to the runs'
time), and on programs that name no such part."""

import json
import os

import pytest

from benchmarks import cells, harness, scopes, trace
from benchmarks.layer_metrics import layers_rest_ms
from galvatron_tpu.models import base as M
from galvatron_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FIXTURES = os.path.join(REPO, "benchmarks", "fixtures")
READERS = ("mlp_ms", "mlp_remat_ms", "mlp_roofline", "attn_proj_ms", "layers_rest_ms")
DENSE = ["qwen7-c1-s2k", "gpt67-c1-s2k", "qwen7-c4-tp2dp2", "qwen7-c1-s8k", "gpt67-c1-s2k-b2-noremat"]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
PHASES = {"fwd": scopes.LAYERS_FWD, "remat": scopes.LAYERS_REMAT, "bwd": scopes.LAYERS_BWD}


def read(name, run):
    return cells.load_module(REPO, "benchmarks/layer_metrics/%s.py" % name).read(run)


def label(instruction, op_name):
    """The label `trace.load` gives an op whose HLO instruction carries `op_name`."""
    return trace._label("%%%s = f32[8] fusion(...)" % instruction, {instruction: op_name})


def run_of(ops, cell=None):
    run = {"trace": {"ops_a_step": {k: [v, 1.0] for k, v in ops.items()}}, "peak": PEAK}
    if cell:
        run["cell"] = cells.load_cell(REPO, cell)
    return run


def recorded(name):
    return trace.reduce(trace.load_events(os.path.join(FIXTURES, name + ".trace_events.json.gz")),
                        harness.STEP_NAMES)


BODY = "/while/body/closed_call/"
FWD = "jit(train_step)/jvp(%s)" + BODY
BWD = "jit(train_step)/transpose(jvp(%s))" + BODY + "checkpoint/"
REMAT = BWD + "rematted_computation/"


def dense_ops(remat=True):
    """A scanned run of dense layers, an op a part and phase, labelled as the
    compiled step labels them (tests/obs/test_tracing.py holds the program to
    these names)."""
    r0 = tracing.layers_scope(0)
    fwd, bwd, again = FWD % r0, BWD % r0, REMAT % r0
    ops = {
        label("fusion.1", fwd + tracing.MLP + "/bsh,h...->bs.../dot_general"): 20e-3,
        label("fusion.2", fwd + tracing.MLP + "/dot_general"): 10e-3,
        label("fusion.3", bwd + tracing.MLP + "/dot_general"): 60e-3,
        label("fusion.5", fwd + tracing.ATTN_PROJ + "/dot_general"): 3e-3,
        label("fusion.7", bwd + tracing.ATTN_PROJ + "/transpose"): 6e-3,
        # the run's own: a norm, the scan's slice of the stacked parameters,
        # what wraps the kernel call
        label("fusion.8", fwd + "reduce_sum"): 0.5e-3,
        label("fusion.9", "jit(train_step)/transpose(jvp(%s))/while/body/dynamic_update_slice" % r0): 1.5e-3,
        label("broadcast.10", bwd + "jit(flash_attention)/broadcast_in_dim"): 0.25e-3,
        # the kernels, inside the run and under no nested scope
        label("flash_attention.11", fwd + "jit(flash_attention)/pallas_call"): 2e-3,
        label("flash_mha_bwd_dkv_1024_512.12", bwd + "jit(flash_attention)/pallas_call"): 4e-3,
        label("flash_mha_bwd_dq_1024_512.13", bwd + "jit(flash_attention)/pallas_call"): 3e-3,
        # outside the runs
        label("fusion.14", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): 30e-3,
        "copy-done.15": 0.75e-3,
    }
    if remat:
        ops.update({
            label("fusion.4", again + tracing.MLP + "/bsh,h...->bs.../dot_general"): 19e-3,
            label("fusion.6", again + tracing.ATTN_PROJ + "/dot_general"): 3.5e-3,
            label("flash_attention.16", again + "jit(flash_attention)/pallas_call"): 2e-3,
            label("fusion.17", again + "reduce_sum"): 0.5e-3,
        })
    return ops


# ------------------------------------------------------------ handmade labels
def test_the_readers_on_a_dense_run_forward_recomputed_and_backward():
    run = run_of(dense_ops())
    assert read("mlp_ms", run) == pytest.approx(109.0)
    assert read("mlp_remat_ms", run) == pytest.approx(19.0)
    assert read("attn_proj_ms", run) == pytest.approx(12.5)
    assert read("layers_rest_ms", run) == pytest.approx(0.5 + 1.5 + 0.25 + 0.5)
    parts = layers_rest_ms.parts(run)
    assert parts == {"flash": pytest.approx(11.0), "rest": pytest.approx(2.75),
                     tracing.MLP: pytest.approx(109.0), tracing.ATTN_PROJ: pytest.approx(12.5)}
    layers = sum(read("layers_%s_ms" % phase, run) for phase in PHASES)
    assert sum(parts.values()) == pytest.approx(layers, abs=1e-9)
    assert layers_rest_ms.parts(run, scopes.LAYERS_REMAT) == {
        "flash": pytest.approx(2.0), "rest": pytest.approx(0.5), tracing.MLP: pytest.approx(19.0),
        tracing.ATTN_PROJ: pytest.approx(3.5)}


def test_a_step_that_recomputes_nothing_reads_zero_not_nothing():
    run = run_of(dense_ops(remat=False))
    assert read("mlp_remat_ms", run) == 0.0 and read("layers_remat_ms", run) == 0.0
    assert read("mlp_ms", run) == pytest.approx(90.0)


def test_a_shared_expert_is_not_the_mlp_and_latent_attention_not_the_projections():
    """A GLM-like stack: run 0 a dense layer, run 1 routed layers beside a
    shared expert (`dense_mlp` under `gt.moe.shared`), latent attention in
    both, and an MTP module whose block runs outside the layer runs."""
    r0, r1 = tracing.layers_scope(0), tracing.layers_scope(1)
    ops = {
        label("fusion.1", "jit(train_step)/jvp(%s)/%s/dot_general" % (r0, tracing.MLP)): 5e-3,
        label("fusion.2", (REMAT % r0).replace(BODY, "/") + tracing.MLP + "/dot_general"): 3e-3,
        label("fusion.3", FWD % r1 + tracing.MOE_SHARED + "/bsh,h...->bs.../dot_general"): 4e-3,
        label("fusion.4", BWD % r1 + tracing.MOE_SHARED + "/dot_general"): 8e-3,
        label("fusion.5", FWD % r1 + tracing.MOE_EXPERTS + "/gmm_in/pallas_call"): 7e-3,
        label("fusion.6", FWD % r1 + tracing.ATTN_LATENT + "/dot_general"): 2e-3,
        label("fusion.7", FWD % r1 + "reduce_sum"): 0.5e-3,
        label("flash_attention.8", FWD % r1 + "pallas_call"): 6e-3,
        label("fusion.9", "jit(train_step)/jvp(%s)/%s/dot_general" % (tracing.MTP, tracing.ATTN_LATENT)): 1e-3,
        label("flash_attention.10", "jit(train_step)/jvp(%s)/pallas_call" % tracing.MTP): 1.5e-3,
        label("fusion.11", "jit(train_step)/jvp(%s)/add" % tracing.MTP): 0.25e-3,
    }
    run = run_of(ops)
    assert read("mlp_ms", run) == pytest.approx(8.0) and read("mlp_remat_ms", run) == pytest.approx(3.0)
    assert read("moe_shared_ms", run) == pytest.approx(12.0)
    assert read("attn_proj_ms", run) is None
    assert read("latent_attn_ms", run) == pytest.approx(3.0)  # the MTP block's too
    assert read("layers_rest_ms", run) == pytest.approx(0.5)
    parts = layers_rest_ms.parts(run)
    assert parts == {"flash": pytest.approx(6.0), "rest": pytest.approx(0.5), tracing.MLP: pytest.approx(8.0),
                     tracing.MOE_SHARED: pytest.approx(12.0), tracing.MOE_EXPERTS: pytest.approx(7.0),
                     tracing.ATTN_LATENT: pytest.approx(2.0)}  # the layers' share alone
    assert sum(parts.values()) == pytest.approx(scopes.ms_a_step(run, scopes.LAYERS), abs=1e-9)


@pytest.mark.parametrize("name", READERS)
def test_a_program_that_names_no_part_has_nothing_to_read_but_its_runs_self_time(name):
    """The parent of the PR that named the parts: every op of the body under
    `gt.layers.r<k>` alone. No trace, or no scopes at all: nothing."""
    r0 = tracing.layers_scope(0)
    parent = run_of({
        label("fusion.1", FWD % r0 + "dot_general"): 30e-3,
        label("fusion.2", REMAT % r0 + "dot_general"): 20e-3,
        label("flash_attention.3", FWD % r0 + "pallas_call"): 2e-3,
        label("fusion.4", "jit(train_step)/jvp(%s)/dot_general" % tracing.HEAD_LOSS): 9e-3,
    }, cell="qwen7-c1-s2k")
    bare = run_of({"fusion.1:jvp__/while/body/dot_general": 1e-3}, cell="qwen7-c1-s2k")
    assert read(name, {"trace": None, "peak": PEAK}) is None and read(name, bare) is None
    if name == "layers_rest_ms":
        assert read(name, parent) == pytest.approx(50.0)  # the whole body but the kernel
    else:
        assert read(name, parent) is None


def test_the_patterns_are_the_programs_names_and_no_longer_ones():
    from benchmarks.layer_metrics import attn_proj_ms, mlp_ms

    fwd = FWD % tracing.layers_scope(0)
    for pattern, name in ((mlp_ms.MLP, tracing.MLP), (attn_proj_ms.PROJ, tracing.ATTN_PROJ)):
        assert scopes.ms_a_step(run_of({label("fusion.1", fwd + name + "/mul"): 1e-3}), pattern) == 1.0
        for other in (name + "_in", name + ".in", name + "x"):
            assert not scopes.ms_a_step(run_of({label("fusion.1", fwd + other + "/mul"): 1e-3}), pattern)
        # a transform's wrapper around the name itself (the pipeline's vmapped stage body) is the name
        wrapped = "jit(train_step)/jvp()/while/body/closed_call/vmap(%s)/mul" % name
        assert scopes.ms_a_step(run_of({label("fusion.1", wrapped): 1e-3}), pattern) == 1.0
    # a nested scope is any of the program's but a layer run's own
    for name in (tracing.MLP, tracing.ATTN_PROJ, tracing.ATTN_LATENT, tracing.ATTN_LINEAR, tracing.ATTN_DELTA,
                 tracing.MOE_ROUTER, tracing.MOE_SHARED):
        run = run_of({label("fusion.1", fwd + name + "/mul"): 1e-3, label("fusion.2", fwd + "mul"): 2e-3})
        assert layers_rest_ms.parts(run) == {"flash": 0.0, "rest": pytest.approx(2.0), name: pytest.approx(1.0)}
    # every attention scope a mixer's table row states is one the parts would name
    stated = {s for mixer in M.MIXERS.values() for s in mixer.scopes}
    # a superset of PR 37's four: each later mixer states its own
    assert stated >= {tracing.ATTN_PROJ, tracing.ATTN_LATENT, tracing.ATTN_LINEAR, tracing.ATTN_DELTA}
    for name in stated:
        assert name.startswith("gt.attn.") and layers_rest_ms.parts(run_of({
            label("fusion.1", fwd + name + "/mul"): 1e-3}))[name] == pytest.approx(1.0)


# ------------------------------------------------------------ hand arithmetic
@pytest.mark.parametrize("cell,flops", [
    # 3 kernels of 3584 x 18944, 2 layers, 8192 tokens, forward + 2 x forward
    ("qwen7-c1-s2k", 3 * 8192 * 2 * (3 * 2 * 3584 * 18944)),
    # under tp2 x dp2: 4 layers, half the columns, the replica's 8 x 2048 / 2 tokens
    ("qwen7-c4-tp2dp2", 3 * 8192 * 4 * (3 * 2 * 3584 * 18944 // 2)),
    # GELU: 2 kernels of 4096 x 16384
    ("gpt67-c1-s2k", 3 * 8192 * 2 * (2 * 2 * 4096 * 16384)),
    ("gpt67-c1-s2k-b2-noremat", 3 * 4096 * 2 * (2 * 2 * 4096 * 16384)),
])
def test_mlp_roofline_by_hand(cell, flops):
    run = run_of(dense_ops(), cell=cell)
    took_s = 109.0e-3
    assert read("mlp_roofline", run) == pytest.approx(100 * flops / 197e12 / took_s, rel=1e-12)
    # ISSUE 37's arithmetic: forward + backward 101.6 ms of Qwen2.5-7B's, 67.0 of Cerebras-GPT's
    if cell.startswith("qwen7"):
        assert flops / 197e12 == pytest.approx(101.6e-3, rel=1e-3)
    elif cell == "gpt67-c1-s2k":
        assert flops / 197e12 == pytest.approx(67.0e-3, rel=1e-3)


# ------------------------------------------------- the step recorded on the chip
@pytest.fixture(scope="module")
def parts_run():
    run = {"trace": recorded("qwen7-c1-s2k-parts"), "peak": PEAK, "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    with open(os.path.join(FIXTURES, "qwen7-c1-s2k-parts.expected.json")) as f:
        return run, json.load(f)


def test_the_readers_on_the_step_recorded_on_the_chip(parts_run):
    """Device 0's events of the traced tail of one `--trace 2` run of
    qwen7-c1-s2k on a v5e with the two scopes in the program; the expected
    numbers are what that run reported."""
    run, expected = parts_run
    assert run["trace"]["steps"] == expected["steps"]
    assert run["trace"]["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    for name in READERS + ("layers_fwd_ms", "layers_remat_ms", "layers_bwd_ms", "flash_ms",
                           "flash_roofline", "unscoped_pct"):
        assert read(name, run) == pytest.approx(expected[name], rel=1e-9), name
    assert 0 < read("mlp_roofline", run) < 100
    # the MLP's up projection and activation run again, its down projection does not
    assert 0.6 < read("mlp_remat_ms", run) / (read("mlp_ms", run) - read("mlp_remat_ms", run)) * 3 < 0.7


def test_the_parts_add_up_to_the_layer_runs_on_the_chip(parts_run):
    """flash + every nested scope + the runs' self time = forward +
    recomputation + backward, to 1e-6 ms: no op is counted twice or dropped."""
    run, _ = parts_run
    parts = layers_rest_ms.parts(run)
    mixer = M.MIXERS["attention"].scopes
    assert set(parts) == {"flash", "rest", tracing.MLP, tracing.ATTN_PROJ}
    assert set(parts) - {"flash", "rest", tracing.MLP} <= set(mixer)
    layers = sum(read("layers_%s_ms" % phase, run) for phase in PHASES)
    assert sum(parts.values()) == pytest.approx(layers, abs=1e-6)
    # the readers take a scope wherever it is: jax hoists rope's cast of the
    # positions out of the scanned run, and its label keeps `gt.attn.proj` alone
    outside = read("attn_proj_ms", run) - parts[tracing.ATTN_PROJ]
    assert 0 < outside < 0.002
    assert read("flash_ms", run) + read("mlp_ms", run) + read("attn_proj_ms", run) + read(
        "layers_rest_ms", run) == pytest.approx(layers + outside, abs=1e-6)
    assert parts["flash"] == pytest.approx(read("flash_ms", run), abs=1e-9)  # all of it inside the runs
    assert parts["rest"] == read("layers_rest_ms", run)


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_a_phases_parts_add_up_to_the_phase(parts_run, phase):
    run, _ = parts_run
    parts = layers_rest_ms.parts(run, PHASES[phase])
    assert sum(parts.values()) == pytest.approx(read("layers_%s_ms" % phase, run), abs=1e-6)
    assert all(ms > 0 for ms in parts.values())
    if phase == "remat":
        assert parts[tracing.MLP] == pytest.approx(read("mlp_remat_ms", run), abs=1e-9)


def test_the_recording_of_the_parents_step_reads_as_one_number():
    """PR 24's recording: the scopes of the step's top level and nothing
    beneath a layer run."""
    run = {"trace": recorded("qwen7-c1-s2k-scoped"), "peak": PEAK, "cell": cells.load_cell(REPO, "qwen7-c1-s2k")}
    assert [read(name, run) for name in READERS[:4]] == [None] * 4
    layers = sum(read("layers_%s_ms" % phase, run) for phase in PHASES)
    assert read("layers_rest_ms", run) == pytest.approx(layers - read("flash_ms", run), abs=1e-6)
    assert set(layers_rest_ms.parts(run)) == {"flash", "rest"}


def test_the_script_prints_the_parts_by_phase(parts_run):
    layer_parts = cells.load_module(REPO, "scripts/layer_parts.py")
    lines = layer_parts.table(parts_run[0]).splitlines()
    assert lines[0].split() == ["part", "fwd", "remat", "bwd", "all"]
    assert [line.split()[0] for line in lines[1:-1]] == [
        "flash", "rest", tracing.ATTN_PROJ, tracing.MLP, "layers_*_ms"]
    assert abs(float(lines[-1].split()[-2])) < 1e-6


# ------------------------------------------------------------- the manifest
def test_the_manifest_lists_the_five_readers_last_and_each_in_its_cells():
    manifest = cells.load_json(REPO, cells.MANIFEST)
    # the entries of those names, in that order, wherever they stand (new entries go last)
    entries = [m for m in manifest["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in entries] == list(READERS)
    by_name = {m["name"]: m for m in entries}
    for m in entries:
        assert (m["source"], m["layer"], m["moves"]) == (
            "device_trace", "model: models/base.py", "tokens_per_s_chip")
        assert (m["unit"], m["better"]) == (("%", "higher") if m["name"] == "mlp_roofline" else ("ms", "lower"))
    # PR 37's cells first and in their order; a later cell is appended (the pipeline's: PR 53)
    assert by_name["mlp_ms"]["workloads"] == by_name["mlp_remat_ms"]["workloads"]
    for name, first in (("mlp_ms", DENSE + ["glm47f-c1-s8k"]), ("mlp_roofline", DENSE),
                        ("attn_proj_ms", DENSE + ["olmoe-c1-s4k", "qwen3next-c1-s8k"])):
        assert by_name[name]["workloads"][:len(first)] == first
        later = by_name[name]["workloads"][len(first):]
        # what came later runs the dense decoder too (the same configuration as a DENSE cell)
        assert {cells.load_cell(REPO, w).workload["config"] for w in later} <= {
            cells.load_cell(REPO, w).workload["config"] for w in DENSE}
    assert "workloads" not in by_name["layers_rest_ms"]  # every model has layer runs
    for workload in manifest["workloads"]:
        names = {m["name"] for m in cells.load_cell(REPO, workload["name"]).metrics("per_layer")}
        assert "layers_rest_ms" in names
        assert set(READERS) <= names or workload["name"] not in by_name["mlp_roofline"]["workloads"]
