"""obs/tracing.py: the scopes in the compiled step, the host loop's spans, and
the one trace control a running training process is traced through."""

import glob
import os
import re
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from galvatron_tpu import HybridParallelConfig, LayerStrategy
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.cli.train import train
from galvatron_tpu.config.strategy import layer_runs, model_layer_kinds
from galvatron_tpu.models import base as M
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.granite_hybrid import granite_hybrid_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.qwen3_next import qwen3_next_config
from galvatron_tpu.obs import telemetry, tracing
from galvatron_tpu.runtime import construct_hybrid_parallel_model, get_optimizer_and_scheduler
from galvatron_tpu.runtime.optimizer import OptimizerArgs

ITERS = 12


def tiny_args(*extra):
    return initialize_galvatron(mode="train_dist", argv=[
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "4", "--num_layers", "2",
        "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "fp32",
        "--global_train_batch_size", "4", "--train_iters", str(ITERS),
        "--world_size", "1", *extra])


def hooked(args, on_step):
    args.fault_hooks = types.SimpleNamespace(
        on_step=on_step, wrap_step_fn=None, wrap_data_iter=None)
    return args


def run_with_sink(args):
    sink = telemetry.install(telemetry.MemorySink())
    try:
        summary = train(args)
    finally:
        telemetry.uninstall(sink)
    return summary, sink.events


@pytest.fixture
def profiler_log(monkeypatch):
    """`jax.profiler.start_trace` / `stop_trace` replaced by a log."""
    log = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: log.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: log.append(("stop",)))
    return log


# ----------------------------------------------------------------- control
@pytest.fixture(scope="module")
def requested_run(devices8, tmp_path_factory):
    """One tiny run started WITHOUT --xla_trace, traced twice through its
    control from `on_step` (the real profiler, on the CPU)."""
    tmp = tmp_path_factory.mktemp("requested")
    args = tiny_args()
    answers = {}

    def on_step(it):
        if it == 3:
            answers["first"] = args.trace_control.request(str(tmp / "a"), 3, 4)
            answers["busy"] = args.trace_control.request(str(tmp / "x"), 3, 4)
        if it == 8:  # the first has ended: steps 3 and 4 drained long ago
            answers["second"] = args.trace_control.request(str(tmp / "b"), 8, 9)

    summary, events = run_with_sink(hooked(args, on_step))
    return tmp, answers, summary, events


def test_a_request_from_on_step_traces_those_steps_and_a_second_follows(requested_run):
    tmp, answers, summary, events = requested_run
    assert answers == {"first": True, "busy": False, "second": True}
    assert len(summary["losses"]) == ITERS
    trace_events = [(e["action"], e.get("first_step"), e.get("last_step"), e.get("dir"))
                    for e in events if e["type"] == "trace"]
    assert trace_events == [
        ("start", 3, 4, str(tmp / "a")), ("stop", None, None, str(tmp / "a")),
        ("start", 8, 9, str(tmp / "b")), ("stop", None, None, str(tmp / "b"))]
    # the trace stops when its last step has drained: right after step 4's
    # (and step 9's) `step` event
    kinds = [(e["type"], e.get("iter", e.get("action"))) for e in events
             if e["type"] in ("trace", "step")]
    assert kinds[kinds.index(("trace", "stop")) - 1] == ("step", 4)
    for name in ("a", "b"):
        assert glob.glob(str(tmp / name / "plugins" / "profile" / "*" / "*.xplane.pb"))
    assert not os.path.exists(tmp / "x")


def test_the_hosts_spans_are_in_the_trace_with_the_step_number(requested_run):
    from jax.profiler import ProfileData

    tmp, _, _, _ = requested_run
    path = glob.glob(str(tmp / "a" / "plugins" / "profile" / "*" / "*.xplane.pb"))[0]
    spans, step_nums = set(), set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("gt/"):
                    spans.add(e.name)
                    if e.name == tracing.DISPATCH:
                        step_nums.add(dict(e.stats).get("step_num"))
    assert {tracing.NEXT_BATCH, tracing.DISPATCH, tracing.DRAIN, tracing.ON_STEP} <= spans
    assert {3, 4} <= step_nums  # the traced steps' dispatches, by number


def test_data_wait_ms_is_in_the_schema_and_in_a_run_with_a_sink(requested_run):
    _, _, _, events = requested_run
    assert "data_wait_ms" in telemetry.EVENT_SCHEMAS["step"][1]
    steps = [e for e in events if e["type"] == "step"]
    assert [e["iter"] for e in steps] == list(range(ITERS))
    assert all(e["data_wait_ms"] >= 0 for e in steps)
    assert all(e["dispatch_ms"] > 0 for e in steps)


# ------------------------------------------------------------- the launch
def jax_listeners():
    from jax._src import monitoring

    return (len(monitoring.get_event_listeners()), len(monitoring.get_event_duration_listeners()))


PHASES = [tracing.LAUNCH_PLAN, tracing.LAUNCH_BUILD, tracing.LAUNCH_INIT_STATE, tracing.LAUNCH_BUILD,
          tracing.LAUNCH_DATA, tracing.LAUNCH_DATA, tracing.COMPILE_TRACE, tracing.COMPILE_LOWER,
          tracing.COMPILE_KEY, tracing.COMPILE_LOAD, tracing.LAUNCH_FIRST_RUN]


def test_the_launchs_phases_are_consecutive_and_cover_the_start(requested_run):
    _, _, summary, events = requested_run
    ms = dict(summary["launch_ms"])
    total = ms.pop("total")
    # in the order they were first entered; no restore without --load
    assert list(ms) == list(dict.fromkeys(PHASES))
    assert all(v >= 0 for v in ms.values()) and total > 0
    assert sum(ms.values()) <= total and sum(ms.values()) == pytest.approx(total, rel=0.05)


def test_trace_ms_and_compile_ms_are_their_two_halves(requested_run):
    _, _, summary, events = requested_run
    ms = summary["launch_ms"]
    assert summary["trace_ms"] == pytest.approx(ms[tracing.COMPILE_TRACE] + ms[tracing.COMPILE_LOWER], abs=1e-6)
    assert summary["compile_ms"] == pytest.approx(ms[tracing.COMPILE_KEY] + ms[tracing.COMPILE_LOAD], abs=1e-6)
    compiled = next(e for e in events if e["type"] == "compile")
    assert (compiled["trace_ms"], compiled["compile_ms"]) == (summary["trace_ms"], summary["compile_ms"])
    assert ms[tracing.COMPILE_TRACE] > 0 and ms[tracing.COMPILE_LOWER] > 0 and ms[tracing.COMPILE_KEY] > 0


def test_one_launch_event_after_the_first_drain_says_what_the_summary_says(requested_run):
    _, _, summary, events = requested_run
    launched = [e for e in events if e["type"] == "launch"]
    assert len(launched) == 1
    telemetry.validate_event(launched[0])
    fields = {k: launched[0][k] for k in ("launch_ms", "launch_imports", "launch_jit")}
    assert fields == {k: summary[k] for k in fields}
    kinds = [(e["type"], e.get("iter")) for e in events if e["type"] in ("compile", "launch", "step")]
    # step 0 drains once step 2 has been sent: the compile, the launch, then step 0's own event
    assert kinds[:3] == [("compile", None), ("launch", None), ("step", 0)]
    assert summary["launch_imports"]["total_s"] > 0 and summary["launch_imports"]["modules"] > 0


def test_the_launchs_counters_saw_the_step_and_the_initialisers(requested_run):
    _, _, summary, _ = requested_run
    jit = summary["launch_jit"]
    traced = {row["fun_name"]: row for row in jit["top_traced"]}
    assert traced["train_step"]["count"] == 1 and len(traced) == 10
    assert traced["train_step"]["trace_s"] * 1e3 <= summary["launch_ms"][tracing.COMPILE_TRACE]
    assert jit["jit_traces"] >= sum(row["count"] for row in traced.values())
    assert jit["lowerings"] >= 1 and jit["lowering_s"] > 0  # the step, and what else no test before had run
    assert jit["cache_requests"] >= jit["cache_hits"] + jit["cache_misses"]
    assert jit["backend_compile_s"] >= 0 and jit["cache_retrieval_s"] >= 0


@pytest.fixture(scope="module")
def second_run(requested_run):
    """The same step a second time in the process (the memo holds its
    executable), and jax's listener lists before, inside and after."""
    args, seen = tiny_args(), {"before": jax_listeners()}

    def on_step(it):
        seen[it] = jax_listeners()
        args.train_iters = 5  # the same program (the schedule is built for ITERS), ended early

    summary = train(hooked(args, on_step))
    seen["after"] = jax_listeners()
    return summary, seen


def test_a_second_train_in_the_process_has_a_launch_of_its_own(requested_run, second_run):
    first, (second, _) = requested_run[2], second_run
    assert list(second["launch_ms"]) == list(first["launch_ms"])
    assert second["launch_ms"][tracing.COMPILE_LOAD] < 5.0  # the memo's hit: nothing to load
    assert second["launch_ms"] != first["launch_ms"] and second["launch_jit"] != first["launch_jit"]
    assert second["compile_ms"] == pytest.approx(
        second["launch_ms"][tracing.COMPILE_KEY] + second["launch_ms"][tracing.COMPILE_LOAD], abs=1e-6)
    # the step was traced again and compiled by nobody; the counters started at 0
    assert second["launch_jit"]["top_traced"][0]["fun_name"] == "train_step"
    assert second["launch_jit"]["top_traced"][0]["count"] == 1
    assert second["launch_jit"]["lowerings"] <= first["launch_jit"]["lowerings"]
    # the import is the process's one
    assert second["launch_imports"] == first["launch_imports"]


def test_the_listener_pair_stands_from_the_entry_to_the_first_drain_and_no_longer(second_run):
    _, seen = second_run
    # on_step(0..2) run before step 0 has drained, on_step(3) after it
    assert seen[0] == seen[1] == seen[2] == (seen["before"][0] + 1, seen["before"][1] + 1)
    assert seen[3] == seen[4] == seen["after"] == seen["before"]


def test_a_train_that_raises_before_its_first_drain_leaves_no_listener(devices8, monkeypatch):
    from galvatron_tpu.cli import train as T

    def refuse(args):
        assert jax_listeners() == (before[0] + 1, before[1] + 1)
        raise RuntimeError("no such model")

    before = jax_listeners()
    monkeypatch.setattr(T, "model_config_from_args", refuse)
    with pytest.raises(RuntimeError, match="no such model"):
        train(tiny_args())
    assert jax_listeners() == before


def test_nothing_requested_never_starts_the_profiler(devices8, profiler_log):
    args = tiny_args()
    summary = train(args)
    assert len(summary["losses"]) == ITERS and profiler_log == []
    assert isinstance(args.trace_control, tracing.TraceControl)
    assert args.trace_control.span("gt/anything") is tracing.OFF


def test_xla_trace_and_trace_steps_still_bracket_k_to_n(devices8, profiler_log, tmp_path):
    args = hooked(tiny_args("--xla_trace", str(tmp_path), "--trace_steps", "5:6"),
                  lambda it: profiler_log.append(("on_step", it)))
    _, events = run_with_sink(args)
    # started when step 5 is dispatched, stopped once step 6 has drained (two
    # steps stay in flight, so that is during iteration 8), and only once
    assert profiler_log.index(("start", str(tmp_path))) == profiler_log.index(("on_step", 5)) + 1
    assert profiler_log.index(("on_step", 8)) < profiler_log.index(("stop",)) \
        < profiler_log.index(("on_step", 9))
    assert sum(1 for e in profiler_log if e[0] in ("start", "stop")) == 2
    order = [(e["type"], e.get("iter", e.get("action"))) for e in events
             if e["type"] in ("trace", "step")]
    assert order[order.index(("trace", "stop")) - 1] == ("step", 6)
    assert order.index(("trace", "start")) == order.index(("step", 2)) + 1


def test_a_window_the_run_never_reaches_is_dropped_and_one_it_ends_in_is_closed(
        devices8, profiler_log, tmp_path):
    train(tiny_args("--xla_trace", str(tmp_path), "--trace_steps", "50:60"))
    assert profiler_log == []
    train(tiny_args("--xla_trace", str(tmp_path), "--trace_steps", "10:60"))
    assert profiler_log == [("start", str(tmp_path)), ("stop",)]


def test_a_backend_that_cannot_trace_says_so_and_carries_on(devices8, monkeypatch, tmp_path):
    def refuse(directory, **kw):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: pytest.fail("nothing to stop"))
    summary, events = run_with_sink(tiny_args("--xla_trace", str(tmp_path), "--trace_steps", "2:3"))
    assert len(summary["losses"]) == ITERS
    assert [(e["action"], e.get("error")) for e in events if e["type"] == "trace"] == [
        ("error", "no profiler here")]


def test_control_alone(profiler_log):
    c = tracing.TraceControl()
    assert c.span("x") is tracing.OFF and tracing.OFF.ms is None
    with pytest.raises(ValueError):
        c.request("/d", 5, 4)
    assert c.request("/d", 5, 6) and not c.request("/e", 9, 9)
    c.before_dispatch(4)
    assert profiler_log == [] and c.span("x") is tracing.OFF
    c.before_dispatch(7)  # steps 5 and 6 are gone: the next dispatch starts it
    assert profiler_log == [("start", "/d")]
    assert not c.request("/e", 9, 9)  # one trace at a time
    with c.span(tracing.DISPATCH, step_num=7) as span:
        pass
    assert span is not tracing.OFF and span.ms >= 0
    c.after_drain(5)
    assert profiler_log == [("start", "/d")]
    c.after_drain(7)
    assert profiler_log == [("start", "/d"), ("stop",)]
    assert c.request("/e", 9, 9)
    c.close()  # a pending request dies with the run
    c.before_dispatch(9)
    assert profiler_log == [("start", "/d"), ("stop",)] and c.span("x") is tracing.OFF
    # a sink alone turns the spans on, timed and not annotated
    sink = telemetry.install(telemetry.MemorySink())
    try:
        c.before_dispatch(10)
        with c.span(tracing.NEXT_BATCH) as span:
            pass
        assert span.ms >= 0 and span._annotation is None
    finally:
        telemetry.uninstall(sink)


# ------------------------------------------------------------------ scopes
FAMILIES = {
    "gpt": lambda: gpt_config("gpt-0.3b", num_layers=3, hidden_size=64, num_heads=4,
                              vocab_size=256, max_seq_len=32, compute_dtype=jnp.float32),
    "llama": lambda: llama_config("llama-0.3b", num_layers=3, hidden_size=64, num_heads=4,
                                  ffn_hidden=128, vocab_size=256, max_seq_len=32,
                                  compute_dtype=jnp.float32),
    "glm": lambda: glm4_moe_lite_config(
        "glm-4.7-flash", hidden_size=64, num_heads=4, num_kv_heads=4, ffn_hidden=32,
        dense_ffn_hidden=96, num_layers=3, vocab_size=256, max_seq_len=32, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, num_experts=8,
        experts_per_token=2, compute_dtype=jnp.float32),
    "qwen3next": lambda: qwen3_next_config(
        "qwen3-next-80b-a3b", hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
        ffn_hidden=32, num_layers=4, vocab_size=256, max_seq_len=64, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16, linear_value_head_dim=8, num_experts=16,
        experts_per_token=4, compute_dtype=jnp.float32, attn_impl="xla"),
    "granite": lambda: granite_hybrid_config(
        "granite-4.0-h-micro", hidden_size=64, num_heads=4, num_kv_heads=2, ffn_hidden=96,
        num_layers=10, vocab_size=256, max_seq_len=32, ssm_num_heads=4, ssm_head_dim=32,
        ssm_state_dim=16, compute_dtype=jnp.float32, attn_impl="xla"),
    "olmoe": lambda: olmoe_config(
        "olmoe-1b-7b", num_layers=3, hidden_size=64, num_heads=4, num_kv_heads=4, ffn_hidden=32,
        vocab_size=256, max_seq_len=32, num_experts=8, experts_per_token=2,
        compute_dtype=jnp.float32),
}
ROUTED = (tracing.MOE_ROUTER, tracing.MOE_DISPATCH, tracing.MOE_EXPERTS, tracing.MOE_COMBINE)
# the scopes nested in a family's layer runs: an op carries at most one of them
NESTED = {
    "gpt": {tracing.ATTN_PROJ, tracing.MLP},
    "llama": {tracing.ATTN_PROJ, tracing.MLP},
    # a dense layer, then routed ones beside a shared expert, latent attention in all
    "glm": {tracing.ATTN_LATENT, tracing.MLP, tracing.MOE_SHARED, *ROUTED},
    # three linear layers to a gated attention layer, every MLP half routed beside a shared expert
    "qwen3next": {tracing.ATTN_LINEAR, tracing.ATTN_DELTA, tracing.ATTN_PROJ, tracing.MOE_SHARED,
                  *ROUTED},
    # five Mamba-2 layers, an attention layer, four Mamba-2 layers, every MLP half dense
    "granite": {tracing.ATTN_SSM, tracing.ATTN_SSD, tracing.ATTN_PROJ, tracing.MLP},
    "olmoe": {tracing.ATTN_PROJ, *ROUTED},
}
NESTED_NAME = re.compile(r"gt\.(?:attn\.[a-z]+|mlp|moe\.[a-z]+)")
CASES = [(scan, family, guard, chunks) for chunks in (1, 2) for guard in (False, True)
         for family in ("gpt", "llama") for scan in (True, False)]
CASES += [(True, "glm", False, 1), (False, "glm", False, 1), (True, "qwen3next", False, 1),
          (True, "olmoe", True, 2), (True, "granite", False, 1)]


@pytest.mark.parametrize("scan,family,guard,chunks", CASES, ids=[
    "%s-%s-%s-%d" % ("scan" if scan else "no_scan_layers", family, "guard" if guard else "plain", chunks)
    for scan, family, guard, chunks in CASES])
def test_every_scope_is_in_the_compiled_steps_op_names(devices8, scan, family, guard, chunks):
    """All layers but one recomputed: in the dense families two layer
    runs, r0 scanned (or unrolled under --no_scan_layers) and r1 always
    unrolled; where the stack has several kinds of layer the runs split on
    the kind too."""
    cfg = FAMILIES[family]()
    layers = [LayerStrategy(checkpoint=1)] * (cfg.num_layers - 1) + [LayerStrategy()]
    if family == "qwen3next":  # its one attention layer is the last: recompute that
        layers.reverse()
    hp = HybridParallelConfig(world_size=1, pp=1, layers=layers, global_bsz=4, chunks=chunks,
                              scan_layers=scan)
    model = construct_hybrid_parallel_model(cfg, hp)
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=2, total_steps=20))

    def sds(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    abstract = model.abstract_params()
    shape = jax.ShapeDtypeStruct((4, cfg.max_seq_len), jnp.int32)
    batch = {k: jax.ShapeDtypeStruct(shape.shape, shape.dtype, sharding=NamedSharding(
        model.mesh, model._batch_spec_for(shape))) for k in ("tokens", "positions", "labels")}
    step_args = [sds(abstract, model.shardings()),
                 sds(jax.eval_shape(tx.init, abstract), model.opt_state_shardings(tx, abstract)),
                 batch] + ([jnp.float32(1e9)] if guard else [])
    hlo = model.make_train_step(tx, guard_anomalies=guard).lower(*step_args).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))

    def under(*parts):
        return [n for n in names if all(p in n for p in parts)]

    runs = layer_runs(hp, model_layer_kinds(cfg))
    assert len(runs) == {"gpt": 2, "llama": 2, "glm": 3, "qwen3next": 3, "olmoe": 2, "granite": 4}[family]
    scopes = [tracing.layers_scope(k) for k in range(len(runs))]
    for scope in (tracing.EMBED, *scopes, tracing.HEAD_LOSS):
        assert under("jvp(%s)" % scope), scope  # its forward
        assert under("transpose(jvp(%s))" % scope), scope  # its backward
    assert under(tracing.OPTIMIZER)
    assert bool(under(tracing.GUARD)) == guard
    assert under(tracing.GUARD, "select_n") or not guard
    assert bool(under(tracing.GRAD_ACCUM)) == (chunks > 1)
    for run, scope in zip(runs, scopes):
        # recomputation is named under the run it recomputes, and only there
        # (the delta rule's XLA form keeps a checkpoint of its own a head, the
        # state-space scan one a group of heads)
        rematted = [n for n in under("transpose(jvp(%s))" % scope, "rematted_computation")
                    if tracing.ATTN_DELTA not in n and tracing.ATTN_SSD not in n]
        assert bool(rematted) == bool(run.strategy.checkpoint), scope
        assert bool(under(scope + ")/while/body")) == (scan and run.length > 1), scope
    # the names are defined once, in obs/tracing.py
    assert {tracing.EMBED, scopes[0], tracing.HEAD_LOSS, tracing.OPTIMIZER, tracing.GUARD,
            tracing.GRAD_ACCUM, tracing.ATTN_PROJ, tracing.MLP} == {
                "gt.embed", "gt.layers.r0", "gt.head_loss", "gt.optimizer", "gt.guard",
                "gt.grad_accum", "gt.attn.proj", "gt.mlp"}
    # inside the runs: each part of the layer body under its name, forward,
    # recomputed and backward, and no op under two of them
    in_layers = [n for n in names if "gt.layers.r" in n]
    assert {m for n in in_layers for m in NESTED_NAME.findall(n)} == NESTED[family]
    assert all(len(set(NESTED_NAME.findall(n))) <= 1 for n in names)
    for nested in NESTED[family]:
        assert under("jvp(gt.layers.r", nested) and under("transpose(jvp(gt.layers.r", nested), nested
        # the combine has a written backward that reads no recomputed forward
        assert bool(under("rematted_computation", nested)) == (nested != tracing.MOE_COMBINE), nested
    # a mixer's scopes are the ones its table row states
    mixers = {cfg.layer_config(kind).mixer for kind in cfg.layer_kinds()}
    stated = {s for m in mixers for s in M.MIXERS[m].scopes}
    assert {s for s in NESTED[family] if s.startswith("gt.attn.")} <= stated
    if family == "glm":
        # the dense layer is run 0; a routed layer's shared expert calls
        # dense_mlp under gt.moe.shared and under no gt.mlp
        assert under(scopes[0], tracing.MLP) and not under(scopes[0], "gt.moe.")
        assert under(scopes[1], tracing.MOE_SHARED, "dot_general")
        assert not under(scopes[1], tracing.MLP) and not under(scopes[2], tracing.MLP)
