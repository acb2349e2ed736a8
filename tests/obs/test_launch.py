"""obs/launch.py: the record of the program's import, a launch's phases and
jit counters, and the one reader of the kernel-form counters. The phases of a
real `train()` are held in tests/obs/test_tracing.py, on the run that is there
to share."""

import collections
import importlib
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from galvatron_tpu.obs import launch, report, telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def tools():
    """Who holds the two `sys.monitoring` ids the import record may take."""
    return [sys.monitoring.get_tool(tool) for tool in launch.TOOL_IDS]


def listeners():
    return (len(jax_monitoring.get_event_listeners()),
            len(jax_monitoring.get_event_duration_listeners()))


def python(code, *argv):
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code), *argv], cwd=REPO, capture_output=True, text=True,
        timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout


# ------------------------------------------------------------- the import
@pytest.fixture
def package(tmp_path, monkeypatch):
    """`gt_pkg` (a package that imports its `a`, which imports `gt_other`, and
    a module the stack's parts are read from) on sys.path; forgotten again."""
    root = tmp_path / "gt_pkg"
    root.mkdir()
    (root / "__init__.py").write_text("import time\ntime.sleep(0.02)\nfrom gt_pkg import a\n")
    (root / "a.py").write_text("import time\ntime.sleep(0.03)\nimport gt_other\n")
    (root / "late.py").write_text("X = 1\n")
    (tmp_path / "gt_other.py").write_text("import time\ntime.sleep(0.05)\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    yield
    for name in [n for n in sys.modules if n.split(".")[0] in ("gt_pkg", "gt_other")]:
        del sys.modules[name]


def test_self_seconds_by_package_inclusive_seconds_and_the_total(package, monkeypatch):
    monkeypatch.setattr(launch, "PACKAGES", ("gt_pkg",))
    monkeypatch.setattr(launch, "CHECKPOINT_MODULE", "gt_pkg.a")
    record = launch.ImportRecord()
    assert record.as_dict() is None
    finders, before = list(sys.meta_path), tools()
    record.install()
    record.install()  # once
    assert tools() == [launch.TOOL_NAME, None] and before == [None, None]
    try:
        import gt_pkg
        import json as already_there  # noqa: F401 -- a module that is loaded is not looked for
    finally:
        record.done()
    record.done()
    assert tools() == [None, None] and sys.meta_path == finders  # it never stood among the finders
    got = record.as_dict()
    assert got["modules"] == 3
    # each sleep is its own module's SELF time; the nested imports are not its
    # (a sleep is never short; the upper ends come from the sum: a nested import
    # counted twice would add 0.05 or 0.08 s to a total of 0.1)
    assert got["by_package_s"]["gt_pkg"] >= 0.05 and got["by_package_s"]["other"] >= 0.05
    assert got["total_s"] >= got["checkpoint_s"] >= 0.08  # gt_pkg.a with gt_other in it
    assert sum(got["by_package_s"].values()) == pytest.approx(got["total_s"], rel=0.05)
    # no spec and no loader was touched
    assert all(type(sys.modules[name].__spec__.loader).__name__ == "SourceFileLoader"
               for name in ("gt_pkg", "gt_pkg.a", "gt_other"))
    # taken out, it times nothing more
    import gt_pkg.late  # noqa: F401
    assert record.as_dict() == got


def test_a_module_that_closes_the_record_from_its_body_is_counted_up_to_there(package, tmp_path):
    record = launch.ImportRecord()
    launch_name = "gt_closer"
    (tmp_path / (launch_name + ".py")).write_text(
        "import time, sys\ntime.sleep(0.02)\nsys.gt_record.done()\ntime.sleep(0.05)\n")
    sys.gt_record = record
    record.install()
    t = time.perf_counter()
    try:
        importlib.import_module(launch_name)
    finally:
        wall = time.perf_counter() - t
        record.done()
        del sys.gt_record, sys.modules[launch_name]
    got = record.as_dict()
    assert got["modules"] == 1 and 0.02 <= got["total_s"] <= wall - 0.05  # the body's rest is not in it
    assert got["by_package_s"]["other"] == pytest.approx(got["total_s"], rel=0.05)
    assert sum(got["by_package_s"].values()) == pytest.approx(got["total_s"], rel=0.05)


def test_a_missing_module_and_another_thread_go_past_it(package):
    import threading

    record = launch.ImportRecord()
    record.install()
    try:
        with pytest.raises(ModuleNotFoundError, match="gt_pkg_that_is_not"):
            import gt_pkg_that_is_not  # noqa: F401
        assert record._stack == []  # the search that failed is closed (its frame unwound)
        thread = threading.Thread(target=importlib.import_module, args=("gt_other",))
        thread.start()
        thread.join(30)
        assert not thread.is_alive() and "gt_other" in sys.modules
    finally:
        record.done()
    assert record.modules == 0  # the other thread's import was not timed
    assert record.self_s[launch.OTHER] > 0 and record.as_dict()["total_s"] >= record.self_s[launch.OTHER]


def test_where_both_tool_ids_are_taken_nothing_is_recorded_and_nothing_breaks(package):
    for tool in launch.TOOL_IDS:
        sys.monitoring.use_tool_id(tool, "somebody else")
    record = launch.ImportRecord()
    try:
        record.install()
        import gt_other  # noqa: F401
        record.done()
    finally:
        for tool in launch.TOOL_IDS:
            sys.monitoring.free_tool_id(tool)
    assert record.as_dict() is None and record.modules == 0
    second = launch.ImportRecord()  # and with the first id taken it takes the second
    sys.monitoring.use_tool_id(launch.TOOL_IDS[0], "somebody else")
    try:
        second.install()
        assert tools() == ["somebody else", launch.TOOL_NAME]
        second.done()
        assert tools() == ["somebody else", None]
    finally:
        sys.monitoring.free_tool_id(launch.TOOL_IDS[0])


@pytest.fixture(scope="module")
def imported_trainer():
    """`import galvatron_tpu.cli.train` in a process of its own."""
    return json.loads(python("""
        import json, sys, time
        t = time.perf_counter()
        import galvatron_tpu
        from galvatron_tpu.obs import launch
        import builtins
        tools = lambda: [sys.monitoring.get_tool(tool) for tool in launch.TOOL_IDS]
        importer = builtins.__import__
        plain = tools()
        import galvatron_tpu.cli.arguments
        between = tools()
        import galvatron_tpu.cli.train
        print(json.dumps({
            "plain": plain, "between": between, "after": tools(),
            "untouched": builtins.__import__ is importer and not any(
                "galvatron_tpu" in str(getattr(type(f), "__module__", "")) + str(getattr(f, "__module__", ""))
                for f in sys.meta_path),
            "wall_s": time.perf_counter() - t, "record": launch.IMPORTS.as_dict()}))
        """).splitlines()[-1])


def test_the_record_opens_with_the_cli_package_and_is_closed_once_the_trainer_is_imported(imported_trainer):
    got = imported_trainer
    assert got["plain"] == [None, None]  # a plain `import galvatron_tpu` installs nothing
    assert got["between"] == [launch.TOOL_NAME, None] and got["after"] == [None, None]
    assert got["untouched"]  # nothing of the program among sys.meta_path's finders, builtins.__import__ as it was


def test_the_by_package_seconds_add_up_to_the_import(imported_trainer):
    record = imported_trainer["record"]
    assert set(record["by_package_s"]) == set(launch.PACKAGES) | {launch.OTHER}
    assert all(v >= 0 for v in record["by_package_s"].values())
    assert sum(record["by_package_s"].values()) == pytest.approx(record["total_s"], rel=0.05)
    assert record["total_s"] <= imported_trainer["wall_s"]
    assert record["modules"] > 500


def test_jaxs_import_is_seen_and_the_checkpoint_modules_is_no_longer_in_it(imported_trainer):
    record = imported_trainer["record"]
    assert record["by_package_s"]["jax"] > 0 and record["by_package_s"]["galvatron_tpu"] > 0
    # since PR 60 the trainer imports runtime/checkpoint when a run first loads or saves
    # (tests/obs/test_checkpoint_import.py): orbax and what it pulls are in no import of the program
    assert record["checkpoint_s"] == 0.0 and record["by_package_s"]["orbax"] == 0.0


def test_report_alone_imports_no_jax_and_another_subcommand_carries_no_monitoring(tmp_path):
    path = tmp_path / "t.jsonl"
    with telemetry.JsonlSink(str(path)) as sink:
        sink.emit("launch", **FIELDS)
    out = python("""
        import sys
        import galvatron_tpu.obs.report
        assert "jax" not in sys.modules
        import galvatron_tpu.cli.__main__ as cli
        from galvatron_tpu.obs import launch
        assert sys.monitoring.get_tool(launch.TOOL_IDS[0]) == launch.TOOL_NAME
        sys.argv = ["cli", "report", sys.argv[1]]
        assert cli.main() == 0
        assert sys.monitoring.get_tool(launch.TOOL_IDS[0]) is None and launch.IMPORTS.total_s is not None
        assert "jax" not in sys.modules
        """, str(path))
    assert "launch: 20 s to the first drained step" in out


# --------------------------------------------------------------- a launch
FIELDS = {
    "launch_ms": {"gt/launch/plan": 40.0, "gt/launch/build": 160.0, "gt/compile/trace": 9000.0,
                  "gt/launch/first_run": 10400.0, "total": 20000.0},
    "launch_imports": {"total_s": 20.5, "modules": 1930, "checkpoint_s": 9.25,
                       "by_package_s": {"jax": 0.25, "google": 8.9, "other": 11.35}},
    "launch_jit": {"jit_traces": 812, "top_traced": [{"fun_name": "train_step", "count": 1, "trace_s": 9.0}],
                   "lowerings": 31, "lowering_s": 4.4, "cache_requests": 31, "cache_hits": 30,
                   "cache_misses": 1, "cache_retrieval_s": 2.2, "backend_compile_s": 2.4},
}


def test_phases_are_recorded_in_order_and_do_not_overlap():
    before = listeners()
    control = tracing.TraceControl()
    started = launch.Launch(imports=launch.ImportRecord())
    assert listeners() == (before[0] + 1, before[1] + 1) and started.open
    with started.phase(control, "a") as span:
        with started.phase(control, "inside") as inner:  # timed, and not a phase
            pass
    assert span.ms >= inner.ms >= 0 and control.span("x") is tracing.OFF
    started.begin(control, "b")
    started.end()
    started.end()  # nothing open: nothing happens
    with started.phase(control, "a"):
        pass
    started.begin(control, "last")  # finish() ends what is open
    fields = started.finish()
    assert [name for name, _, _ in started.phases] == ["a", "b", "a", "last"]
    assert all(end >= start >= 0 for _, start, end in started.phases)
    assert all(b[1] >= a[2] for a, b in zip(started.phases, started.phases[1:]))
    ms = fields["launch_ms"]
    assert list(ms) == ["a", "b", "last", "total"]  # a name met twice is added up
    assert ms["a"] == pytest.approx(sum(e - s for n, s, e in started.phases if n == "a"))
    assert sum(v for k, v in ms.items() if k != "total") <= ms["total"]
    assert fields["launch_imports"] is None and fields["launch_jit"]["jit_traces"] == 0
    assert not started.open and listeners() == before
    # over, it still times and records nothing more
    with started.phase(control, "later") as span:
        pass
    assert span.ms >= 0 and started.fields() == fields
    started.close()  # again
    assert listeners() == before


def test_a_phase_is_an_annotation_while_a_trace_runs(monkeypatch):
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    control = tracing.TraceControl()
    started = launch.Launch(imports=launch.ImportRecord())
    try:
        with started.phase(control, tracing.LAUNCH_PLAN) as span:
            pass
        assert span._annotation is None
        control.request("/d", 0, 1)  # --xla_trace from step 0
        control.before_dispatch(0)
        with started.phase(control, tracing.COMPILE_TRACE) as span:
            pass
        assert isinstance(span._annotation, jax.profiler.TraceAnnotation)
    finally:
        started.close()
        control.close()
    assert [name for name, _, _ in started.phases] == [tracing.LAUNCH_PLAN, tracing.COMPILE_TRACE]


def test_the_phase_names_are_defined_once_in_tracing():
    names = {tracing.LAUNCH_PLAN, tracing.LAUNCH_BUILD, tracing.LAUNCH_INIT_STATE, tracing.LAUNCH_RESTORE,
             tracing.LAUNCH_DATA, tracing.COMPILE_TRACE, tracing.COMPILE_LOWER, tracing.COMPILE_KEY,
             tracing.COMPILE_LOAD, tracing.LAUNCH_FIRST_RUN}
    assert names == {"gt/launch/plan", "gt/launch/build", "gt/launch/init_state", "gt/launch/restore",
                     "gt/launch/data", "gt/compile/trace", "gt/compile/lower", "gt/compile/key",
                     "gt/compile/load", "gt/launch/first_run"}
    assert all(n.startswith(tracing.COMPILE + "/") for n in names if "compile" in n)
    with open(launch.__file__) as f:
        assert "gt/" not in f.read().split('"""', 2)[2]  # none spelled out beside them


def test_the_jit_counters_count_while_a_with_is_open_and_only_then():
    before = listeners()
    counters = launch.JitCounters()

    def gt_counted(x):
        return x * 2 + 1

    with counters:
        with counters:  # `_compile_step` inside a launch: the same pair
            assert listeners() == (before[0] + 1, before[1] + 1)
            jax.jit(gt_counted)(jnp.arange(3.0)).block_until_ready()
        assert listeners() == (before[0] + 1, before[1] + 1)
    assert listeners() == before
    got = counters.as_dict()
    assert got["jit_traces"] >= 1 and got["lowerings"] >= 1 and got["lowering_s"] > 0
    assert got["backend_compile_s"] > 0
    assert got["cache_requests"] == got["cache_hits"] + got["cache_misses"] or got["cache_requests"] >= got["cache_hits"]
    by_name = {row["fun_name"]: row for row in got["top_traced"]}
    assert by_name["gt_counted"]["count"] == 1 and by_name["gt_counted"]["trace_s"] > 0
    assert len(got["top_traced"]) <= launch.TOP_TRACED
    assert [r["trace_s"] for r in got["top_traced"]] == sorted((r["trace_s"] for r in got["top_traced"]), reverse=True)
    jax.jit(lambda x: x - 1)(jnp.arange(3.0)).block_until_ready()
    assert counters.as_dict() == got  # unregistered: nothing more is counted
    with counters:  # after the launch: a pair for this call alone
        jax.jit(lambda x: x - 2)(jnp.arange(3.0)).block_until_ready()
    assert counters.jit_traces > got["jit_traces"] and listeners() == before


# ----------------------------------------------------- the event, the table
def test_the_launch_event_is_in_the_schema_and_refuses_another_key():
    assert telemetry.EVENT_SCHEMAS["launch"] == (
        (), ("launch_ms", "launch_imports", "launch_jit", "checkpoint_import"))
    sink = telemetry.MemorySink()
    event = sink.emit("launch", **{**FIELDS, "launch_imports": None})
    telemetry.validate_event(event)
    assert "launch_imports" not in event  # a None is dropped, as everywhere
    with pytest.raises(telemetry.TelemetryError, match="phases"):
        sink.emit("launch", phases={})
    assert telemetry.EVENT_SCHEMAS["compile"][1][:5] == (
        "trace_ms", "compile_ms", "compiled_memory_mb", "xla_flops_per_step", "cache_hit")


def test_cli_report_prints_the_launch_table_above_the_compile_line(tmp_path, capsys):
    path = tmp_path / "t.jsonl"
    with telemetry.JsonlSink(str(path)) as sink:
        sink.emit("run_start", model="m", world_size=1)
        sink.emit("launch", **FIELDS)
        sink.emit("compile", trace_ms=9000.0, compile_ms=2600.0)
    assert report.run([str(path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    at = next(i for i, line in enumerate(lines) if line.startswith("launch: "))
    assert lines[at] == "launch: 20 s to the first drained step, 2 % of it outside the phases"
    table = lines[at + 1:next(i for i, line in enumerate(lines) if line.startswith("compile: "))]
    assert [line.split()[0] for line in table[:4]] == [
        "gt/launch/plan", "gt/launch/build", "gt/compile/trace", "gt/launch/first_run"]
    assert "20.5 s, 1930 modules" in table[4] and "other 11.35, google 8.90, jax 0.25" in table[4]
    assert "galvatron_tpu.runtime.checkpoint" in table[5] and "9.25 s" in table[5]
    assert "812 jit traces, 31 lowerings" in table[6] and "31 requests, 30 hits, 1 misses" in table[6]
    assert table[7].strip() == "most traced: train_step x1 9.00 s"
    # a stream without the event prints no table, and --json carries the event's fields
    analysis = report.analyze([{"v": 1, "t": 0.0, "seq": 0, "type": "launch", **FIELDS}])
    assert analysis["launch"] == FIELDS
    assert not any(line.startswith("launch: ") for line in report.render(report.analyze([])).splitlines())
