"""When a run imports `runtime/checkpoint` (cli/train.CheckpointModule): never
where it neither loads nor saves, inside `gt/launch/restore` under --load,
on a helper thread behind the first steps under --save; what the summary, the
`launch` event and `cli report` say of it. The import itself is held in a
process of its own, so that no other test's imports answer for it."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import types

import numpy as np
import pytest

from galvatron_tpu.cli import train as T
from galvatron_tpu.cli.arguments import initialize_galvatron
from galvatron_tpu.obs import report, telemetry, tracing

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TINY = ["--model_type", "llama", "--set_model_config_manually", "1", "--hidden_size", "64",
        "--num_attention_heads", "4", "--num_layers", "2", "--vocab_size", "128", "--seq_length", "32",
        "--mixed_precision", "fp32", "--global_train_batch_size", "4", "--train_iters", "5",
        "--world_size", "1"]
# what `import orbax.checkpoint` loads and the trainer's import must not: 12 of a warm start's 17 s
# of import on the chip's host (PERF.md section 5). `google` and `google.cloud` themselves are
# namespace packages a .pth file registers when the interpreter starts: they prove nothing
HEAVY = ("orbax", "tensorstore", "google.cloud.logging", "google.api_core")
WHY = ("%s loaded by %s: some module imports galvatron_tpu.runtime.checkpoint (or orbax) at module scope "
       "again. A run that neither loads nor saves then pays about 12 s of every start for nothing "
       "(setup_s in all the benchmark's cells). Import it inside the function that needs it; "
       "cli/train.py reaches it through CheckpointModule alone.")


# ------------------------------------------------- the import, in a process
@pytest.fixture(scope="module")
def fresh_process():
    """What `benchmarks/harness.import_program` imports, then a tiny `train`
    with neither --load nor --save: sys.modules' heavy entries after each."""
    code = """
        import json, sys
        import galvatron_tpu.cli.arguments, galvatron_tpu.cli.train
        import galvatron_tpu.obs.telemetry, galvatron_tpu.runtime.model_api
        heavy = lambda: sorted(m for m in sys.modules if any(m == p or m.startswith(p + ".") for p in %r))
        imported = heavy()
        from galvatron_tpu.cli.arguments import initialize_galvatron
        from galvatron_tpu.cli.train import train
        summary = train(initialize_galvatron(mode="train_dist", argv=%r))
        print(json.dumps({"imported": imported, "trained": heavy(),
                          "checkpoint_module": "galvatron_tpu.runtime.checkpoint" in sys.modules,
                          "launch_imports": summary["launch_imports"],
                          "checkpoint_import": summary["checkpoint_import"],
                          "threads": [t.name for t in __import__("threading").enumerate()]}))
        """ % (HEAVY, TINY)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)], cwd=REPO, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_importing_the_trainer_loads_no_checkpoint_library(fresh_process):
    assert not fresh_process["imported"], WHY % (fresh_process["imported"][:6], "the import of the program")


def test_a_run_that_neither_loads_nor_saves_never_imports_it(fresh_process):
    assert not fresh_process["trained"], WHY % (fresh_process["trained"][:6], "a train() without --load or --save")
    assert not fresh_process["checkpoint_module"]
    assert fresh_process["checkpoint_import"] == {"how": "never", "import_s": None, "waited_s": None}
    assert "gt-checkpoint-import" not in fresh_process["threads"]


def test_the_import_records_checkpoint_seconds_read_zero_and_not_none(fresh_process):
    record = fresh_process["launch_imports"]
    assert record["checkpoint_s"] == 0.0 and isinstance(record["checkpoint_s"], float)
    assert record["by_package_s"]["orbax"] == 0.0 and record["by_package_s"]["tensorstore"] == 0.0
    assert record["total_s"] > 0


# --------------------------------------------------- the runs that need it
def run(*extra, on_step=None, sink=None):
    args = initialize_galvatron(mode="train_dist", argv=TINY + list(extra))
    if on_step is not None:
        args.fault_hooks = types.SimpleNamespace(
            on_step=lambda it: on_step(args, it), wrap_step_fn=None, wrap_data_iter=None)
    if sink is None:
        return T.train(args)
    telemetry.install(sink)
    try:
        return T.train(args)
    finally:
        telemetry.uninstall(sink)


def slowed(monkeypatch, seconds, calls=None):
    """The seam: the import takes `seconds` longer, on whichever thread runs it."""
    real = T._import_checkpoint

    def slow_import():
        if calls is not None:
            calls.append(threading.current_thread().name)
        time.sleep(seconds)
        return real()

    monkeypatch.setattr(T, "_import_checkpoint", slow_import)


def end_after(steps):
    return lambda args, it: setattr(args, "train_iters", steps)  # the schedule stays the five steps'


@pytest.fixture(scope="module")
def whole_run(devices8):
    return run()


@pytest.fixture
def saved(devices8, tmp_path, monkeypatch):
    """Three steps of the five under --save with the import slowed by 0.4 s: the
    final save is its first use. -> (directory, summary, events, importing threads)."""
    calls = []
    slowed(monkeypatch, 0.4, calls)
    sink = telemetry.MemorySink()
    summary = run("--save", str(tmp_path / "ck"), on_step=end_after(3), sink=sink)
    return str(tmp_path / "ck"), summary, sink.events, calls


def test_a_run_that_saves_imports_on_the_helper_thread_behind_its_first_steps(saved):
    _, summary, events, calls = saved
    got = summary["checkpoint_import"]
    assert calls == ["gt-checkpoint-import"]  # once, and not on the loop's thread
    assert got["how"] == "background" and got["import_s"] >= 0.4
    # the save waited for what was left of it, not for all of it again
    assert 0 < got["waited_s"] <= got["import_s"]
    assert "gt-checkpoint-import" not in [t.name for t in threading.enumerate()]  # joined
    # the thread started after the first step was dispatched: the step's trace, lowering and compile were over
    launched = next(e for e in events if e["type"] == "launch")
    assert launched["checkpoint_import"]["how"] == "background"
    telemetry.validate_event(launched)
    ended = next(e for e in events if e["type"] == "run_end")
    assert ended["summary"]["checkpoint_import"] == got


def test_the_saved_checkpoint_reads_back_to_the_bit_inside_the_restore_phase(saved, whole_run, monkeypatch):
    directory, first, _, _ = saved
    calls = []
    slowed(monkeypatch, 0.5, calls)
    resumed = run("--load", directory)
    got = resumed["checkpoint_import"]
    assert got["how"] == "at_load" and got["import_s"] >= 0.5 and got["waited_s"] is None
    assert calls == ["MainThread"]
    # the import is the restore's: the phase holds it, and the launch's unspanned share does not rise
    ms = dict(resumed["launch_ms"])
    total = ms.pop("total")
    assert ms[tracing.LAUNCH_RESTORE] >= 500.0
    assert sum(ms.values()) == pytest.approx(total, rel=0.05)
    # steps 3 and 4 continue the saved run exactly as the run that never stopped
    assert first["losses"] == whole_run["losses"][:3]
    assert resumed["losses"] == whole_run["losses"][3:]
    assert np.isfinite(resumed["losses"]).all() and len(resumed["losses"]) == 2


def sigterm_at(step):
    def on_step(args, it):
        if it == step:
            os.kill(os.getpid(), signal.SIGTERM)
    return on_step


def test_an_interruption_during_the_import_still_commits_an_intact_emergency_checkpoint(
        whole_run, tmp_path, monkeypatch):
    from galvatron_tpu.runtime import checkpoint as ckpt

    slowed(monkeypatch, 1.0)
    directory = str(tmp_path / "ck")
    summary = run("--save", directory, "--emergency_save", "1", on_step=sigterm_at(2))
    got = summary["checkpoint_import"]
    assert summary["interrupted"] == "SIGTERM" and summary["resilience"]["emergency_saves"] == 1
    assert got["how"] == "background" and got["import_s"] >= 1.0
    assert 0 < got["waited_s"] <= got["import_s"]  # what was left of the import, not the import again
    assert ckpt.intact_iterations(directory) == [2] and ckpt.read_manifest(directory, 2)["iteration"] == 2
    # and it is the state two steps in: the run goes on from it as if nothing had happened
    assert summary["losses"] == whole_run["losses"][:2]
    assert run("--load", directory)["losses"] == whole_run["losses"][2:]


def test_an_interruption_before_the_first_step_imports_on_the_spot(devices8, tmp_path, monkeypatch):
    from galvatron_tpu.runtime import checkpoint as ckpt

    calls = []
    slowed(monkeypatch, 0.2, calls)
    directory = str(tmp_path / "ck")
    summary = run("--save", directory, "--emergency_save", "1", on_step=sigterm_at(0))
    got = summary["checkpoint_import"]
    assert got["how"] == "at_use" and got["import_s"] >= 0.2 and got["waited_s"] is None
    assert calls == ["MainThread"] and summary["losses"] == []
    assert ckpt.intact_iterations(directory) == [0]


def test_an_import_that_raises_on_the_thread_is_raised_at_the_first_use_with_its_traceback(
        devices8, tmp_path, monkeypatch):
    def gt_broken_import():
        raise ImportError("no orbax on this host")

    monkeypatch.setattr(T, "_import_checkpoint", gt_broken_import)
    with pytest.raises(ImportError, match="no orbax on this host") as caught:
        run("--save", str(tmp_path / "ck"), on_step=end_after(2))
    names = [entry.name for entry in caught.traceback]
    assert "save_now" in names and names[-1] == "gt_broken_import"  # the use, and where the thread was
    assert "gt-checkpoint-import" not in [t.name for t in threading.enumerate()]
    assert not os.path.exists(str(tmp_path / "ck"))


def test_a_second_use_does_not_import_again_and_a_finished_thread_costs_no_wait(monkeypatch):
    calls = []
    monkeypatch.setattr(T, "_import_checkpoint", lambda: calls.append(1) or json)
    module = T.CheckpointModule()
    assert module.fields() == {"how": "never", "import_s": None, "waited_s": None}
    module.join()  # no thread: nothing happens
    module.start()
    module.start()  # once
    module.join()
    assert module() is json and module("at_load") is json and calls == [1]
    assert module.how == "background" and module.import_s >= 0
    assert module.waited_s < 0.05  # the first use's wait; a later use does not overwrite it
    first = module.waited_s
    assert module() is json and module.waited_s == first
    on_the_spot = T.CheckpointModule()
    assert on_the_spot("at_load") is json and on_the_spot.how == "at_load"
    on_the_spot.start()  # imported already: no thread
    assert on_the_spot._thread is None and on_the_spot.how == "at_load" and calls == [1, 1]


# ------------------------------------------------------ the table's line
LAUNCH = {
    "launch_ms": {"gt/launch/plan": 40.0, "gt/launch/first_run": 960.0, "total": 1000.0},
    "launch_imports": {"total_s": 6.5, "modules": 1610, "checkpoint_s": 0.0,
                       "by_package_s": {"jax": 2.5, "other": 4.0}},
}


@pytest.mark.parametrize("at_drain,at_end,line", [
    ({"how": "never", "import_s": None, "waited_s": None}, None,
     "the run's own import of it: never, - s where it ran, the first use waited - s"),
    ({"how": "background", "import_s": None, "waited_s": None},
     {"how": "background", "import_s": 11.5, "waited_s": 0.25},
     "the run's own import of it: background, 11.5 s where it ran, the first use waited 0.25 s"),
    ({"how": "at_load", "import_s": 12.25, "waited_s": None}, None,
     "the run's own import of it: at_load, 12.25 s where it ran, the first use waited - s"),
], ids=["never", "background_read_from_the_summary", "at_load"])
def test_cli_report_prints_how_the_run_imported_it_under_the_modules_line(tmp_path, capsys, at_drain, at_end, line):
    path = tmp_path / "t.jsonl"
    with telemetry.JsonlSink(str(path)) as sink:
        sink.emit("launch", **LAUNCH, checkpoint_import=at_drain)
        if at_end is not None:
            sink.emit("run_end", summary={"checkpoint_import": at_end})
    assert report.run([str(path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    at = next(i for i, text in enumerate(lines) if "galvatron_tpu.runtime.checkpoint (orbax), inclusive: 0 s" in text)
    assert lines[at + 1].strip() == line
