"""CLI contract: `python -m galvatron_tpu.cli lint` exit codes and output
formats. In-process through `cli.lint.run` (fast); one subprocess test pins
the real `python -m` wiring."""

import json
import os
import subprocess
import sys

import pytest

from galvatron_tpu.cli.lint import run

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")


def fx(rel):
    return os.path.join(FIXTURES, rel)


def test_valid_corpus_exits_zero(capsys):
    assert run([fx("valid/uniform_dp8.json"), fx("valid/hybrid_pp2_1f1b.json"),
                fx("valid/ring_cp_uniform.json"), "--world_size", "8"]) == 0
    assert "0 error(s)" in capsys.readouterr().out


def test_broken_corpus_exits_one(capsys):
    import glob

    broken = sorted(glob.glob(fx("broken/*.json")))
    assert broken
    assert run(broken + ["--world_size", "8"]) == 1
    out = capsys.readouterr().out
    assert "GLS001" in out and "GLS010" in out


def test_json_output_parses(capsys):
    assert run([fx("broken/gls005_bad_enum.json"), "--world_size", "8",
                "--json"]) == 1
    payload = json.loads(capsys.readouterr().out)
    assert payload["summary"]["errors"] >= 1
    assert "GLS005" in payload["summary"]["codes"]


def test_model_aware_flags_require_model(capsys):
    # without a model config the heads/tp mismatch is invisible...
    assert run([fx("broken/gls007_heads_tp.json"), "--world_size", "8"]) == 0
    capsys.readouterr()
    # ...and a model family whose heads don't divide tp=4 trips GLS007
    # (gpt-0.3b has 16 heads -> passes; bert default has 12 -> 12 % 4 == 0;
    # use swin? keep it simple: llama-7b has 32 heads -> passes). The
    # per-model check is covered in test_strategy_lint with a crafted
    # config; here we only pin that --model_type resolves and lints.
    assert run([fx("broken/gls007_heads_tp.json"), "--world_size", "8",
                "--model_type", "gpt"]) == 0
    capsys.readouterr()


def test_code_fixtures_through_cli(capsys):
    assert run([os.path.join(FIXTURES, "code", "glc001_bad.py")]) == 1
    out = capsys.readouterr().out
    assert "GLC001" in out
    assert run([os.path.join(FIXTURES, "code", "glc001_good.py")]) == 0
    capsys.readouterr()


def test_warnings_pass_unless_strict(capsys):
    args = [fx("warn/gls103_inert_flags.json"), "--world_size", "8"]
    assert run(args) == 0
    capsys.readouterr()
    assert run(args + ["--strict"]) == 1
    capsys.readouterr()


def test_serve_mode_flag(capsys):
    """--serve turns on the GLS014 feasibility layer: the shipped serve
    strategy passes, a pp=2 layout with serve knobs is refused."""
    assert run([fx("valid/serve_tp2.json"), "--world_size", "8",
                "--serve"]) == 0
    capsys.readouterr()
    assert run([fx("broken/gls014_serve_pp.json"), "--world_size", "8",
                "--serve"]) == 1
    assert "GLS014" in capsys.readouterr().out


def test_explain_prints_code_table(capsys):
    assert run(["--explain"]) == 0
    out = capsys.readouterr().out
    for code in ("GLS001", "GLS014", "GLS101", "GLC001", "GLC004",
                 "GLC007", "GLT001", "GLT003", "GLT101", "WA004", "WA006"):
        assert code in out


def test_did_you_mean_covers_new_families():
    from galvatron_tpu.analysis import diagnostics as D

    assert "GLT001" in D.did_you_mean("GLT0001", D.CODES)
    assert "WA004" in D.did_you_mean("WA04", D.CODES)


def test_trace_flag_on_fixture(capsys, devices8):
    """--trace over a shipped strategy: exits 0, GLT family in the report
    path, audit table printed in human mode."""
    assert run([fx("valid/uniform_dp8.json"), "--world_size", "8",
                "--trace", "--model_type", "gpt", "--hidden_size", "64",
                "--num_heads", "4", "--seq_length", "64",
                "--vocab_size", "128"]) == 0
    out = capsys.readouterr().out
    assert "trace audit" in out and "traced collectives" in out


def test_trace_and_compat_json_additive(capsys, devices8):
    """--json stays ONE parseable document; --trace/--compat add keys
    without touching the schema existing consumers read."""
    assert run(["--trace", "--compat", "--json", "--world_size", "8",
                "--model_type", "gpt", "--hidden_size", "64",
                "--num_heads", "4", "--seq_length", "64",
                "--vocab_size", "128"]) == 0
    payload = json.loads(capsys.readouterr().out)
    # the original schema is intact...
    assert payload["version"] == 1
    assert set(payload["summary"]) == {"errors", "warnings", "codes"}
    assert payload["summary"]["errors"] == 0
    # ...and the new families ride along additively
    assert [r["code"] for r in payload["compat_inventory"]] == [
        "WA004", "WA005", "WA006"]
    assert all(r["pinning_tests"] for r in payload["compat_inventory"])
    assert payload["trace_audit"][0]["target"].startswith("<uniform")


def test_compat_human_output_lists_workarounds(capsys):
    assert run(["--compat"]) == 0
    out = capsys.readouterr().out
    assert "jax workaround inventory" in out
    for code in ("WA004", "WA006"):
        assert code in out


def test_usage_error_exits_two(capsys):
    assert run([]) == 2


def test_module_entrypoint_subprocess():
    """One real `python -m galvatron_tpu.cli lint` run: non-zero on the
    broken corpus, zero on the shipped valid corpus."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    bad = subprocess.run(
        [sys.executable, "-m", "galvatron_tpu.cli", "lint",
         fx("broken/gls002_tp_overflow.json"), "--world_size", "8", "--json"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    )
    assert bad.returncode == 1, bad.stderr
    assert json.loads(bad.stdout)["summary"]["errors"] >= 1


def test_train_driver_lints_before_tracing(devices8):
    """The cli/train.py hook: a strategy whose heads don't divide tp is
    refused by the linter before any compile (DiagnosticError, not an XLA
    error)."""
    from galvatron_tpu.analysis.diagnostics import DiagnosticError
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train

    args = initialize_galvatron(mode="train", argv=[
        "--model_type", "gpt", "--set_model_config_manually", "1",
        "--hidden_size", "96", "--num_attention_heads", "6",
        "--num_layers", "2", "--seq_length", "64", "--vocab_size", "128",
        "--global_tp_deg", "4", "--world_size", "8",
        "--global_train_batch_size", "8", "--train_iters", "1",
    ])
    # 6 heads, tp=4 -> 6 % 4 != 0 -> GLS007 raised before tracing starts
    with pytest.raises(DiagnosticError) as ei:
        train(args)
    assert any(d.code == "GLS007" for d in ei.value.diagnostics)


def test_train_driver_trace_lint_hook_refuses_on_glt_error(devices8, monkeypatch):
    """--trace_lint 1: a GLT error from the traced-program linter aborts the
    driver after model construction but before any compile. The linter's
    actual verdicts are pinned in test_trace_lint.py; here the result is
    injected so the test never compiles."""
    from galvatron_tpu.analysis import diagnostics as D
    from galvatron_tpu.analysis import trace_lint as TL
    from galvatron_tpu.analysis.diagnostics import DiagnosticError
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train

    def fake_lint(model, **kw):
        rep = D.DiagnosticReport()
        rep.add(D.make("GLT001", "injected traced-program hazard"))
        return TL.TraceLintResult(report=rep)

    monkeypatch.setattr(TL, "lint_hybrid_model", fake_lint)
    args = initialize_galvatron(mode="train", argv=[
        "--model_type", "gpt", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "4",
        "--num_layers", "2", "--seq_length", "64", "--vocab_size", "128",
        "--world_size", "8", "--global_train_batch_size", "8",
        "--train_iters", "1", "--trace_lint", "1",
    ])
    with pytest.raises(DiagnosticError) as ei:
        train(args)
    assert any(d.code == "GLT001" for d in ei.value.diagnostics)
