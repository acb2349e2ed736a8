"""The repo continuously lints ITSELF: the shipped package and the shipped
strategy corpus are diagnostic-clean, via the same entry points CI uses
(scripts/lint.sh). Keeping this in tier-1 is the point of the analyzers —
the next jax pin change or search-engine schema drift fails here in
milliseconds instead of on a TPU pod."""

import glob
import json
import os
import subprocess

import galvatron_tpu
from galvatron_tpu.analysis import code_lint as C
from galvatron_tpu.analysis import strategy_lint as S

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PACKAGE = os.path.dirname(galvatron_tpu.__file__)

# Accepted exceptions, each with a justification. The code linter also honors
# inline `# galv-lint: ignore[CODE]` pragmas; entries here are for whole
# (file, code) pairs that cannot carry a pragma. Currently empty: the
# package is fully clean, and new exceptions need a review.
ALLOWLIST: set = set()


def _allowed(d):
    return (os.path.relpath(d.file or "", REPO), d.code) in ALLOWLIST


def test_package_has_zero_missing_jax_api_findings():
    """Acceptance: with the jax_compat shim installed, every jax attribute
    chain in the package resolves against the installed jax (this is the
    check that would have caught the shard_map/get_abstract_mesh breakage
    on day one)."""
    report = C.lint_paths([PACKAGE], rules={"GLC001"})
    findings = [d for d in report.diagnostics if not _allowed(d)]
    assert findings == [], "\n".join(d.format() for d in findings)


def test_package_is_error_free_under_all_rules():
    report = C.lint_paths([PACKAGE])
    errors = [d for d in report.errors if not _allowed(d)]
    assert errors == [], "\n".join(d.format() for d in errors)


def test_shipped_strategy_corpus_is_clean():
    corpus = sorted(glob.glob(os.path.join(
        REPO, "tests", "analysis", "fixtures", "valid", "*.json")))
    assert corpus, "shipped strategy corpus missing"
    for path in corpus:
        report = S.lint_strategy_file(path, world_size=8)
        assert report.ok, "%s:\n%s" % (path, report.render())


def test_package_traces_glt_clean(gpt_cfg, devices8):
    """The shipped model/runtime code realizes into GLT-clean traced
    programs: the traced-program linter finds none of the pinned GSPMD
    miscompile shapes in the train step the package itself jits. One dp and
    one pp+tp layout cover the scan-stacked layer runs, the microbatch
    split and the init program (abstract tracing only — no compiles)."""
    from galvatron_tpu.analysis import trace_lint as TL
    from galvatron_tpu.config.strategy import HybridParallelConfig

    for hp in (
        HybridParallelConfig.uniform(8, gpt_cfg.num_layers),
        HybridParallelConfig.uniform(8, gpt_cfg.num_layers, pp=2, tp=2,
                                     chunks=2),
    ):
        res = TL.lint_model(gpt_cfg, hp, devices8)
        errors = [d for d in res.report.errors if not _allowed(d)]
        assert errors == [], "\n".join(d.format() for d in errors)


def test_lint_sh_json_contract():
    """scripts/lint.sh is the CI entry point: exits 0 on the shipped tree
    and its --json output parses with zero errors."""
    proc = subprocess.run(
        ["bash", os.path.join(REPO, "scripts", "lint.sh"), "--json"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["summary"]["errors"] == 0, proc.stdout


def test_no_mention_of_what_was_removed():
    """The benchmark under benchmarks/ and the ledger are the one way this
    repo measures (PR 29). The CPU measuring stack, the options only it
    needed and the environment's peak override are gone, and nothing that
    describes or drives the program names them any more; the records
    (CHANGES.md, PERF.md, ROADMAP.md) may, as history. So are the four
    functions that said which layouts a part supports, the two that asked
    the layer pattern and the hand-kept digest defaults (PR 45: models/parts),
    and the `compile` fields that said which form a part took (PR 59:
    obs/forms.py, the event's one field `forms`)."""
    gone = ("bench.py", "_bench_util", "--no_async_loop", "--donate_step",
            "GALVATRON_PEAK_FLOPS", "expert_layout_reason", "linear_layers_reason", "_has_linear",
            "_has_mixer", "assert_expert_layout_supported", "_DIGEST_DEFAULTS",
            # PR 59: the `compile` fields that the module counters filled (obs/forms.py: one field, `forms`)
            "linear_kernel_layers", "linear_pass_kernel_layers", "kda_kernel_layers", "kda_pass_kernel_layers",
            "moe_row_kernel_blocks", "expert_window_rows", "shortconv_layers", "kernel_grads_relaid",
            "window_kernel_layers", "window_operands_as_projected", "table_rows_over_dp", "vocab_split_axes",
            "selscan_kernel_layers")
    files = [os.path.join(REPO, "README.md"), os.path.join(REPO, "COVERAGE.md")]
    for top in (PACKAGE, os.path.join(REPO, "scripts"), os.path.join(REPO, ".claude")):
        for ext in ("py", "md", "sh"):
            files += glob.glob(os.path.join(top, "**", "*." + ext), recursive=True)
    assert len(files) > 50, "the scan found too little to mean anything"
    hits = []
    for path in files:
        with open(path, errors="replace") as f:
            for n, line in enumerate(f, 1):
                hits += ["%s:%d: %s" % (os.path.relpath(path, REPO), n, word)
                         for word in gone if word in line]
    assert hits == [], "\n".join(hits)
