"""Golden-corpus tests: every GLS diagnostic code has at least one failing
fixture (tests/analysis/fixtures/broken|warn) and one passing fixture
(tests/analysis/fixtures/valid, linted under the same options)."""

import glob
import os

import pytest

from galvatron_tpu.analysis import strategy_lint as S
from galvatron_tpu.analysis.diagnostics import ERROR, WARNING
from galvatron_tpu.models.config import TransformerConfig

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
WORLD = 8

# small model whose dimensions deliberately don't divide the broken corpus's
# degrees: heads=6 (not %4), seq=100 (not %8), vocab=100 (not %8)
MODEL = TransformerConfig(
    hidden_size=96, num_heads=6, num_layers=4, vocab_size=100, max_seq_len=100,
)


def lint(rel, **kw):
    return S.lint_strategy_file(os.path.join(FIXTURES, rel), WORLD, **kw)


# code -> (broken fixture, lint kwargs)
BROKEN = {
    "GLS001": ("broken/gls001_typo_key.json", {}),
    "GLS002": ("broken/gls002_tp_overflow.json", {}),
    "GLS003": ("broken/gls003_bad_division.json", {}),
    "GLS004": ("broken/gls004_bad_bsz.json", {}),
    "GLS005": ("broken/gls005_bad_enum.json", {}),
    "GLS006": ("broken/gls006_len_mismatch.json", {}),
    "GLS007": ("broken/gls007_heads_tp.json", {"model_cfg": MODEL}),
    "GLS008": ("broken/gls008_seq_cp.json", {"model_cfg": MODEL}),
    "GLS009": ("broken/gls009_vocab_tp.json", {"model_cfg": MODEL}),
    "GLS010": ("broken/gls010_gpipe_nonuniform.json", {}),
    "GLS011": ("broken/gls011_ckpt_nonuniform.json", {}),
    "GLS013": ("broken/gls013_quant_unsupported.json", {}),
    "GLS014": ("broken/gls014_serve_pp.json", {"mode": "serve"}),
}
WARN = {
    "GLS101": ("warn/gls101_over_budget.json",
               {"model_cfg": MODEL, "memory_budget_gb": 0.0001}),
    "GLS102": ("warn/gls102_reshard.json", {}),
    "GLS103": ("warn/gls103_inert_flags.json", {}),
}


@pytest.mark.parametrize("code", sorted(BROKEN))
def test_broken_fixture_fails_with_code(code):
    rel, kw = BROKEN[code]
    report = lint(rel, **kw)
    assert not report.ok, "expected errors for %s" % rel
    assert code in report.codes(), (code, report.render())
    assert report.exit_code() == 1
    # location metadata survives into the report
    assert all(d.file.endswith(rel.split("/")[-1]) for d in report.diagnostics)


@pytest.mark.parametrize("code", sorted(WARN))
def test_warn_fixture_warns_with_code(code):
    rel, kw = WARN[code]
    report = lint(rel, **kw)
    assert report.ok, report.render()  # warnings never fail the exit code
    assert code in {d.code for d in report.warnings}, report.render()
    assert report.exit_code() == 0


@pytest.mark.parametrize(
    "rel", sorted(os.path.relpath(p, FIXTURES)
                  for p in glob.glob(os.path.join(FIXTURES, "valid", "*.json")))
)
def test_valid_corpus_is_diagnostic_clean(rel):
    """The passing side of every code: the valid corpus is clean even under
    the strictest options the broken corpus is linted with."""
    report = lint(rel, model_cfg=None)
    assert report.ok and not report.warnings, report.render()


def test_valid_corpus_clean_with_model_and_budget():
    """GLS007/8/9 and GLS101 have passing fixtures too: a model config whose
    dims divide (tp=1 everywhere) and a generous budget produce nothing."""
    report = lint("valid/uniform_dp8.json", model_cfg=MODEL,
                  memory_budget_gb=1024.0)
    assert report.ok and not report.warnings, report.render()


def test_serve_fixture_clean_in_serve_mode():
    """The shipped serve strategy lints clean under the FULL serve layer
    (model-aware KV budget included) — and stays clean in the default
    file-level mode lint.sh runs."""
    report = lint("valid/serve_tp2.json", model_cfg=MODEL, mode="serve",
                  memory_budget_gb=64.0)
    assert report.ok and not report.warnings, report.render()
    report = lint("valid/serve_tp2.json")
    assert report.ok and not report.warnings, report.render()


def test_serve_kv_budget_overflow_is_gls014():
    """Same valid layout, starvation budget: the KV+weight budget check
    refuses with GLS014 rather than emitting a doomed serving config."""
    report = lint("valid/serve_tp2.json", model_cfg=MODEL, mode="serve",
                  memory_budget_gb=0.0001)
    assert not report.ok and "GLS014" in report.codes(), report.render()


def test_serve_knobs_warn_inert_in_train_mode():
    """GLS103's serve-flag variant: serve_max_concurrency/serve_page_size in
    a config consumed by the TRAIN driver warn (nothing allocates a cache)."""
    report = lint("warn/gls103_serve_knobs.json", mode="train")
    assert report.ok, report.render()
    assert "GLS103" in {d.code for d in report.warnings}, report.render()
    # without driver mode context the knobs are dormant, not diagnosable
    assert not lint("warn/gls103_serve_knobs.json").warnings


def test_shed_knobs_warn_inert_in_train_mode():
    """GLS103's shedding-knob variant: serve_p99_ttft_ms/serve_max_pending
    in a TRAIN-consumed config warn — admission control and overload
    shedding live in the serve batcher, not the training loop."""
    report = lint("warn/gls103_shed_knobs.json", mode="train")
    assert report.ok, report.render()
    assert "GLS103" in {d.code for d in report.warnings}, report.render()
    assert not lint("warn/gls103_shed_knobs.json").warnings
    # in SERVE mode the knobs are live configuration, not a smell
    assert not lint("warn/gls103_shed_knobs.json", mode="serve").warnings


def test_ring_nonuniform_second_gls010_variant():
    report = lint("broken/gls010_ring_nonuniform.json")
    assert "GLS010" in report.codes() and not report.ok


def test_gpipe_cp_is_gls010():
    report = S.lint_strategy_dict(
        {"pp_deg": 2, "tp_sizes_enc": "1,1,1,1", "cp_sizes_enc": "2,2,2,2",
         "dp_types_enc": "0,0,0,0", "global_bsz": 8, "chunks": 2,
         "pipeline_type": "gpipe"}, WORLD)
    assert "GLS010" in report.codes() and not report.ok


def test_did_you_mean_hint_attached():
    report = lint("broken/gls001_typo_key.json")
    [d] = [d for d in report.diagnostics if d.code == "GLS001"]
    assert d.hint and "dp_types_enc" in d.hint


def test_json_report_schema():
    import json

    report = lint("broken/gls002_tp_overflow.json")
    payload = json.loads(report.to_json())
    assert payload["version"] == 1
    assert payload["summary"]["errors"] >= 1
    assert payload["summary"]["codes"] == report.codes()
    assert all({"code", "severity", "message"} <= set(d) for d in payload["diagnostics"])
    assert all(d["severity"] in (ERROR, WARNING) for d in payload["diagnostics"])


def test_memory_estimate_profiled_tables_beat_analytic():
    """GLS101 accepts the profiler's memory JSON; a profile claiming huge
    layers trips a budget the analytic estimate of the tiny model never
    would."""
    profile = {"layertype_0": {
        "parameter_size": 4096.0,  # MB per layer: a deliberately huge claim
        "tp_activation_per_bsz_dict": {"1": 512.0, "2": 256.0, "checkpoint": 64.0},
    }}
    over = lint("valid/uniform_dp8.json", model_cfg=MODEL, memory_budget_gb=4.0,
                memory_profile=profile)
    assert "GLS101" in {d.code for d in over.warnings}, over.render()
    under = lint("valid/uniform_dp8.json", model_cfg=MODEL, memory_budget_gb=4.0)
    assert "GLS101" not in {d.code for d in under.warnings}, under.render()


def test_estimate_stage_memory_shape():
    from galvatron_tpu.config.strategy import HybridParallelConfig

    hp = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8, chunks=2,
                                      pipeline_type="pipedream_flush")
    mb = S.estimate_stage_memory_mb(hp, MODEL)
    assert mb is not None and len(mb) == 2 and all(m > 0 for m in mb)
    # no model, no profile -> not enough information, not a guess
    assert S.estimate_stage_memory_mb(hp, None) is None


# --------------------------------------------------- tp_comm_mode (ISSUE 8)
# a runtime knob like remat_policy: never an on-disk key, so the fixtures
# are linted WITH the override the CLI/driver would apply
def test_tp_comm_mode_inert_fixture_warns_gls103():
    report = lint("warn/gls103_inert_tp_comm_mode.json", tp_comm_mode="overlap")
    assert report.ok, report.render()
    warns = [d for d in report.warnings if d.code == "GLS103"]
    assert warns and "tp_comm_mode" in warns[0].message, report.render()


def test_tp_comm_mode_inert_with_pp_warns_gls103():
    report = lint("valid/hybrid_pp2_1f1b.json", tp_comm_mode="shard_map")
    msgs = [d.message for d in report.warnings if d.code == "GLS103"]
    assert any("pp=" in m for m in msgs), report.render()


def test_tp_comm_mode_gspmd_default_stays_clean():
    report = lint("warn/gls103_inert_tp_comm_mode.json")
    assert report.ok and not report.warnings, report.render()


def test_tp_comm_mode_unsupported_config_is_gls012():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "2,2,2,2", "use_sp": "1,1,1,1",
         "dp_types_enc": "0,0,0,0", "global_bsz": 8}, WORLD,
        model_cfg=MODEL, tp_comm_mode="overlap")
    assert not report.ok and "GLS012" in report.codes(), report.render()
    # identical strategy under the default path is not refused
    ok = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "2,2,2,2", "use_sp": "1,1,1,1",
         "dp_types_enc": "0,0,0,0", "global_bsz": 8}, WORLD, model_cfg=MODEL)
    assert "GLS012" not in ok.codes()


def test_tp_comm_mode_supported_config_lint_clean():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "2,2,2,2",
         "dp_types_enc": "0,0,0,0", "global_bsz": 8}, WORLD,
        model_cfg=TransformerConfig(
            hidden_size=64, num_heads=4, num_layers=4, vocab_size=128,
            max_seq_len=64),
        tp_comm_mode="overlap")
    assert report.ok, report.render()
    assert "GLS012" not in report.codes() and "GLS103" not in report.codes()


def test_tp_comm_mode_bad_value_is_gls005():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1",
         "dp_types_enc": "0,0,0,0", "global_bsz": 8}, WORLD,
        tp_comm_mode="bogus")
    assert not report.ok and "GLS005" in report.codes(), report.render()


# ------------------------------------------- quantized collectives (ISSUE 9)
def test_comm_quant_inert_param_fixture_warns_gls103():
    report = lint("warn/gls103_inert_param_comm.json")
    assert report.ok, report.render()
    warns = [d for d in report.warnings if d.code == "GLS103"]
    assert warns and "param_comm_dtype" in warns[0].message, report.render()


def test_comm_quant_valid_fixture_is_clean():
    report = lint("valid/quant_dp8.json")
    assert report.ok and not report.warnings, report.render()


def test_comm_quant_with_tp_is_gls013():
    report = lint("broken/gls013_quant_unsupported.json")
    assert not report.ok and "GLS013" in report.codes(), report.render()
    [d] = [d for d in report.diagnostics if d.code == "GLS013"]
    assert "pure" in d.message and "data-parallel" in d.message


def test_comm_quant_anomaly_guard_is_gls013():
    """Driver state the strategy cannot see: the guard's bitwise
    spike/rollback contract refuses the quantized sync — only when the
    caller (the train driver) passes anomaly_guard."""
    d = {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1", "dp_types_enc": "0,0,0,0",
         "grad_comm_dtype": "int8,int8,int8,int8", "global_bsz": 8}
    from galvatron_tpu.config.strategy import HybridParallelConfig

    hp = HybridParallelConfig.from_json(d, world_size=WORLD)
    assert S.lint_hp(hp, anomaly_guard=True).codes() == ["GLS013"]
    assert S.lint_hp(hp, anomaly_guard=False).ok
    assert S.lint_hp(hp).ok  # file-level lints skip the driver-state check


def test_comm_quant_zero2_is_gls013():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1", "dp_types_enc": "0,0,0,0",
         "grad_comm_dtype": "bf16,bf16,bf16,bf16", "global_bsz": 8,
         "default_dp_type": "zero2"}, WORLD)
    assert not report.ok and "GLS013" in report.codes(), report.render()


def test_comm_quant_bad_dtype_is_gls005_with_hint():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1", "dp_types_enc": "0,0,0,0",
         "grad_comm_dtype": "int8,in8,int8,int8", "global_bsz": 8}, WORLD)
    assert not report.ok and "GLS005" in report.codes(), report.render()
    [d] = [d for d in report.diagnostics if d.code == "GLS005"]
    assert d.hint and "int8" in d.hint


def test_tp_comm_quant_under_gspmd_is_gls013():
    # construct-time refusal too: validate() raises the same diagnostic
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "2,2,2,2", "dp_types_enc": "0,0,0,0",
         "global_bsz": 8}, WORLD, tp_comm_quant="int8")
    assert not report.ok and "GLS013" in report.codes(), report.render()


def test_tp_comm_quant_with_manual_mode_is_clean():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "2,2,2,2", "dp_types_enc": "0,0,0,0",
         "global_bsz": 8}, WORLD, tp_comm_mode="overlap", tp_comm_quant="int8")
    assert report.ok and "GLS103" not in report.codes(), report.render()


def test_tp_comm_quant_inert_at_tp1_warns_gls103():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1", "dp_types_enc": "0,0,0,0",
         "global_bsz": 8}, WORLD, tp_comm_mode="overlap", tp_comm_quant="int8")
    assert report.ok, report.render()
    msgs = [d.message for d in report.warnings if d.code == "GLS103"]
    assert any("tp_comm_quant" in m for m in msgs), report.render()


# ------------------------------------------------------- online autotuner
def _dp8(**kw):
    from galvatron_tpu.config.strategy import HybridParallelConfig

    return HybridParallelConfig.uniform(WORLD, 4, global_bsz=8, **kw)


def test_autotune_apply_with_pinned_strategy_is_gls017():
    report = S.lint_hp(
        _dp8(), autotune="apply", elastic_strategy="/tmp/pinned.json")
    assert not report.ok and "GLS017" in report.codes(), report.render()
    [d] = [d for d in report.errors if d.code == "GLS017"]
    assert "elastic_strategy" in d.message


def test_autotune_observe_with_pinned_strategy_composes():
    report = S.lint_hp(
        _dp8(), autotune="observe", elastic_strategy="/tmp/pinned.json")
    assert "GLS017" not in report.codes(), report.render()


def test_autotune_without_scan_layers_warns_gls103():
    report = S.lint_hp(_dp8(scan_layers=False), autotune="apply")
    assert report.ok, report.render()
    msgs = [d.message for d in report.warnings if d.code == "GLS103"]
    assert any("scan_layers" in m for m in msgs), report.render()


def test_autotune_with_pipeline_warns_gls103():
    report = S.lint_hp(_dp8(pp=2, chunks=2), autotune="observe")
    assert report.ok, report.render()
    msgs = [d.message for d in report.warnings if d.code == "GLS103"]
    assert any("per-LayerRun" in m for m in msgs), report.render()


def test_autotune_margin_inert_without_mode_warns_gls103():
    report = S.lint_hp(_dp8(), autotune_margin=0.1)
    msgs = [d.message for d in report.warnings if d.code == "GLS103"]
    assert any("autotune_margin" in m for m in msgs), report.render()
    # ... and is clean when the tuner is actually on
    report2 = S.lint_hp(_dp8(), autotune="apply", autotune_margin=0.1)
    assert "GLS103" not in report2.codes(), report2.render()


# -------------------------------------- per-layer remat search (ISSUE 15)
def test_remat_mixed_fixture_is_clean():
    """A searched mixed per-layer remat plan is a first-class citizen of the
    valid corpus: no warning for deviating from the global default."""
    report = lint("valid/remat_mixed.json")
    assert report.ok and not report.warnings, report.render()


def test_remat_all_full_key_warns_gls103():
    """Serialized remat_policy of all-'full' carries no information beyond
    the checkpoint flag — the key should be dropped."""
    report = lint("warn/gls103_remat_full_key.json")
    assert report.ok, report.render()
    warns = [d for d in report.warnings if d.code == "GLS103"]
    assert warns and any(d.key == "remat_policy" for d in warns), report.render()


def test_remat_global_flag_shadowed_warns_gls103():
    """Precedence rule: serialized per-layer policies win; a non-default
    --remat_policy flag over a JSON that carries the key was shadowed."""
    report = lint("valid/remat_mixed.json", remat_policy="dots_saveable")
    assert report.ok, report.render()
    msgs = [d.message for d in report.warnings if d.code == "GLS103"]
    assert any("shadowed" in m for m in msgs), report.render()
    # the default flag value never warns
    assert not lint("valid/remat_mixed.json", remat_policy="full").warnings


def test_remat_bad_value_is_gls005():
    report = S.lint_strategy_dict(
        {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1", "dp_types_enc": "0,0,0,0",
         "checkpoint": "1,1,1,1", "remat_policy": "none,none,bogus,none",
         "global_bsz": 8}, WORLD)
    assert not report.ok and "GLS005" in report.codes(), report.render()


def test_remat_policy_prices_into_memory_estimate():
    """dots_saveable holds strictly less than full (activations shrink to
    the dot outputs) and strictly more than none on checkpointed layers."""
    from galvatron_tpu.config.strategy import HybridParallelConfig

    def est(rp):
        hp = HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1",
             "dp_types_enc": "0,0,0,0", "checkpoint": "1,1,1,1",
             "remat_policy": ",".join([rp] * 4), "global_bsz": 8},
            world_size=WORLD)
        return sum(S.estimate_stage_memory_mb(hp, MODEL))

    full, dots, none = est("full"), est("dots_saveable"), est("none")
    assert full < dots < none, (full, dots, none)
