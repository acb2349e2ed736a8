import json

import pytest

from galvatron_tpu.config.strategy import (
    HybridParallelConfig,
    LayerStrategy,
    even_pp_division,
    pp_stage_of_layer,
)


def test_uniform_config():
    cfg = HybridParallelConfig.uniform(world_size=8, num_layers=4, pp=2, tp=2, global_bsz=8)
    assert cfg.per_stage_devices == 4
    assert cfg.dp(0) == 2
    assert cfg.pp_division == [2, 2]
    assert cfg.stage_of_layer == [0, 0, 1, 1]
    assert cfg.layers_of_stage(1) == [2, 3]


def test_even_pp_division():
    assert even_pp_division(10, 4) == [2, 2, 2, 4]
    assert pp_stage_of_layer([1, 3]) == [0, 1, 1, 1]


def test_validation_errors():
    with pytest.raises(ValueError):
        HybridParallelConfig.uniform(world_size=8, num_layers=2, pp=3)
    with pytest.raises(ValueError):
        HybridParallelConfig.uniform(world_size=8, num_layers=2, tp=3)
    with pytest.raises(ValueError):
        # global_bsz not a multiple of dp degree
        HybridParallelConfig.uniform(world_size=8, num_layers=2, tp=1, global_bsz=3)


def test_json_roundtrip(tmp_path):
    layers = [
        LayerStrategy(tp=2, fsdp=1, checkpoint=1),
        LayerStrategy(tp=4, sp=1),
        LayerStrategy(tp=1, cp=2),
        LayerStrategy(tp=2, tp_consec=0),
    ]
    cfg = HybridParallelConfig(
        world_size=16, pp=2, layers=layers, global_bsz=16, chunks=2,
        pipeline_type="pipedream_flush", default_dp_type="zero2", vocab_tp=2,
    )
    path = str(tmp_path / "cfg.json")
    cfg.save(path)
    cfg2 = HybridParallelConfig.from_json(path, world_size=16)
    cfg.assert_equal(cfg2)
    assert cfg2.layers[1].sp == 1
    assert cfg2.layers[3].tp_consec == 0
    assert cfg2.dp_type(0) == "zero3"
    assert cfg2.dp_type(2) == "zero2"


def test_reference_format_json(tmp_path):
    """Load a reference-style searched config (BASELINE.md example schema)."""
    ref = {
        "pp_deg": 1,
        "tp_sizes_enc": "1,1,1,1",
        "tp_consecutive_flags": "1,1,1,1",
        "dp_types_enc": "0,0,0,0",
        "global_bsz": 16,
        "chunks": 1,
        "pp_division": "4",
        "checkpoint": "0,0,0,0",
        "pipeline_type": "pipedream_flush",
        "default_dp_type": "zero2",
    }
    p = tmp_path / "ref.json"
    p.write_text(json.dumps(ref))
    cfg = HybridParallelConfig.from_json(str(p), world_size=8)
    assert cfg.pp == 1 and cfg.num_layers == 4
    assert cfg.dp_type(0) == "zero2"
    assert cfg.dp(0) == 8


def test_from_json_rejects_unknown_keys(tmp_path):
    """from_json hardening: a typo'd key fails loudly with a structured
    GLS001 diagnostic and a did-you-mean hint instead of silently falling
    back to the default (the old behavior trained the WRONG parallelism)."""
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    ref = {
        "pp_deg": 1,
        "tp_sizes_enc": "1,1",
        "dp_types_enc": "0,0",
        "global_bsz": 8,
        "tp_consecutive_flag": "1,1",  # typo: missing trailing 's'
    }
    with pytest.raises(DiagnosticError) as ei:
        HybridParallelConfig.from_json(ref, world_size=8)
    [d] = ei.value.diagnostics
    assert d.code == "GLS001" and d.key == "tp_consecutive_flag"
    assert "tp_consecutive_flags" in (d.hint or "")
    # DiagnosticError is a ValueError: legacy callers' handling still works
    assert isinstance(ei.value, ValueError)


def test_from_json_rejects_length_mismatch():
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    with pytest.raises(DiagnosticError) as ei:
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1,1,1", "dp_types_enc": "0,0"},
            world_size=8,
        )
    assert {d.code for d in ei.value.diagnostics} == {"GLS006"}


def test_validate_carries_diagnostic_codes():
    """validate() errors are routed through the shared diagnostic codes, so
    the CLI linter and the constructor report identically."""
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    with pytest.raises(DiagnosticError) as ei:
        HybridParallelConfig.uniform(world_size=8, num_layers=2, tp=3)
    assert any(d.code == "GLS002" for d in ei.value.diagnostics)
    with pytest.raises(DiagnosticError) as ei:
        HybridParallelConfig.uniform(world_size=8, num_layers=2, global_bsz=3)
    assert any(d.code == "GLS004" for d in ei.value.diagnostics)


def test_fa_families_pin_flash_attention():
    """gpt_fa / llama_fa (reference flash-attn-native variants) resolve to the
    same configs with attn_impl pinned to the pallas flash kernel."""
    from galvatron_tpu.models.registry import family_names, get_family

    assert {"gpt_fa", "llama_fa"} <= set(family_names())
    for name in ("gpt_fa", "llama_fa"):
        fam = get_family(name)
        cfg = fam.config_fn(fam.default_size)
        assert cfg.attn_impl == "flash"
    # base families stay on auto
    assert get_family("gpt").config_fn("gpt-0.3b").attn_impl == "auto"


def test_parallel_search_matches_serial():
    """--parallel_search must find the same optimum as the serial loop."""
    import numpy as np

    from galvatron_tpu.search.engine import GalvatronSearchEngine, SearchArgs

    def run(parallel):
        args = SearchArgs(memory_constraint=8.0, max_tp_deg=2, max_pp_deg=1,
                          min_bsz=8, max_bsz=16, bsz_scale=8,
                          parallel_search=parallel)
        eng = GalvatronSearchEngine(
            args, 8,
            [{"hidden_size": 64, "seq_len": 32, "layer_num": 2}],
        )
        eng.set_model_profiles(
            {"layertype_0": 1.0, "other_time": 0.5},
            {"layertype_0": {"parameter_size": 10.0,
                             "tp_activation_per_bsz_dict": {1: 2.0, 2: 1.0, "checkpoint": 0.5}},
             "other_memory_pp_off": {"model_states": {1: 40.0, 2: 20.0},
                                     "activation": {1: 4.0, 2: 2.0}},
             "other_memory_pp_on": {"first_stage": {"model_states": {1: 20.0, 2: 10.0},
                                                    "activation": {1: 2.0, 2: 1.0}},
                                    "last_stage": {"model_states": {1: 20.0, 2: 10.0},
                                                   "activation": {1: 2.0, 2: 1.0}}}},
        )
        eng.set_hardware_profiles({"allreduce_size_8_consec_1": 100.0,
                                   "allreduce_size_4_consec_1": 100.0,
                                   "allreduce_size_2_consec_1": 100.0})
        eng.initialize_search_engine()
        return eng.parallelism_optimization()

    serial, parallel = run(False), run(True)
    assert serial is not None and parallel is not None
    assert np.isclose(serial["cost"], parallel["cost"])
    assert serial["bsz"] == parallel["bsz"]


# ------------------------------------------- comm-precision fields (ISSUE 9)
def test_comm_dtype_fields_round_trip_json():
    """grad/param comm dtypes are SERIALIZED per-layer strategy fields
    (unlike the tp_comm_mode runtime knob): save -> from_json -> save is
    the identity, and provenance built from the config carries them."""
    from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy

    layers = [
        LayerStrategy(tp=1, fsdp=1, grad_comm_dtype="int8",
                      param_comm_dtype="int8"),
        LayerStrategy(tp=1, fsdp=1, grad_comm_dtype="fp8_e4m3",
                      param_comm_dtype="none"),
        LayerStrategy(tp=1, grad_comm_dtype="bf16"),
        LayerStrategy(tp=1),
    ]
    hp = HybridParallelConfig(world_size=8, pp=1, layers=layers,
                              global_bsz=8, comm_quant_block=32)
    d = hp.to_json_dict()
    assert d["grad_comm_dtype"] == "int8,fp8_e4m3,bf16,none"
    assert d["param_comm_dtype"] == "int8,none,none,none"
    assert d["comm_quant_block"] == 32
    hp2 = HybridParallelConfig.from_json(d, world_size=8)
    assert hp2.to_json_dict() == d
    assert [s.grad_comm_dtype for s in hp2.layers] == \
        ["int8", "fp8_e4m3", "bf16", "none"]
    hp2.assert_equal(hp)

    # elastic provenance round-trip: the strategy block IS the json dict,
    # so a resume on the same world restores the comm-precision axis
    import types

    from galvatron_tpu.runtime.elastic import build_provenance

    prov = build_provenance(hp, model_cfg=types.SimpleNamespace(hidden_size=8))
    hp3 = HybridParallelConfig.from_json(dict(prov["strategy"]), world_size=8)
    assert [s.grad_comm_dtype for s in hp3.layers] == \
        [s.grad_comm_dtype for s in hp.layers]
    assert hp3.comm_quant_block == 32


def test_comm_dtype_defaults_absent_keys():
    """Pre-ISSUE-9 strategy JSONs (no comm keys) load with 'none'
    everywhere — old checkpoints' provenance stays resumable."""
    from galvatron_tpu.config.strategy import HybridParallelConfig

    hp = HybridParallelConfig.from_json(
        {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
         "global_bsz": 8}, world_size=8)
    assert all(s.grad_comm_dtype == "none" for s in hp.layers)
    assert all(s.param_comm_dtype == "none" for s in hp.layers)
    assert hp.comm_quant_block == 64


def test_comm_dtype_unknown_key_strictness_gls001():
    """GLS001 strictness still rejects typos of the NEW keys."""
    from galvatron_tpu.analysis.diagnostics import DiagnosticError
    from galvatron_tpu.config.strategy import HybridParallelConfig

    with pytest.raises(DiagnosticError, match="GLS001"):
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
             "grad_com_dtype": "int8,int8", "global_bsz": 8}, world_size=8)


def test_comm_dtype_bad_enum_and_length_rejected():
    from galvatron_tpu.analysis.diagnostics import DiagnosticError
    from galvatron_tpu.config.strategy import HybridParallelConfig

    with pytest.raises(DiagnosticError, match="GLS005"):
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
             "grad_comm_dtype": "int9,int8", "global_bsz": 8}, world_size=8)
    with pytest.raises(DiagnosticError, match="GLS006"):
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
             "grad_comm_dtype": "int8", "global_bsz": 8}, world_size=8)
    with pytest.raises(DiagnosticError, match="GLS005"):
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
             "comm_quant_block": 0, "global_bsz": 8}, world_size=8)


def test_comm_dtype_does_not_split_layer_runs():
    """Comm precision changes the grad sync, not the layer program: a
    per-layer dtype mix still compiles as ONE scanned run."""
    from galvatron_tpu.config.strategy import (
        HybridParallelConfig,
        LayerStrategy,
        layer_runs,
    )

    hp = HybridParallelConfig(
        world_size=8, pp=1,
        layers=[LayerStrategy(grad_comm_dtype="int8"),
                LayerStrategy(grad_comm_dtype="none"),
                LayerStrategy(grad_comm_dtype="fp8_e4m3"),
                LayerStrategy()],
        global_bsz=8)
    assert len(layer_runs(hp)) == 1


def test_comm_dtype_survives_migration_resolution(tmp_path):
    """Acceptance criterion: a quantized strategy JSON resolves as a live-
    migration target with no GLS refusal, comm-precision fields intact
    (the relayout itself is agnostic — the fields only steer the rebuilt
    train step)."""
    import argparse
    import json

    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.config import TransformerConfig
    from galvatron_tpu.runtime.elastic import resolve_migration_strategy

    cfg = TransformerConfig(hidden_size=64, num_heads=4, num_layers=2,
                            vocab_size=128, max_seq_len=32)
    current = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8)
    target = HybridParallelConfig.uniform(
        8, 2, tp=1, global_bsz=8, grad_comm_dtype="int8",
        param_comm_dtype="int8", sdp=1)
    path = tmp_path / "target.json"
    path.write_text(json.dumps(target.to_json_dict()))
    args = argparse.Namespace(elastic_strategy=str(path),
                              elastic_memory_gb=1024.0)
    hp, action = resolve_migration_strategy(args, cfg, 8, current)
    assert action == "strategy_file"
    assert all(s.grad_comm_dtype == "int8" for s in hp.layers)
    assert all(s.param_comm_dtype == "int8" for s in hp.layers)


# ------------------------------------------- per-layer remat (ISSUE 15)
def test_remat_policy_round_trips_json_and_provenance():
    """remat_policy is a SERIALIZED per-layer strategy field (like the comm
    dtypes): save -> from_json -> save is the identity, and elastic
    provenance built from the config carries the mixed plan."""
    from galvatron_tpu.config.strategy import (
        HybridParallelConfig,
        LayerStrategy,
        layer_runs,
    )

    layers = [
        LayerStrategy(checkpoint=1, remat_policy="dots_saveable"),
        LayerStrategy(checkpoint=1, remat_policy="dots_saveable"),
        LayerStrategy(checkpoint=1),  # full (the checkpoint default)
        LayerStrategy(),              # not checkpointed
    ]
    hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
    d = hp.to_json_dict()
    assert d["remat_policy"] == "dots_saveable,dots_saveable,full,full"
    hp2 = HybridParallelConfig.from_json(d, world_size=8)
    assert hp2.to_json_dict() == d
    hp2.assert_equal(hp)
    # effective policy partitions the runs: [dots, dots] | [full] | [none]
    assert [(r.start, r.stop) for r in layer_runs(hp2)] == [(0, 2), (2, 3), (3, 4)]
    assert [r.strategy.effective_remat_policy for r in layer_runs(hp2)] == \
        ["dots_saveable", "full", "none"]

    import types

    from galvatron_tpu.runtime.elastic import build_provenance

    prov = build_provenance(hp, model_cfg=types.SimpleNamespace(hidden_size=8))
    hp3 = HybridParallelConfig.from_json(dict(prov["strategy"]), world_size=8)
    assert [s.remat_policy for s in hp3.layers] == \
        [s.remat_policy for s in hp.layers]


def test_remat_inert_differences_do_not_split_runs():
    """The run splitter keys on the EFFECTIVE policy: a remat_policy on a
    checkpoint=0 layer is inert, and checkpoint=1 with remat_policy='none'
    executes exactly like checkpoint=0 — neither forks a scan program."""
    from galvatron_tpu.config.strategy import (
        HybridParallelConfig,
        LayerStrategy,
        layer_runs,
    )

    hp = HybridParallelConfig(
        world_size=8, pp=1,
        layers=[LayerStrategy(remat_policy="dots_saveable"),
                LayerStrategy(),
                LayerStrategy(checkpoint=1, remat_policy="none")],
        global_bsz=8)
    assert len(layer_runs(hp)) == 1


def test_remat_absent_key_defaults_and_override():
    """Pre-ISSUE-15 JSONs (no remat_policy key) load as 'full' everywhere;
    the global-flag override fills them — but ONLY when the key is absent
    (serialized per-layer values always win, see test_arguments.py for the
    CLI half of the precedence rule)."""
    from galvatron_tpu.config.strategy import HybridParallelConfig

    base = {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
            "checkpoint": "1,1", "global_bsz": 8}
    hp = HybridParallelConfig.from_json(base, world_size=8)
    assert all(s.remat_policy == "full" for s in hp.layers)
    hp = HybridParallelConfig.from_json(
        base, world_size=8, remat_policy="dots_saveable")
    assert all(s.remat_policy == "dots_saveable" for s in hp.layers)
    hp = HybridParallelConfig.from_json(
        dict(base, remat_policy="none,full"), world_size=8,
        remat_policy="dots_saveable")
    assert [s.remat_policy for s in hp.layers] == ["none", "full"]


def test_remat_bad_enum_and_length_rejected():
    from galvatron_tpu.analysis.diagnostics import DiagnosticError
    from galvatron_tpu.config.strategy import HybridParallelConfig

    with pytest.raises(DiagnosticError, match="GLS005"):
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
             "remat_policy": "dots_savable,full", "global_bsz": 8},
            world_size=8)
    with pytest.raises(DiagnosticError, match="GLS006"):
        HybridParallelConfig.from_json(
            {"pp_deg": 1, "tp_sizes_enc": "1,1", "dp_types_enc": "0,0",
             "remat_policy": "full", "global_bsz": 8}, world_size=8)


def test_remat_plan_survives_migration_resolution(tmp_path):
    """A mixed per-layer remat plan resolves as a live-migration target with
    the plan intact — the hot-swap rebuilds the train step under the same
    per-layer policies the search chose."""
    import argparse
    import json

    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.config import TransformerConfig
    from galvatron_tpu.runtime.elastic import resolve_migration_strategy

    cfg = TransformerConfig(hidden_size=64, num_heads=4, num_layers=2,
                            vocab_size=128, max_seq_len=32)
    current = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8)
    import dataclasses

    target = HybridParallelConfig.uniform(8, 2, tp=1, global_bsz=8)
    target = dataclasses.replace(target, layers=[
        dataclasses.replace(s, checkpoint=c, remat_policy=rp)
        for s, (c, rp) in zip(
            target.layers, [(1, "dots_saveable"), (0, "full")])])
    path = tmp_path / "target.json"
    path.write_text(json.dumps(target.to_json_dict()))
    args = argparse.Namespace(elastic_strategy=str(path),
                              elastic_memory_gb=1024.0)
    hp, action = resolve_migration_strategy(args, cfg, 8, current)
    assert action == "strategy_file"
    assert [s.effective_remat_policy for s in hp.layers] == \
        ["dots_saveable", "none"]
