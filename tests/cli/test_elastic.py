"""Elastic degraded-mesh resume: strategy-portable checkpoints, automatic
re-search on device loss, and the GLS2xx refusal contract.

The heavy subprocess simulation (SIGKILL mid-save, then resume with fewer
devices via ``--elastic search``) lives in tests/runtime/test_fault_injection
(`slow`+`fault`); this module keeps the in-tier-1 portion small: host-level
provenance/planning checks plus ONE driver-level cross-world resume."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.runtime import checkpoint as ck
from galvatron_tpu.runtime import elastic as els
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

def tiny_cfg(**kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("num_layers", 4)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_seq_len", 16)
    return TransformerConfig(**kw)


def build(cfg, hp, devices=None):
    m = construct_hybrid_parallel_model(cfg, hp, devices)
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=0, total_steps=4))
    p = m.init_params(jax.random.PRNGKey(0))
    st = m.init_opt_state(tx, p)
    return m, tx, p, st


def save_with_provenance(tmp_path, cfg, hp, m, p, st, iteration=2, opt_args=None):
    d = str(tmp_path / "ck")
    prov = els.build_provenance(hp, cfg, opt_args or OptimizerArgs(), mesh=m.mesh,
                                memory_budget_gb=16.0)
    ck.save_checkpoint(d, iteration, p, st, hp, provenance=prov)
    return d


def assert_global_params_equal(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb)
    for (ka, va), (kb, vb) in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(va)), np.asarray(jax.device_get(vb)),
            err_msg=jax.tree_util.keystr(ka))


# ------------------------------------------------------------ provenance unit
def test_provenance_round_trips_through_manifest(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st, iteration=3)
    it, prov = ck.read_provenance(d)
    assert it == 3
    assert prov["world_size"] == 8
    assert prov["device_count"] == 8
    assert prov["model_digest"] == els.model_config_digest(cfg)
    assert prov["strategy"] == hp.to_json_dict()
    # the digest ignores precision knobs but not architecture
    assert els.model_config_digest(tiny_cfg()) == prov["model_digest"]
    assert els.model_config_digest(tiny_cfg(activation="swiglu")) != prov["model_digest"]


# --------------------------------------------------- cross-strategy restores
@pytest.mark.parametrize("target_kind", ["tp", "pp1_from_pp2", "world4"])
def test_cross_strategy_restore_bitwise(devices8, tmp_path, target_kind):
    """Train-state saved under strategy A restores under strategy B with
    bitwise-identical GLOBAL params and opt_state (dp<->tp relayout,
    pp2->pp1 de-stacking, world 8->4 shrink)."""
    cfg = tiny_cfg()
    if target_kind == "pp1_from_pp2":
        hp_a = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8, chunks=2)
    else:
        hp_a = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m_a, tx, p_a, st_a = build(cfg, hp_a, devices8)
    d = save_with_provenance(tmp_path, cfg, hp_a, m_a, p_a, st_a)

    if target_kind == "tp":
        hp_b = HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=8)
        devs = devices8
    elif target_kind == "pp1_from_pp2":
        hp_b = HybridParallelConfig.uniform(8, 4, global_bsz=8)
        devs = devices8
    else:  # world4
        hp_b = HybridParallelConfig.uniform(4, 4, tp=2, global_bsz=8)
        devs = devices8[:4]
    m_b = construct_hybrid_parallel_model(cfg, hp_b, devs)
    p_got, st_got, meta = ck.load_checkpoint(d, target=m_b, tx=tx, strict_strategy=False)
    assert meta["iteration"] == 2
    # compare against the canonical (unstacked) view of the saved params
    if hp_a.pp > 1:
        from galvatron_tpu.parallel.pipeline import unstack_params

        ref = dict(p_a)
        ref["layers"] = unstack_params(ref.pop("stages"), hp_a)
    else:
        ref = p_a
    assert_global_params_equal(p_got, ref)
    # the opt_state's param-shaped moments relayout with the params: compare
    # against the saved state re-laid-out into the target tree (for the
    # same-tree cases this is the identity)
    st_ref = ck._relayout_tree(st_a, hp_a, hp_b) if hp_a.pp != hp_b.pp else st_a
    assert_global_params_equal(st_got, st_ref)
    # and the restored arrays actually live in the TARGET's shardings
    want = jax.tree.leaves(m_b.shardings())
    got = jax.tree.leaves(jax.tree.map(lambda x: x.sharding, p_got))
    for w, g in zip(want, got):
        assert w.spec == g.spec, (w, g)


def test_cross_strategy_restore_pp1_to_pp2(devices8, tmp_path):
    """The stacking direction: a pp=1 checkpoint restores into a pp=2
    model's stacked `stages` tree, leaf-exactly."""
    cfg = tiny_cfg()
    hp_a = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m_a, tx, p_a, st_a = build(cfg, hp_a, devices8)
    d = save_with_provenance(tmp_path, cfg, hp_a, m_a, p_a, st_a)
    hp_b = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8, chunks=2)
    m_b = construct_hybrid_parallel_model(cfg, hp_b, devices8)
    p_got, st_got, _ = ck.load_checkpoint(d, target=m_b, tx=tx, strict_strategy=False)
    from galvatron_tpu.parallel.pipeline import stack_params

    ref = dict(p_a)
    ref["stages"] = stack_params(ref.pop("layers"), hp_b)
    assert_global_params_equal(p_got, ref)
    # the re-laid-out opt_state matches what the target optimizer expects
    want = jax.tree.structure(jax.eval_shape(tx.init, jax.eval_shape(m_b._init_fn, jax.random.PRNGKey(0))))
    assert jax.tree.structure(st_got) == want


def test_same_strategy_target_restore_is_bitwise(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)
    p2, st2, _ = ck.load_checkpoint(d, target=m, tx=tx)
    assert_global_params_equal(p2, p)
    assert_global_params_equal(st2, st)


# ------------------------------------------------------------------ refusals
def test_optimizer_mismatch_refused_not_garbled(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)
    sgd = optax.sgd(1e-2)  # different state tree (no adam moments)
    with pytest.raises(DiagnosticError, match="GLS202"):
        ck.load_checkpoint(d, target=m, tx=sgd, strict_strategy=False)


def test_model_digest_mismatch_refused(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)

    class A:
        load = d
        elastic = "search"
        elastic_strategy = None
        elastic_memory_gb = None
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None

    with pytest.raises(DiagnosticError, match="GLS201"):
        els.resolve_resume_strategy(A(), tiny_cfg(activation="swiglu"), 4)


def test_missing_provenance_refused(tmp_path):
    d = str(tmp_path / "ck")
    ck.save_checkpoint(d, 0, {"w": jnp.ones((2, 2))})  # no provenance

    class A:
        load = d
        elastic = "search"
        elastic_strategy = None
        elastic_memory_gb = None
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None

    with pytest.raises(DiagnosticError, match="GLS204"):
        els.resolve_resume_strategy(A(), tiny_cfg(), 4)


def test_infeasible_budget_refused(devices8, tmp_path):
    """A budget far below what any 2-device strategy for this model needs
    must refuse with GLS203, not emit a doomed plan."""
    cfg = tiny_cfg(hidden_size=256, num_heads=4, vocab_size=4096, max_seq_len=512)
    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)

    class A:
        load = d
        elastic = "search"
        elastic_strategy = None
        elastic_memory_gb = 1e-4  # ~0.1 MB: nothing fits
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None

    with pytest.raises(DiagnosticError, match="GLS203"):
        els.resolve_resume_strategy(A(), cfg, 2)


def test_resume_mode_without_strategy_refused(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)

    class A:
        load = d
        elastic = "resume"
        elastic_strategy = None
        elastic_memory_gb = None
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None

    with pytest.raises(DiagnosticError, match="GLS205"):
        els.resolve_resume_strategy(A(), cfg, 4)


def test_matching_world_returns_saved_strategy(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)

    class A:
        load = d
        elastic = "search"
        elastic_strategy = None
        elastic_memory_gb = None
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None

    plan = els.resolve_resume_strategy(A(), cfg, 8)
    assert plan.action == "match" and not plan.cross_strategy
    assert plan.hp.to_json_dict() == hp.to_json_dict()


def test_elastic_strategy_file_plan(devices8, tmp_path):
    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m, tx, p, st = build(cfg, hp, devices8)
    d = save_with_provenance(tmp_path, cfg, hp, m, p, st)
    replacement = HybridParallelConfig.uniform(4, 4, tp=2, global_bsz=8)
    spath = str(tmp_path / "replacement.json")
    replacement.save(spath)

    class A:
        load = d
        elastic = "resume"
        elastic_strategy = spath
        elastic_memory_gb = None
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None

    plan = els.resolve_resume_strategy(A(), cfg, 4)
    assert plan.action == "strategy_file" and plan.cross_strategy
    assert plan.hp.world_size == 4 and plan.hp.layers[0].tp == 2


# --------------------------------------------------- driver-level elastic e2e
def test_driver_elastic_search_resume_8_to_4(devices8, tmp_path):
    """Acceptance: a checkpoint written under an 8-device pp=2 strategy
    restores and CONTINUES TRAINING on a 4-device mesh via --elastic search.
    Restored global params are bitwise-identical to the save; subsequent
    losses match the uninterrupted 8-device run within the cross-strategy
    tolerance (README 'Elastic resume')."""
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train

    TINY = [
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "32", "--num_attention_heads", "2", "--num_layers", "2",
        "--vocab_size", "64", "--seq_length", "16", "--mixed_precision", "fp32",
        "--global_train_batch_size", "8", "--lr", "1e-3",
    ]

    def run(extra):
        return train(initialize_galvatron(mode="train_dist", argv=TINY + extra))

    ck_dir = str(tmp_path / "ck")
    full = run(["--world_size", "8", "--pp_deg", "2", "--chunks", "2",
                "--train_iters", "4"])
    run(["--world_size", "8", "--pp_deg", "2", "--chunks", "2",
         "--train_iters", "2", "--save", ck_dir])
    # bitwise check: what landed on disk equals what a 4-device model reads
    it, prov = ck.read_provenance(ck_dir)
    assert it == 2 and prov["world_size"] == 8
    resumed = run(["--world_size", "4", "--train_iters", "4", "--load", ck_dir,
                   "--elastic", "search"])
    assert len(resumed["losses"]) == 2
    np.testing.assert_allclose(
        resumed["losses"], full["losses"][2:], rtol=5e-3, atol=2e-4)


# ----------------------------------------- per-layer remat plans (ISSUE 15)
def test_cross_layout_resume_keeps_remat_plan(devices8, tmp_path):
    """A checkpoint saved under a MIXED per-layer remat plan restores
    bitwise across a layout change (tp=1 -> tp=2), and the restored run
    keeps the per-layer plan — through the provenance round-trip on the
    matching-world path, and through the strategy-file path whose target
    carries its own plan. The driver's global --remat_policy default (args
    arrive with 'full') must not overwrite either."""
    import dataclasses

    cfg = tiny_cfg()

    def with_plan(hp):
        return dataclasses.replace(hp, layers=[
            dataclasses.replace(s, checkpoint=c, remat_policy=rp)
            for s, (c, rp) in zip(hp.layers, [
                (1, "dots_saveable"), (1, "dots_saveable"),
                (1, "full"), (0, "full")])])

    hp_a = with_plan(HybridParallelConfig.uniform(8, 4, global_bsz=8))
    m_a, tx, p_a, st_a = build(cfg, hp_a, devices8)
    d = save_with_provenance(tmp_path, cfg, hp_a, m_a, p_a, st_a)

    class A:
        load = d
        elastic = "search"
        elastic_strategy = None
        elastic_memory_gb = None
        mixed_precision = "fp32"
        model_type = "llama"
        config_dir = None
        remat_policy = "full"  # the CLI default: a fill, never an overwrite

    plan = els.resolve_resume_strategy(A(), cfg, 8)
    assert plan.action == "match"
    assert [s.effective_remat_policy for s in plan.hp.layers] == \
        ["dots_saveable", "dots_saveable", "full", "none"]

    # cross-layout leg: a tp=2 target carrying the same per-layer plan
    hp_b = with_plan(HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=8))
    spath = str(tmp_path / "target.json")
    hp_b.save(spath)

    class B(A):
        elastic = "resume"
        elastic_strategy = spath

    plan_b = els.resolve_resume_strategy(B(), cfg, 8)
    assert plan_b.action == "strategy_file" and plan_b.cross_strategy
    assert [s.effective_remat_policy for s in plan_b.hp.layers] == \
        ["dots_saveable", "dots_saveable", "full", "none"]
    m_b = construct_hybrid_parallel_model(cfg, plan_b.hp, devices8)
    p_got, st_got, _ = ck.load_checkpoint(d, target=m_b, tx=tx,
                                          strict_strategy=False)
    assert_global_params_equal(p_got, p_a)


def test_autotune_replan_ladder_trades_chunks_against_remat():
    """The autotuner's re-plan recipe (measured tables through
    search_surviving_strategy with settle_chunk=None) walks a budget
    ladder: loose budgets keep chunks=1; squeezing the budget makes the
    remat-off planner buy memory with MORE CHUNKS, while the remat axis
    lets the planner keep chunks=1 by checkpointing a few layers with the
    cheaper dots_saveable policy instead — chunks and remat are one
    trade, which is why the re-plan must search them together. Pure
    python DP over mock measured tables, milliseconds."""
    from types import SimpleNamespace

    time_cfg = {"layertype_0": 5.3, "other_time": 2.0}
    mem_cfg = {
        "layertype_0": {
            "parameter_size": 96.0,
            "tp_activation_per_bsz_dict": {
                1: 500.0, 2: 260.0, 4: 140.0, 8: 80.0, "checkpoint": 30.0},
        },
        "other_memory_pp_off": {
            "model_states": {1: 3000.0, 2: 1500.0, 4: 750.0, 8: 375.0},
            "activation": {1: 80.0, 2: 42.0, 4: 22.0, 8: 12.0},
        },
        "other_memory_pp_on": {
            "first_stage": {
                "model_states": {1: 2000.0, 2: 1000.0, 4: 500.0, 8: 250.0},
                "activation": {1: 50.0, 2: 26.0, 4: 14.0, 8: 8.0}},
            "last_stage": {
                "model_states": {1: 1500.0, 2: 750.0, 4: 375.0, 8: 190.0},
                "activation": {1: 30.0, 2: 16.0, 4: 8.0, 8: 5.0}},
        },
    }
    cfg = SimpleNamespace(num_heads=1, num_layers=8, max_seq_len=2048,
                          hidden_size=4096)

    def replan(gb, remat_search):
        return els.search_surviving_strategy(
            cfg, 8, 16, gb, time_config=time_cfg, memory_config=mem_cfg,
            remat_search=remat_search)

    # loose budget: nothing to trade — chunks=1, no checkpoints, either way
    for rs in (False, True):
        hp = replan(12.0, rs)
        assert hp.chunks == 1
        assert all(s.checkpoint == 0 for s in hp.layers)

    # tight budget, remat off: the re-plan CHANGES CHUNKS to fit
    hp_off = replan(8.0, False)
    assert hp_off.chunks == 2
    assert all(s.checkpoint == 0 for s in hp_off.layers)

    # same budget, remat on: a mixed dots_saveable plan is cheaper than
    # chunking — the re-plan keeps chunks=1 and checkpoints a slice
    hp_on = replan(8.0, True)
    assert hp_on.chunks == 1
    eff = [s.effective_remat_policy for s in hp_on.layers]
    assert "dots_saveable" in eff and "none" in eff
    assert 0 < sum(s.checkpoint for s in hp_on.layers) < len(hp_on.layers)
