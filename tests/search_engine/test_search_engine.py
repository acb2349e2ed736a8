"""End-to-end search over mock profiles (reference
tests/search_engine/test_parallelsim_optimization.py style, pure CPU)."""

import numpy as np
import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.search.engine import (
    GalvatronSearchEngine,
    SearchArgs,
    generate_strategies,
    pp_division_memory_balanced,
)

pytestmark = [pytest.mark.search_engine]

ALLREDUCE_BW = {
    "allreduce_size_8_consec_1": 150.0,
    "allreduce_size_4_consec_1": 155.0,
    "allreduce_size_4_consec_0": 150.0,
    "allreduce_size_2_consec_1": 130.0,
    "allreduce_size_2_consec_0": 145.0,
}
P2P_BW = {"pp_size_2": 160.0, "pp_size_4": 140.0, "pp_size_8": 110.0}
TIME_CONFIG = {"layertype_0": 5.3, "other_time": 2.0}
MEMORY_CONFIG = {
    "layertype_0": {
        "parameter_size": 96.0,
        "tp_activation_per_bsz_dict": {1: 500.0, 2: 260.0, 4: 140.0, 8: 80.0, "checkpoint": 30.0},
    },
    "other_memory_pp_off": {
        "model_states": {1: 3000.0, 2: 1500.0, 4: 750.0, 8: 375.0},
        "activation": {1: 80.0, 2: 42.0, 4: 22.0, 8: 12.0},
    },
    "other_memory_pp_on": {
        "first_stage": {"model_states": {1: 2000.0, 2: 1000.0, 4: 500.0, 8: 250.0},
                        "activation": {1: 50.0, 2: 26.0, 4: 14.0, 8: 8.0}},
        "last_stage": {"model_states": {1: 1500.0, 2: 750.0, 4: 375.0, 8: 190.0},
                       "activation": {1: 30.0, 2: 16.0, 4: 8.0, 8: 5.0}},
    },
}


def make_engine(mem_gb=16.0, world=8, layers=8, **kw):
    args = SearchArgs(memory_constraint=mem_gb, settle_bsz=kw.pop("bsz", 16),
                      settle_chunk=kw.pop("chunk", 2), max_tp_deg=8, **kw)
    eng = GalvatronSearchEngine(
        args, world, [{"hidden_size": 4096, "seq_len": 2048, "layer_num": layers}],
        model_name="mock",
    )
    eng.set_model_profiles(TIME_CONFIG, MEMORY_CONFIG)
    eng.set_hardware_profiles(ALLREDUCE_BW, P2P_BW, {"overlap_coe": 1.12})
    eng.initialize_search_engine()
    return eng


def test_generate_strategies_filters():
    args = SearchArgs()
    s_full = generate_strategies(8, args)
    assert any(s[0] == 4 for s in s_full)
    assert any(s[1] == 8 for s in s_full)
    assert any(s[3].get("fsdp") for s in s_full)
    s_dp = generate_strategies(8, SearchArgs(search_space="dp"))
    assert all(s[0] == 1 and s[1] == 1 for s in s_dp)
    s_notp = generate_strategies(8, SearchArgs(disable_tp=True))
    assert all(s[1] == 1 for s in s_notp)
    s_sp = generate_strategies(8, SearchArgs(sp_space="tp+sp"))
    assert any(s[3].get("sp") for s in s_sp)
    # degrees multiply back to world size per stage
    for s in s_full:
        assert (8 // s[0]) % (s[1] * s[3].get("cp", 1)) == 0


def test_pp_division_memory_balanced():
    costs = [10.0] * 4 + [30.0] * 4
    div = pp_division_memory_balanced(costs, 2)
    assert sum(div) == 8 and len(div) == 2
    # heavier tail -> first stage gets more layers
    assert div[0] > div[1]
    assert pp_division_memory_balanced(costs, 1) == [8]


def test_search_returns_feasible_config(tmp_path):
    eng = make_engine(mem_gb=16.0)
    best = eng.parallelism_optimization()
    assert best is not None and np.isfinite(best["cost"])
    path = eng.save_results(best, str(tmp_path / "out.json"))
    cfg = HybridParallelConfig.from_json(path, world_size=8)
    assert cfg.num_layers == 8
    assert cfg.global_bsz == 16


def test_tight_memory_forces_sharding_or_ckpt():
    roomy = make_engine(mem_gb=24.0).parallelism_optimization()
    tight = make_engine(mem_gb=7.0).parallelism_optimization()
    assert roomy is not None and tight is not None

    def mem_savers(result):
        return sum(
            s[3].get("fsdp", 0) + s[3].get("cpt", 0) + (s[1] > 1) + (s[0] > 1)
            for s in result["strategies"]
        )

    assert mem_savers(tight) >= mem_savers(roomy)
    assert tight["cost"] >= roomy["cost"] - 1e-9  # saving memory costs time


def test_infeasible_budget_returns_none():
    eng = make_engine(mem_gb=0.5)
    assert eng.parallelism_optimization() is None


def test_search_prefers_cheap_comm():
    """With free compute and expensive comm, pure strategies with less
    communication should win over tp-heavy ones."""
    eng = make_engine(mem_gb=64.0)
    best = eng.parallelism_optimization()
    tps = {s[1] for s in best["strategies"]}
    # roomy memory -> no need for tp=8 everywhere
    assert min(tps) <= 4


def test_pp_space_excludes_dp_and_tp():
    """search_space='pp' must return only pure-pipeline layouts."""
    s = generate_strategies(8, SearchArgs(search_space="pp"))
    assert s, "pp space empty"
    assert all(st[1] == 1 and st[2] == 1 for st in s), s


def test_3d_space_is_plain_grid():
    """'3d' = pp x tp x dp without sp/zero/ckpt/placement variants."""
    s = generate_strategies(8, SearchArgs(search_space="3d"))
    assert s
    for st in s:
        info = st[3]
        assert not (set(info) & {"sp", "fsdp", "cpt"}), st
    # exactly one variant per (pp, tp, dp)
    keys = [(st[0], st[1], st[2]) for st in s]
    assert len(keys) == len(set(keys))


def test_dp_exceeding_bsz_is_pruned():
    """dp > bsz (or non-dividing dp) must never be returned as a winner:
    the runtime config would reject it."""
    eng = make_engine(mem_gb=64.0, bsz=4, chunk=1)
    best = eng.parallelism_optimization()
    assert best is not None
    for st in best["strategies"]:
        assert st[2] <= 4 and 4 % st[2] == 0
    cfg = eng.result_to_config(best)  # validates without raising


def test_ulysses_compute_parity_with_tp():
    """Ulysses shards per-device compute tp-fold just like megatron-tp; the
    time model must not overcharge sp strategies (they'd never be chosen)."""
    from galvatron_tpu.search.cost_model import TimeCostModel
    from galvatron_tpu.search.cost_model_args import (
        ModelArgs, ParallelArgs, ProfileHardwareArgs, ProfileModelArgs, TrainArgs)

    common = dict(
        global_batch_size=16,
        model_args=ModelArgs(parameter_size=96.0, seq_length=2048, hidden_size=4096, layer_num=8),
        train_args=TrainArgs(mixed_precision=True),
        parallel_args=ParallelArgs(sp_space="tp+sp"),
        profile_model_args=ProfileModelArgs(
            forward_computation_time=5.0,
            tp_activation_per_bsz_dict=MEMORY_CONFIG["layertype_0"]["tp_activation_per_bsz_dict"],
            other_memory_pp_off=MEMORY_CONFIG["other_memory_pp_off"],
            other_memory_pp_on=MEMORY_CONFIG["other_memory_pp_on"],
            other_time_profiled=2.0),
        profile_hardware_args=ProfileHardwareArgs(
            comm_coe_dict={"1": 0.0, "2": 0.008, "4": 0.009, "8": 0.01},
            allreduce_dict={2: {"popt": [0.01, 0.1]}, 4: {"popt": [0.01, 0.1]}, 8: {"popt": [0.01, 0.1]}},
            all2all_dict={2: {"popt": [0.005, 0.1]}, 4: {"popt": [0.005, 0.1]}, 8: {"popt": [0.005, 0.1]}}),
    )
    t_tp = TimeCostModel([1, 4, 2, {"tp": 1}], **common).gen_result()
    t_sp = TimeCostModel([1, 4, 2, {"sp": 1}], **common).gen_result()
    # same compute share; only the collective pattern differs -> within 2x
    assert t_sp < 2.0 * t_tp


# ------------------------------------------------- inter-layer transition cost
def _bare_dpom():
    """A DpOnModel shell with just the state _inter_layer_cost reads."""
    from galvatron_tpu.search.cost_model_args import ModelArgs, TrainArgs
    from galvatron_tpu.search.dynamic_programming import DpOnModel

    d = object.__new__(DpOnModel)
    d.model_args_list = [ModelArgs(seq_length=128, hidden_size=64)]
    d.train_args_list = [TrainArgs(mixed_precision=False)]
    d.comm_coe_dict = {"2": 0.01, "4_1": 0.02, "4_0": 0.03}
    d.sequence_parallel = True
    d._reshard_coe = 0.01
    return d


def test_inter_layer_cost_cases():
    """The per-case table (reference dynamic_programming.py:290-372): growing
    tp costs, shrinking does not (megatron-sp retile aside), tp_consec flips
    cost, identical strategies are free, and the consecutivity of the larger
    side picks the coefficient."""
    d = _bare_dpom()
    s_tp1 = [1, 1, 8, {}]
    s_tp2 = [1, 2, 4, {"tp": 1}]
    s_tp4 = [1, 4, 2, {"tp": 1}]
    s_tp4n = [1, 4, 2, {"tp": 0}]
    strats = [s_tp1, s_tp2, s_tp4, s_tp4n]
    cost = d._inter_layer_cost(strats, 0, mbsz=2, min_tp=1)
    i1, i2, i4, i4n = 0, 1, 2, 3
    assert cost[i1, i1] == 0.0
    assert cost[i1, i2] > 0.0            # tp grows
    assert cost[i2, i4] > cost[i1, i2]   # wider group moves more
    assert cost[i4, i4n] > 0.0           # consecutivity flip retiles
    # the larger-tp side's consecutivity selects minor vs major coefficient
    assert cost[i1, i4n] > cost[i1, i4]
    # without megatron-sp, shrinking tp needs no boundary collective
    d.sequence_parallel = False
    cost2 = d._inter_layer_cost(strats, 0, mbsz=2, min_tp=1)
    assert cost2[i4, i2] == 0.0 and cost2[i2, i4] > 0.0


def test_inter_layer_tiebreak_ordering():
    """Equivalent variants order deterministically: entering sp is cheapest,
    then fsdp, then ckpt, then fsdp+ckpt (reference :347-371)."""
    d = _bare_dpom()
    base = [1, 2, 4, {"tp": 1}]
    sp = [1, 2, 4, {"tp": 1, "sp": 1}]
    fsdp = [1, 2, 4, {"tp": 1, "fsdp": 1}]
    cpt = [1, 2, 4, {"tp": 1, "cpt": 1}]
    both = [1, 2, 4, {"tp": 1, "fsdp": 1, "cpt": 1}]
    strats = [base, sp, fsdp, cpt, both]
    cost = d._inter_layer_cost(strats, 0, mbsz=2, min_tp=1)
    assert cost[0, 1] < cost[0, 2] < cost[0, 3] < cost[0, 4]


def test_sp_space_sweep_changes_winner():
    """The sp-sub-space dimension must be able to change the winner: with an
    all2all table that makes ulysses communication ~free and an expensive
    allreduce table, sp_space='tp+sp' finds an sp winner that
    sp_space='tp' cannot (the round-2 search had no sp-space sweep)."""
    slow_ar = {k: 2.0 for k in ALLREDUCE_BW}          # ~zero bandwidth
    cheap_a2a = {"all2all": {"2": {"popt": [1e-6, 0.0]}, "4": {"popt": [1e-6, 0.0]},
                             "8": {"popt": [1e-6, 0.0]}}}

    def run(sp_space):
        args = SearchArgs(memory_constraint=16.0, settle_bsz=16, settle_chunk=2,
                          max_tp_deg=8, sp_space=sp_space, disable_pp=True)
        eng = GalvatronSearchEngine(
            args, 8, [{"hidden_size": 4096, "seq_len": 2048, "layer_num": 8}],
            model_name="mock",
        )
        eng.set_model_profiles(TIME_CONFIG, MEMORY_CONFIG)
        eng.set_hardware_profiles(slow_ar, P2P_BW, {"overlap_coe": 1.12},
                                  sp_time_config=cheap_a2a)
        eng.initialize_search_engine()
        return eng.parallelism_optimization()

    tp_only = run("tp")
    mixed = run("tp+sp")
    assert mixed is not None
    uses_sp = any((s[3] if len(s) > 3 else {}).get("sp") for s in mixed["strategies"])
    assert uses_sp, mixed["strategies"]
    if tp_only is not None:
        assert 16.0 / mixed["cost"] >= 16.0 / tp_only["cost"]


def test_search_log_dir_writes_task_files(tmp_path):
    """--log_dir produces one log file per outer-loop task (reference
    get_thread_logger, search_engine/utils.py:9-32)."""
    eng = make_engine(log_dir=str(tmp_path))
    eng.parallelism_optimization()
    logs = list(tmp_path.rglob("*.log"))
    assert logs, "no per-task log files written"
    text = "\n".join(p.read_text() for p in logs)
    assert "start: bsz=" in text
    assert "result: cost=" in text or "no feasible strategies" in text


def test_uneven_pp_division_searched_and_trains(devices8):
    """6 layers with pp=4 in the space: the search emits a memory-balanced
    UNEVEN division (generic 1F1B accepts it; reference slices arbitrary
    model_ranks, pipeline.py:110-112) and the emitted config trains."""
    eng = make_engine(layers=6, bsz=8, chunk=2, search_space="dp+pp",
                      max_pp_deg=4, disable_vtp=True)
    div = eng._pp_stage_dict(eng._bundles(2))
    assert 4 in div and sum(div[4]) == 6 and len(div[4]) == 4
    best = eng.parallelism_optimization()
    assert best is not None
    hp = eng.result_to_config(best)
    if hp.pp == 4:
        assert hp.pp_division == div[4]
    # train one step whatever the winner is
    import jax
    import jax.numpy as jnp
    import numpy as np

    from galvatron_tpu.models.config import TransformerConfig
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

    cfg = TransformerConfig(hidden_size=64, num_heads=4, num_layers=6,
                              vocab_size=128, max_seq_len=32,
                              compute_dtype=jnp.float32)
    m = construct_hybrid_parallel_model(cfg, hp, devices8)
    p = m.init_params(jax.random.PRNGKey(0))
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=1, total_steps=4))
    st = m.init_opt_state(tx, p)
    step = m.make_train_step(tx)
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (hp.global_bsz, 32)))
    batch = m.shard_batch(dict(
        tokens=tokens,
        positions=jnp.broadcast_to(jnp.arange(32), (hp.global_bsz, 32)),
        labels=jnp.roll(tokens, -1, 1),
    ))
    p, st, mets = step(p, st, batch)
    assert np.isfinite(float(mets["loss"]))


def test_mid_stage_type_boundary_flag_relaxes_filter():
    """Families whose pipeline engine accepts mid-stage layer-type boundaries
    (swin patch merges, validate_swin_config) must not lose pp configs to the
    enc-dec alignment requirement (advisor r3): depths like (1,3) at pp=2 put
    the type boundary inside stage 0 yet are runnable."""
    layer_cfgs = [
        {"hidden_size": 4096, "seq_len": 2048, "layer_num": 1},
        {"hidden_size": 4096, "seq_len": 2048, "layer_num": 3},
    ]
    time_cfg = {"layertype_0": 5.3, "layertype_1": 5.3, "other_time": 2.0}
    mem_cfg = dict(MEMORY_CONFIG)
    mem_cfg["layertype_1"] = MEMORY_CONFIG["layertype_0"]

    def run(align):
        eng = GalvatronSearchEngine(
            SearchArgs(memory_constraint=16.0, settle_bsz=8, settle_chunk=1,
                       search_space="pp", max_pp_deg=2),
            2, layer_cfgs, model_name="mock_midstage",
            align_type_boundaries=align,
        )
        eng.set_model_profiles(time_cfg, mem_cfg)
        eng.set_hardware_profiles(ALLREDUCE_BW, P2P_BW, {"overlap_coe": 1.12})
        eng.initialize_search_engine()
        return eng.parallelism_optimization()

    assert run(True) is None  # boundary at layer 1, lps=2 -> filtered out
    relaxed = run(False)
    assert relaxed is not None and relaxed["pp"] == 2


def test_no_sequence_sharding_filters_sp_at_any_pp():
    """Families without a shardable sequence dimension (swin,
    supports_sequence_sharding=False) must not receive cp/ulysses-sp
    strategies even at pp=1, where validate_swin_config is the only other
    line of defense (code-review r4)."""

    def run(allow):
        args = SearchArgs(memory_constraint=16.0, settle_bsz=16, settle_chunk=2,
                          sp_space="sp", max_tp_deg=8, max_pp_deg=1)
        eng = GalvatronSearchEngine(
            args, 8, [{"hidden_size": 4096, "seq_len": 2048, "layer_num": 8}],
            model_name="mock_noseq", allow_sequence_sharding=allow,
        )
        eng.set_model_profiles(TIME_CONFIG, MEMORY_CONFIG)
        sp_tables = {
            "allreduce": {str(k): {"popt": [0.01, 0.05]} for k in (2, 4, 8)},
            "all2all": {str(k): {"popt": [0.005, 0.05]} for k in (2, 4, 8)},
        }
        eng.set_hardware_profiles(ALLREDUCE_BW, P2P_BW, {"overlap_coe": 1.12},
                                  sp_tables)
        eng.initialize_search_engine()
        return eng.parallelism_optimization()

    allowed = run(True)
    assert allowed is not None and any(
        s[3].get("sp") for s in allowed["strategies"] if len(s) > 3
    )
    blocked = run(False)
    # sp-only space with sp filtered out: only sp-free strategies (tp=1
    # carries no sp flag) or nothing may be emitted
    assert blocked is None or not any(
        s[3].get("sp") for s in blocked["strategies"] if len(s) > 3
    )


# ------------------------------------------- comm-precision axis (ISSUE 9)
def _quant_engine(bw_gbps, quant_coe, budget=1.0, comm_quant="int8"):
    allreduce = {"allreduce_size_%d_consec_1" % d: bw_gbps for d in (2, 4, 8)}
    args = SearchArgs(memory_constraint=16.0, settle_bsz=16, settle_chunk=2,
                      search_space="dp", disable_pp=True, disable_tp=True,
                      disable_vtp=True, comm_quant=comm_quant,
                      comm_quant_budget=budget)
    eng = GalvatronSearchEngine(
        args, 8, [{"hidden_size": 4096, "seq_len": 2048, "layer_num": 8}],
        model_name="mock")
    eng.set_model_profiles(TIME_CONFIG, MEMORY_CONFIG)
    eng.set_hardware_profiles(
        allreduce, None,
        {"overlap_coe": 1.12, "quant_overhead_coe": quant_coe})
    eng.initialize_search_engine()
    return eng


def _gcds(best):
    return [(s[3] if len(s) > 3 else {}).get("gcd", "none")
            for s in best["strategies"]]


def test_search_picks_int8_when_bandwidth_dominated():
    """Slow interconnect (2 GB/s) + cheap quantization: the grad-sync bytes
    dominate the step, so every layer flips to the int8 wire."""
    best = _quant_engine(2.0, 0.001).parallelism_optimization()
    assert best is not None
    assert all(g == "int8" for g in _gcds(best)), _gcds(best)


def test_search_keeps_fp32_when_compute_dominated():
    """Fast interconnect + an expensive quantize/dequantize toll: the sync
    is already cheap, so quantization only adds overhead and loses."""
    best = _quant_engine(500.0, 5.0).parallelism_optimization()
    assert best is not None
    assert all(g == "none" for g in _gcds(best)), _gcds(best)


def test_search_accuracy_budget_caps_quantized_fraction():
    best = _quant_engine(2.0, 0.001, budget=0.5).parallelism_optimization()
    assert best is not None
    assert sum(1 for g in _gcds(best) if g == "int8") == 4, _gcds(best)


def test_quantized_winner_round_trips_save_lint_load(tmp_path):
    """Acceptance criterion: the emitted strategy JSON carries per-layer
    comm-precision fields and survives save_results' lint gate, a reload,
    and a fresh lint with no GLS refusals."""
    from galvatron_tpu.analysis import strategy_lint as slint

    eng = _quant_engine(2.0, 0.001)
    best = eng.parallelism_optimization()
    path = eng.save_results(best, str(tmp_path / "quant.json"))
    cfg = HybridParallelConfig.from_json(path, world_size=8)
    assert all(s.grad_comm_dtype == "int8" for s in cfg.layers)
    report = slint.lint_strategy_file(path, 8)
    assert report.ok, report.render()
    # zero3 layers in the space also carry the quantized param gather
    import json

    with open(path) as f:
        d = json.load(f)
    assert "grad_comm_dtype" in d and "comm_quant_block" in d


def test_comm_quant_off_leaves_space_unchanged():
    s_off = generate_strategies(8, SearchArgs())
    assert not any(
        (s[3] if len(s) > 3 else {}).get("gcd") for s in s_off)
    s_on = generate_strategies(8, SearchArgs(comm_quant="int8"))
    quant = [s for s in s_on if (s[3] if len(s) > 3 else {}).get("gcd")]
    assert quant
    # variants exist only where the quantized ring can run (pure dp, dp>1)
    assert all(s[0] == 1 and s[1] == 1 and s[2] > 1
               and not s[3].get("sp") for s in quant)
    # zero3 variants carry the quantized param gather too
    assert any(s[3].get("fsdp") and s[3].get("pcd") == "int8" for s in quant)


# ------------------------------------------- remat search axis (ISSUE 15)
def test_remat_search_variants_generated():
    """remat_search adds a dots_saveable variant for every checkpointed
    strategy — and ONLY those (none ≡ cpt=0 is already in the space, full
    is the cpt=1 default, nothing_saveable prices like full)."""
    base = generate_strategies(8, SearchArgs())
    remat = generate_strategies(8, SearchArgs(remat_search=True))
    extra = [s for s in remat if s[3].get("rp")]
    assert extra and all(s[3]["rp"] == "dots_saveable" for s in extra)
    assert all(s[3].get("cpt", s[3].get("ckpt", 0)) for s in extra)
    assert len(remat) == len(base) + len(extra)


def test_remat_search_steering_by_budget(tmp_path):
    """Loose budget: remat never engages (the plan matches the remat-off
    search). Tight budget infeasible for all-none: the DP mixes per-layer
    dots_saveable checkpointing and beats the full-remat-only search's
    cost — and the emitted mixed plan round-trips through the on-disk JSON
    and lints clean."""
    from galvatron_tpu.analysis import strategy_lint as SL

    def plan(result):
        return [(s[3].get("cpt", s[3].get("ckpt", 0)),
                 s[3].get("rp", "full")) for s in result["strategies"]]

    # loose: nothing checkpoints, so the remat axis stays untouched
    loose = make_engine(mem_gb=24.0, remat_search=True).parallelism_optimization()
    assert all(c == 0 for c, _ in plan(loose))

    # tight: all-none is infeasible (the no-ckpt engine of the same budget
    # must checkpoint), and the remat-aware DP finds a cheaper MIXED plan
    tight_off = make_engine(mem_gb=5.0).parallelism_optimization()
    tight_on_eng = make_engine(mem_gb=5.0, remat_search=True)
    tight_on = tight_on_eng.parallelism_optimization()
    assert any(c for c, _ in plan(tight_off))  # budget forces checkpointing
    cpts = [c for c, _ in plan(tight_on)]
    assert 0 < sum(cpts) < len(cpts), plan(tight_on)  # mixed, not uniform
    assert any(rp == "dots_saveable" for c, rp in plan(tight_on) if c)
    assert tight_on["cost"] <= tight_off["cost"] + 1e-9

    # the mixed plan is a first-class on-disk strategy
    path = tight_on_eng.save_results(tight_on, str(tmp_path / "mixed.json"))
    cfg = HybridParallelConfig.from_json(path, world_size=8)
    policies = [s.effective_remat_policy for s in cfg.layers]
    assert "dots_saveable" in policies and "none" in policies
    report = SL.lint_strategy_file(path, 8)
    assert report.ok and not report.warnings, report.render()
