"""Cost-model-vs-compiler memory validation (north-star metric #2:
peak HBM vs cost-model prediction, BASELINE.json)."""

import pytest

import jax
import jax.numpy as jnp

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.profiler.model import ModelProfileArgs, ModelProfiler
from galvatron_tpu.profiler.validate import validate_memory

pytestmark = [pytest.mark.profiler]
# The 1F1B engines compile and run on the installed jax. One parity case per
# engine (generic, enc-dec, Swin) stays in tier-1; the other compile-heavy
# cases are `slow`, so that tier-1 still ends inside its clock.



@pytest.fixture(scope="module")
def cfg():
    return gpt_config(
        "gpt-0.3b", hidden_size=128, num_heads=4, num_layers=4, vocab_size=512,
        max_seq_len=128, compute_dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def memory_config(cfg):
    args = ModelProfileArgs(
        profile_batch_size=4, layernum_min=1, layernum_max=3, warmup=0, iters=1,
        max_tp_deg=2, mixed_precision="fp32",
    )
    return ModelProfiler(cfg, "gpt", args).profile_memory()


@pytest.mark.parametrize(
    "kw",
    [dict(tp=1), dict(tp=2, vocab_tp=2), dict(sdp=1), dict(tp=2, checkpoint=1)],
    ids=["dp8", "tp2", "zero3", "tp2_ckpt"],
)
def test_prediction_within_2x_of_compiled(cfg, memory_config, kw, devices8):
    hp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=8, **kw)
    v = validate_memory(cfg, hp, memory_config)
    assert v.measured_mb > 0 and v.predicted_mb > 0
    # layer-differenced tables + compiler-reported footprint won't agree to
    # the MB on tiny CPU-mesh models; the contract is the right ORDER — the
    # reference's search quality depends on exactly this fidelity
    assert 0.4 < v.ratio < 2.5, (kw, v)


@pytest.mark.parametrize(
    "kw",
    [dict(pp=2, chunks=2), dict(pp=2, tp=2, vocab_tp=2, chunks=2),
     dict(pp=4, chunks=4), dict(pp=2, chunks=2, checkpoint=1)],
    ids=["pp2", "pp2_tp2", "pp4", "pp2_ckpt"],
)
@pytest.mark.slow
def test_1f1b_prediction_within_20pct(cfg, memory_config, kw, devices8):
    """North-star metric #2 for the schedule the search actually emits: the
    1F1B memory model (stash + engine buffers + replicated-grad states +
    pp-sharded vocab, cost_model.py pipedream branch) must track the
    compiler-measured per-chip footprint. Measured on this mesh: ratios
    1.02-1.16 across these configs; the bound leaves cross-host headroom."""
    hp = HybridParallelConfig.uniform(
        8, cfg.num_layers, global_bsz=8, pipeline_type="pipedream_flush", **kw
    )
    v = validate_memory(cfg, hp, memory_config)
    assert 0.8 < v.ratio < 1.2, (kw, v)


def test_zero3_predicts_less_param_memory_than_ddp(cfg, memory_config, devices8):
    ddp = validate_memory(cfg, HybridParallelConfig.uniform(8, 4, global_bsz=8), memory_config)
    z3 = validate_memory(cfg, HybridParallelConfig.uniform(8, 4, global_bsz=8, sdp=1), memory_config)
    assert z3.predicted_layers_mb < ddp.predicted_layers_mb
    assert z3.measured_mb < ddp.measured_mb


def test_measured_strategy_activation_rows(cfg, memory_config, devices8):
    """The multi-device profile writes MEASURED ulysses_k / cp_k activation
    rows (reference measures per-strategy, model_profiler.py:374-559), and
    the memory model consumes them: predictions for ulysses/cp configs stay
    order-correct."""
    act = memory_config["layertype_0"]["tp_activation_per_bsz_dict"]
    assert "ulysses_2" in act, sorted(map(str, act))
    assert "cp_2" in act, sorted(map(str, act))
    # measured footprints are positive and within an order of the derivation
    for key in ("ulysses_2", "cp_2"):
        assert 0.1 * act[1] / 2 < act[key] < 10 * act[1], (key, act)
    for kw in (dict(tp=2, sp=1), dict(cp=2)):
        hp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=8, **kw)
        v = validate_memory(cfg, hp, memory_config)
        assert 0.4 < v.ratio < 2.5, (kw, v)


@pytest.fixture(scope="module")
def time_config(cfg):
    args = ModelProfileArgs(
        profile_batch_size=4, layernum_min=1, layernum_max=3, warmup=0, iters=2,
        max_tp_deg=2, mixed_precision="fp32", profile_mode="batch",
        profile_min_batch_size=1, profile_max_batch_size=4, batch_size_step=1,
    )
    return ModelProfiler(cfg, "gpt", args).profile_computation()


@pytest.fixture(scope="module")
def hw_profiles(devices8):
    from galvatron_tpu.profiler.hardware import HardwareProfileArgs, HardwareProfiler

    hargs = HardwareProfileArgs(start_mb=0.25, end_mb=0.25, warmup=0, iters=1,
                                max_tp_deg=2)
    return HardwareProfiler(hargs, devices=devices8).profile_all(write=False)


@pytest.mark.parametrize("kw", [dict(pp=2, chunks=2), dict(pp=4, chunks=4)],
                         ids=["pp2", "pp4"])
@pytest.mark.slow
def test_time_prediction_pipedream(cfg, time_config, memory_config, hw_profiles,
                                   kw, devices8):
    """Predicted-vs-measured STEP TIME, the TimeCostModel analogue of the
    memory validation (VERDICT r4 item 8). The profiled per-layer tables come
    from the SAME serialising virtual-mesh host the measurement runs on, so
    the host distortion largely cancels — measured ratios here are 1.0-1.3;
    the band tolerates CI noise while catching order-of-magnitude
    mispricing. Real-chip runs use the same entry point for the true
    per-chip contract."""
    from galvatron_tpu.profiler.validate import validate_time

    hp = HybridParallelConfig.uniform(
        8, cfg.num_layers, global_bsz=8, pipeline_type="pipedream_flush", **kw
    )
    v = validate_time(cfg, hp, time_config, memory_config, hw_profiles)
    assert v.predicted_ms > 0 and v.measured_ms > 0, v
    assert 0.25 < v.ratio < 4.0, v


def test_split_prices_comm_into_owning_slot(memory_config, time_config,
                                            hw_profiles):
    """The fwd/bwd slot split (search/cost_model.gen_result_split): DP grad
    allreduce rides the backward slot ONLY; TP collectives split 1:2; the
    parts always sum exactly to gen_result."""
    from galvatron_tpu.profiler.validate import _hw_dicts
    from galvatron_tpu.search.cost_model import TimeCostModel
    from galvatron_tpu.search.cost_model_args import (
        ModelArgs,
        ParallelArgs,
        ProfileHardwareArgs,
        ProfileModelArgs,
        TrainArgs,
    )

    hwp = _hw_dicts(hw_profiles)
    comm, p2p, coe = hwp["comm_coe_dict"], hwp["p2p_coe_dict"], hwp["overlap_coe"]
    kw = dict(
        global_batch_size=8,
        model_args=ModelArgs(
            parameter_size=memory_config["layertype_0"]["parameter_size"],
            seq_length=128, hidden_size=128, layer_num=4),
        train_args=TrainArgs(mixed_precision=False),
        parallel_args=ParallelArgs(chunks=2),
        profile_model_args=ProfileModelArgs(
            forward_computation_time=time_config["layertype_0"],
            tp_activation_per_bsz_dict=memory_config["layertype_0"]["tp_activation_per_bsz_dict"]),
        profile_hardware_args=ProfileHardwareArgs(
            comm_coe_dict=comm, dp_overlap_coe=coe, bct_overlap_coe=coe,
            p2p_comm_coe_dict=p2p),
    )
    for strat in ([2, 1, 4, {}], [2, 2, 2, {}], [2, 2, 2, {"fsdp": 1}],
                  [2, 1, 4, {"cp": 1}], [1, 2, 4, {"sp": 1}]):
        m = TimeCostModel(strat, **kw)
        f, b = m.gen_result_split()
        assert f + b == pytest.approx(m.gen_result(), rel=1e-12), strat
    # dp-only at pp=1 (no p2p term): every comm term lands in the backward
    # slot, fwd is pure compute
    m = TimeCostModel([1, 1, 8, {}], **kw)
    f, b = m.gen_result_split()
    scale = m.pha.costmodel_coe / m.layer_num
    assert f == pytest.approx(m.fct * scale, rel=1e-9)
    assert b > m.bct * scale  # backward carries the dp allreduce
    # at pp=2 the p2p charge splits 1:1 — fwd is compute plus half the p2p
    m = TimeCostModel([2, 1, 4, {}], **kw)
    f2, b2 = m.gen_result_split()
    exp_p2p = m.p2p_message_size * m.p2p_comm_coe / 2 if m.p2p_comm_coe else 0.0
    assert f2 == pytest.approx((m.fct + exp_p2p) * scale, rel=1e-9)
    # tp collectives are symmetric (2 fwd + 2 bwd): split 1:1 un-checkpointed,
    # and 1:2 with activation checkpointing (the recompute replays the
    # forward collectives inside the backward slot)
    m = TimeCostModel([1, 2, 4, {"sp": 0}], **kw)
    if m.tp_communication_time > 0:
        f, b = m.gen_result_split()
        assert f == pytest.approx((m.fct + m.tp_communication_time / 2) * scale, rel=1e-9)
    mc = TimeCostModel([1, 2, 4, {"sp": 0, "cpt": 1}], **kw)
    if mc.tp_communication_time > 0:
        f, b = mc.gen_result_split()
        assert f == pytest.approx((mc.fct + mc.tp_communication_time / 3) * scale, rel=1e-9)
