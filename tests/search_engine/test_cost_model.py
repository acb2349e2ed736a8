"""Cost-model behavior (reference tests/search_engine/test_cost_model.py:19-60
style: parametrised strategy cases over mock profiled configs)."""

import numpy as np
import pytest

from galvatron_tpu.search.cost_model import MemoryCostModel, TimeCostModel, comm_coe
from galvatron_tpu.search.cost_model_args import (
    ModelArgs,
    ParallelArgs,
    ProfileHardwareArgs,
    ProfileModelArgs,
    TrainArgs,
)

pytestmark = [pytest.mark.search_engine]

ACT = {1: 500.0, 2: 260.0, 4: 140.0, 8: 80.0, "checkpoint": 30.0}
OTHER_OFF = {"model_states": {1: 1000.0, 2: 500.0, 4: 250.0}, "activation": {1: 80.0, 2: 42.0, 4: 22.0}}
OTHER_ON = {
    "first_stage": {"model_states": {1: 600.0, 2: 300.0, 4: 150.0}, "activation": {1: 50.0, 2: 26.0, 4: 14.0}},
    "last_stage": {"model_states": {1: 400.0, 2: 200.0, 4: 100.0}, "activation": {1: 30.0, 2: 16.0, 4: 8.0}},
}
COMM = {"8": 0.01, "4_0": 0.012, "4_1": 0.011, "2_0": 0.014, "2_1": 0.013, "1": 0.0}


def mk(strategy, bsz=8, chunks=1, use_zero2=False, **kw):
    return MemoryCostModel(
        strategy, global_batch_size=bsz, mbsz=1, min_tp=1, max_tp=4,
        model_args=ModelArgs(parameter_size=48.0, layer_num=8),
        train_args=TrainArgs(),
        parallel_args=ParallelArgs(chunks=chunks, use_zero2_for_dp=use_zero2),
        profile_model_args=ProfileModelArgs(
            tp_activation_per_bsz_dict=ACT,
            other_memory_pp_off=OTHER_OFF,
            other_memory_pp_on=OTHER_ON,
        ),
        **kw,
    ).get_memory_cost()


def tk(strategy, bsz=8, **kw):
    return TimeCostModel(
        strategy, global_batch_size=bsz,
        model_args=ModelArgs(parameter_size=48.0, seq_length=2048, hidden_size=4096, layer_num=8),
        train_args=TrainArgs(),
        parallel_args=ParallelArgs(),
        profile_model_args=ProfileModelArgs(forward_computation_time=5.0),
        profile_hardware_args=ProfileHardwareArgs(comm_coe_dict=COMM, p2p_comm_coe_dict={2: 0.01, 4: 0.012}),
        **kw,
    ).gen_result()


def test_tp_divides_parameters():
    m1 = mk([1, 1, 8, {}])
    m2 = mk([1, 2, 4, {}])
    assert np.isclose(m2["parameter"], m1["parameter"] / 2)
    # ulysses keeps full parameters
    m3 = mk([1, 2, 4, {"sp": 1}])
    assert np.isclose(m3["parameter"], m1["parameter"])


def test_zero_ratios_ordering():
    ddp = mk([1, 1, 8, {}])["model_states"]
    z2 = mk([1, 1, 8, {}], use_zero2=True)["model_states"]
    z3 = mk([1, 1, 8, {"fsdp": 1}])["model_states"]
    assert z3 < z2 < ddp
    # zero3 with grad accumulation keeps more state resident
    z3_acc = mk([1, 1, 8, {"fsdp": 1}], bsz=64, chunks=4)["model_states"]
    assert z3_acc > z3


def test_checkpoint_reduces_activation():
    base = mk([1, 2, 4, {}])["activation"]
    ckpt = mk([1, 2, 4, {"cpt": 1}])["activation"]
    assert ckpt < base


def test_chunks_reduce_activation_pp1():
    # bsz=64 so local_bsz=8 and chunks are not clamped
    a1 = mk([1, 1, 8, {}], bsz=64, chunks=1)["activation"]
    a4 = mk([1, 1, 8, {}], bsz=64, chunks=4)["activation"]
    assert a4 < a1
    # scan pipeline (pp>1) holds the whole local batch regardless of chunks
    p1 = mk([2, 1, 4, {}], bsz=64, chunks=1)["activation"]
    p4 = mk([2, 1, 4, {}], bsz=64, chunks=4)["activation"]
    assert np.isclose(p1, p4)


def test_other_memory_has_vtp_candidates_and_stages():
    other = mk([2, 2, 2, {}], bsz=8)["other"]
    assert set(other.keys()) >= {1, 2}
    assert len(other[1]) == 2  # per-stage
    assert other[1][0] > 0 and other[1][-1] > 0


def _other(strategy, use_zero2, vsp, pipeline_type="gpipe"):
    model = MemoryCostModel(
        strategy, global_batch_size=8, mbsz=1, min_tp=1, max_tp=4, vsp=vsp,
        model_args=ModelArgs(parameter_size=48.0, layer_num=8), train_args=TrainArgs(),
        parallel_args=ParallelArgs(chunks=2, use_zero2_for_dp=use_zero2, pipeline_type=pipeline_type),
        profile_model_args=ProfileModelArgs(
            tp_activation_per_bsz_dict=ACT, other_memory_pp_off=OTHER_OFF, other_memory_pp_on=OTHER_ON))
    return model, model.get_memory_cost()["other"]


@pytest.mark.parametrize("strategy,use_zero2,vsp", [
    ([2, 2, 2, {}], False, 0), ([2, 2, 2, {}], True, 0), ([4, 1, 2, {}], True, 0), ([2, 2, 2, {"sp": 1}], False, 1),
], ids=["pp2tp2dp2-ddp", "pp2tp2dp2-zero2", "pp4dp2-zero2", "pp2-vocab-sp"])
def test_scan_pipeline_holds_the_vocabulary_over_pp_on_every_stage(strategy, use_zero2, vsp):
    """What parallel/pipeline.py holds: the table's and the head's measured
    states split over ('pp',) + vocab_tp, `(ms_f + ms_l) * ratio / pp` on EVERY
    stage, the embedded batch whole and the head's activations on 1/pp of the
    columns; under vocab-SP both layers whole on every stage. The 1F1B branch
    stores the same share and keeps its transient copy beside it."""
    pp, tp, dp = strategy[:3]
    first, last = OTHER_ON["first_stage"], OTHER_ON["last_stage"]
    context = TrainArgs().runtime_context_mem
    model, other = _other(strategy, use_zero2, vsp)
    flush = _other(strategy, use_zero2, vsp, "pipedream_flush")[1]
    assert sorted(other) == sorted(flush) and len(other) >= 2
    for vtp, stages in other.items():
        ratio = model.zero2_ratio(tp * dp if vsp else tp * dp // vtp) if use_zero2 else 1.0
        ms = first["model_states"][1 if vsp else vtp] + last["model_states"][1 if vsp else vtp]
        a_f, a_l = first["activation"][vtp], last["activation"][vtp]
        over_pp, bsz = 1 if vsp else pp, 8 * vtp / (tp * dp)
        assert stages == pytest.approx([ms * ratio / over_pp + (a_f + a_l / over_pp) * bsz + context] * pp)
        assert flush[vtp] == pytest.approx([ms * ratio / pp + 0.5 * ms + (a_f + a_l) * bsz / 2 + context] * pp)


def test_time_comm_overhead_positive():
    # strategies at the same pp pay for their collectives vs a no-comm run
    t_tp = tk([1, 8, 1, {}])
    t_tp_nc = tk([1, 8, 1, {}], no_comm=True)
    assert t_tp > t_tp_nc
    t_dp = tk([1, 1, 8, {}])
    t_dp_nc = tk([1, 1, 8, {}], no_comm=True)
    assert t_dp > t_dp_nc


def test_time_checkpoint_adds_recompute():
    base = tk([1, 2, 4, {"tp": 1}])
    ck = tk([1, 2, 4, {"tp": 1, "cpt": 1}])
    assert ck > base


def test_fsdp_adds_allgather_time():
    base = tk([1, 1, 8, {}])
    f = tk([1, 1, 8, {"fsdp": 1}])
    assert f > base


def test_comm_coe_placement():
    assert comm_coe(COMM, 4, consec=True) == 0.011
    assert comm_coe(COMM, 4, consec=False) == 0.012
    assert comm_coe(COMM, 8) == 0.01
    assert comm_coe(COMM, 1) == 0.0


# ---------------------------------------------------------- other-time model
def ot(pp_deg, embed_sdp=False, vsp=0, dp_overlap_coe=1.2, min_tp=1, max_tp=4,
       allreduce_dict=None, seqs=None):
    from galvatron_tpu.search.cost_model import OtherTimeCostModel

    return OtherTimeCostModel(
        mbsz=2, pp_deg=pp_deg, world_size=8, vsp=vsp, embed_sdp=embed_sdp,
        min_tp=min_tp, max_tp=max_tp, sequence_length_list=seqs or [2048],
        model_args=ModelArgs(hidden_size=4096),
        train_args=TrainArgs(),
        parallel_args=ParallelArgs(),
        profile_model_args=ProfileModelArgs(
            other_time_profiled=2.0,
            other_memory_pp_off=OTHER_OFF,
            other_memory_pp_on=OTHER_ON,
        ),
        profile_hardware_args=ProfileHardwareArgs(
            comm_coe_dict=COMM, dp_overlap_coe=dp_overlap_coe,
            allreduce_dict=allreduce_dict or {},
        ),
    ).gen_result()


def test_other_time_stage_layout():
    """pp>1: only the embedding (first) and head (last) stages carry cost
    (reference gen_result, cost_model.py:648-658)."""
    res = ot(pp_deg=4)
    for k, stages in res.items():
        assert len(stages) == 4
        assert stages[0] > 0 and stages[-1] > 0
        assert stages[1] == 0 and stages[2] == 0


def test_other_time_embed_sdp_costs_more():
    """ZeRO-3 on embeddings adds the forward re-gather (fwd factor 0.5 vs 0)
    and doubles the backward factor (reference estimate_dp_time:621-625)."""
    plain = ot(pp_deg=2, embed_sdp=False)
    sdp = ot(pp_deg=2, embed_sdp=True)
    for k in plain:
        dp_deg = 8 // 2 // k
        if dp_deg > 1:
            assert sum(sdp[k]) > sum(plain[k])
        else:
            # no vocab dp group -> nothing to sync either way
            assert sum(sdp[k]) == sum(plain[k])


def test_other_time_vocab_tp_adds_message():
    """vocab-tp>1 pays the per-direction activation allreduce (priced from
    the measured table when present); k=1 and vsp pay none (reference
    estimate_tp_time:532-570)."""
    free = ot(pp_deg=2, allreduce_dict={"2": {"popt": [0.0, 0.0]}, "4": {"popt": [0.0, 0.0]}})
    paid = ot(pp_deg=2, allreduce_dict={"2": {"popt": [0.01, 0.1]}, "4": {"popt": [0.01, 0.1]}})
    assert sum(paid[2]) > sum(free[2])
    assert sum(paid[1]) == sum(free[1])  # no vocab-tp group at k=1
    vsp_paid = ot(pp_deg=2, vsp=1, allreduce_dict={"2": {"popt": [0.01, 0.1]}})
    vsp_free = ot(pp_deg=2, vsp=1, allreduce_dict={"2": {"popt": [0.0, 0.0]}})
    assert sum(vsp_paid[2]) == sum(vsp_free[2])  # vsp shards: no message


def test_other_time_dp_sync_overlaps_compute():
    """The vocab-state grad sync hides under compute up to dp_overlap_coe:
    with comm smaller than compute the stage cost approaches pure compute
    (reference get_overlap_time:634-645)."""
    fast_net = ot(pp_deg=1, dp_overlap_coe=1.0)
    slow_net = ot(pp_deg=1, dp_overlap_coe=2.0)
    for k in fast_net:
        assert sum(slow_net[k]) >= sum(fast_net[k]) - 1e-9


def test_other_time_pp1_single_seq_charges_tp_msg_once():
    """pp=1 charges two one-way messages (embed fwd allreduce + head bwd
    allreduce) via the reference's sum(seqs)+last rule — tp_msg itself is ONE
    message with no internal fwd+bwd doubling (advisor r3; reference
    estimate_tp_time, cost_model.py:533-567)."""
    table_free = {"2": {"popt": [0.0, 0.0]}, "4": {"popt": [0.0, 0.0]}}
    table_paid = {"2": {"popt": [0.01, 0.1]}, "4": {"popt": [0.01, 0.1]}}
    free = ot(pp_deg=1, allreduce_dict=table_free)
    paid = ot(pp_deg=1, allreduce_dict=table_paid)
    msg_mb = 2 * 2048 * 4096 * 2 / 1024 / 1024  # mbsz x seq x hidden, bf16
    two_msgs = 2 * (0.01 * msg_mb + 0.1)  # embed fwd + head bwd allreduce
    assert sum(paid[2]) - sum(free[2]) == pytest.approx(two_msgs)
    # multi-seq (T5-style): reference sums all seqs + last again
    paid2 = ot(pp_deg=1, allreduce_dict=table_paid, seqs=[2048, 1024])
    free2 = ot(pp_deg=1, allreduce_dict=table_free, seqs=[2048, 1024])
    msg_mb_dec = 2 * 1024 * 4096 * 2 / 1024 / 1024
    t5_total = (0.01 * msg_mb + 0.1) + 2 * (0.01 * msg_mb_dec + 0.1)
    assert sum(paid2[2]) - sum(free2[2]) == pytest.approx(t5_total)
    # pp>1 per-stage parity: each vocab stage pays exactly ONE message
    paid_pp = ot(pp_deg=2, allreduce_dict=table_paid)
    free_pp = ot(pp_deg=2, allreduce_dict=table_free)
    one_msg = 0.01 * msg_mb + 0.1
    assert paid_pp[2][0] - free_pp[2][0] == pytest.approx(one_msg)
    assert paid_pp[2][-1] - free_pp[2][-1] == pytest.approx(one_msg)


# ------------------------------------------------------ pipeline tick model
def test_schedule_mirror_matches_engine_tables():
    """schedule_total_time re-derives the 1F1B engine's slot equations
    without importing jax; pin it against build_schedule's actual tables."""
    from galvatron_tpu.parallel.pipeline_1f1b import build_schedule
    from galvatron_tpu.search.cost_model import schedule_total_time

    rng = np.random.RandomState(0)
    for pp in (2, 3, 4):
        for chunks in (1, 2, 4, 7):
            fwd = rng.uniform(1.0, 3.0, pp)
            bwd = rng.uniform(2.0, 6.0, pp)
            sch = build_schedule(pp, chunks)
            want = 0.0
            for t in range(sch.T):
                tick = 0.0
                for s in range(pp):
                    c = 0.0
                    if sch.fwd_valid[t, s]:
                        c += fwd[s]
                    if sch.bwd_valid[t, s]:
                        c += bwd[s]
                    tick = max(tick, c)
                want += tick
            got = schedule_total_time(fwd, bwd, pp, chunks)
            assert abs(got - want) < 1e-9, (pp, chunks, got, want)


def test_tick_pricing_orders_chunks_and_hits_steady_state():
    """More chunks amortise the bubble, and the per-microbatch cost
    approaches the engine's steady-state rate. NB the exact price EXCEEDS the
    old max(stage) x (chunks+pp) bound: the engine's fwd/bwd slot parities
    coincide per stage (build_schedule), so in the steady state stages of one
    parity idle while the other parity hosts fwd+bwd — one microbatch retires
    per TWO ticks. The old formula understated this; the mirror prices it."""
    from galvatron_tpu.search.cost_model import schedule_total_time

    fwd, bwd = [1.0, 1.0], [2.0, 2.0]
    # closed form at pp=2 balanced stages: one microbatch per two
    # (fwd+bwd)-cost ticks => total = 2(f+b)c - 1 for c >= 2, with the
    # warmup's cheap fwd-only ticks shaving the constant
    for c in (2, 4, 8, 32):
        assert schedule_total_time(fwd, bwd, 2, c) == pytest.approx(6 * c - 1)
    steady = 2 * (fwd[0] + bwd[0])
    per_mb = [schedule_total_time(fwd, bwd, 2, c) / c for c in (2, 8, 32)]
    # per-mb cost approaches the steady rate from below
    assert per_mb[0] < per_mb[1] < per_mb[2] <= steady
    # the exact price dominates the naive textbook bound (the price of the
    # single-collective-per-tick design) — pinned so a schedule improvement
    # that removes the parity idling shows up as this assertion flipping
    naive = (8 + 2) * (fwd[0] + bwd[0])
    assert schedule_total_time(fwd, bwd, 2, 8) > naive
