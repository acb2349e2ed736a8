"""T5 two-layer-type profile -> search -> train loop (reference T5 path:
models/T5/profiler.py + search_dist.py + multi-layer-type DP,
dynamic_programming.py:170-189)."""

import os

import pytest

from galvatron_tpu.utils.jsonio import write_json_config

pytestmark = [pytest.mark.search_engine]

SEQ_ARGS = ["--set_seqlen_manually", "1", "--seq_length", "32"]


def test_t5_profile_search_train(tmp_path, devices8):
    d = str(tmp_path)
    from galvatron_tpu.cli.profile import main_model

    res = main_model(
        ["--model_type", "t5", "--model_size", "t5-test",
         "--profile_batch_size", "1", "--layernum_min", "1", "--layernum_max", "2",
         "--mixed_precision", "bf16", "--config_dir", d] + SEQ_ARGS
    )
    assert res["computation"]["layertype_0"] > 0
    # both types are timed; how the two times compare is the machine's clock (a difference of two wall times of
    # a tiny stack: a loaded machine broke a ratio here), so what compares them below is a count, the parameters
    assert res["computation"]["layertype_1"] > 0
    assert res["memory"]["layertype_1"]["parameter_size"] > res["memory"]["layertype_0"][
        "parameter_size"
    ], "decoder layers (extra cross-attn) must be bigger than encoder layers"

    write_json_config(
        {"allreduce_size_8_consec_1": 100.0, "allreduce_size_4_consec_1": 100.0,
         "allreduce_size_2_consec_1": 100.0},
        os.path.join(d, "allreduce_bandwidth_8chips.json"),
    )
    write_json_config({"pp_size_2": 120.0}, os.path.join(d, "p2p_bandwidth_8chips.json"))
    write_json_config({"overlap_coe": 1.1}, os.path.join(d, "overlap_coefficient.json"))

    from galvatron_tpu.cli.search import main as search_main

    strategy_path = os.path.join(d, "t5_strategy.json")
    res = search_main(
        ["--model_type", "t5", "--model_size", "t5-test", "--config_dir", d,
         "--memory_constraint", "8", "--max_pp_deg_search", "2",
         "--max_tp_deg_search", "2", "--settle_bsz", "8", "--mixed_precision",
         "bf16", "--output_config_path", strategy_path,
         "--log_dir", os.path.join(d, "logs")] + SEQ_ARGS
    )
    assert res["strategies"] is not None and len(res["strategies"]) == 4  # t5-test: 2 enc + 2 dec
    assert os.path.exists(strategy_path)

    from galvatron_tpu.cli.train import main as train_main

    s = train_main(
        ["--model_type", "t5", "--model_size", "t5-test",
         "--galvatron_config_path", strategy_path,
         "--train_iters", "2", "--lr", "1e-4", "--mixed_precision", "bf16"] + SEQ_ARGS
    )
    assert len(s["losses"]) == 2
