"""Model profiler: layer differencing + schema + end-to-end feed into search.

The end-to-end test is the TPU analogue of the reference's full
profile -> search loop (SURVEY.md §3.5 + §3.3) with a tiny model."""

import jax.numpy as jnp
import pytest

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.profiler.model import ModelProfiler, ModelProfileArgs
from galvatron_tpu.profiler.runtime import RuntimeProfiler


def tiny_cfg(**kw):
    kw.setdefault("hidden_size", 64)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("vocab_size", 128)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("compute_dtype", jnp.float32)
    return TransformerConfig(**kw)


@pytest.fixture(scope="module")
def profiled():
    args = ModelProfileArgs(
        profile_batch_size=2, layernum_min=1, layernum_max=2, warmup=1, iters=2,
        profile_seq_length=64, max_tp_deg=2, mixed_precision="fp32",
    )
    prof = ModelProfiler(tiny_cfg(), "tiny", args)
    return prof.profile_all(write=False)


def test_computation_schema(profiled):
    t = profiled["computation"]
    assert t["layertype_0"] > 0
    assert t["other_time"] > 0


def test_memory_schema(profiled):
    m = profiled["memory"]
    lt = m["layertype_0"]
    assert lt["parameter_size"] > 0
    act = lt["tp_activation_per_bsz_dict"]
    assert act[1] > 0 and act["checkpoint"] <= act[1]
    # tp=2 entry is MEASURED on the 8-device test mesh (not the act/2
    # derivation): sharding should shrink it, but megatron-sp's full-sequence
    # attention gathers keep it above a naive half (the reason derivation was
    # replaced, reference model_profiler.py:374-559)
    assert 0.3 * act[1] <= act[2] <= 1.5 * act[1], act
    for key in ("other_memory_pp_off", "other_memory_pp_on"):
        assert key in m
    off = m["other_memory_pp_off"]
    assert off["model_states"][1] > 0 and off["activation"][1] > 0
    on = m["other_memory_pp_on"]
    assert on["first_stage"]["model_states"][1] > 0
    assert on["last_stage"]["model_states"][1] > 0


def test_batch_mode_fit():
    args = ModelProfileArgs(
        profile_mode="batch", profile_min_batch_size=1, profile_max_batch_size=3,
        batch_size_step=1, layernum_min=1, layernum_max=2, warmup=0, iters=1,
        profile_seq_length=64, mixed_precision="fp32",
    )
    t = ModelProfiler(tiny_cfg(), "tiny", args).profile_computation()
    m, c = t["layertype_0"]
    assert m >= 0  # time grows with batch


def test_profile_to_search_end_to_end(devices8):
    """Profiled tables must drive a real search to a valid strategy."""
    from galvatron_tpu.profiler.hardware import HardwareProfiler, HardwareProfileArgs
    from galvatron_tpu.search.engine import GalvatronSearchEngine, SearchArgs

    cfg = tiny_cfg()
    margs = ModelProfileArgs(
        profile_batch_size=2, layernum_min=1, layernum_max=2, warmup=0, iters=1,
        profile_seq_length=64, max_tp_deg=2, mixed_precision="fp32",
    )
    model_results = ModelProfiler(cfg, "tiny", margs).profile_all(write=False)
    hargs = HardwareProfileArgs(start_mb=0.25, end_mb=0.25, warmup=0, iters=1, max_tp_deg=2)
    hw = HardwareProfiler(hargs, devices=devices8).profile_all(write=False)

    eng = GalvatronSearchEngine(
        SearchArgs(memory_constraint=64.0, settle_bsz=8, settle_chunk=1, max_tp_deg=2),
        world_size=8,
        model_layer_configs=[{"hidden_size": cfg.hidden_size, "seq_len": 64,
                              "layer_num": cfg.num_layers}],
        model_name="tiny",
    )
    eng.set_model_profiles(model_results["computation"], model_results["memory"])
    eng.set_hardware_profiles(hw["allreduce"], hw["p2p"], hw["overlap"], hw["sp"])
    eng.initialize_search_engine()
    best = eng.parallelism_optimization()
    assert best is not None and best["strategies"] is not None
    hp = eng.result_to_config(best)
    assert hp.world_size == 8 and hp.num_layers == cfg.num_layers


def test_runtime_profiler_summary():
    import numpy as np

    rp = RuntimeProfiler(warmup=1)
    for it in range(4):
        rp.start(it)
        x = np.ones(4).sum()
        rp.end(it, n_samples=8)
        rp.profile_memory(it, "after_step")
    s = rp.summary()
    assert s["iters"] == 3
    assert s["avg_iter_ms"] >= 0
    assert s["samples_per_s"] > 0


def test_runtime_profiler_save(tmp_path):
    p = str(tmp_path / "runtime.json")
    rp = RuntimeProfiler(warmup=0, save_path=p, model_name="tiny")
    rp.start(0)
    rp.end(0, n_samples=4)
    rp.save()
    from galvatron_tpu.utils.jsonio import read_json_config

    assert read_json_config(p)["tiny"]["iters"] == 1


def test_profiler_bert_and_vit_families(tmp_path):
    """Profiler must handle post-LN MLM (no final_norm) and patch-input
    classification trees (review finding: new families crashed _full_model)."""
    import jax.numpy as jnp

    from galvatron_tpu.models.bert import bert_config
    from galvatron_tpu.models.vit import vit_config
    from galvatron_tpu.profiler.model import ModelProfileArgs, ModelProfiler

    args = ModelProfileArgs(
        profile_batch_size=2, layernum_min=1, layernum_max=2, warmup=0, iters=1,
        max_tp_deg=2, mixed_precision="fp32", config_dir=str(tmp_path),
    )
    for cfg, name in (
        (bert_config("bert-base", hidden_size=32, num_heads=2, num_layers=2,
                     vocab_size=64, max_seq_len=16, compute_dtype=jnp.float32), "bert"),
        (vit_config("vit-base", hidden_size=32, num_heads=2, num_layers=2, ffn_hidden=64,
                    image_size=16, patch_size=8, num_classes=4, compute_dtype=jnp.float32), "vit"),
    ):
        res = ModelProfiler(cfg, name, args).profile_all(write=False)
        assert res["computation"]["layertype_0"] > 0
        assert res["memory"]["layertype_0"]["parameter_size"] > 0


def test_profiler_rejects_multi_layer_type_config():
    import pytest as _pytest

    from galvatron_tpu.models.t5 import t5_config
    from galvatron_tpu.profiler.model import ModelProfiler

    with _pytest.raises(TypeError, match="layer type"):
        ModelProfiler(t5_config("t5-small"))


def test_t5_profiler_batch_mode(tmp_path):
    """profile_mode=batch must produce [m, c] fits for BOTH t5 layer types
    (review finding: T5 profiler silently ignored profile_mode)."""
    from galvatron_tpu.models.t5 import t5_config
    from galvatron_tpu.profiler.model import ModelProfileArgs, T5ModelProfiler

    cfg = t5_config("t5-small", hidden_size=32, num_heads=2, head_dim=16,
                    ffn_hidden=64, num_enc_layers=2, num_dec_layers=2,
                    vocab_size=64, max_seq_len=16)
    args = ModelProfileArgs(
        profile_mode="batch", profile_min_batch_size=1, profile_max_batch_size=2,
        profile_batch_size=2, layernum_min=1, layernum_max=2, warmup=0, iters=1,
        max_tp_deg=2, mixed_precision="fp32", config_dir=str(tmp_path),
    )
    res = T5ModelProfiler(cfg, "t5", args).profile_computation()
    for key in ("layertype_0", "layertype_1"):
        assert isinstance(res[key], list) and len(res[key]) == 2, res[key]


def test_t5_swin_measured_tp_activation_rows(devices8):
    """The per-strategy activation measurement covers the multi-layer-type
    families too: t5 enc/dec (tp + ulysses) and swin blocks (tp) measure on
    a k-device mesh; inapplicable strategies fall back (None)."""
    import jax.numpy as jnp

    from galvatron_tpu.models.t5 import t5_config
    from galvatron_tpu.models.swin import swin_config
    from galvatron_tpu.profiler.model import SwinModelProfiler, T5ModelProfiler

    tcfg = t5_config(
        "t5-test", hidden_size=32, num_heads=2, head_dim=16, ffn_hidden=64,
        num_enc_layers=2, num_dec_layers=2, vocab_size=64, max_seq_len=16,
        compute_dtype=jnp.float32,
    )
    targs = ModelProfileArgs(profile_batch_size=2, layernum_min=1, layernum_max=2,
                             warmup=0, iters=1, max_tp_deg=2, mixed_precision="fp32")
    tp = T5ModelProfiler(tcfg, "t5", targs)
    assert tp._act_bytes_tp(0, 2, 16, 2, kind="tp")      # encoder, megatron-sp
    assert tp._act_bytes_tp(1, 2, 16, 2, kind="tp")      # decoder (cross-attn)
    assert tp._act_bytes_tp(0, 2, 16, 2, kind="ulysses")
    assert tp._act_bytes_tp(0, 2, 16, 2, kind="cp") is None  # documented fallback

    scfg = swin_config(
        "swin-test", embed_dim=16, depths=(1, 1), num_heads=(2, 2),
        image_size=16, patch_size=4, window=4, num_classes=4,
        compute_dtype=jnp.float32,
    )
    sp = SwinModelProfiler(scfg, "swin", targs)
    assert sp._act_bytes_tp(0, 2, 16, 2, kind="tp")
    assert sp._act_bytes_tp(1, 2, 16, 2, kind="tp")
    assert sp._act_bytes_tp(0, 2, 16, 2, kind="cp") is None
