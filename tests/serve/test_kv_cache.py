"""KV-cache geometry, masking, and strategy-derived layout units."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.parallel.mesh import build_mesh
from galvatron_tpu.serve import kv_cache as KV

pytestmark = [pytest.mark.serve]


def tiny_cfg(**kw):
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_seq_len", 32)
    kw.setdefault("compute_dtype", jnp.float32)
    return TransformerConfig(**kw)


def test_kv_cache_config_geometry():
    kv = KV.KVCacheConfig(max_slots=4, page_size=8, max_pages=3)
    assert kv.max_ctx == 24
    with pytest.raises(ValueError):
        KV.KVCacheConfig(max_slots=0)
    with pytest.raises(ValueError):
        KV.KVCacheConfig(page_size=0)


def test_bucket_pages_boundaries():
    # a length-L request needs room for L cached tokens PLUS the decode write
    assert KV.bucket_pages(0, 16, 4) == 1
    assert KV.bucket_pages(15, 16, 4) == 1
    assert KV.bucket_pages(16, 16, 4) == 2  # 16 cached + 1 write > one page
    assert KV.bucket_pages(62, 16, 4) == 4
    assert KV.bucket_pages(63, 16, 4) == 4
    with pytest.raises(ValueError, match="max_pages"):
        KV.bucket_pages(64, 16, 4)


def test_length_bias_admits_through_write_position():
    bias = np.asarray(KV.length_bias(jnp.asarray([0, 3]), ctx=8))
    assert bias.shape == (2, 1, 1, 8)
    # slot 0 has nothing cached beyond its write at column 0
    np.testing.assert_array_equal(bias[0, 0, 0] == 0.0,
                                  np.arange(8) <= 0)
    # slot 1: columns 0..3 (3 cached + the write at 3) are admitted
    np.testing.assert_array_equal(bias[1, 0, 0] == 0.0,
                                  np.arange(8) <= 3)
    # explicit write_pos overrides the default lengths-as-write-pos
    bias2 = np.asarray(KV.length_bias(jnp.asarray([0, 3]), ctx=8,
                                      write_pos=jnp.asarray([5, 1])))
    np.testing.assert_array_equal(bias2[0, 0, 0] == 0.0, np.arange(8) <= 5)
    np.testing.assert_array_equal(bias2[1, 0, 0] == 0.0, np.arange(8) <= 1)


def test_write_prompt_kv_isolates_slots():
    cfg = tiny_cfg()
    kv_cfg = KV.KVCacheConfig(max_slots=4, page_size=8, max_pages=2)
    cache = KV.init_kv_cache(cfg, kv_cfg)
    rng = np.random.default_rng(0)
    bucket = kv_cfg.page_size  # one-page prefill block
    kvs = []
    for _ in range(cfg.num_layers):
        k = jnp.asarray(rng.normal(size=(1, bucket, cfg.num_kv_heads,
                                         cfg.head_dim)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, bucket, cfg.num_kv_heads,
                                         cfg.head_dim)), jnp.float32)
        kvs.append((k, v))
    out = KV.write_prompt_kv(cache, kvs, jnp.int32(2), jnp.int32(5))
    lengths = np.asarray(out["lengths"])
    assert lengths[2] == 5 and np.all(lengths[[0, 1, 3]] == 0)
    for li in range(cfg.num_layers):
        k = np.asarray(out["k"][li])
        # the written row carries the block, bucket columns onward stay zero
        np.testing.assert_array_equal(k[2, :bucket], np.asarray(kvs[li][0][0]))
        assert np.all(k[2, bucket:] == 0)
        # every other slot row is untouched
        assert np.all(np.delete(k, 2, axis=0) == 0)


def test_kv_bytes_per_slot_arithmetic():
    cfg = tiny_cfg()
    got = KV.kv_bytes_per_slot(cfg, max_ctx=24, dtype_bytes=2)
    assert got == 2 * cfg.num_layers * 24 * cfg.num_kv_heads * cfg.head_dim * 2


def test_layer_kv_spec_derives_from_strategy(devices8):
    cfg = tiny_cfg()
    # tp=2: kv-head dim sharded over the tp axes, slot dim over dp
    hp = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2, global_bsz=8)
    mesh = build_mesh(hp, devices8)
    sp = KV.layer_kv_spec(hp, 0, mesh, cfg)
    assert sp[2] is not None and sp[0] is not None
    assert sp[1] is None and sp[3] is None  # ctx pages stay replicated
    # pure dp: no head sharding
    hp_dp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=8)
    sp_dp = KV.layer_kv_spec(hp_dp, 0, build_mesh(hp_dp, devices8), cfg)
    assert sp_dp[2] is None and sp_dp[0] is not None
    # the full-cache spec tree mirrors init_kv_cache's structure
    specs = KV.kv_cache_specs(hp, mesh, cfg)
    assert len(specs["k"]) == cfg.num_layers == len(specs["v"])


def test_layer_kv_spec_gqa_falls_back_to_replicated_heads(devices8):
    # 1 kv head under tp=2: the training path replicates kv there too
    cfg = tiny_cfg(num_kv_heads=1)
    hp = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2, global_bsz=8)
    sp = KV.layer_kv_spec(hp, 0, build_mesh(hp, devices8), cfg)
    assert sp[2] is None


def test_layer_kv_spec_refuses_decode_incompatible_layouts(devices8):
    cfg = tiny_cfg()
    hp_cp = HybridParallelConfig.uniform(8, cfg.num_layers, cp=2, global_bsz=8)
    with pytest.raises(ValueError, match="cp=2"):
        KV.layer_kv_spec(hp_cp, 0, build_mesh(hp_cp, devices8), cfg)
    hp_sp = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2, sp=1,
                                         global_bsz=8)
    with pytest.raises(ValueError, match="Ulysses"):
        KV.layer_kv_spec(hp_sp, 0, build_mesh(hp_sp, devices8), cfg)
