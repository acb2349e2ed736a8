"""Pipelined T5: the enc-dec 1F1B schedule must reproduce the pp=1
trajectory (north-star ladder config #4 is T5 + Megatron-SP + 1F1B; the
reference pipelines T5 via multi-tensor sends, pipeline.py:1442-1580)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models.t5 import construct_t5_model, t5_config
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]


B = 8


@pytest.fixture(scope="module")
def cfg():
    return t5_config(
        "t5-test", hidden_size=64, num_heads=4, head_dim=16, ffn_hidden=128,
        num_enc_layers=2, num_dec_layers=2, vocab_size=256, max_seq_len=32,
        compute_dtype=jnp.float32,
    )


def make_batch(cfg, seed, se=32, sd=24):
    """Unequal enc/dec lengths exercise the padding path; padded encoder
    positions are masked."""
    rng = np.random.RandomState(seed)
    mask = np.ones((B, se), np.float32)
    mask[:, -4:] = 0.0
    return dict(
        tokens=jnp.asarray(rng.randint(0, cfg.vocab_size, (B, se))),
        dec_tokens=jnp.asarray(rng.randint(0, cfg.vocab_size, (B, sd))),
        labels=jnp.asarray(rng.randint(0, cfg.vocab_size, (B, sd))),
        attn_mask=jnp.asarray(mask),
    )


def _traj(cfg, hp, devices, steps=3):
    m = construct_t5_model(cfg, hp, devices)
    p = m.init_params(jax.random.PRNGKey(0))
    tx, _ = get_optimizer_and_scheduler(
        OptimizerArgs(lr=1e-3, warmup_steps=2, total_steps=10, weight_decay=0.0)
    )
    st = m.init_opt_state(tx, p)
    step = m.make_train_step(tx)
    out = []
    for i in range(steps):
        p, st, mets = step(p, st, m.shard_batch(make_batch(cfg, i % 2)))
        out.append(float(mets["loss"]))
    return out


def test_t5_1f1b_matches_single_stage(cfg, devices8):
    """pp=2 (1 enc stage + 1 dec stage) trajectory parity vs pp=1. The pp=1
    reference is padded identically (t5_pad_batch is the engine's contract)."""
    from galvatron_tpu.models.t5 import t5_pad_batch

    ref_hp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=B)
    m1 = construct_t5_model(cfg, ref_hp, devices8)
    p1 = m1.init_params(jax.random.PRNGKey(0))
    tx, _ = get_optimizer_and_scheduler(
        OptimizerArgs(lr=1e-3, warmup_steps=2, total_steps=10, weight_decay=0.0)
    )
    st1 = m1.init_opt_state(tx, p1)
    step1 = m1.make_train_step(tx)
    ref = []
    for i in range(3):
        p1, st1, mets = step1(p1, st1, m1.shard_batch(t5_pad_batch(make_batch(cfg, i % 2))))
        ref.append(float(mets["loss"]))

    hp = HybridParallelConfig.uniform(
        8, cfg.num_layers, pp=2, global_bsz=B, chunks=2,
        pipeline_type="pipedream_flush",
    )
    got = _traj(cfg, hp, devices8)
    # pp=1 and pipelined params are initialised from the same canonical tree,
    # so the trajectories must agree to fp32 reduction-order drift
    assert max(abs(a - b) for a, b in zip(ref, got)) < 2.5e-4, (ref, got)


_EXT = pytest.mark.skipif(
    not __import__("os").environ.get("GALVATRON_EXTENDED_TESTS"),
    reason="extended matrix (set GALVATRON_EXTENDED_TESTS=1); enc-dec parity "
    "covers the engine, tp/sp composition is covered by the gpt 1F1B tests",
)


@_EXT
def test_t5_1f1b_tp2_trains(cfg, devices8):
    """pp=2 x tp=2 (megatron-sp default) + ckpt on the decoder stage: loss
    drops while memorizing one batch."""
    hp = HybridParallelConfig(
        world_size=8, pp=2,
        layers=[LayerStrategy(tp=2)] * 2 + [LayerStrategy(tp=2, checkpoint=1)] * 2,
        global_bsz=B, chunks=2, vocab_tp=2, pipeline_type="pipedream_flush",
    )
    m = construct_t5_model(cfg, hp, devices8)
    p = m.init_params(jax.random.PRNGKey(0))
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=3e-3, warmup_steps=1, total_steps=20))
    st = m.init_opt_state(tx, p)
    step = m.make_train_step(tx)
    batch = m.shard_batch(make_batch(cfg, 0))
    losses = []
    for _ in range(4):
        p, st, mets = step(p, st, batch)
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0], losses


def test_stack_unstack_roundtrip(cfg):
    from galvatron_tpu.models.t5 import init_t5_params
    from galvatron_tpu.parallel.pipeline_1f1b_encdec import (
        stack_t5_params, unstack_t5_params,
    )

    hp = HybridParallelConfig.uniform(
        8, cfg.num_layers, pp=2, global_bsz=B, chunks=2,
        pipeline_type="pipedream_flush",
    )
    canonical = init_t5_params(jax.random.PRNGKey(0), cfg)
    stacked = stack_t5_params(canonical, cfg, hp)
    back = unstack_t5_params(stacked, cfg, hp)
    for key in ("enc_rel_bias", "dec_rel_bias"):
        assert np.allclose(back[key], canonical[key])
    assert np.allclose(back["enc_norm"]["scale"], canonical["enc_norm"]["scale"])
    for a, b in zip(back["enc_layers"], canonical["enc_layers"]):
        chex_equal = jax.tree.map(lambda x, y: np.allclose(x, y), a, b)
        assert all(jax.tree.leaves(chex_equal))
    for a, b in zip(back["dec_layers"], canonical["dec_layers"]):
        chex_equal = jax.tree.map(lambda x, y: np.allclose(x, y), a, b)
        assert all(jax.tree.leaves(chex_equal))
