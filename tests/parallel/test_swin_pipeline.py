"""Pipelined Swin: the hierarchical 1F1B schedule (padded universal slots +
flat canonical channel) must reproduce the pp=1 trajectory. The reference
pipelines Swin through the same stage machinery as every family
(pipeline.py:110-112; per-stage layer lists, model_profiler.py:71-100)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models.swin import construct_swin_model, swin_config
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]


B = 8


@pytest.fixture(scope="module")
def cfg():
    # one block per swin stage: every pipeline cut crosses a patch merge and
    # every slot pads across two different channel widths
    return swin_config(
        "swin-test", embed_dim=16, depths=(1, 1, 1, 1), num_heads=(2, 2, 2, 2),
        image_size=32, patch_size=4, window=4, num_classes=10,
        compute_dtype=jnp.float32,
    )


def make_batch(cfg, seed):
    rng = np.random.RandomState(seed)
    return dict(
        pixels=jnp.asarray(
            rng.randn(B, cfg.image_size, cfg.image_size, cfg.num_channels).astype(np.float32)
        ),
        labels=jnp.asarray(rng.randint(0, cfg.num_classes, (B,))),
    )


def _traj(cfg, hp, devices, steps=3):
    m = construct_swin_model(cfg, hp, devices)
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


def test_swin_1f1b_matches_single_stage(cfg, devices8):
    ref_hp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=B)
    ref = _traj(cfg, ref_hp, devices8)
    hp = HybridParallelConfig.uniform(
        8, cfg.num_layers, pp=2, global_bsz=B, chunks=2,
        pipeline_type="pipedream_flush",
    )
    got = _traj(cfg, hp, devices8)
    assert max(abs(a - b) for a, b in zip(ref, got)) < 2.5e-4, (ref, got)


_EXT = pytest.mark.skipif(
    not __import__("os").environ.get("GALVATRON_EXTENDED_TESTS"),
    reason="extended matrix (set GALVATRON_EXTENDED_TESTS=1); the parity and "
    "roundtrip tests cover the swin 1F1B engine in the default tier",
)


@_EXT
def test_swin_1f1b_tp2_ckpt_trains(cfg, devices8):
    """pp=2 x tp=2 with remat on the deeper blocks: loss drops while
    memorizing one batch (heterogeneous per-stage strategies)."""
    hp = HybridParallelConfig(
        world_size=8, pp=2,
        layers=[LayerStrategy(tp=2)] * 2 + [LayerStrategy(tp=2, checkpoint=1)] * 2,
        global_bsz=B, chunks=2, pipeline_type="pipedream_flush",
    )
    m = construct_swin_model(cfg, hp, devices8)
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


def test_swin_stack_unstack_roundtrip(cfg):
    from galvatron_tpu.models.swin import init_swin_params
    from galvatron_tpu.parallel.pipeline_1f1b_swin import (
        stack_swin_params, unstack_swin_params,
    )

    hp = HybridParallelConfig.uniform(
        8, cfg.num_layers, pp=2, global_bsz=B, chunks=2,
        pipeline_type="pipedream_flush",
    )
    canonical = init_swin_params(jax.random.PRNGKey(0), cfg)
    stacked = stack_swin_params(canonical, cfg, hp)
    back = unstack_swin_params(stacked, cfg, hp)
    for a, b in zip(back["blocks"], canonical["blocks"]):
        eq = jax.tree.map(lambda x, y: np.allclose(x, y), a, b)
        assert all(jax.tree.leaves(eq))
    for a, b in zip(back["merges"], canonical["merges"]):
        eq = jax.tree.map(lambda x, y: np.allclose(x, y), a, b)
        assert all(jax.tree.leaves(eq))
