"""Batch prep (zigzag layout) + masked grad-accumulation equivalence."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.ops.ring_attention import inverse_permutation, zigzag_permutation
from galvatron_tpu.runtime.dataloader import prepare_batch
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

pytestmark = [pytest.mark.parallel]

B, S, V = 8, 32, 128


def test_prepare_batch_zigzag_applied():
    hp = HybridParallelConfig.uniform(8, 2, cp=2, global_bsz=B, cp_mode="zigzag")
    tokens = np.arange(B * S).reshape(B, S) % V
    batch = prepare_batch(hp, tokens)
    idx = zigzag_permutation(S, 2)
    assert (np.asarray(batch["tokens"]) == tokens[:, idx]).all()
    assert (np.asarray(batch["positions"])[0] == idx).all()
    # ring mode: no permutation
    hp2 = HybridParallelConfig.uniform(8, 2, cp=2, global_bsz=B, cp_mode="ring")
    batch2 = prepare_batch(hp2, tokens)
    assert (np.asarray(batch2["tokens"]) == tokens).all()


def test_zigzag_layout_loss_invariant(devices8):
    """Model loss must be identical in zigzag and linear layouts."""
    cfg = TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=2, vocab_size=V, max_seq_len=64,
        compute_dtype=jnp.float32,
    )
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    tokens = np.random.RandomState(0).randint(0, V, (B, S))
    hp_ring = HybridParallelConfig.uniform(8, 2, cp=2, global_bsz=B, cp_mode="ring")
    hp_zig = HybridParallelConfig.uniform(8, 2, cp=2, global_bsz=B, cp_mode="zigzag")
    out = {}
    for name, hp in [("ring", hp_ring), ("zigzag", hp_zig)]:
        m = construct_hybrid_parallel_model(cfg, hp, devices8)
        p = jax.device_put(params, m.shardings())
        batch = m.shard_batch(prepare_batch(hp, tokens))
        out[name] = float(jax.jit(m.loss_fn)(p, batch))
    assert abs(out["ring"] - out["zigzag"]) < 2e-5, out


def test_masked_grad_accum_matches_unchunked(devices8):
    """chunks=2 with an unbalanced loss_mask must match chunks=1 exactly
    (weighted microbatch accumulation)."""
    cfg = TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=2, vocab_size=V, max_seq_len=64,
        compute_dtype=jnp.float32,
    )
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    tokens = np.random.RandomState(0).randint(0, V, (B, S))
    mask = np.ones((B, S), np.float32)
    mask[: B // 2, S // 4 :] = 0.0  # first half-batch has 4x fewer valid tokens

    def run(chunks):
        hp = HybridParallelConfig.uniform(8, 2, global_bsz=B, chunks=chunks)
        m = construct_hybrid_parallel_model(cfg, hp, devices8)
        p = jax.device_put(jax.tree.map(jnp.copy, params), m.shardings())
        tx, _ = get_optimizer_and_scheduler(
            OptimizerArgs(lr=1e-3, warmup_steps=0, total_steps=10, weight_decay=0.0)
        )
        st = m.init_opt_state(tx, p)
        step = m.make_train_step(tx)
        batch = m.shard_batch(prepare_batch(hp, tokens, loss_mask=mask))
        losses = []
        for _ in range(3):
            p, st, mets = step(p, st, batch)
            losses.append(float(mets["loss"]))
        return losses

    one, two = run(1), run(2)
    assert max(abs(a - b) for a, b in zip(one, two)) < 5e-5, (one, two)


def test_zigzag_padded_attn_mask_loss_invariant(devices8):
    """Padded (bert-style) batches under zigzag cp: prepare_batch permutes
    attn_mask with the tokens, so the cp-sharded key bias indexes the
    permuted K/V correctly — the loss must match the cp=1 unpermuted run
    (review finding: the mask previously bypassed the permutation)."""
    cfg = TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=2, vocab_size=V, max_seq_len=64,
        compute_dtype=jnp.float32, causal=False,
    )
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, V, (B, S))
    mask = np.ones((B, S), np.float32)
    mask[:, -6:] = 0.0
    labels = np.roll(tokens, -1, axis=1)
    out = {}
    for name, kw in [("cp1", dict()), ("zigzag_cp2", dict(cp=2, cp_mode="zigzag"))]:
        hp = HybridParallelConfig.uniform(8, 2, global_bsz=B, **kw)
        m = construct_hybrid_parallel_model(cfg, hp, devices8)
        p = jax.device_put(params, m.shardings())
        batch = m.shard_batch(prepare_batch(
            hp, tokens, labels=labels, loss_mask=mask, attn_mask=mask,
        ))
        out[name] = float(jax.jit(m.loss_fn)(p, batch))
    assert abs(out["cp1"] - out["zigzag_cp2"]) < 2e-5, out
