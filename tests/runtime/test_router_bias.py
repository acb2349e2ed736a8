"""The one piece of model state that no gradient moves: a sigmoid router's
`e_score_correction_bias` (models/parts/mlp.ROUTER_BIAS). It takes no gradient,
the optimizer holds no state of it and does not decay it, and the train step
moves it once a step by `rate x sign(mean(c) - c)` from the GLOBAL batch's
assignment counts (models/base.update_router_bias), the same on one device,
under dp and over microbatches."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from galvatron_tpu import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.parts.mlp import ROUTER_BIAS
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.runtime import construct_hybrid_parallel_model, get_optimizer_and_scheduler
from galvatron_tpu.runtime import optimizer as O

BATCH, SEQ, VOCAB, EXPERTS, RATE = 4, 32, 256, 8, 0.01


def tiny(**kw):
    fields = dict(
        hidden_size=64, num_heads=4, num_kv_heads=4, ffn_hidden=32, dense_ffn_hidden=96,
        num_layers=3, vocab_size=VOCAB, max_seq_len=SEQ, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, num_experts=EXPERTS,
        experts_per_token=2, compute_dtype=jnp.float32, router_bias_update_rate=RATE)
    fields.update(kw)
    return glm4_moe_lite_config("glm-4.7-flash", **fields)


def batch_of(seed=1):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (BATCH, SEQ), 0, VOCAB)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (BATCH, SEQ)),
                labels=jnp.roll(tok, -1, 1),
                loss_mask=jnp.ones((BATCH, SEQ), jnp.float32).at[:, -1].set(0.0))


def biases(params):
    return np.stack([np.asarray(r[ROUTER_BIAS]) for r in M.router_bias_leaves(params)])


def tx_of(weight_decay=0.1):
    return get_optimizer_and_scheduler(O.OptimizerArgs(
        lr=1e-2, warmup_steps=1, total_steps=10, weight_decay=weight_decay))[0]


def one_step(world=1, chunks=1, dp_type="ddp", steps=1, cfg=None, start=None):
    cfg = cfg or tiny()
    hp = HybridParallelConfig.uniform(world, cfg.num_layers, global_bsz=BATCH, chunks=chunks,
                                      default_dp_type=dp_type, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:world])
    tx = tx_of()
    params = model.init_params(jax.random.PRNGKey(0)) if start is None else jax.device_put(
        start, model.shardings())
    opt = model.init_opt_state(tx, params)
    step = model.make_train_step(tx, donate=False)
    metrics = None
    for _ in range(steps):
        params, opt, metrics = step(params, opt, model.shard_batch(batch_of()))
    return jax.device_get(params), opt, {k: np.asarray(v) for k, v in metrics.items()}


def test_the_bias_takes_no_gradient_and_the_choice_reads_it():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    grads = jax.grad(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    for router in M.router_bias_leaves(grads):
        assert not np.any(np.asarray(router[ROUTER_BIAS]))
        assert np.any(np.asarray(router["kernel"]))
    # a large bias on expert 5 sends every token there; the weights stay the scores'
    _, parts = M.lm_loss_fn(params, batch, cfg, with_parts=True)
    for router in M.router_bias_leaves(params):
        router[ROUTER_BIAS] = router[ROUTER_BIAS].at[5].set(10.0)
    _, pushed = M.lm_loss_fn(params, batch, cfg, with_parts=True)
    assert np.all(np.asarray(pushed[M.ROUTER_COUNTS])[:, 5] == BATCH * SEQ)
    assert np.any(np.asarray(parts[M.ROUTER_COUNTS])[:, 5] < BATCH * SEQ)
    assert float(pushed["router_bias_abs_max"]) == 10.0


def test_one_sign_step_from_known_counts():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    counts = jnp.array([[9, 1, 4, 4, 4, 4, 3, 3],  # mean 4
                        [4, 4, 4, 4, 4, 4, 4, 4],
                        [0, 0, 0, 0, 0, 0, 0, 32]], jnp.float32)
    moved = M.update_router_bias(params, counts, 0.25)
    np.testing.assert_array_equal(biases(moved), 0.25 * np.array(
        [[-1, 1, 0, 0, 0, 0, 1, 1], [0] * 8, [1] * 7 + [-1]], np.float32))
    # rows in the order of router_bias_leaves: the stack's routed layers, then MTP's block
    assert len(M.router_bias_leaves(params)) == 3 == cfg.routed_layers
    assert M.router_bias_leaves(moved)[2] is moved["mtp"]["block"]["router"]
    # nothing else is touched, and the input tree is not
    assert moved["layers"][0] is params["layers"][0] and not np.any(biases(params))
    assert moved["layers"][1]["wi"] is params["layers"][1]["wi"]


def test_adam_holds_no_state_of_it_and_nothing_decays_it():
    cfg = tiny(router_bias_update_rate=0.0)
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    for router in M.router_bias_leaves(params):
        router[ROUTER_BIAS] = jnp.full((EXPERTS,), 0.5)
    tx = tx_of(weight_decay=0.5)
    state = tx.init(params)
    adam = next(s for s in state if isinstance(s, optax.ScaleByAdamState))
    n_params, n_bias = len(jax.tree.leaves(params)), len(M.router_bias_leaves(params))
    assert len(jax.tree.leaves(adam.mu)) == len(jax.tree.leaves(adam.nu)) == n_params - n_bias
    assert adam.mu["layers"][1]["router"][ROUTER_BIAS] is None
    assert adam.mu["layers"][1]["router"]["kernel"].shape == (64, EXPERTS)
    grads = jax.tree.map(jnp.ones_like, params)  # even a gradient that is not zero
    _, state = tx.update(grads, state, params)  # the schedule's first step has lr 0
    updates, _ = tx.update(grads, state, params)
    for router in M.router_bias_leaves(updates):
        assert not np.any(np.asarray(router[ROUTER_BIAS]))
        assert np.any(np.asarray(router["kernel"]))
    # a tree without such a leaf: the inner chain's own state, a moment a leaf
    dense = {"w": jnp.ones((4, 4)), "b": {"bias": jnp.ones((4,))}}
    dense_adam = next(s for s in tx.init(dense) if isinstance(s, optax.ScaleByAdamState))
    assert jax.tree.structure(dense_adam.mu) == jax.tree.structure(dense)
    # through the whole step at rate 0: held still, neither decayed nor moved
    got, opt, _ = one_step(cfg=cfg, start=params, steps=2)
    np.testing.assert_array_equal(biases(got), 0.5)
    assert not np.array_equal(np.asarray(got["layers"][1]["router"]["kernel"]),
                              np.asarray(params["layers"][1]["router"]["kernel"]))


@pytest.fixture(scope="module")
def on_one_device():
    return one_step()


def test_the_step_moves_it_by_the_rule_alone(on_one_device):
    params, _, metrics = on_one_device
    assert M.ROUTER_COUNTS not in metrics and "router_bias_abs_max" in metrics
    moved = biases(params)
    assert set(np.unique(np.abs(moved))) <= {0.0, np.float32(RATE)}
    # by the counts of this very batch on the initial weights (the bias was 0)
    cfg = tiny()
    start = M.init_model_params(jax.random.PRNGKey(0), cfg)
    _, parts = M.lm_loss_fn(start, batch_of(), cfg, with_parts=True)
    counts = np.asarray(parts[M.ROUTER_COUNTS])
    np.testing.assert_array_equal(
        moved, np.float32(RATE) * np.sign(counts.mean(axis=1, keepdims=True) - counts))
    assert float(metrics["router_bias_abs_max"]) == 0.0  # the bias this step READ


@pytest.mark.parametrize("world,chunks,dp_type", [(2, 1, "zero2"), (2, 1, "zero3"), (4, 1, "zero2"),
                                                  (1, 2, "ddp"), (2, 2, "zero2")])
def test_dp_and_microbatches_move_it_as_one_device_does(on_one_device, world, chunks, dp_type):
    """The counts are the global batch's: summed over dp inside the routed
    block's region and over the microbatches in the step, before the sign."""
    params, _, _ = on_one_device
    got, _, metrics = one_step(world, chunks, dp_type)
    np.testing.assert_array_equal(biases(got), biases(params))
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0


def test_three_steps_move_it_three_times_and_the_guard_holds_it_back():
    params, _, _ = one_step(steps=3)
    assert np.abs(biases(params)).max() <= 3 * RATE + 1e-7
    assert np.abs(biases(params)).max() >= 2 * RATE  # some expert stays over or under
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    tx = tx_of()
    start = model.init_params(jax.random.PRNGKey(0))
    step = model.make_train_step(tx, guard_anomalies=True, donate=False)
    kept, _, m = step(start, model.init_opt_state(tx, start), model.shard_batch(batch_of()),
                      jnp.float32(0.0))  # every loss is over a cap of 0
    assert bool(m["anomalous"]) and not np.any(biases(kept))
