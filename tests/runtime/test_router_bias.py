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

from galvatron_tpu import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.parts.mlp import ROUTER_BIAS
from galvatron_tpu.runtime import construct_hybrid_parallel_model
from tests.runtime.router_bias_cases import BATCH, EXPERTS, RATE, SEQ, batch_of, biases, one_step, tiny, tx_of


def test_the_bias_takes_no_gradient_and_the_choice_reads_it():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    grads = jax.jit(jax.grad(lambda p: M.lm_loss_fn(p, batch, cfg)))(params)
    for router in M.router_bias_leaves(grads):
        assert not np.any(np.asarray(router[ROUTER_BIAS]))
        assert np.any(np.asarray(router["kernel"]))
    # a large bias on expert 5 sends every token there; the weights stay the scores'
    with_parts = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True))
    _, parts = with_parts(params)
    for router in M.router_bias_leaves(params):
        router[ROUTER_BIAS] = router[ROUTER_BIAS].at[5].set(10.0)
    _, pushed = with_parts(params)
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
