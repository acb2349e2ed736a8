"""What the router-bias tests share (tests/runtime/test_router_bias.py: the rule and the
optimizer; tests/models/test_router_bias_layouts.py: a step on one device and the layouts that must move the
bias as it does): the tiny GLM configuration, its batch, a step of the model's own train step."""

import jax
import jax.numpy as jnp
import numpy as np

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
