"""Optimizer + LR schedule.

Replaces apex FusedAdam + Megatron's OptimizerParamScheduler (reference:
galvatron/core/runtime/utils.py:137-167). On TPU, optax adamw is XLA-fused;
ZeRO-1/2 optimizer-state sharding is a *sharding of the adam moments over the
per-layer dp sub-axes* (`opt_state_specs`, by `parallel/spec.zero_split_spec`)
rather than a different optimizer wrapper — GSPMD inserts the gather/scatter around the elementwise update."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.parts.attention import LAMBDA_INIT
from galvatron_tpu.models.parts.mlp import ROUTER_BIAS
from galvatron_tpu.parallel.spec import zero_split_spec


@dataclass
class OptimizerArgs:
    lr: float = 1e-4
    min_lr: float = 1e-5
    weight_decay: float = 0.01
    adam_beta1: float = 0.9
    adam_beta2: float = 0.999
    adam_eps: float = 1e-8
    clip_grad: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    lr_decay_style: str = "cosine"  # cosine | linear | constant


def make_schedule(a: OptimizerArgs):
    if a.lr_decay_style == "constant":
        warm = optax.linear_schedule(0.0, a.lr, max(a.warmup_steps, 1))
        return optax.join_schedules([warm, optax.constant_schedule(a.lr)], [a.warmup_steps])
    if a.lr_decay_style == "linear":
        warm = optax.linear_schedule(0.0, a.lr, max(a.warmup_steps, 1))
        decay = optax.linear_schedule(a.lr, a.min_lr, max(a.total_steps - a.warmup_steps, 1))
        return optax.join_schedules([warm, decay], [a.warmup_steps])
    return optax.warmup_cosine_decay_schedule(
        0.0, a.lr, max(a.warmup_steps, 1), max(a.total_steps, 2), end_value=a.min_lr
    )


def _no_weight_decay(path, _leaf) -> bool:
    """Megatron convention: no decay for biases and norm scales."""
    keys = {getattr(k, "key", getattr(k, "idx", None)) for k in path}
    return not ({"bias", "scale"} & {k for k in keys if isinstance(k, str)})


# Leaves of the parameter tree that no gradient moves, by their key: a
# sigmoid router's `e_score_correction_bias` (models/parts/mlp.ROUTER_BIAS), which
# the train step itself steps once a step (models/base.update_router_bias), and
# a differential attention layer's `lambda_init` (models/parts/attention.LAMBDA_INIT),
# a constant of the layer's published index that nothing ever moves.
# The optimizer never sees them: no clipping share, no Adam moments, no decay.
NO_GRADIENT_KEYS = (ROUTER_BIAS, LAMBDA_INIT)


def _without_no_gradient_leaves(tree):
    """`tree` with the NO_GRADIENT_KEYS leaves replaced by None (an empty
    subtree): a tree without any is returned leaf for leaf as it came."""
    def keep(path, leaf):
        return None if getattr(path[-1], "key", None) in NO_GRADIENT_KEYS else leaf

    return jax.tree_util.tree_map_with_path(keep, tree, is_leaf=lambda x: isinstance(x, P))


def on_gradient_leaves(inner: optax.GradientTransformation) -> optax.GradientTransformation:
    """`inner` run on the tree without its NO_GRADIENT_KEYS leaves, which get
    a zero update and no place in `inner`'s state. For a tree that has none
    (every model but one with such a router) state and updates are `inner`'s
    own, structure and values."""
    def init(params):
        return inner.init(_without_no_gradient_leaves(params))

    def update(updates, state, params=None):
        pruned = _without_no_gradient_leaves(updates)
        out, state = inner.update(
            pruned, state, None if params is None else _without_no_gradient_leaves(params))
        # a pruned leaf's place holds None in `out`
        out = jax.tree.map(lambda g, u: jnp.zeros_like(g) if u is None else u,
                           updates, out, is_leaf=lambda x: x is None)
        return out, state

    return optax.GradientTransformation(init, update)


def get_optimizer_and_scheduler(args: Optional[OptimizerArgs] = None):
    a = args or OptimizerArgs()
    schedule = make_schedule(a)
    tx = on_gradient_leaves(optax.chain(
        optax.clip_by_global_norm(a.clip_grad) if a.clip_grad and a.clip_grad > 0 else optax.identity(),
        optax.scale_by_adam(b1=a.adam_beta1, b2=a.adam_beta2, eps=a.adam_eps),
        optax.add_decayed_weights(
            a.weight_decay,
            mask=lambda params: jax.tree_util.tree_map_with_path(_no_weight_decay, params),
        )
        if a.weight_decay
        else optax.identity(),
        optax.scale_by_learning_rate(schedule),
    ))
    return tx, schedule


# ------------------------------------------------------------- state sharding
# (ZeRO-1/2: a moment lies where `parallel/spec.zero_split_spec` puts dp on its leaf)
def opt_state_specs(tx_state, param_specs, param_shapes, zero_axes_tree, mesh):
    """Build a sharding-spec pytree for an optax state.

    `zero_axes_tree`: per-param tuple of dp axes to shard moments over (empty
    tuple => keep the param's own sharding, i.e. pure DP)."""

    def moment_spec(ps, shape, zax):
        shp = shape.shape if hasattr(shape, "shape") else shape
        return zero_split_spec(ps, shp, tuple(zax), dict(mesh.shape))

    def map_state(state):
        if isinstance(state, optax.ScaleByAdamState):
            # the moments' own tree: without the leaves Adam never sees
            mu = _without_no_gradient_leaves(jax.tree.map(
                moment_spec, param_specs, param_shapes, zero_axes_tree,
                is_leaf=lambda x: isinstance(x, P)))
            return optax.ScaleByAdamState(count=P(), mu=mu, nu=mu)
        if isinstance(state, tuple) and type(state) is not tuple:
            # other NamedTuple states: replicate scalars, param-like trees get param specs
            return jax.tree.map(lambda _: P(), state)
        if isinstance(state, tuple):
            return tuple(map_state(s) for s in state)
        return P()

    return map_state(tx_state)
