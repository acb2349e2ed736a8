"""A looped stack's own parts (`TransformerConfig.loop_steps` > 1, Ouro's LoopLM): the exit gate, the
distribution it makes over the passes, the objective that distribution weighs, and what the loop says to an
asker that has no form of it. No layer part: the loop wraps the whole stack (`models/base.looped_states`), so
neither table of `models/parts` holds it, and `unsupported_reason` asks it beside the layers' parts."""

from __future__ import annotations

from typing import Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import Params, _dense_init
from galvatron_tpu.obs import telemetry, tracing

PARTS = telemetry.LOOP_STEP_FIELDS  # what `expected_loss` hands back beside `loss_ce`: the `step` event's fields

# the loop re-enters the same leaves: a pipeline's stages would form a ring, the decode engine would keep a
# cache a pass and stop early, and the cost models price a layer's time and activations once
UNSUPPORTED = {
    "serve": "no per-pass caches and no early exit for a stack run several times over the same weights "
             "(loop_steps > 1)",
    "autotune": "a looped stack (loop_steps > 1) as a plain one: a layer's time and activations once, not a pass",
    "pp": "have no ring: the last stage's output of a looped stack (loop_steps > 1) re-enters the first",
    "tp_comm": "a looped stack (loop_steps > 1) and its sandwich norms",
    "quant": "a looped stack's exit terms (loop_steps > 1)",
    "search": "a looped stack (loop_steps > 1: time and activations a pass, state once)",
    "profile": "a looped stack (loop_steps > 1)",
}
# the decode engine's block and the manual TP path's add each half's raw output (the pipelines' stages and the
# profiler run `layer_forward` itself, and two norms are noise to the cost models)
POST_NORM_UNSUPPORTED = {
    "serve": "no sandwich norm (post_norm) on a decoded token's halves",
    "tp_comm": "sandwich norms (post_norm)",
}
RUNS = "one chip, under dp with ZeRO-1/2/3 and under GSPMD tensor parallelism"


def unsupported(cfg) -> Mapping[str, str]:
    """What the loop and the sandwich norms say to each asker that has no form of them ({} for a plain stack)."""
    if getattr(cfg, "loop_steps", 1) > 1:
        return UNSUPPORTED
    return POST_NORM_UNSUPPORTED if getattr(cfg, "post_norm", False) else {}


def init_exit_gate(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """Linear(hidden, 1): the kernel as every other, the bias 0 (a pass exits with probability near 1/2)."""
    return {"kernel": _dense_init(rng, (cfg.hidden_size, 1), cfg.init_std, cfg.param_dtype),
            "bias": jnp.zeros((1,), cfg.param_dtype)}


def exit_gate_specs() -> Params:
    return {"kernel": P(None, None), "bias": P(None)}


def exit_distribution(gate: Optional[Params], states: jax.Array) -> jax.Array:
    """`states` (T, B, S, H), the normed state after each pass -> p (T, B, S), float32, summing to 1 over T:
    lambda_t = sigmoid(h_t . w + b) for t < T, a float32 dot product a position at `highest` precision;
    p_t = lambda_t prod_{j<t} (1 - lambda_j), p_T = prod_{j<T} (1 - lambda_j), as products of sigmoids (1 -
    lambda = sigmoid(-logit): no difference of nearly equal floats; formed in logs instead, the chip's float32
    `log` left a p 8e-5 off, products of sigmoids 1e-6: my chip runs, PR 64). No gate: all mass on pass T."""
    steps = states.shape[0]
    if gate is None:
        return jnp.zeros(states.shape[:-1], jnp.float32).at[steps - 1].set(1.0)
    w, b = gate["kernel"].astype(jnp.float32)[:, 0], gate["bias"].astype(jnp.float32)[0]
    logits = jnp.dot(states[:steps - 1].astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST) + b
    leave, stay = jax.nn.sigmoid(logits), jax.nn.sigmoid(-logits)
    stayed = jnp.concatenate([jnp.ones_like(stay[:1]), jnp.cumprod(stay, axis=0)])  # prod_{j<t} (1 - lambda_j)
    return stayed * jnp.concatenate([leave, jnp.ones_like(leave[:1])])


def expected_loss(p: jax.Array, nll: jax.Array, loss_mask: Optional[jax.Array],
                  entropy_coef: float) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """The looped objective and its parts from p (T, B, S) and each pass's cross entropy a position `nll`
    (T, B, S), float32: the masked mean over positions of `sum_t p_t nll_t - entropy_coef x H(p)`, H(p) =
    -sum_t p_t ln p_t. Parts: `loss_ce` the weighted cross entropy, `loss_ce_first` / `loss_ce_last` pass 1's
    and pass T's plain means, `exit_step_mean` the mean of sum_t t p_t (1 .. T), `exit_entropy` the mean H(p)."""
    counted = jnp.ones(nll.shape[1:], jnp.float32) if loss_mask is None else loss_mask.astype(jnp.float32)
    total = jnp.maximum(jnp.sum(counted), 1.0)

    def mean(a):
        return jnp.sum(a * counted) / total

    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, jnp.finfo(jnp.float32).tiny)), axis=0)
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None, None]
    parts = {"loss_ce": mean(jnp.sum(p * nll, axis=0)), "loss_ce_first": mean(nll[0]), "loss_ce_last": mean(nll[-1]),
             "exit_step_mean": mean(jnp.sum(steps * p, axis=0)), "exit_entropy": mean(entropy)}
    loss = parts["loss_ce"] - entropy_coef * parts["exit_entropy"] if entropy_coef else parts["loss_ce"]
    return loss, parts


SCOPES = (tracing.LOOP, tracing.NORM_POST, tracing.EXIT)
