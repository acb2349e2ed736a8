"""Plain reference of OLMoE's training loss (arXiv:2409.02060; HF
`OlmoeForCausalLM`): a decoder whose every block is attention with QK-norm
and a mixture of experts, and whose objective adds two router losses to the
cross entropy.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of
the program's model code, no sort, no gather of rows and no grouped matmul:
**every expert is applied densely to the whole sequence** and its output
masked by whether the token chose it (`lax.map` over the experts, one
sequence at a time), so a dropped, duplicated or misrouted token in the
program's dispatch shows as a difference. It reads the program's parameter
tree (`models/base.py:init_layer_params`, the one coupling): `wqkv` (h, 3, nh,
hd), `q_norm` / `k_norm` (nh x hd), `router` (h, E), `wi` (E, h, 2F) the gate's
F columns beside the up projection's, `wo_mlp` (E, F, h).

A block, as published: x + wo . attention(rope(heads(RMSNorm_whole(q))),
rope(heads(RMSNorm_whole(k))), v) with q, k, v = RMSNorm(x) W, no biases, rope
in HF's rotate_half convention; then y = RMSNorm(x), logits = y W_r,
p = softmax(logits), the `experts_per_token` largest p (the lower index wins
a tie, as `lax.top_k` and `torch.topk` have it), weights = those p, NOT
renormalised unless `norm_topk_prob`; x + sum over the chosen experts of
p_e x down_e(silu(gate_e(y)) x up_e(y)).

Loss = cross entropy + `router_aux_loss_coef` x load balancing +
`router_z_loss_coef` x router z-loss, where for each layer, over all the
batch's tokens, load balancing = E x sum_e f_e P_e (f_e = assignments to
expert e / tokens, P_e = mean probability of e) and z = mean logsumexp(logits)^2;
each is the mean over layers. Departures, each also in the configuration's
`assumed` / `not_modelled`: HF pools the layers' tokens BEFORE the product
f_e P_e (the same at one layer); HF's modelling code has no z-loss (the
paper's, 0.001); HF leaves padded tokens out of the load balancing when it is
given an attention mask (the benchmark's batches have no padding); `clip_qkv`
is null in the published config and not applied.

`batch["forced_experts"]` (batch, layers, seq, k), where given, replaces the
reference's own top-k by the experts named there, everything else unchanged:
top-k is discontinuous, so two evaluations of the stream that differ in the
last digits send a nearly tied token to different experts, and a comparison
of arithmetic wants the routing held equal (scripts/olmoe_chip_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x, positions, theta):
    """HF rotate_half convention on (S, heads, head_dim)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(lp, y, positions, fields):
    s = y.shape[0]
    eps = fields["layernorm_eps"]
    if "wqkv" in lp:
        qkv = jnp.einsum("sh,hcnd->csnd", y, lp["wqkv"]["kernel"])
        q, k, v = qkv[0], qkv[1], qkv[2]
    else:
        q = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
        kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"])
        k, v = kv[0], kv[1]
    if fields["qk_norm"]:  # over the whole projection, before the head split
        q = _rms(q.reshape(s, -1), lp["q_norm"]["scale"], eps).reshape(q.shape)
        k = _rms(k.reshape(s, -1), lp["k_norm"]["scale"], eps).reshape(k.shape)
    q = _rotate_half(q, positions, fields["rope_theta"])
    k = _rotate_half(k, positions, fields["rope_theta"])
    group = q.shape[1] // k.shape[1]
    mask = jnp.tril(jnp.ones((s, s), bool))

    def one_head(i):
        qi = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        ki = jax.lax.dynamic_index_in_dim(k, i // group, axis=1, keepdims=False)
        vi = jax.lax.dynamic_index_in_dim(v, i // group, axis=1, keepdims=False)
        scores = jnp.where(mask, qi @ ki.T / jnp.sqrt(jnp.float32(q.shape[-1])), -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vi

    heads = jax.lax.map(one_head, jnp.arange(q.shape[1]))  # (nh, S, hd)
    return heads.transpose(1, 0, 2).reshape(s, -1) @ lp["wo"]["kernel"]


def _experts(lp, y, fields, forced=None):
    """(S, h) -> the block's output and the router's sums over this sequence:
    assignments an expert (E,), summed probabilities (E,), summed z."""
    logits = y @ lp["router"]["kernel"]  # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top, chosen = jax.lax.top_k(probs, fields["experts_per_token"])
    if forced is not None:
        top, chosen = jnp.take_along_axis(probs, forced, axis=-1), forced
    gate = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=probs.dtype), axis=1)  # 0/1
    weights = probs * gate
    if fields["norm_topk_prob"]:
        weights = weights / jnp.sum(top, axis=-1, keepdims=True)

    @jax.checkpoint
    def one_expert(args):
        wi, wo, w = args  # (h, 2F), (F, h), (S,)
        gate, up = jnp.split(y @ wi, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wo * w[:, None]

    out = jnp.sum(jax.lax.map(
        one_expert, (lp["wi"]["kernel"], lp["wo_mlp"]["kernel"], weights.T)), axis=0)
    z = jnp.sum(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return out, (jnp.sum(gate, axis=0), jnp.sum(probs, axis=0), z)


def _sequence(params, fields, tokens, positions, labels, forced=None):
    """One sequence: its tokens' cross entropies (S,) and, a layer, the
    router's sums."""
    eps = fields["layernorm_eps"]
    x = params["embed"]["wte"][tokens]
    sums = []
    for i, lp in enumerate(params["layers"]):
        x = x + _attention(lp, _rms(x, lp["ln1"]["scale"], eps), positions, fields)
        out, layer_sums = _experts(lp, _rms(x, lp["ln2"]["scale"], eps), fields,
                                   None if forced is None else forced[i])
        x = x + out
        sums.append(layer_sums)
    logits = _rms(x, params["final_norm"]["scale"], eps) @ params["lm_head"]["kernel"]
    ce = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]
    return ce, jax.tree.map(lambda *a: jnp.stack(a), *sums)  # each (layers, ...)


def loss_parts(params, batch, fields):
    """{"ce", "load_balance", "router_z", "loss"}: the three terms before
    their coefficients, and the objective."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        rows = (batch["tokens"], batch["positions"], batch["labels"])
        if "forced_experts" in batch:
            rows += (batch["forced_experts"],)
        ce, (counts, prob_sums, z_sums) = jax.lax.map(
            lambda row: _sequence(params, fields, *row), rows)
        mask = batch["loss_mask"].astype(jnp.float32)
        ce = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        tokens = jnp.float32(batch["tokens"].size)
        f = jnp.sum(counts, axis=0) / tokens  # (layers, E)
        p = jnp.sum(prob_sums, axis=0) / tokens
        load_balance = jnp.mean(fields["num_experts"] * jnp.sum(f * p, axis=-1))
        router_z = jnp.mean(jnp.sum(z_sums, axis=0) / tokens)
        return {
            "ce": ce, "load_balance": load_balance, "router_z": router_z,
            "loss": (ce + fields["router_aux_loss_coef"] * load_balance
                     + fields["router_z_loss_coef"] * router_z),
        }


def loss(params, batch, fields):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields)["loss"]
