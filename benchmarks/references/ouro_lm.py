"""Plain reference of Ouro's training loss (HF `model_type: ouro`; ByteDance Seed et al., "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): a LoopLM, ONE stack of decoder layers applied T =
`loop_steps` times over the same weights, sandwich norms, an exit gate and the expected loss over the passes.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the program's model code and none
of its algorithms: Python loops over the rows of the batch, over the passes and over the layers (no scan, no
stacked leaves, no kernel), the rotation and the mask written out from positions, **every query scores EVERY
key under an explicit mask**, `QUERY_BLOCK` queries at a time so that 4096 positions at the published widths
fit a chip beside nothing else (one block at the tests' sizes). At the chip's sizes (a sequence of whole
blocks) a layer APPLICATION is recomputed in a backward pass (`jax.checkpoint`, as the other references'
layers: 24 applications of float32 activations do not fit a chip otherwise; the values are those of the plain
form, which a shorter sequence runs as it is). It reads the program's parameter tree
(`models/base.py: init_model_params`, the one coupling): `wqkv.kernel` (h, 3, nh, hd) and `wqkv.bias` (3,
nh, hd) where the tree has one, `wo.kernel` (nh x hd, h), `ln1`, `ln1_post`, `ln2`, `ln2_post` (scale),
`wi.kernel` (h, 2, F) the gate's F columns beside the up projection's, `wo_mlp.kernel` (F, h); `embed.wte`
(V, h), `final_norm.scale`, `exit_gate.{kernel (h, 1), bias (1,)}`, `lm_head.kernel` (h, V).

The equations (x a position's row; RMS(x; w) = x / sqrt(mean(x^2) + eps) x w; nh heads of hd dims on as many
key heads; s = hd^-1/2):

    layer(x):   a = RMS(x; ln1);  q, k, v = a Wq, a Wk, a Wv;  q, k turned by rope (theta, the whole head,
                rotate-half: dims d and d + hd / 2 a pair, frequency theta^(-2 d / hd))
                o = softmax(s q k^T + causal) v
                y = x + RMS(o Wo; ln1_post)                       <- sandwich: the half's OUTPUT is normed
                m = RMS(y; ln2);  f = (silu(m Wg) * (m Wu)) Wd
                x' = y + RMS(f; ln2_post)                         <- sandwich
    h_0 = E[token];  h_t = RMS(F(h_{t-1}); final_norm), F the stack, t = 1 .. T: the SAME F, the same norm;
                the normed state feeds the head AND re-enters the stack
    z_t = h_t W_head;  lambda_t = sigmoid(h_t . w_g + b_g), t < T
    p_1 = lambda_1, p_t = lambda_t prod_{j<t}(1 - lambda_j), p_T = prod_{j<T}(1 - lambda_j)
    loss = mean over counted positions of [ sum_t p_t CE(z_t, label) - beta H(p) ],  H(p) = -sum_t p_t ln p_t

Departures from the published description, each also in the configuration's `assumed`: the published
config.json is silent on the sandwich norms, on the norm between passes, on the gate's form, on beta, on a QKV
bias and on the rotation's convention; those are the public form as recalled, not fetched. `switch_off` (a
set of names) changes one piece of the mathematics at a time, for the tests that show each matters and for a
later PR that has the model's files to settle them: "post_norm" (a half's raw output joins the stream),
"loop_norm" (the stack's raw output re-enters it; the head and the gate still read the normed state), "gate"
(p = all mass on pass T), "entropy" (beta = 0), "qkv_bias" (a bias the tree holds on q, k, v, Qwen2's, is
left out: the reference ADDS a bias wherever the tree has one).

`params["passes"]`, where given, is a list of T stacks (each a list of layers' trees) in place of the one
`params["layers"]` every pass runs: the UNTIED model, whose T gradients sum to the tied model's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * p["scale"]


def _turned(x, positions, theta):
    """x (S, nh, hd) at `positions` (S,), rotate-half on the whole head."""
    hd = x.shape[-1]
    angles = positions.astype(jnp.float32)[:, None] * theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v):
    """q, k, v (S, nh, hd) -> (S, nh, hd): every query on every key, keys after the query masked out."""
    s, _, hd = q.shape
    keys = jnp.arange(s)
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out = []
    for start in range(0, s, block):
        t = keys[start:start + block]
        scores = jnp.einsum("bnd,knd->nbk", q[start:start + block], k) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(keys[None, None, :] <= t[None, :, None], scores, -jnp.inf), axis=-1)
        out.append(jnp.einsum("nbk,knd->bnd", p, v))
    return jnp.concatenate(out)


def layer(lp, x, positions, fields, off=frozenset()):
    """One layer APPLICATION on x (S, h); recomputed in a backward pass where the sequence is whole blocks."""
    eps = fields["layernorm_eps"]

    def once(lp, x):
        qkv = jnp.einsum("sh,hand->asnd", _rms(x, lp["ln1"], eps), lp["wqkv"]["kernel"])
        if "bias" in lp["wqkv"] and "qkv_bias" not in off:
            qkv = qkv + lp["wqkv"]["bias"][:, None]
        q, k = (_turned(t, positions, fields["rope_theta"]) for t in (qkv[0], qkv[1]))
        o = causal_attention(q, k, qkv[2]).reshape(x.shape[0], -1) @ lp["wo"]["kernel"]
        y = x + (o if "post_norm" in off else _rms(o, lp["ln1_post"], eps))
        gate_up = jnp.einsum("sh,hgf->gsf", _rms(y, lp["ln2"], eps), lp["wi"]["kernel"])
        f = (jax.nn.silu(gate_up[0]) * gate_up[1]) @ lp["wo_mlp"]["kernel"]
        return y + (f if "post_norm" in off else _rms(f, lp["ln2_post"], eps))

    return (jax.checkpoint(once) if x.shape[0] % QUERY_BLOCK == 0 else once)(lp, x)


def sequence_states(params, fields, tokens, positions, off=frozenset()):
    """One sequence's normed state after each pass, a list of T arrays (S, h)."""
    steps = fields["loop_steps"]
    passes = params.get("passes") or [params["layers"]] * steps
    x, states = params["embed"]["wte"][tokens], []
    for stack in passes:
        for lp in stack:
            x = layer(lp, x, positions, fields, off)
        normed = _rms(x, params["final_norm"], fields["layernorm_eps"])
        states.append(normed)
        x = x if "loop_norm" in off else normed
    return states


def exit_distribution(params, states, off=frozenset()):
    """The T normed states of a sequence -> p (T, S), each position's distribution over the passes."""
    if "gate" in off or "exit_gate" not in params:
        return jnp.stack([jnp.zeros(states[0].shape[0])] * (len(states) - 1) + [jnp.ones(states[0].shape[0])])
    w, b = params["exit_gate"]["kernel"][:, 0], params["exit_gate"]["bias"][0]
    p, stayed = [], jnp.ones(states[0].shape[0])
    for h in states[:-1]:
        lam = jax.nn.sigmoid(h @ w + b)
        p.append(lam * stayed)
        stayed = stayed * (1.0 - lam)
    return jnp.stack(p + [stayed])


def _sequence(params, fields, off, tokens, positions, labels):
    """(each pass's cross entropy a position (T, S), p (T, S)) of one sequence."""
    states = sequence_states(params, fields, tokens, positions, off)
    nll = []
    for h in states:
        logits = h @ params["lm_head"]["kernel"]
        nll.append(jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0])
    return jnp.stack(nll), exit_distribution(params, states, off)


def loss_parts(params, batch, fields, switch_off=()):
    """(the objective, its parts under the program's names), float32: `loss_ce` the weighted cross entropy,
    `loss_ce_first` / `loss_ce_last` pass 1's and pass T's plain means, `exit_step_mean` the mean of
    sum_t t p_t, `exit_entropy` the mean H(p), and `exit_p` the mean distribution over the passes (T,)."""
    if "layers" not in params and "passes" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); this tree has %s" % sorted(params))
    off = frozenset(switch_off)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        rows = [_sequence(params, fields, off, batch["tokens"][i], batch["positions"][i], batch["labels"][i])
                for i in range(batch["tokens"].shape[0])]
        nll, p = jnp.stack([r[0] for r in rows], axis=1), jnp.stack([r[1] for r in rows], axis=1)  # (T, B, S)
        mask = (batch["loss_mask"] if "loss_mask" in batch else jnp.ones(batch["labels"].shape)).astype(jnp.float32)

        def mean(a):
            return jnp.sum(a * mask, axis=(-2, -1)) / jnp.maximum(jnp.sum(mask), 1.0)

        entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0), axis=0)
        steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None, None]
        parts = {"loss_ce": mean(jnp.sum(p * nll, axis=0)), "loss_ce_first": mean(nll[0]), "loss_ce_last": mean(nll[-1]),
                 "exit_step_mean": mean(jnp.sum(steps * p, axis=0)), "exit_entropy": mean(entropy), "exit_p": mean(p)}
        beta = 0.0 if "entropy" in off else fields.get("exit_entropy_coef", 0.0)
        return parts["loss_ce"] - beta * parts["exit_entropy"], parts


def loss(params, batch, fields, switch_off=()):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields, switch_off)[0]
