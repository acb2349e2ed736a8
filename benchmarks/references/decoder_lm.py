"""Plain reference of a decoder-only language model's loss.

Straightforward float32 `jax.numpy`, written from the published descriptions
(GPT-2: Radford et al. 2019 and HF `GPT2LMHeadModel`; Qwen2: HF
`Qwen2ForCausalLM`): no kernel, no fused path, no sharding rule, none of the
program's model code. It reads the program's parameter tree (the layout of
`models/base.py:init_model_params`, the one coupling) and the batch the
trainer feeds, and is switched by the configuration's fields alone:
norm_type, activation, position_type, causal, tie_embeddings, layernorm_eps,
rope_theta, the head counts. One sequence at a time and one head at a time
(`lax.map`), so the score matrix of an 8k sequence is 256 MiB and not 7.5 GiB.
Matmuls run at `highest` precision: on a TPU a float32 matmul is otherwise
done in bf16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _norm(x, p, fields):
    if fields["norm_type"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + fields["layernorm_eps"]) * p["scale"]
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + fields["layernorm_eps"]) * p["scale"] + p["bias"]


def _rotate_half(x, positions, theta):
    """HF rotate_half convention on (S, heads, head_dim)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * inv_freq  # (S, half)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _qkv(lp, y):
    """(S, hidden) -> q (S, nh, hd), k and v (S, nkv, hd), biases where the
    tree has them."""
    def proj(p):
        out = jnp.einsum("sh,h...->s...", y, p["kernel"])
        return out + p["bias"] if "bias" in p else out

    if "wqkv" in lp:
        qkv = proj(lp["wqkv"])  # (S, 3, nh, hd)
        return qkv[:, 0], qkv[:, 1], qkv[:, 2]
    kv = proj(lp["wkv"])  # (S, 2, nkv, hd)
    return proj(lp["wq"]), kv[:, 0], kv[:, 1]


def _attention(q, k, v, causal):
    """Softmax attention, a query head at a time; k/v head j serves query
    heads j*g .. (j+1)*g-1 (grouped-query attention)."""
    s, nh, hd = q.shape
    group = nh // k.shape[1]
    mask = jnp.tril(jnp.ones((s, s), bool)) if causal else None

    def one_head(i):
        qi = jax.lax.dynamic_index_in_dim(q, i, axis=1, keepdims=False)
        ki = jax.lax.dynamic_index_in_dim(k, i // group, axis=1, keepdims=False)
        vi = jax.lax.dynamic_index_in_dim(v, i // group, axis=1, keepdims=False)
        scores = qi @ ki.T / jnp.sqrt(jnp.float32(hd))
        if mask is not None:
            scores = jnp.where(mask, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vi  # (S, hd)

    out = jax.lax.map(one_head, jnp.arange(nh))  # (nh, S, hd)
    return out.transpose(1, 0, 2).reshape(s, nh * hd)


def _mlp(lp, y, fields):
    wi = jnp.einsum("sh,h...->s...", y, lp["wi"]["kernel"])
    if "bias" in lp["wi"]:
        wi = wi + lp["wi"]["bias"]
    act = fields["activation"]
    if act == "swiglu":  # wi is (S, 2, ffn): gate, up
        mid = jax.nn.silu(wi[:, 0]) * wi[:, 1]
    elif act == "gelu":  # GPT-2's tanh approximation ("gelu_new")
        mid = jax.nn.gelu(wi, approximate=True)
    elif act == "gelu_exact":
        mid = jax.nn.gelu(wi, approximate=False)
    else:
        raise ValueError("no reference for activation %r" % act)
    out = mid @ lp["wo_mlp"]["kernel"]
    return out + lp["wo_mlp"]["bias"] if "bias" in lp["wo_mlp"] else out


def _sequence_losses(params, fields, tokens, positions, labels):
    """Per-token cross entropy of one sequence, (S,)."""
    x = params["embed"]["wte"][tokens]
    if fields["position_type"] == "learned":
        x = x + params["embed"]["wpe"][positions]
    for lp in params["layers"]:
        q, k, v = _qkv(lp, _norm(x, lp["ln1"], fields))
        if fields["position_type"] == "rope":
            q = _rotate_half(q, positions, fields["rope_theta"])
            k = _rotate_half(k, positions, fields["rope_theta"])
        o = _attention(q, k, v, fields.get("causal", True)) @ lp["wo"]["kernel"]
        x = x + (o + lp["wo"]["bias"] if "bias" in lp["wo"] else o)
        x = x + _mlp(lp, _norm(x, lp["ln2"], fields), fields)
    x = _norm(x, params["final_norm"], fields)
    head = params["embed"]["wte"].T if fields["tie_embeddings"] else params["lm_head"]["kernel"]
    logits = x @ head
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def loss(params, batch, fields):
    """Masked token-mean cross entropy of the batch, float32."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        losses = jax.lax.map(
            lambda row: _sequence_losses(params, fields, *row),
            (batch["tokens"], batch["positions"], batch["labels"]))
        mask = batch["loss_mask"].astype(jnp.float32)
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
