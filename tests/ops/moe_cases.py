"""ops/moe.py against a plain per-token loop, float32 on the CPU.

The loop below sorts nothing and gathers nothing: for every token it walks
the token's `k` chosen experts, multiplies the token's row by that expert's
two kernels and adds the result at the router's weight. The block must give
the same output, the same gradient to every operand and the same counters,
whatever `k` is (a TPU tiles an array's two minor dimensions by 8 x 128, so
the block keeps its `tokens x k` assignments k-major: `ops/moe.py`).
"""

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.ops import moe


TOKENS, HIDDEN, WIDTH, EXPERTS = 24, 16, 8, 16


HELD = (5, 6)  # experts 5 to 10 of the 16


KS = (1, 2, 4, 6, 8)


ROUTERS = {
    "softmax": dict(score="softmax", norm_topk_prob=False, scale=1.0),
    "sigmoid": dict(score="sigmoid", norm_topk_prob=True, scale=1.8),
}


def _operands(seed, held):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = EXPERTS if held is None else held[1]
    return dict(
        y=jax.random.normal(keys[0], (TOKENS, HIDDEN), jnp.float32),
        router=jax.random.normal(keys[1], (HIDDEN, EXPERTS), jnp.float32) * 0.3,
        wi=jax.random.normal(keys[2], (n, HIDDEN, 2 * WIDTH), jnp.float32) * 0.2,
        wo=jax.random.normal(keys[3], (n, WIDTH, HIDDEN), jnp.float32) * 0.2,
        bias=jax.random.normal(keys[4], (EXPERTS,), jnp.float32) * 0.05,
        cot=jax.random.normal(keys[5], (TOKENS, HIDDEN), jnp.float32),
    )


def _scores(y, router, score):
    logits = jnp.dot(y, router, precision=jax.lax.Precision.HIGHEST)
    return logits, (jax.nn.softmax(logits, axis=-1) if score == "softmax" else jax.nn.sigmoid(logits))


def _choices(ops, k, score):
    """(tokens, k) numpy: the k highest experts a token, ties to the lower
    index, by the score (plus the bias for the sigmoid router)."""
    ranked = np.asarray(_scores(ops["y"], ops["router"], score)[1])
    if score == "sigmoid":
        ranked = ranked + np.asarray(ops["bias"])
    return np.argsort(-ranked, axis=-1, kind="stable")[:, :k]


def _loop(y, router, wi, wo, choices, *, score, norm_topk_prob, scale, held):
    """The block as a loop over tokens and their choices; `choices` concrete."""
    first, count = (0, EXPERTS) if held is None else held
    logits, scores = _scores(y, router, score)
    out = []
    for t in range(TOKENS):
        w = scores[t, choices[t]]
        if norm_topk_prob:
            w = w / (jnp.sum(w) + (1e-20 if score == "sigmoid" else 0.0))
        w = w * scale
        row = jnp.zeros((HIDDEN,), jnp.float32)
        for j, e in enumerate(choices[t] - first):
            if 0 <= e < count:
                mid = y[t] @ wi[e]
                row = row + w[j] * ((jax.nn.silu(mid[:WIDTH]) * mid[WIDTH:]) @ wo[e])
        out.append(row)
    counts = np.bincount(choices.reshape(-1), minlength=EXPERTS).astype(np.float32)
    aux = {"load_max_over_mean": counts.max() / counts.mean()}
    if score == "softmax":
        aux["load_balance"] = EXPERTS * jnp.sum(counts / TOKENS * jnp.mean(scores, axis=0))
        aux["router_z"] = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    else:
        aux["counts"] = counts
    if held is not None:
        aux["rows_held"] = counts[first:first + count].sum()
    return jnp.stack(out), aux


def _block(y, router, wi, wo, bias, k, router_kw, held):
    out, aux = moe.moe_ffn(y[None], router, wi, wo, experts_per_token=k, dtype=jnp.float32,
                           bias=bias if router_kw["score"] == "sigmoid" else None, held=held,
                           **router_kw)
    return out[0], aux


def _objective(out, aux, cot, score):
    return jnp.sum(out * cot) + (aux["load_balance"] + aux["router_z"] if score == "softmax" else 0.0)
