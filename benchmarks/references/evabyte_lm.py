"""Plain reference of EvaByte's training loss (HF `model_type: evabyte`,
`attention_class: eva`; EVA: Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023, as the EvaByte release runs it): a byte-level decoder
whose every layer attends exactly inside the query's own window and, in the
same softmax, to one pooled key and value for each chunk before that window,
and whose head predicts the next `pred_heads` bytes of every position.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: the rotation, the pooling and
the masks are written out here from positions, **every query scores EVERY key
and EVERY pooled chunk of the sequence under two explicit masks** (no window is
cut out, no block is skipped, no running maximum), a block of `QUERY_BLOCK`
queries at a time so that 8192 positions at the published widths fit a chip
(one block at the tests' sizes: dense (S, S) and (S, S / c) masks), and a layer
is recomputed in a backward pass (`jax.checkpoint`). It reads the program's
parameter tree (`models/base.py: init_layer_params`, the one coupling):
`wqkv.kernel` (h, 3, nh, hd), `wo.kernel` (nh x hd, h), `eva.{phi, mu}` (nh, hd),
`ln1`, `ln2` (scale), `wi.kernel` (h, 2, F) the gate's F columns beside the up
projection's, `wo_mlp.kernel` (F, h); `embed.wte` (V, h), `final_norm.scale`,
`lm_head.kernel` (h, pred_heads x V), head i's columns the i-th run of V.

The equations (x a position's row; RMS(x; w) = x / sqrt(mean(x^2) + eps) x (1 +
w): `norm_add_unit_offset`; W = eva_window, c = eva_chunk, C = W / c, s =
hd^-1/2; nh heads of hd dims, no biases anywhere):

- x_0 = E[byte]; every layer: h = x + EVA(RMS(x; ln1)); x = h + (silu(y Wg) * (y
  Wu)) Wd on y = RMS(h; ln2).
- q, k, v = y Wq, y Wk, y Wv a head; q and k turned by rope BEFORE anything
  else: theta `rope_theta`, the whole head, rotate-half (dims d and d + hd / 2 a
  pair, frequency theta^(-2 d / hd)).
- pooling: chunk j holds positions c j .. c j + c - 1; a_i = softmax_i(<phi, k_i>)
  over the chunk's positions (unscaled); K~_j = sum_i a_i k_i + mu; V~_j = sum_i
  a_i v_i.
- aggregation: query t lies in window n = t // W; its scores are s <q_t, k_i> for
  n W <= i <= t and s <q_t, K~_j> for j < n C (the chunks of every EARLIER window,
  none of its own); ONE softmax over the union; out_t = sum_i p_i v_i + sum_j p_j
  V~_j; then Wo. Window 0 is plain causal attention.
- head: logits = RMS(x_L; final_norm) W, (pred_heads, V) a position, float32;
  head i at position t predicts byte t + 1 + i = `labels[t + i]`; a target past
  the sequence's end is masked. Loss = the mean over the heads of each head's
  mean cross entropy.

Departures from the published description, each also in the configuration's
`assumed`: the published config.json is silent on the pooling's form (the
softmax of <phi, k> within the chunk, unscaled; mu on the pooled key alone), on
phi's and mu's initialisation, on the rotation's convention (rotate-half) and on
the eight losses' weights (equal): those are EvaByte's public form as recalled,
not fetched. `fp32_skip_add` (the residual add in float32) is the identity in a
float32 reference. `switch_off` (a set of names) changes one piece of the
mathematics at a time, for the tests that show each matters: "far_context" (no
pooled keys: attention inside the window alone), "mu" (no mu on the pooled key),
"own_chunks" (a query also sees the pooled chunks of its OWN window before its
own chunk: the other reading of "before"), "head_shift" (every head predicts
byte t + 1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + p["scale"])


def _turned(x, theta):
    """x (S, nh, hd) at positions 0 .. S - 1, rotate-half on the whole head."""
    s, _, hd = x.shape
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def pooled(k, v, phi, mu, chunk, off=frozenset()):
    """k, v (S, nh, hd), phi, mu (nh, hd) -> K~, V~ (S / chunk, nh, hd)."""
    s, nh, hd = k.shape
    kc, vc = k.reshape(s // chunk, chunk, nh, hd), v.reshape(s // chunk, chunk, nh, hd)
    a = jax.nn.softmax(jnp.einsum("jcnd,nd->jcn", kc, phi), axis=1)
    kp = jnp.einsum("jcn,jcnd->jnd", a, kc)
    return (kp if "mu" in off else kp + mu), jnp.einsum("jcn,jcnd->jnd", a, vc)


def eva_attention(q, k, v, phi, mu, window, chunk, off=frozenset(), with_mass=False):
    """q, k, v (S, nh, hd), q and k turned -> (S, nh, hd): every query on every
    key and every pooled chunk under the two masks, one softmax. `with_mass`:
    also the share of each query's softmax mass that falls on pooled chunks,
    (S, nh) (what the program reports as `eva_pooled_mass`; the chip check reads it)."""
    s, nh, hd = q.shape
    kp, vp = pooled(k, v, phi, mu, chunk, off)
    keys, chunks = jnp.arange(s), jnp.arange(s // chunk)

    @jax.checkpoint
    def block(qb, t):
        """Queries qb (b, nh, hd) at positions t (b,)."""
        first = (t // window) * window  # the window's first position
        own = (keys[None, :] <= t[:, None]) & (keys[None, :] >= first[:, None])
        far = chunks[None, :] < (t // chunk if "own_chunks" in off else first // chunk)[:, None]
        if "far_context" in off:
            far = jnp.zeros_like(far)
        scores = jnp.concatenate([jnp.where(own[None], jnp.einsum("bnd,knd->nbk", qb, k), -jnp.inf),
                                  jnp.where(far[None], jnp.einsum("bnd,jnd->nbj", qb, kp), -jnp.inf)], axis=-1)
        p = jax.nn.softmax(scores * hd ** -0.5, axis=-1)
        out = jnp.einsum("nbk,knd->bnd", p[..., :s], v) + jnp.einsum("nbj,jnd->bnd", p[..., s:], vp)
        return out, jnp.sum(p[..., s:], axis=-1).T

    b = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    out, mass = jax.lax.map(lambda args: block(*args), (q.reshape(s // b, b, nh, hd), keys.reshape(s // b, b)))
    return (out.reshape(s, nh, hd), mass.reshape(s, nh)) if with_mass else out.reshape(s, nh, hd)


def layer(lp, x, fields, off=frozenset()):
    """One layer on x (S, h); recomputed in a backward pass."""
    eps = fields["layernorm_eps"]

    def once(lp, x):
        y = _rms(x, lp["ln1"], eps)
        qkv = jnp.einsum("sh,hand->asnd", y, lp["wqkv"]["kernel"])
        q, k = _turned(qkv[0], fields["rope_theta"]), _turned(qkv[1], fields["rope_theta"])
        attn = eva_attention(q, k, qkv[2], lp["eva"]["phi"], lp["eva"]["mu"], fields["eva_window"],
                             fields["eva_chunk"], off)
        h = x + attn.reshape(x.shape[0], -1) @ lp["wo"]["kernel"]
        y = _rms(h, lp["ln2"], eps)
        gate_up = jnp.einsum("sh,hgf->gsf", y, lp["wi"]["kernel"])
        return h + (jax.nn.silu(gate_up[0]) * gate_up[1]) @ lp["wo_mlp"]["kernel"]

    return jax.checkpoint(once)(lp, x)


def sequence_logits(params, fields, tokens, off=frozenset()):
    """One sequence's logits (S, pred_heads, V), float32."""
    x = params["embed"]["wte"][tokens]
    for lp in params["layers"]:
        x = layer(lp, x, fields, off)
    x = _rms(x, params["final_norm"], fields["layernorm_eps"])
    return (x @ params["lm_head"]["kernel"]).reshape(x.shape[0], fields["pred_heads"], -1)


def _sequence(params, fields, off, tokens, labels, mask):
    """(each head's sum of cross entropies, each head's count of targets), (pred_heads,) each."""
    logits = sequence_logits(params, fields, tokens, off)
    s, heads, _ = logits.shape
    sums, counts = [], []
    for i in range(heads):
        shift = 0 if "head_shift" in off else i
        target = jnp.roll(labels, -shift)  # labels[t + i], where t + i is a position
        counted = jnp.roll(mask, -shift) * (jnp.arange(s) < s - shift)
        nll = jax.nn.logsumexp(logits[:, i], axis=-1) - jnp.take_along_axis(logits[:, i], target[:, None], axis=-1)[:, 0]
        sums.append(jnp.sum(nll * counted))
        counts.append(jnp.sum(counted))
    return jnp.stack(sums), jnp.stack(counts)


def logits(params, tokens, fields, switch_off=()):
    """(B, S, pred_heads, V), float32: every head's logits of every position."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return jax.lax.map(lambda row: sequence_logits(params, fields, row, frozenset(switch_off)), tokens)


def loss(params, batch, fields, switch_off=()):
    """The objective of the batch, float32: the mean over the heads of each head's mean cross entropy."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    off = frozenset(switch_off)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        mask = (batch["loss_mask"] if "loss_mask" in batch else jnp.ones(batch["labels"].shape)).astype(jnp.float32)
        sums, counts = jax.lax.map(lambda row: _sequence(params, fields, off, *row),
                                   (batch["tokens"], batch["labels"], mask))
        return jnp.mean(jnp.sum(sums, axis=0) / jnp.maximum(jnp.sum(counts, axis=0), 1.0))
