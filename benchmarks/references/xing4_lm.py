"""Plain reference of Xing4.0-29B-A4B's training loss (`model_type: xing4_0`): DeepSeek-V3's block
(arXiv:2412.19437: latent attention, leading dense layers, then a shared expert beside routed ones chosen by a
sigmoid router with a bias) inside manifold-constrained hyper-connections ("mHC", arXiv:2512.24880, on
"Hyper-Connections", arXiv:2409.19606), without its multi-token-prediction module.

Straightforward float32 `jax.numpy` at `highest` matmul precision and none of the program's model code: Python
loops over the layers, a layer's two halves, the n streams and the Sinkhorn steps; the streams a (S, n, C)
array; every query on every key under an explicit mask, a head at a time, q and k at their 192 dims and v at
its 128, unpadded; **every held expert applied densely to the whole sequence** and its output weighted by
whether the token chose it (so a dropped, duplicated or misrouted token in the program's dispatch shows as a
difference); no scan, no kernel, no sort, no gather of rows. Each layer and each head's attention is wrapped in
`jax.checkpoint`, a tool of memory and not of the model, so that a gradient of 4096 tokens at the published
widths fits a chip; a sequence at a time (`jax.lax.map` over the batch's rows). It reads the program's
parameter tree (`models/base.py:init_layer_params`, the one coupling): GLM-4.7-Flash's leaves (`wq_a`,
`q_a_norm`, `wq_b`, `wkv_a`, `kv_a_norm`, `wkv_b`, `wo`; a dense layer's `wi` (h, 2, F) and `wo_mlp`; a routed
layer's `router.kernel`, `router.e_score_correction_bias`, `wi` (held, h, 2F), `wo_mlp` (held, F, h), `shared`)
and, a half, `hc1` / `hc2`: `phi` (n C, n^2 + 2n) with columns [pre | post | res row by row], `b` (n^2 + 2n),
`a` (3,) = (a_pre, a_post, a_res).

The equations (X a token's n streams of C; RMSNorm eps `layernorm_eps`; no biases):

- a hyper-connected half with body F (F = MLA o RMSNorm(.; ln1), or FFN o RMSNorm(.; ln2)):
  x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps); [p | q | r] = x~ Phi;
  H_pre = sigmoid(a_pre p + b_pre); H_post = 2 sigmoid(a_post q + b_post);
  M_0 = exp(clip(a_res mat(r) + b_res, hc_res_clamp)); M_t = rows(cols(M_{t-1})), cols(M) = M / (1^T M +
  hc_eps), rows(M) = M / (M 1 + hc_eps), t = 1 .. hc_sinkhorn_iters; H_res = M_T;
  u = sum_j H_pre[j] X[j]; o = F(u); X'[i] = sum_j H_res[i, j] X[j] + H_post[i] o.
- MLA: cq = RMSNorm(a Wqa); q_h = cq Wqb_h = [q_nope_h | q_rope_h]; [ckv | kr] = a Wkva; [k_nope_h | v_h] =
  RMSNorm(ckv) Wkvb_h; k_h = [k_nope_h | rope(kr)] (ONE rotated key for all heads), q_h = [q_nope_h |
  rope(q_rope_h)]; o_h = softmax_causal(q_h k_h^T x scale) v_h; MLA = concat_h(o_h) Wo.
- rope: rotate_half over the `qk_rope_head_dim` dims as they lie, at yarn's frequencies written out here
  (`yarn_inv_freq`: DeepSeek's `yarn_find_correction_range` and linear ramp), cos and sin x m(MSCALE) /
  m(MSCALE_ALL_DIM); scale = (nope + rope)^-1/2 x m(MSCALE_ALL_DIM)^2, m(s) = 0.1 s ln(factor) + 1. `MSCALE`
  and `MSCALE_ALL_DIM` are the published 1 and 1, constants HERE: the program's config carries what its family
  file made of them (`attention_factor`, `attention_multiplier`), and this file does not read those two.
- FFN: the first `first_dense_layers` layers (silu(x Wg) * (x Wu)) Wd; after them Shared(x) + sum over the
  picked experts HELD HERE of g_e Expert_e(x): s = sigmoid(x Wr); pick = the `experts_per_token` largest of s +
  b (the lower index wins a tie); g_e = `routed_scaling_factor` x s_e / (sum over the pick of s + 1e-20). b
  takes no gradient; the objective has no router loss.
- model: X_0[i] = Emb[token] for every i; L layers; h = sum_i X_L[i]; logits = RMSNorm(h; final_norm) W_head;
  loss = the mean cross entropy over the positions that count.

`switch_off` (a set of names) puts the OTHER candidate in the place of one form the published config is silent
on (the configuration file's `assumed`), so that a later PR with HF's modeling file can settle each: "sum_out"
(the streams' mean feeds the final norm), "x_scale" (x~ is vec(X) as it lies, no division by its RMS: any
learned scale of x~ changes the coefficients through here), "sinkhorn_order" (rows, then columns), "clamp" (no
clamp on the logit), "yarn_mscale" (the softmax at (nope + rope)^-1/2 alone), "hyper" (ONE stream and the plain
residual `x + F(norm x)`: the hc leaves unread). A chip's share of the experts (`experts_held` of `num_experts`
from `experts_held_start`) and of the vocabulary are the configuration's cut: what the experts held elsewhere
would add is left out here as in the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

BIAS = "e_score_correction_bias"
MSCALE, MSCALE_ALL_DIM = 1.0, 1.0  # the published rope_scaling's, which the program's config holds mapped


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _m(factor, s):
    return 0.1 * s * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_inv_freq(dims, theta, scaling):
    """(dims / 2,) inverse frequencies: theta^(-2i/dims) where dim i turns more than beta_fast times over the
    original context, that / factor where fewer than beta_slow, a linear ramp between (floor, ceil)."""
    plain = [theta ** (-2.0 * i / dims) for i in range(dims // 2)]
    if scaling is None:
        return jnp.asarray(plain, jnp.float32)

    def dim_of(turns):
        return dims * math.log(scaling["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(dim_of(scaling["beta_fast"])), 0), min(math.ceil(dim_of(scaling["beta_slow"])), dims - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(plain):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f * (1.0 - ramp) + f / scaling["factor"] * ramp)
    return jnp.asarray(out, jnp.float32)


def _rotate_half(x, positions, theta, scaling):
    """HF's rotate_half on (S, heads, dims) at yarn's frequencies, cos and sin x m(MSCALE) / m(MSCALE_ALL_DIM)."""
    half = x.shape[-1] // 2
    angles = positions[:, None].astype(jnp.float32) * yarn_inv_freq(x.shape[-1], theta, scaling)
    by = 1.0 if scaling is None else _m(scaling["factor"], MSCALE) / _m(scaling["factor"], MSCALE_ALL_DIM)
    cos, sin = jnp.cos(angles)[:, None, :] * by, jnp.sin(angles)[:, None, :] * by
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _causal_attention(q, k, v, scale):
    """(S, heads, dq), (S, heads, dq), (S, heads, dv) -> (S, heads, dv): a head at a time, every query on every
    key under the mask."""
    s = q.shape[0]
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv
        scores = jnp.where(seen, qh @ kh.T * scale, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh

    heads = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def _latent_attention(lp, y, positions, fields, off):
    eps, theta, scaling = fields["layernorm_eps"], fields["rope_theta"], fields["rope_scaling"]
    nope, rope, lora = fields["qk_nope_head_dim"], fields["qk_rope_head_dim"], fields["kv_lora_rank"]
    cq = _rms(y @ lp["wq_a"]["kernel"], lp["q_a_norm"]["scale"], eps)
    q = jnp.einsum("sr,rnd->snd", cq, lp["wq_b"]["kernel"])
    ckv_kr = y @ lp["wkv_a"]["kernel"]
    ckv = _rms(ckv_kr[:, :lora], lp["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rnd->snd", ckv, lp["wkv_b"]["kernel"])
    k_rope = _rotate_half(ckv_kr[:, None, lora:], positions, theta, scaling)  # (S, 1, rope): one key
    q = jnp.concatenate([q[..., :nope], _rotate_half(q[..., nope:], positions, theta, scaling)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rope, (k_rope.shape[0], q.shape[1], rope))], axis=-1)
    scale = (nope + rope) ** -0.5
    if scaling is not None and MSCALE_ALL_DIM and "yarn_mscale" not in off:
        scale *= _m(scaling["factor"], MSCALE_ALL_DIM) ** 2
    out = _causal_attention(q, k, kv[..., nope:], scale)
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def _routed(lp, y, fields):
    """-> the routed experts' part held here (S, h), and the pick (S, k)."""
    scores = jax.nn.sigmoid(y @ lp["router"]["kernel"])  # (S, E)
    pick = jax.lax.top_k(scores + jax.lax.stop_gradient(lp["router"][BIAS]), fields["experts_per_token"])[1]
    chosen = jnp.sum(jax.nn.one_hot(pick, scores.shape[-1], dtype=scores.dtype), axis=1)  # 0/1
    weights = scores * chosen
    if fields["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * fields["routed_scaling_factor"]
    first = fields["experts_held_start"] if fields["experts_held"] else 0
    out = 0.0
    for e in range(lp["wi"]["kernel"].shape[0]):  # the experts held here, one by one
        gate, up = jnp.split(y @ lp["wi"]["kernel"][e], 2, axis=-1)
        out = out + (jax.nn.silu(gate) * up) @ lp["wo_mlp"]["kernel"][e] * weights[:, first + e, None]
    return out, pick


def _ffn(lp, y, fields):
    if "router" not in lp:
        return _swiglu(lp, y), None
    routed, pick = _routed(lp, y, fields)
    return _swiglu(lp["shared"], y) + routed, pick


def coefficients(hp, x, fields, off=frozenset()):
    """A half's (H_pre (S, n), H_post (S, n), H_res (S, n, n)) from its streams `x` (S, n, C)."""
    s, n, c = x.shape
    eps = fields["hc_eps"]
    flat = x.reshape(s, n * c)  # vec(X): stream j the entries j C .. (j + 1) C
    if "x_scale" not in off:
        flat = flat / jnp.sqrt(jnp.mean(flat * flat, axis=-1, keepdims=True) + eps)
    pqr = flat @ hp["phi"]
    a_pre, a_post, a_res = hp["a"][0], hp["a"][1], hp["a"][2]
    h_pre = jax.nn.sigmoid(a_pre * pqr[:, :n] + hp["b"][:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * pqr[:, n:2 * n] + hp["b"][n:2 * n])
    logit = a_res * pqr[:, 2 * n:].reshape(s, n, n) + hp["b"][2 * n:].reshape(n, n)
    if fields["hc_res_clamp"] is not None and "clamp" not in off:
        logit = jnp.clip(logit, fields["hc_res_clamp"][0], fields["hc_res_clamp"][1])
    m = jnp.exp(logit)  # m[:, i, j] = H_res[i, j]
    order = (2, 1) if "sinkhorn_order" in off else (1, 2)  # axis 1 sums a column's entries, axis 2 a row's
    for _ in range(fields["hc_sinkhorn_iters"]):
        for axis in order:
            m = m / (jnp.sum(m, axis=axis, keepdims=True) + eps)
    return h_pre, h_post, m


def _half(hp, x, body, fields, off):
    """One hyper-connected half on the streams (S, n, C) -> the streams, and what `body` hands back beside."""
    n = x.shape[1]
    h_pre, h_post, h_res = coefficients(hp, x, fields, off)
    u = 0.0
    for j in range(n):
        u = u + h_pre[:, j, None] * x[:, j]
    o, said = body(u)
    streams = []
    for i in range(n):
        kept = 0.0
        for j in range(n):
            kept = kept + h_res[:, i, j, None] * x[:, j]
        streams.append(kept + h_post[:, i, None] * o)
    return jnp.stack(streams, axis=1), said


def _layer(lp, x, positions, fields, off):
    eps = fields["layernorm_eps"]

    def attn(u):
        return _latent_attention(lp, _rms(u, lp["ln1"]["scale"], eps), positions, fields, off), None

    def ffn(u):
        return _ffn(lp, _rms(u, lp["ln2"]["scale"], eps), fields)

    if "hyper" in off:  # one stream, the plain residual
        x = x + attn(x)[0]
        o, pick = ffn(x)
        return x + o, pick
    x, _ = _half(lp["hc1"], x, attn, fields, off)
    return _half(lp["hc2"], x, ffn, fields, off)


def sequence_hidden(params, fields, tokens, positions, off=frozenset()):
    """One sequence: what the final norm reads (S, C), and the routed blocks' picks."""
    n = fields["hc_mult"]
    x = params["embed"]["wte"][tokens]
    if "hyper" not in off:
        x = jnp.stack([x] * n, axis=1)
    picks = []
    for lp in params["layers"]:
        x, pick = jax.checkpoint(lambda lp, x: _layer(lp, x, positions, fields, off))(lp, x)
        picks += [] if pick is None else [pick]
    if "hyper" not in off:
        x = jnp.mean(x, axis=1) if "sum_out" in off else jnp.sum(x, axis=1)
    return x, (jnp.stack(picks) if picks else jnp.zeros((0,), jnp.int32))


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]


def loss_parts(params, batch, fields, switch_off=()):
    """{"loss", "picks"}: the objective (the mean cross entropy) and the experts picked (batch, routed blocks,
    seq, k)."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); this tree has %s" % sorted(params))
    if "mtp" in params:
        raise ValueError("the reference has no form of a multi-token-prediction module beside hyper-connections")
    off = frozenset(switch_off)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)

        def row(args):
            tokens, positions, labels = args
            x, picks = sequence_hidden(params, fields, tokens, positions, off)
            logits = _rms(x, params["final_norm"]["scale"], fields["layernorm_eps"]) @ params["lm_head"]["kernel"]
            return _nll(logits, labels), picks

        ce, picks = jax.lax.map(row, (batch["tokens"], batch["positions"], batch["labels"]))
        mask = (batch["loss_mask"] if "loss_mask" in batch else jnp.ones(ce.shape)).astype(jnp.float32)
        return {"loss": jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0), "picks": picks}


def loss(params, batch, fields, switch_off=()):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields, switch_off)["loss"]
