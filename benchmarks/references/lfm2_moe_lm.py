"""Plain reference of LFM2-MoE's training loss (HF `Lfm2MoeForCausalLM`,
`model_type: lfm2_moe`; `Lfm2MoeDecoderLayer`, `Lfm2MoeShortConv`,
`Lfm2MoeAttention`, `Lfm2MoeSparseMoeBlock`): gated short-convolution layers
among grouped-query attention layers, leading dense layers, then
sigmoid-routed experts with no shared one, a head tied to the embedding.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: the convolution is K shifted
multiply-adds of a sequence **padded once in front** (`conv_shifted`),
attention is a plain masked softmax computed a head and a block of
`QUERY_BLOCK` rows at a time with rope as HF's `rotate_half`, and there is no
sort, no gather of rows and no grouped matmul: **every held expert is applied
densely to the whole sequence** and its output masked by whether the token
chose it. One sequence at a time, a layer recomputed in a backward pass, so
that 2 x 8192 tokens at the published widths fit a chip beside the trainer. It
reads the program's parameter tree (`models/base.py:init_layer_params`, the one
coupling): a convolution layer's `conv.{win (h, [B | C | u]), taps (h, K),
wout (h, h)}`; an attention layer's `wq` (h, nh, hd), `wkv` (h, 2, nkv, hd),
`q_norm.scale` and `k_norm.scale` (hd,), `wo` (nh x hd, h); a dense layer's
`wi` (h, 2, F) gate then up and `wo_mlp` (F, h); a routed layer's
`router.kernel` (h, E), `router.e_score_correction_bias` (E,), `wi` (held, h,
2F) the gate's F columns beside the up projection's, `wo_mlp` (held, F, h);
`embed.wte` (V, h), which is also the head, and `final_norm` (HF's
`embedding_norm`). Which layers convolve, and which halves are routed, the tree
says.

The equations (x a token's row; RMS(x; w) = x / sqrt(mean x^2 + eps) w; no
biases):

- every layer: x <- x + Mixer(RMS(x; ln1)); x <- x + FFN(RMS(x; ln2)); after
  the stack RMS and the tied head, logits = RMS(x_L; final_norm) E^T.
- convolution mixer: [B | C | u] = y Win, three chunks of h channels;
  `v_t = sum_j taps_j (B * u)_{t-(K-1)+j}`, zeros before the start, no
  activation; (C * v) Wout.
- attention mixer: q, k, v = y Wq, y Wkv; q and k RMS-normed over a head's dims
  with ONE scale for all heads; rope(theta) on all of a head's dims; causal
  softmax(q k^T / sqrt(hd)) v, a key head serving nh / nkv consecutive query
  heads; Wo.
- FFN: the first `first_dense_layers` layers (silu(x Wg) * (x Wu)) Wd. After
  them Routed(x): s = sigmoid(x Wr); pick = the `experts_per_token` largest of
  s + b (the lower index wins a tie); g_e = `routed_scaling_factor` x s_e /
  (sum over the pick of s + 1e-6), **HF's epsilon** (the program adds 1e-20:
  5e-7 relative at a sum of about 2, the configuration's `assumed`);
  Routed(x) = sum over the picked experts HELD HERE of g_e Expert_e(x). b takes
  no gradient.
- loss = CE, the mean over the positions that have a label.

Departures from HF's code, each also in the configuration's `assumed` /
`not_modelled`: a chip's share of the experts (`experts_held` of `num_experts`
from `experts_held_start`) and of the vocabulary are the configuration's cut:
what the experts held elsewhere would add is left out here as in the program.
`switch_off` (a set of names) drops one piece of the mathematics at a time, for
the tests that show each matters: "qk_norm" (q and k as projected), "rope" (no
rotation), "gqa" (key head i % nkv serves query head i, not i // group),
"first_tap" (the tap that reaches furthest back is zero), "router_bias" (the
pick is the scores' own).

`batch["forced_experts"]` (batch, routed blocks, seq, k), where given, replaces
the reference's own pick by the experts named there, everything else
unchanged: top-k is discontinuous, so a comparison of arithmetic wants the
routing held equal (scripts/lfm2_chip_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
ROUTER_EPS = 1e-6
BIAS = "e_score_correction_bias"


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x, positions, theta):
    """HF's rotate_half convention on (S, heads, dims), all of a head's dims."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _causal_attention(q, k, v):
    """q (S, heads, d), k, v (S, heads, d) -> (S, heads, d): a head at a time, a
    block of query rows at a time against all keys."""
    s = q.shape[0]
    block = next(b for b in range(min(QUERY_BLOCK, s), 0, -1) if s % b == 0)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    key_pos = jnp.arange(s)

    def one_head(qkv):
        qh, kh, vh = qkv  # (S, d)

        @jax.checkpoint
        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, axis=0)
            seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
            scores = jnp.where(seen, qb @ kh.T * scale, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1)

    heads = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def attention(lp, y, positions, fields, off=frozenset()):
    """The attention mixer on normed rows (S, h) -> (S, h)."""
    eps = fields["layernorm_eps"]
    q = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
    kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"])
    k, v = kv[0], kv[1]
    if "qk_norm" not in off:
        q, k = _rms(q, lp["q_norm"]["scale"], eps), _rms(k, lp["k_norm"]["scale"], eps)
    if "rope" not in off:
        q, k = (_rotate_half(t, positions, fields["rope_theta"]) for t in (q, k))
    heads, kv_heads = q.shape[1], k.shape[1]
    serves = jnp.arange(heads) % kv_heads if "gqa" in off else jnp.arange(heads) // (heads // kv_heads)
    out = _causal_attention(q, k[:, serves], v[:, serves])
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def conv_shifted(x, taps):
    """(S, C), (C, K) -> (S, C): c_t = sum_j taps[:, j] x_{t - (K - 1) + j}, zeros
    before the start: K - 1 rows of zeros in front ONCE (`jnp.pad`), then K
    shifted slices. (NOT a tap at a time as `concatenate([zeros, x[:S - back]])`,
    nor as a roll under a mask: at 8192 rows XLA:TPU compiles either into a
    shift WITHIN 1024-row tiles, and rows 1024 n to 1024 n + 2 lose the taps
    that reach into the tile before: PERF.md section 6, PR 42.)"""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + taps[:, j] * padded[j:j + s]
    return out


def short_conv(lp, y, off=frozenset()):
    """The gated short convolution on normed rows (S, h) -> (S, h)."""
    lp = lp["conv"]
    gate_in, gate_out, u = jnp.split(y @ lp["win"]["kernel"], 3, axis=-1)  # HF: B, C, x = chunk(3)
    taps = lp["taps"].at[:, 0].set(0.0) if "first_tap" in off else lp["taps"]
    return (gate_out * conv_shifted(gate_in * u, taps)) @ lp["wout"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def routed(lp, y, fields, forced=None, off=frozenset()):
    """-> the routed experts' part held here (S, h), and the pick (S, k)."""
    scores = jax.nn.sigmoid(y @ lp["router"]["kernel"])  # (S, E)
    ranked = scores
    if BIAS in lp["router"] and "router_bias" not in off:
        ranked = scores + jax.lax.stop_gradient(lp["router"][BIAS])
    pick = jax.lax.top_k(ranked, fields["experts_per_token"])[1] if forced is None else forced
    chosen = jnp.sum(jax.nn.one_hot(pick, scores.shape[-1], dtype=scores.dtype), axis=1)  # 0/1
    weights = scores * chosen
    if fields["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + ROUTER_EPS)
    weights = weights * fields["routed_scaling_factor"]
    first = fields["experts_held_start"] if fields["experts_held"] else 0
    held = lp["wi"]["kernel"].shape[0]
    weights = weights[:, first:first + held]  # what the others would add is left out

    @jax.checkpoint
    def one_expert(args):
        wi, wo, w = args  # (h, 2F), (F, h), (S,)
        gate, up = jnp.split(y @ wi, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wo * w[:, None]

    out = jnp.sum(jax.lax.map(
        one_expert, (lp["wi"]["kernel"], lp["wo_mlp"]["kernel"], weights.T)), axis=0)
    return out, pick


def _block(lp, x, positions, fields, forced, off):
    """One layer; recomputed in a backward pass (`jax.checkpoint`), so that a
    gradient of the whole sequence at the published widths fits a chip."""
    def once(lp, x, forced):
        eps = fields["layernorm_eps"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        x = x + (short_conv(lp, y, off) if "conv" in lp else attention(lp, y, positions, fields, off))
        y = _rms(x, lp["ln2"]["scale"], eps)
        if "router" not in lp:
            return x + _swiglu(lp, y), None
        out, pick = routed(lp, y, fields, forced, off)
        return x + out, pick

    return jax.checkpoint(once)(lp, x, forced)


def _nll(out, labels):
    return jax.nn.logsumexp(out, axis=-1) - jnp.take_along_axis(out, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, off, tokens, positions, labels=None, forced=None):
    """One sequence: its logits (S, V), or with labels its tokens' cross
    entropies (S,); and the routed blocks' picks (routed blocks, S, k)."""
    table = params["embed"]["wte"]
    x = table[tokens]
    picks = []
    for lp in params["layers"]:
        x, pick = _block(lp, x, positions, fields,
                         None if forced is None or "router" not in lp else forced[len(picks)], off)
        picks += [] if pick is None else [pick]
    x = _rms(x, params["final_norm"]["scale"], fields["layernorm_eps"])
    out = x @ (table.T if fields["tie_embeddings"] else params["lm_head"]["kernel"])
    picks = jnp.stack(picks) if picks else jnp.zeros((0, tokens.shape[0], fields["experts_per_token"]), jnp.int32)
    return (out if labels is None else _nll(out, labels)), picks


def _rows(params, batch, fields, switch_off, labels):
    """A sequence at a time: (logits (B, S, V), or the cross entropies (B, S)
    where `labels`; picks (B, routed blocks, S, k))."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    off = frozenset(switch_off)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    forced = batch.get("forced_experts")

    def row(r):
        return _sequence(params, fields, off, r["tokens"], r["positions"], r.get("labels"), r.get("forced"))

    rows = {"tokens": batch["tokens"], "positions": batch["positions"]}
    if labels:
        rows["labels"] = batch["labels"]
    if forced is not None:
        rows["forced"] = forced
    return jax.lax.map(row, rows)


def logits(params, batch, fields, switch_off=()):
    """The head's logits (B, S, V), float32."""
    with jax.default_matmul_precision("highest"):
        return _rows(params, batch, fields, switch_off, labels=False)[0]


def loss_parts(params, batch, fields, switch_off=()):
    """{"ce", "loss", "picks"}: the cross entropy, which is the objective, and
    the experts picked (batch, routed blocks, seq, k)."""
    with jax.default_matmul_precision("highest"):
        ce, picks = _rows(params, batch, fields, switch_off, labels=True)
        mask = batch["loss_mask"].astype(jnp.float32) if "loss_mask" in batch else jnp.ones_like(ce)
        ce = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return {"ce": ce, "loss": ce, "picks": picks}


def loss(params, batch, fields, switch_off=()):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields, switch_off)["loss"]
