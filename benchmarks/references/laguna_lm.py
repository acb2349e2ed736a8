"""Plain reference of Laguna's training loss (poolside Laguna-XS.2,
`model_type: laguna`): layers of softmax attention over a window among layers
of full attention, each kind with its own head count and rope, a per-head
output gate, a leading dense layer, then softmax-routed experts beside a
shared one, an untied head.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: attention is a plain masked
softmax on EXPLICIT logits against all keys, **the band an explicit mask** `j
<= i and i - j < window`, computed a head and a block of `QUERY_BLOCK` rows at
a time (so that 64 heads at 8192 tokens fit), rope as HF's `rotate_half` with
**yarn's frequencies written out** (`yarn_inv_freq`), and there is no sort, no
gather of rows and no grouped matmul: **every held expert is applied densely
to the whole sequence** and its output masked by whether the token chose it.
One sequence at a time, a layer recomputed in a backward pass. It reads the
program's parameter tree (`models/base.py:init_layer_params`, the one
coupling): an attention layer's `wq` (h, heads, hd), `wkv` (h, 2, kv heads,
hd), `wg` (h, heads), `wo` (heads x hd, h), the heads a layer's OWN (48 on a
full layer, 64 on a window layer: the tree says); a dense layer's `wi` (h, 2,
F) gate then up and `wo_mlp` (F, h); a routed layer's `router.kernel` (h, E),
`wi` (held, h, 2F) the gate's F columns beside the up projection's, `wo_mlp`
(held, F, h), `shared.{wi (h, 2, F), wo_mlp (F, h)}`; `embed.wte` (V, h),
`lm_head.kernel` (h, V), `final_norm`. Which layers attend over the window
`fields["layer_types"]` says ("sliding_attention" or "window"), which halves
are routed the tree.

The equations (x a token's row; RMS(x; w) = x / sqrt(mean x^2 + eps) w; no
biases), t the layer's type:

- every layer: x <- x + Mixer_t(RMS(x; ln1)); x <- x + FFN(RMS(x; ln2)); after
  the stack logits = RMS(x_L; final_norm) W_head.
- mixer: q_h = rope_t(y Wq_h), k_g = rope_t(y Wk_g), v_g = y Wv_g, key head g =
  h // (heads / kv heads); a_h[i] = sum_j softmax_j(q_h[i] . k_g[j] / sqrt(hd))
  v_g[j] over j <= i (full) or i - window < j <= i (window: `window` keys, the
  token's own among them); o = concat_h(sigmoid((y Wg)_h) a_h) Wo.
- rope_t: a full layer turns the first `partial_rotary_factor` x hd dims at
  yarn's frequencies with cos and sin x `attention_factor` (so only the turned
  dims of q and of k carry the factor); a window layer turns
  `window_partial_rotary_factor` x hd dims at `window_rope_theta`, unscaled.
  Yarn at d turned dims (HF `_compute_yarn_parameters`): f_i = theta^(-2i/d);
  inv_freq_i = (1 - r_i) f_i + r_i f_i / factor, r_i = clip((i - low) / (high -
  low), 0, 1), low = floor(c(beta_fast)), high = ceil(c(beta_slow)), c(b) = d
  ln(original / (2 pi b)) / (2 ln theta).
- FFN: the first `first_dense_layers` layers (silu(x Wg) * (x Wu)) Wd. After
  them p = softmax(x Wr) over all E; pick = the `experts_per_token` largest
  (the lower index wins a tie); g_e = `routed_scaling_factor` x p_e / (sum over
  the pick of p); FFN(x) = sum over the picked experts HELD HERE of g_e
  Expert_e(x) + Shared(x).
- loss = CE, the mean over the positions that have a label.

Departures from a whole Laguna, each also in the configuration's `reduced` /
`not_modelled`: a chip's share of the experts (`experts_held` of `num_experts`
from `experts_held_start`) and of the vocabulary are the configuration's cut:
what the experts held elsewhere would add is left out here as in the program.
What the published config has no key for (a QK-norm, a gate on the shared
expert, a selection bias) is absent here as there; a tree that carries one is
refused. `switch_off` (a set of names) breaks one piece of the mathematics at
a time, for the tests and the chip check that show each matters: "head_gate"
(no gate), "yarn_scale" (cos and sin unscaled), "yarn" (plain frequencies),
"window_rope" (the window layers turned as the full layers are), "window" (the
window layers see every key up to their own), "gqa" (key head h % kv heads),
"shared" (no shared expert). A window off by one is `fields` with another
`sliding_window`.

`batch["forced_experts"]` (batch, routed blocks, seq, k), where given, replaces
the reference's own pick by the experts named there, everything else
unchanged: top-k is discontinuous, so a comparison of arithmetic wants the
routing held equal (scripts/laguna_chip_check.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
WINDOW_TYPES = ("sliding_attention", "window")


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def yarn_inv_freq(dims: int, theta: float, scaling):
    """The `dims / 2` inverse frequencies of a head's turned dims; `scaling`
    None: theta^(-2i/dims)."""
    plain = 1.0 / theta ** (jnp.arange(dims // 2, dtype=jnp.float32) * 2.0 / dims)
    if scaling is None:
        return plain

    def correction(turns):
        return dims * math.log(scaling["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction(scaling["beta_slow"])), dims - 1)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(dims // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / scaling["factor"]


def _rope(x, positions, dims: int, theta: float, scaling, scale: float):
    """HF's rotate_half on the first `dims` of a head's dims of (S, heads, hd), cos and sin x `scale`."""
    angles = positions[:, None].astype(jnp.float32) * yarn_inv_freq(dims, theta, scaling)
    cos, sin = scale * jnp.cos(angles)[:, None, :], scale * jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :dims // 2], x[..., dims // 2:dims]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., dims:]], axis=-1)


def _masked_attention(q, k, v, window):
    """q, k, v (S, heads, d) -> (S, heads, d): a head at a time, a block of
    query rows at a time against ALL keys under the explicit mask; `window`
    None: causal."""
    s = q.shape[0]
    block = next(b for b in range(min(QUERY_BLOCK, s), 0, -1) if s % b == 0)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    key_pos = jnp.arange(s)

    def one_head(qkv):
        qh, kh, vh = qkv  # (S, d)

        @jax.checkpoint
        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, axis=0)
            query_pos = (start + jnp.arange(block))[:, None]
            seen = key_pos[None, :] <= query_pos
            if window is not None:
                seen = seen & (query_pos - key_pos[None, :] < window)
            scores = jnp.where(seen, qb @ kh.T * scale, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1)

    heads = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return heads.transpose(1, 0, 2)


def attention(lp, y, positions, fields, windowed: bool, off=frozenset()):
    """The mixer of a full (`windowed` False) or window layer on normed rows (S, h) -> (S, h)."""
    if "q_norm" in lp:
        raise ValueError("the reference has no QK-norm (the published Laguna-XS.2 config states none)")
    q = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
    kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"])
    k, v = kv[0], kv[1]
    hd = q.shape[-1]
    as_full = not windowed or "window_rope" in off
    theta = fields["rope_theta"] if as_full else fields["window_rope_theta"]
    share = fields["partial_rotary_factor"] if as_full else fields["window_partial_rotary_factor"]
    scaling = fields["rope_scaling"] if as_full else None
    factor = scaling["attention_factor"] if scaling is not None and "yarn_scale" not in off else 1.0
    if "yarn" in off:
        scaling = None
    q, k = (_rope(t, positions, int(hd * share), theta, scaling, factor) for t in (q, k))
    heads, kv_heads = q.shape[1], k.shape[1]
    serves = jnp.arange(heads) % kv_heads if "gqa" in off else jnp.arange(heads) // (heads // kv_heads)
    window = fields["sliding_window"] if windowed and "window" not in off else None
    out = _masked_attention(q, k[:, serves], v[:, serves], window)
    if "wg" in lp and "head_gate" not in off:
        out = out * jax.nn.sigmoid(y @ lp["wg"]["kernel"])[:, :, None]
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def routed(lp, y, fields, forced=None, off=frozenset()):
    """-> the routed experts' part held here plus the shared expert (S, h), and the pick (S, k)."""
    if "e_score_correction_bias" in lp["router"] or "gate" in lp.get("shared", {}):
        raise ValueError("the reference has no selection bias and no gate on the shared expert "
                         "(the published Laguna-XS.2 config states neither)")
    probs = jax.nn.softmax(y @ lp["router"]["kernel"], axis=-1)  # (S, E)
    pick = jax.lax.top_k(probs, fields["experts_per_token"])[1] if forced is None else forced
    chosen = jnp.sum(jax.nn.one_hot(pick, probs.shape[-1], dtype=probs.dtype), axis=1)  # 0/1
    weights = probs * chosen
    if fields["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
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
    if "shared" in lp and "shared" not in off:
        out = out + _swiglu(lp["shared"], y)
    return out, pick


def _block(lp, x, positions, fields, windowed, forced, off):
    """One layer; recomputed in a backward pass (`jax.checkpoint`), so that a
    gradient of the whole sequence at the published widths fits a chip."""
    def once(lp, x, forced):
        eps = fields["layernorm_eps"]
        x = x + attention(lp, _rms(x, lp["ln1"]["scale"], eps), positions, fields, windowed, off)
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
    x = params["embed"]["wte"][tokens]
    picks = []
    for lp, kind in zip(params["layers"], fields["layer_types"]):
        x, pick = _block(lp, x, positions, fields, kind in WINDOW_TYPES,
                         None if forced is None or "router" not in lp else forced[len(picks)], off)
        picks += [] if pick is None else [pick]
    x = _rms(x, params["final_norm"]["scale"], fields["layernorm_eps"])
    out = x @ (params["embed"]["wte"].T if fields["tie_embeddings"] else params["lm_head"]["kernel"])
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
