"""Plain reference of GLM-4.7-Flash's training loss (HF
`Glm4MoeLiteForCausalLM`, `model_type: glm4_moe_lite`; the block and the
objective are DeepSeek-V3's, arXiv:2412.19437): latent attention (MLA), a
leading dense layer, then layers of a shared expert beside routed ones chosen
by a sigmoid router with a bias, and one multi-token-prediction module.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code, no sort, no gather of rows and no grouped matmul:
**every held expert is applied densely to the whole sequence** and its output
masked by whether the token chose it, so a dropped, duplicated or misrouted
token in the program's dispatch shows as a difference. Attention is computed a
head and a block of `QUERY_BLOCK` queries at a time, so that the float32
scores of 20 heads x 8192 x 8192 never exist whole. It reads the program's
parameter tree (`models/base.py:init_layer_params`, the one coupling): `wq_a`
(h, q_lora), `q_a_norm`, `wq_b` (q_lora, nh, nope + rope), `wkv_a` (h, kv_lora +
rope), `kv_a_norm`, `wkv_b` (kv_lora, nh, nope + v), `wo` (nh x v, h); a dense
layer's `wi` (h, 2, F) gate then up and `wo_mlp` (F, h); a routed layer's
`router.kernel` (h, E), `router.e_score_correction_bias` (E,), `wi` (held, h,
2F) the gate's F columns beside the up projection's, `wo_mlp` (held, F, h),
`shared.wi` / `shared.wo_mlp` as a dense layer's; `mtp.{enorm, hnorm, eh_proj,
block, norm}`.

The equations (x a token's row, RMSNorm eps `layernorm_eps`, no biases):

- block: h1 = h + MLA(RMSNorm(h)); h2 = h1 + FFN(RMSNorm(h1)). Layer 0 (the
  first `first_dense_layers`): FFN(x) = (silu(x Wg) * (x Wu)) Wd. After it:
  FFN(x) = Shared(x) + Routed(x), Shared the same SwiGLU at the experts' width.
- MLA: cq = RMSNorm(x Wqa); q_h = cq Wqb_h = [q_nope_h | q_rope_h];
  [ckv | kr] = x Wkva; [k_nope_h | v_h] = RMSNorm(ckv) Wkvb_h;
  k_h = [k_nope_h | rope(kr)] (ONE rotated key for all heads),
  q_h = [q_nope_h | rope(q_rope_h)]; o_h = softmax_causal(q_h k_h^T /
  sqrt(nope + rope)) v_h; MLA = concat_h(o_h) Wo.
- router: s = sigmoid(x Wr); pick = the `experts_per_token` largest of s + b
  (the lower index wins a tie, as `lax.top_k` and `torch.topk` have it);
  g_e = `routed_scaling_factor` x s_e / (sum over the pick of s + 1e-20);
  Routed(x) = sum over the picked experts HELD HERE of g_e Expert_e(x). b takes
  no gradient; the objective has no auxiliary router loss.
- MTP: m_i = [RMSNorm_h(hL_i) ; RMSNorm_e(Emb(t_{i+1}))] Weh with hL the last
  layer's output before the final norm; m'_i = Block_routed(m_i); its logits
  are the model's own head on RMSNorm_mtp(m'_i), against t_{i+2}.
- loss = CE + `mtp_loss_weight` x CE_mtp, each the mean over the positions
  that have the label.

Departures from the published description, each also in the configuration's
`assumed` / `not_modelled`: rope in HF's rotate_half convention over the
`qk_rope_head_dim` dims as they lie (HF's DeepSeek-family code first
de-interleaves them: on random weights a permutation of Wqb's and Wkva's
columns); the concatenation order [hidden ; embedding] of `eh_proj`'s input
(HF concatenates [embedding ; hidden]: a permutation of Weh's rows);
`rope_scaling` null; DeepSeek-V3's complementary sequence-wise balance loss
(its alpha is 1e-4) is left out; a chip's share of the experts
(`experts_held` of `num_experts` from `experts_held_start`) and of the
vocabulary are the configuration's cut: what the experts held elsewhere would
add is left out here as in the program.

`batch["forced_experts"]` (batch, routed blocks, seq, k), where given, replaces
the reference's own pick by the experts named there (the stack's routed layers
in order, then the MTP block), everything else unchanged: top-k is
discontinuous, so a comparison of arithmetic wants the routing held equal
(scripts/glm47f_chip_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
BIAS = "e_score_correction_bias"


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rotate_half(x, positions, theta):
    """HF rotate_half convention on (S, heads, dims)."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _causal_attention(q, k, v):
    """(S, heads, d) x 3 -> (S, heads, d): a head at a time, a block of
    queries at a time against all keys."""
    s = q.shape[0]
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
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


def _latent_attention(lp, y, positions, fields):
    eps, theta = fields["layernorm_eps"], fields["rope_theta"]
    nope, lora = fields["qk_nope_head_dim"], fields["kv_lora_rank"]
    cq = _rms(y @ lp["wq_a"]["kernel"], lp["q_a_norm"]["scale"], eps)
    q = jnp.einsum("sr,rnd->snd", cq, lp["wq_b"]["kernel"])
    ckv_kr = y @ lp["wkv_a"]["kernel"]
    ckv = _rms(ckv_kr[:, :lora], lp["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("sr,rnd->snd", ckv, lp["wkv_b"]["kernel"])
    k_rope = _rotate_half(ckv_kr[:, None, lora:], positions, theta)  # (S, 1, rope): one key
    q = jnp.concatenate([q[..., :nope], _rotate_half(q[..., nope:], positions, theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, (k_rope.shape[0], q.shape[1], k_rope.shape[2]))], axis=-1)
    out = _causal_attention(q, k, kv[..., nope:])
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def _routed(lp, y, fields, forced=None):
    """-> the routed experts' part held here (S, h), and the pick (S, k)."""
    scores = jax.nn.sigmoid(y @ lp["router"]["kernel"])  # (S, E)
    ranked = scores + jax.lax.stop_gradient(lp["router"][BIAS])
    pick = jax.lax.top_k(ranked, fields["experts_per_token"])[1] if forced is None else forced
    chosen = jnp.sum(jax.nn.one_hot(pick, scores.shape[-1], dtype=scores.dtype), axis=1)  # 0/1
    weights = scores * chosen
    if fields["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
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


def _block(lp, x, positions, fields, forced=None):
    """One block; recomputed in a backward pass (`jax.checkpoint`), so that a
    gradient of the whole sequence at the published widths fits a chip."""
    return jax.checkpoint(lambda lp, x, forced: _block_once(lp, x, positions, fields, forced))(
        lp, x, forced)


def _block_once(lp, x, positions, fields, forced=None):
    eps = fields["layernorm_eps"]
    x = x + _latent_attention(lp, _rms(x, lp["ln1"]["scale"], eps), positions, fields)
    y = _rms(x, lp["ln2"]["scale"], eps)
    if "router" not in lp:
        return x + _swiglu(lp, y), None
    routed, pick = _routed(lp, y, fields, forced)
    return x + _swiglu(lp["shared"], y) + routed, pick


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, tokens, positions, labels, forced=None):
    """One sequence: its tokens' cross entropies (S,), those of the token
    after next (S,; the last has none), and the routed blocks' picks."""
    eps = fields["layernorm_eps"]
    table, head = params["embed"]["wte"], params["lm_head"]["kernel"]
    x = table[tokens]
    picks = []
    for lp in params["layers"]:
        x, pick = _block(lp, x, positions, fields,
                         None if forced is None or "router" not in lp else forced[len(picks)])
        picks += [] if pick is None else [pick]
    ce = _nll(_rms(x, params["final_norm"]["scale"], eps) @ head, labels)
    mp = params["mtp"]
    m = jnp.concatenate([_rms(x, mp["hnorm"]["scale"], eps),
                         _rms(table[labels], mp["enorm"]["scale"], eps)], axis=-1)
    m, pick = _block(mp["block"], m @ mp["eh_proj"]["kernel"], positions, fields,
                     None if forced is None else forced[len(picks)])
    picks.append(pick)
    ce_mtp = _nll(_rms(m, mp["norm"]["scale"], eps) @ head, jnp.roll(labels, -1))
    return ce, ce_mtp, jnp.stack(picks)


def loss_parts(params, batch, fields):
    """{"ce", "mtp", "loss", "picks"}: the two cross entropies before the
    MTP weight, the objective, and the experts picked (batch, routed blocks,
    seq, k)."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        rows = (batch["tokens"], batch["positions"], batch["labels"])
        if "forced_experts" in batch:
            rows += (batch["forced_experts"],)
        ce, ce_mtp, picks = jax.lax.map(lambda row: _sequence(params, fields, *row), rows)
        mask = batch["loss_mask"].astype(jnp.float32)
        ahead = jnp.roll(mask, -1, axis=1).at[:, -1].set(0.0)  # t_{i+2} exists and counts
        ce = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        ce_mtp = jnp.sum(ce_mtp * ahead) / jnp.maximum(jnp.sum(ahead), 1.0)
        return {"ce": ce, "mtp": ce_mtp, "picks": picks,
                "loss": ce + fields["mtp_loss_weight"] * ce_mtp}


def loss(params, batch, fields):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields)["loss"]
