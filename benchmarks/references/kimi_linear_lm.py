"""Plain reference of Kimi-Linear's training loss (HF `KimiLinearForCausalLM`,
`model_type: kimi_linear`; arXiv:2510.26692, "Kimi Linear"): Kimi-Delta-
Attention layers among latent-attention layers without positions, a leading
dense layer, then sigmoid-routed experts beside an ungated shared one.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: **the delta rule runs token by
token** (`kda_recurrence`: one `lax.scan` step a token with the gate a vector
over the key's channels, no chunk, no sub-block, no triangular solve; blocks
of `TOKEN_BLOCK` steps are recomputed in a backward pass so that a gradient at
8192 tokens fits a chip), the convolution is four shifted multiply-adds of a
sequence padded once in front, attention is a plain masked softmax computed a
head and a block of
`QUERY_BLOCK` rows at a time (q and k at their 192 dims, v at its 128: no
padding), and there is no sort, no gather of rows and no grouped matmul:
**every held expert is applied densely to the whole sequence** and its output
masked by whether the token chose it. It reads the program's parameter tree
(`models/base.py:init_layer_params`, the one coupling): a KDA layer's `kda.{wqkv
(h, [q | k | v]), conv (channels, taps), wf_a (h, d), wf_b (d, H d_k), wb (h, H),
A_log (H), dt_bias (H d_k), wg_a (h, d), wg_b (d, H d_v), norm.scale (d_v),
wout}`; an attention layer's `wq` (h, nh, nope + rope), `wkv_a` (h, kv_lora +
rope), `kv_a_norm`, `wkv_b` (kv_lora, nh, nope + v), `wo` (nh x v, h); a dense
layer's `wi` (h, 2, F) gate then up and `wo_mlp` (F, h); a routed layer's
`router.kernel` (h, E), `router.e_score_correction_bias` (E,), `wi` (held, h,
2F) the gate's F columns beside the up projection's, `wo_mlp` (held, F, h),
`shared.wi` / `shared.wo_mlp` as a dense layer's. Which layers are KDA, and
which halves routed, the tree says.

The equations (x a token's row; RMS(x; w) = x / sqrt(mean x^2 + eps) w; no
biases; no position anywhere):

- every layer: x <- x + Mixer(RMS(x; ln1)); x <- x + FFN(RMS(x; ln2)); after
  the stack RMS and the untied head.
- KDA mixer: [q, k, v] = silu(conv4(y Wqkv)), `c_t = sum_j taps_j x_{t-3+j}`,
  zeros before the start; q, k a head `x / sqrt(sum x^2 + 1e-6)`, q / sqrt(d_k);
  g = -exp(A_log_h) softplus((y Wfa) Wfb + dt_bias), (H, d_k) a token; beta =
  sigmoid(y Wb); a head's state S (d_k, d_v) from 0:
  `S' = Diag(e^{g_t}) S; u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T; o_t = S^T q_t`;
  o <- RMS(o; w_n) sigmoid((y Wga) Wgb) a head (the norm before the gate);
  concat heads; Wout.
- MLA mixer: q_h = y Wq_h = [q_nope_h | q_pe_h]; [ckv | k_pe] = y Wkva;
  [k_nope_h | v_h] = RMS(ckv; w) Wkvb_h; k_h = [k_nope_h | k_pe] (ONE unrotated
  vector a token for all heads); o_h = softmax_causal(q_h k_h^T / sqrt(nope +
  rope)) v_h; concat heads; Wo.
- FFN: layer 0 (the first `first_dense_layers`) (silu(x Wg) * (x Wu)) Wd. After
  it Shared(x) + Routed(x): s = sigmoid(x Wr); pick = the `experts_per_token`
  largest of s + b (the lower index wins a tie); g_e = `routed_scaling_factor`
  x s_e / (sum over the pick of s + 1e-20); Routed(x) = sum over the picked
  experts HELD HERE of g_e Expert_e(x). b takes no gradient.
- loss = CE, the mean over the positions that have a label: no auxiliary
  router loss, no multi-token-prediction module.

Departures from HF's code, each also in the configuration's `assumed` /
`not_modelled`: q, k and v come from ONE kernel whose columns lie [q | k | v]
and pass ONE convolution over those columns (HF has three of each: on random
weights the same three side by side); a chip's share of the experts
(`experts_held` of `num_experts` from `experts_held_start`) and of the
vocabulary are the configuration's cut: what the experts held elsewhere would
add is left out here as in the program.

`batch["forced_experts"]` (batch, routed blocks, seq, k), where given, replaces
the reference's own pick by the experts named there, everything else
unchanged: top-k is discontinuous, so a comparison of arithmetic wants the
routing held equal (scripts/kimilin_chip_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
TOKEN_BLOCK = 64
BIAS = "e_score_correction_bias"


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _causal_attention(q, k, v):
    """q, k (S, heads, d_qk), v (S, heads, d_v) -> (S, heads, d_v): a head at a
    time, a block of query rows at a time against all keys."""
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


def _latent_attention(lp, y, fields):
    nope, lora = fields["qk_nope_head_dim"], fields["kv_lora_rank"]
    q = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
    ckv_kpe = y @ lp["wkv_a"]["kernel"]
    ckv = _rms(ckv_kpe[:, :lora], lp["kv_a_norm"]["scale"], fields["layernorm_eps"])
    kv = jnp.einsum("sr,rnd->snd", ckv, lp["wkv_b"]["kernel"])
    k_pe = ckv_kpe[:, None, lora:]  # (S, 1, rope): one key for all heads, as it is
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe, (k_pe.shape[0], q.shape[1], k_pe.shape[2]))], axis=-1)
    out = _causal_attention(q, k, kv[..., nope:])
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def conv_shifted(x, taps):
    """(S, C), (C, K) -> (S, C): c_t = sum_j taps[:, j] x_{t - (K - 1) + j}, zeros
    before the start: K - 1 rows of zeros in front once, then K shifted slices.
    (NOT a tap at a time as `concatenate([zeros, x[:S - back]])`, nor as a roll
    under a mask: at 8192 rows XLA:TPU compiles either into a shift WITHIN
    1024-row tiles, and rows 1024 n to 1024 n + 2 lose the taps that reach into
    the tile before, 92 %, 74 % and 53 % off against float64 on the host; this
    form is exact to 6e-7 there: PERF.md section 6, PR 42.)"""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + taps[:, j] * padded[j:j + s]
    return out


def kda_recurrence(q, k, v, g, beta):
    """Kimi Delta Attention's rule token by token. q, k (S, H, d_k) normalised,
    v (S, H, d_v), g (S, H, d_k) <= 0, beta (S, H) -> o (S, H, d_v) and the final
    states (H, d_k, d_v)."""
    s, heads, dk = q.shape
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0, (s, block)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, :, None] * state  # a row forgets at its own rate
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = state + kt[:, :, None] * u[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(t.reshape((s // block, block) + t.shape[1:]) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(tokens, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(v.shape), state


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_inputs(lp, y, fields):
    """A KDA layer's q, k, v (S, H, d), g (S, H, d_k), beta (S, H) from its
    normed input (S, h): what the recurrence takes."""
    heads = fields["linear_num_value_heads"]
    dk, dv = fields["linear_key_head_dim"], fields["linear_value_head_dim"]
    kd, s = heads * dk, y.shape[0]
    qkv = jax.nn.silu(conv_shifted(y @ lp["wqkv"]["kernel"], lp["conv"]))
    q = _unit(qkv[:, :kd].reshape(s, heads, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _unit(qkv[:, kd:2 * kd].reshape(s, heads, dk))
    f = (y @ lp["wf_a"]["kernel"]) @ lp["wf_b"]["kernel"]
    g = -jnp.exp(lp["A_log"])[:, None] * jax.nn.softplus(f + lp["dt_bias"]).reshape(s, heads, dk)
    beta = jax.nn.sigmoid(y @ lp["wb"]["kernel"])
    return q, k, qkv[:, 2 * kd:].reshape(s, heads, dv), g, beta


def _kda(lp, y, fields):
    lp = lp["kda"]
    q, k, v, g, beta = kda_inputs(lp, y, fields)
    o, _ = kda_recurrence(q, k, v, g, beta)
    gate = ((y @ lp["wg_a"]["kernel"]) @ lp["wg_b"]["kernel"]).reshape(o.shape)
    o = _rms(o, lp["norm"]["scale"], fields["layernorm_eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(o.shape[0], -1) @ lp["wout"]["kernel"]


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


def _block(lp, x, fields, forced=None):
    """One layer; recomputed in a backward pass (`jax.checkpoint`), so that a
    gradient of the whole sequence at the published widths fits a chip."""
    def once(lp, x, forced):
        eps = fields["layernorm_eps"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        x = x + (_kda(lp, y, fields) if "kda" in lp else _latent_attention(lp, y, fields))
        y = _rms(x, lp["ln2"]["scale"], eps)
        if "router" not in lp:
            return x + _swiglu(lp, y), None
        routed, pick = _routed(lp, y, fields, forced)
        return x + _swiglu(lp["shared"], y) + routed, pick

    return jax.checkpoint(once)(lp, x, forced)


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, tokens, labels, forced=None):
    """One sequence: its tokens' cross entropies (S,) and the routed blocks'
    picks (routed blocks, S, k)."""
    x = params["embed"]["wte"][tokens]
    picks = []
    for lp in params["layers"]:
        x, pick = _block(lp, x, fields,
                         None if forced is None or "router" not in lp else forced[len(picks)])
        picks += [] if pick is None else [pick]
    x = _rms(x, params["final_norm"]["scale"], fields["layernorm_eps"])
    return _nll(x @ params["lm_head"]["kernel"], labels), jnp.stack(picks)


def loss_parts(params, batch, fields):
    """{"ce", "loss", "picks"}: the cross entropy, which is the objective, and
    the experts picked (batch, routed blocks, seq, k)."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        rows = (batch["tokens"], batch["labels"])
        if "forced_experts" in batch:
            rows += (batch["forced_experts"],)
        ce, picks = jax.lax.map(lambda row: _sequence(params, fields, *row), rows)
        mask = batch["loss_mask"].astype(jnp.float32)
        ce = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return {"ce": ce, "loss": ce, "picks": picks}


def loss(params, batch, fields):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields)["loss"]
