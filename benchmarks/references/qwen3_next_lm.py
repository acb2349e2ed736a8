"""Plain reference of Qwen3-Next's training loss (HF `Qwen3NextForCausalLM`,
`model_type: qwen3_next`; the linear layers are Gated Delta Networks,
arXiv:2412.06464): gated-DeltaNet linear-attention layers among gated
softmax-attention layers, every MLP half routed experts beside a gated shared
expert.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: **the delta rule runs token by
token** (`delta_rule`: one `lax.scan` step a token, no chunk, no triangular
solve; blocks of `TOKEN_BLOCK` steps are recomputed in a backward pass so that
a gradient at 8192 tokens fits a chip), the convolution is four shifted
multiply-adds, attention is computed a head and a block of `QUERY_BLOCK`
queries at a time, and there is no sort, no gather of rows and no grouped
matmul: **every held expert is applied densely to the whole sequence** and its
output masked by whether the token chose it. It reads the program's parameter
tree (`models/base.py:init_layer_params`, the one coupling): a linear layer's
`linear.{wqkvz (h, [q | k | v | z]), wba (h, [b | a]), conv (channels, taps),
A_log, dt_bias, norm.scale (d_v), wout}`; an attention layer's `wq` (h, nh, [256
query | 256 gate dims]), `wkv` (h, 2, nkv, hd), `q_norm` / `k_norm` (hd,), `wo`;
every layer's `ln1`, `ln2`, `router.kernel` (h, E), `wi` (held, h, 2F) the
gate's F columns beside the up projection's, `wo_mlp` (held, F, h), `shared.{wi
(h, 2, F), wo_mlp, gate (h, 1)}`. Which layers are linear the tree says.

The equations (x a token's row; RMS0(x; w) = x / sqrt(mean x^2 + eps) (1 + w)):

- every layer: x <- x + Mixer(RMS0(x; ln1)); x <- x + MoE(RMS0(x; ln2)); after
  the stack RMS0 and the untied head.
- linear mixer: [q, k, v, z] = y Wqkvz, [b, a] = y Wba; [q, k, v] <-
  silu(conv4([q, k, v])), `c_t = sum_j taps_j x_{t-3+j}`, zeros before the
  start; beta = sigmoid(b), g = -exp(A_log) softplus(a + dt_bias); q, k a head
  `x / sqrt(sum x^2 + 1e-6)`, q / sqrt(d_k), each key head serving value / key
  consecutive value heads; a head's state S (d_k, d_v) from 0:
  `S' = e^{g_t} S; u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T; o_t = S^T q_t`;
  o <- o / sqrt(mean o^2 + eps) w_n silu(z) a head (a plain scale, the norm
  before the gate); concat heads; Wout.
- attention mixer: [q | gate] = y Wq a head, k, v = y Wkv; q, k <- RMS0 a head;
  rope (theta, rotate-half) on the leading `partial_rotary_factor` of a head's
  dims; causal softmax attention at 1 / sqrt(head_dim), a key head serving nh /
  nkv consecutive query heads; x sigmoid(gate); Wo.
- MoE: p = softmax(y Wr) over ALL experts; the `experts_per_token` largest (the
  lower index wins a tie); weights p_e / sum over the pick; sum over the picked
  experts HELD HERE of w_e Wd(silu(Wg y) Wu y); plus sigmoid(y w_sg) Shared(y).
- loss = CE + `router_aux_loss_coef` x mean over layers of E sum_e f_e P_e (f_e
  the share of the batch's tokens whose pick holds e, P_e the batch's mean p_e).

Departures from HF's code, each also in the configuration's `assumed` /
`not_modelled`: the columns of Wqkvz and Wba lie [q | k | v | z] and [b | a]
with heads in order (HF groups them a key head: a permutation of columns on
random weights); the load-balancing loss is the mean of the layers' own (HF
pools the layers' tokens before the product); no multi-token-prediction
module (the published config has no key for it); a chip's share of the
experts (`experts_held` of `num_experts` from `experts_held_start`) and of the
vocabulary are the configuration's cut: what the experts held elsewhere would
add is left out here as in the program.

`batch["forced_experts"]` (batch, layers, seq, k), where given, replaces the
reference's own pick by the experts named there, everything else unchanged:
top-k is discontinuous, so a comparison of arithmetic wants the routing held
equal (scripts/qwen3next_chip_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
TOKEN_BLOCK = 64


def _rms0(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def _rotate_half(x, positions, theta, rotary):
    """HF rotate_half convention on the leading `rotary` dims of (S, heads, dims)."""
    half = rotary // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rotary:]], axis=-1)


def _causal_attention(q, k, v):
    """(S, heads, d), (S, kv heads, d) x 2 -> (S, heads, d): a head at a time,
    a block of queries at a time against all keys."""
    s, heads = q.shape[0], q.shape[1]
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    key_pos = jnp.arange(s)
    serves = heads // k.shape[1]
    k, v = (jnp.repeat(t, serves, axis=1) for t in (k, v))

    def one_head(qkv):
        qh, kh, vh = qkv  # (S, d)

        @jax.checkpoint
        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, axis=0)
            seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
            scores = jnp.where(seen, qb @ kh.T * scale, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1)

    out = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return out.transpose(1, 0, 2)


def _attention(lp, y, positions, fields):
    eps, hd = fields["layernorm_eps"], fields["head_dim"]
    rotary = int(hd * fields["partial_rotary_factor"])
    q_gate = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
    q, gate = q_gate[..., :hd], q_gate[..., hd:]
    kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"])
    q = _rotate_half(_rms0(q, lp["q_norm"]["scale"], eps), positions, fields["rope_theta"], rotary)
    k = _rotate_half(_rms0(kv[0], lp["k_norm"]["scale"], eps), positions, fields["rope_theta"], rotary)
    out = _causal_attention(q, k, kv[1]) * jax.nn.sigmoid(gate)
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def conv_shifted(x, taps):
    """(S, C), (C, K) -> (S, C): c_t = sum_j taps[:, j] x_{t - (K - 1) + j}."""
    s, k = x.shape[0], taps.shape[1]
    out = jnp.zeros_like(x)
    for j in range(k):
        back = k - 1 - j  # tap j reads `back` tokens ago
        out = out + taps[:, j] * jnp.concatenate([jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]])
    return out


def delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """The gated delta rule token by token. q, k (S, H, d_k) normalised, v (S,
    H, d_v), g, beta (S, H) -> o (S, H, d_v) and the final states (H, d_k,
    d_v). `state_dtype`: the dtype the carried state is rounded to after every
    token (float32 here; the chip check's control carries it in bf16)."""
    s, heads, dk = q.shape
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0, (s, block)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[:, None, None] * state
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", state, kt))
        state = (state + kt[:, :, None] * u[:, None, :]).astype(state_dtype).astype(jnp.float32)
        return state, jnp.einsum("hkv,hk->hv", state, qt)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(t.reshape((s // block, block) + t.shape[1:]) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(tokens, jnp.zeros((heads, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(v.shape), state


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _linear(lp, y, fields):
    lp = lp["linear"]
    nk, nv = fields["linear_num_key_heads"], fields["linear_num_value_heads"]
    dk, dv = fields["linear_key_head_dim"], fields["linear_value_head_dim"]
    kd, vd = nk * dk, nv * dv
    s = y.shape[0]
    qkvz = y @ lp["wqkvz"]["kernel"]
    ba = y @ lp["wba"]["kernel"]
    qkv = jax.nn.silu(conv_shifted(qkvz[:, :2 * kd + vd], lp["conv"]))
    z = qkvz[:, 2 * kd + vd:].reshape(s, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(lp["A_log"]) * jax.nn.softplus(ba[:, nv:] + lp["dt_bias"])
    q = jnp.repeat(_unit(qkv[:, :kd].reshape(s, nk, dk)) / jnp.sqrt(jnp.float32(dk)), nv // nk, axis=1)
    k = jnp.repeat(_unit(qkv[:, kd:2 * kd].reshape(s, nk, dk)), nv // nk, axis=1)
    o, _ = delta_rule(q, k, qkv[:, 2 * kd:].reshape(s, nv, dv), g, beta)
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + fields["layernorm_eps"])
    o = o * lp["norm"]["scale"] * jax.nn.silu(z)
    return o.reshape(s, vd) @ lp["wout"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def _moe(lp, y, fields, forced=None):
    """-> the MLP half's output (S, h), the pick (S, k), and the router's
    sums for the load-balancing loss: assignments an expert, probability an
    expert, logsumexp^2 (each summed over this sequence's tokens)."""
    logits = y @ lp["router"]["kernel"]  # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    pick = jax.lax.top_k(probs, fields["experts_per_token"])[1] if forced is None else forced
    chosen = jnp.sum(jax.nn.one_hot(pick, probs.shape[-1], dtype=probs.dtype), axis=1)  # 0/1
    weights = probs * chosen
    if fields["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    first = fields["experts_held_start"] if fields["experts_held"] else 0
    held = lp["wi"]["kernel"].shape[0]
    held_weights = weights[:, first:first + held]  # what the others would add is left out

    @jax.checkpoint
    def one_expert(args):
        wi, wo, w = args  # (h, 2F), (F, h), (S,)
        gate, up = jnp.split(y @ wi, 2, axis=-1)
        return (jax.nn.silu(gate) * up) @ wo * w[:, None]

    out = jnp.sum(jax.lax.map(
        one_expert, (lp["wi"]["kernel"], lp["wo_mlp"]["kernel"], held_weights.T)), axis=0)
    shared = _swiglu(lp["shared"], y) * jax.nn.sigmoid(y @ lp["shared"]["gate"]["kernel"])
    sums = (jnp.sum(chosen, axis=0), jnp.sum(probs, axis=0),
            jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1))))
    return out + shared, pick, sums


def _block(lp, x, positions, fields, forced=None):
    """One layer; recomputed in a backward pass (`jax.checkpoint`), so that a
    gradient of the whole sequence at the published widths fits a chip."""
    def once(lp, x, forced):
        eps = fields["layernorm_eps"]
        y = _rms0(x, lp["ln1"]["scale"], eps)
        x = x + (_linear(lp, y, fields) if "linear" in lp else _attention(lp, y, positions, fields))
        out, pick, sums = _moe(lp, _rms0(x, lp["ln2"]["scale"], eps), fields, forced)
        return x + out, pick, sums

    return jax.checkpoint(once)(lp, x, forced)


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, tokens, positions, labels, forced=None):
    """One sequence: its tokens' cross entropies (S,), the layers' picks
    (layers, S, k) and their routers' sums."""
    x = params["embed"]["wte"][tokens]
    picks, sums = [], []
    for i, lp in enumerate(params["layers"]):
        x, pick, layer_sums = _block(lp, x, positions, fields, None if forced is None else forced[i])
        picks.append(pick)
        sums.append(layer_sums)
    x = _rms0(x, params["final_norm"]["scale"], fields["layernorm_eps"])
    sums = tuple(jnp.stack(t) for t in zip(*sums))
    return _nll(x @ params["lm_head"]["kernel"], labels), jnp.stack(picks), sums


def loss_parts(params, batch, fields):
    """{"ce", "load_balance", "router_z", "loss", "picks"}: the cross entropy,
    the routers' two losses before their coefficients (the mean over the
    layers), the objective, and the experts picked (batch, layers, seq, k)."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        rows = (batch["tokens"], batch["positions"], batch["labels"])
        if "forced_experts" in batch:
            rows += (batch["forced_experts"],)
        ce, picks, (counts, probs, z) = jax.lax.map(
            lambda row: _sequence(params, fields, *row), rows)
        mask = batch["loss_mask"].astype(jnp.float32)
        ce = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        tokens = jnp.float32(batch["tokens"].size)
        experts = counts.shape[-1]
        # a layer's own E sum_e f_e P_e over the batch's tokens, then the mean over layers
        balance = jnp.mean(experts * jnp.sum(
            jnp.sum(counts, axis=0) / tokens * (jnp.sum(probs, axis=0) / tokens), axis=-1))
        router_z = jnp.mean(jnp.sum(z, axis=0) / tokens)
        return {"ce": ce, "load_balance": balance, "router_z": router_z, "picks": picks,
                "loss": ce + fields["router_aux_loss_coef"] * balance
                + fields["router_z_loss_coef"] * router_z}


def loss(params, batch, fields):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields)["loss"]
