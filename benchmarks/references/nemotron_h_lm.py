"""Plain reference of Nemotron-H's training loss (HF `NemotronHForCausalLM`,
`model_type: nemotron_h`, as NVIDIA-Nemotron-3-Nano-30B-A3B configures it; the
state-space blocks are Mamba-2, arXiv:2405.21060, the router DeepSeek-V3's,
arXiv:2412.19437): blocks of ONE half in the published order, an untied head.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: the blocks run ONE AT A TIME
in published order, each `x + f(RMS(x; w))` with its one norm; **the
state-space recurrence runs token by token** (`ssm_scan`: one `lax.scan` step a
token, no chunk, no decay mask; blocks of `TOKEN_BLOCK` steps are recomputed in
a backward pass so that a gradient at 8192 tokens fits a chip), B and C are a
GROUP's, the gated norm runs over a group's channels, the convolution is four
shifted multiply-adds and a bias, attention is computed a head and a block of
`QUERY_BLOCK` queries at a time, and the experts are multiplied one after
another on every token, a token's weight for an expert it did not pick being
0. It reads the program's parameter tree (`models/base.py:init_layer_params`,
the one coupling), a layer a published block, told apart by its leaves:

- `ln1` + `ssm.{win (h, [z | x | B | C | dt]), conv.{kernel (channels, taps),
  bias}, dt_bias, A_log, D (heads,), norm.scale (inner,), wout}`: an `M` block;
- `ln1` + `wq` (h, nh, hd), `wkv` (h, 2, nkv, hd), `wo`: a `*` block;
- `ln2` + `router.{kernel (h, E), e_score_correction_bias (E,)}`, `wi` (held, h,
  F), `wo_mlp` (held, F, h), `shared.{wi (h, W), wo_mlp (W, h)}`: an `E` block;

`embed.wte` (V, h), `lm_head.kernel` (h, V) and `final_norm`.

The equations (x a token's row; RMS(x; w) = x / sqrt(mean x^2 + eps) w):

- x_0 = E[token] (unscaled); block i: x <- x + f_i(RMS(x; w_i)); logits =
  RMS(x_L; final_norm) W_head; the loss the mean cross entropy alone.
- `M`: [z | xBC | dt] = y Win; xBC <- silu(conv4(xBC) + b), `c_t = sum_j taps_j
  x_{t-3+j}`, zeros before the start; xBC = [x (heads, d_head) | B (G, d_state)
  | C (G, d_state)]; dt = softplus(dt + dt_bias), A = -exp(A_log) a head; head n
  of group g = n // (heads / G), its state h (d_head, d_state) from 0:
  `h = exp(dt_t A) h + dt_t x_t B_{g,t}^T;  y_t = h C_{g,t} + D x_t`;
  u = y silu(z) (the gate BEFORE the norm), u normed over EACH GROUP's inner / G
  channels apart (`u.reshape(G, inner / G)`, RMS over the last, x the weight);
  Wout. No position enters.
- `*`: q, k, v = y Wq, y Wkv (no bias, NO positions of any kind: HF's
  `NemotronHAttention` applies no rotary embedding); causal softmax(q k^T /
  sqrt(head_dim)) v, a key head serving nh / nkv consecutive query heads; Wo.
- `E`: s = sigmoid(y Wr) over all E experts; the k largest of s + b are the
  pick (b the bias, no gradient; the lower index wins a tie); w = s[pick] /
  (sum + 1e-20) x routed_scaling_factor; out = sum over the HELD experts e of
  w_e relu(y U_e)^2 D_e (two matrices, NO gate) + relu(y U_s)^2 D_s, the shared
  expert. What the experts held elsewhere would add is left out, as the
  program leaves it out.

Departures from HF's code, each also in the configuration's `assumed` /
`not_modelled`: `time_step_limit` is (0, inf), so dt is not clamped; the
initialisation of A_log, dt_bias and D is the Mamba-2 reference's (the tree's,
not this file's); `n_group` 1 / `topk_group` 1, so the group-limited choice is
the plain top-k; the bias moves between steps (the trainer's business: this is
one forward); a chip's share of the experts and of the vocabulary and the
first nine blocks are the configuration's cut. `state_dtype` (the controls
carry the state in bfloat16) and `forced_experts` in the batch (the picks of
another forward, for comparisons that must not hang on a near-tie) are the
tests' and the chip check's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
TOKEN_BLOCK = 64
BIAS = "e_score_correction_bias"


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _causal_attention(q, k, v):
    """(S, heads, d), (S, kv heads, d) x 2 -> (S, heads, d): a head at a time,
    a block of queries at a time against all keys, at 1 / sqrt(d)."""
    s, heads, d = q.shape
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)
    k, v = (jnp.repeat(t, heads // k.shape[1], axis=1) for t in (k, v))

    def one_head(qkv):
        qh, kh, vh = qkv  # (S, d)

        @jax.checkpoint
        def one_block(start):
            qb = jax.lax.dynamic_slice_in_dim(qh, start, block, axis=0)
            seen = (start + jnp.arange(block))[:, None] >= key_pos[None, :]
            scores = jnp.where(seen, qb @ kh.T * d ** -0.5, -jnp.inf)
            return jax.nn.softmax(scores, axis=-1) @ vh

        return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1)

    out = jax.lax.map(one_head, tuple(t.transpose(1, 0, 2) for t in (q, k, v)))
    return out.transpose(1, 0, 2)


def _attention(lp, y):
    q = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
    kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"])
    out = _causal_attention(q, kv[0], kv[1])
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def conv_shifted(x, taps, bias):
    """(S, C), (C, K), (C,) -> (S, C): c_t = sum_j taps[:, j] x_{t - (K - 1) + j} + bias."""
    s, k = x.shape[0], taps.shape[1]
    out = jnp.broadcast_to(bias, x.shape)
    for j in range(k):
        back = k - 1 - j  # tap j reads `back` tokens ago
        out = out + taps[:, j] * jnp.concatenate([jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]])
    return out


def ssm_scan(x, dt, a, bm, cm, d, state_dtype=jnp.float32):
    """Mamba-2's recurrence token by token. x (S, H, P), dt (S, H) > 0, a (H,)
    < 0, bm, cm (S, G, N), head n reading group n // (H / G), d (H,) -> y (S, H,
    P) and the final states (H, P, N). `state_dtype`: the dtype the carried
    state is rounded to after every token (float32 here; the controls carry it
    in bfloat16)."""
    s, heads, p = x.shape
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0, (s, block)
    serves = heads // bm.shape[1]  # heads a group

    def token(state, t):
        xt, dtt, bt, ct = t
        bt, ct = jnp.repeat(bt, serves, axis=0), jnp.repeat(ct, serves, axis=0)  # (H, N): a head its group's
        state = jnp.exp(dtt * a)[:, None, None] * state + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :]
        state = state.astype(state_dtype).astype(jnp.float32)
        return state, jnp.einsum("hpn,hn->hp", state, ct) + d[:, None] * xt

    @jax.checkpoint
    def tokens(state, ts):
        return jax.lax.scan(token, state, ts)

    ts = tuple(t.reshape((s // block, block) + t.shape[1:]) for t in (x, dt, bm, cm))
    state, y = jax.lax.scan(tokens, jnp.zeros((heads, p, bm.shape[-1]), jnp.float32), ts)
    return y.reshape(x.shape), state


def _ssm(lp, y, fields, state_dtype=jnp.float32):
    lp = lp["ssm"]
    heads, groups, state = lp["A_log"].shape[0], fields["ssm_groups"], fields["ssm_state_dim"]
    inner = lp["norm"]["scale"].shape[0]
    s = y.shape[0]
    zxbcdt = y @ lp["win"]["kernel"]
    z = zxbcdt[:, :inner]
    xbc = jax.nn.silu(conv_shifted(zxbcdt[:, inner:2 * inner + 2 * groups * state], lp["conv"]["kernel"],
                                   lp["conv"]["bias"]))
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * groups * state:] + lp["dt_bias"])
    bm = xbc[:, inner:inner + groups * state].reshape(s, groups, state)
    cm = xbc[:, inner + groups * state:].reshape(s, groups, state)
    out, _ = ssm_scan(xbc[:, :inner].reshape(s, heads, inner // heads), dt, -jnp.exp(lp["A_log"]), bm, cm,
                      lp["D"], state_dtype)
    gated = (out.reshape(s, inner) * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    normed = _rms(gated, lp["norm"]["scale"].reshape(groups, inner // groups), fields["layernorm_eps"])
    return normed.reshape(s, inner) @ lp["wout"]["kernel"]


def _relu2_mlp(wi, wo, y):
    return jnp.square(jax.nn.relu(y @ wi)) @ wo


def _routed(lp, y, fields, forced=None):
    """-> the routed experts' part held here plus the shared expert (S, h), and the pick (S, k)."""
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
        wi, wo, w = args  # (h, F), (F, h), (S,)
        return _relu2_mlp(wi, wo, y) * w[:, None]

    out = jnp.sum(jax.lax.map(one_expert, (lp["wi"]["kernel"], lp["wo_mlp"]["kernel"], weights.T)), axis=0)
    return out + _relu2_mlp(lp["shared"]["wi"]["kernel"], lp["shared"]["wo_mlp"]["kernel"], y), pick


def block_kind(lp) -> str:
    """The published character of a layer's tree: "M", "*" or "E"."""
    return "M" if "ssm" in lp else "E" if "router" in lp else "*"


def _block(lp, x, fields, forced=None, state_dtype=jnp.float32):
    """One published block, `x + f(RMS(x; w))` -> (x, the pick of an `E` block or None); recomputed in a
    backward pass (`jax.checkpoint`), so that a gradient of the whole sequence at the published widths fits
    a chip."""
    kind = block_kind(lp)

    def once(lp, x, forced):
        y = _rms(x, lp["ln2" if kind == "E" else "ln1"]["scale"], fields["layernorm_eps"])
        if kind == "E":
            out, pick = _routed(lp, y, fields, forced)
            return x + out, pick
        return x + (_ssm(lp, y, fields, state_dtype) if kind == "M" else _attention(lp, y)), None

    return jax.checkpoint(once)(lp, x, forced)


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, state_dtype, tokens, labels, forced=None):
    """One sequence: its tokens' cross entropies (S,) and the `E` blocks' picks (blocks, S, k)."""
    x = params["embed"]["wte"][tokens]
    picks = []
    for lp in params["layers"]:
        x, pick = _block(lp, x, fields, None if forced is None or block_kind(lp) != "E" else forced[len(picks)],
                         state_dtype)
        picks += [] if pick is None else [pick]
    logits = _rms(x, params["final_norm"]["scale"], fields["layernorm_eps"]) @ params["lm_head"]["kernel"]
    return _nll(logits, labels), jnp.stack(picks) if picks else jnp.zeros((0,), jnp.int32)


def loss_parts(params, batch, fields, state_dtype=jnp.float32):
    """{"loss", "picks"}: the objective, float32, and the experts picked (batch, E blocks, seq, k)."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); this tree has %s" % sorted(params))
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        rows = (batch["tokens"], batch["labels"]) + (
            (batch["forced_experts"],) if "forced_experts" in batch else ())
        ce, picks = jax.lax.map(lambda row: _sequence(params, fields, state_dtype, *row), rows)
        mask = batch["loss_mask"].astype(jnp.float32) if "loss_mask" in batch else jnp.ones_like(ce)
        return {"loss": jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0), "picks": picks}


def loss(params, batch, fields):
    """The objective of the batch, float32."""
    return loss_parts(params, batch, fields)["loss"]
