"""Plain reference of Granite-4.0-H's training loss (HF
`GraniteMoeHybridForCausalLM`, `model_type: granitemoehybrid`; the state-space
layers are Mamba-2, arXiv:2405.21060): Mamba-2 layers among softmax-attention
layers without positions, every MLP half a dense SwiGLU, four multipliers, a
head tied to the embedding.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: **the state-space recurrence
runs token by token** (`ssm_scan`: one `lax.scan` step a token, no chunk, no
decay mask; blocks of `TOKEN_BLOCK` steps are recomputed in a backward pass so
that a gradient at 4096 tokens fits a chip), the convolution is four shifted
multiply-adds and a bias, attention is computed a head and a block of
`QUERY_BLOCK` queries at a time. It reads the program's parameter tree
(`models/base.py:init_layer_params`, the one coupling): a state-space layer's
`ssm.{win (h, [z | x | B | C | dt]), conv.{kernel (channels, taps), bias},
dt_bias, A_log, D (heads,), norm.scale (inner,), wout}`; an attention layer's
`wq` (h, nh, hd), `wkv` (h, 2, nkv, hd), `wo`; every layer's `ln1`, `ln2`, `wi`
(h, 2, F) the gate's F columns beside the up projection's, `wo_mlp` (F, h);
`embed.wte` (V, h), which is also the head, and `final_norm`. Which layers are
state-space layers the tree says.

The equations (x a token's row; RMS(x; w) = x / sqrt(mean x^2 + eps) w; the
four multipliers are `fields`' `embedding_multiplier`, `residual_multiplier`,
`attention_multiplier`, `logits_scaling`):

- x_0 = embedding_multiplier x E[token]; every layer: x <- x +
  residual_multiplier x Mixer(RMS(x; ln1)); x <- x + residual_multiplier x
  MLP(RMS(x; ln2)), MLP(y) = (silu(y Wg) * (y Wu)) Wd; logits = RMS(x_L;
  final_norm) E^T / logits_scaling; the loss the mean cross entropy.
- state-space mixer: [z | xBC | dt] = y Win; xBC <- silu(conv4(xBC) + b), `c_t =
  sum_j taps_j x_{t-3+j}`, zeros before the start; xBC = [x (heads, d_head) | B
  (d_state) | C (d_state)], B and C the same for every head; dt = softplus(dt +
  dt_bias), A = -exp(A_log) a head; a head's state h (d_head, d_state) from 0:
  `h = exp(dt_t A) h + dt_t x_t B_t^T;  y_t = h C_t + D x_t`;
  y <- RMS(y silu(z); w_n) over ALL heads' channels at once (the gate BEFORE
  the norm); Wout.
- attention mixer: q, k, v = y Wq, y Wkv (no bias, NO positions); causal
  softmax(attention_multiplier x q k^T) v, a key head serving nh / nkv
  consecutive query heads; Wo.

Departures from HF's code, each also in the configuration's `assumed` /
`not_modelled`: `time_step_limit` is (0, inf), HF's default, so dt is not
clamped; the initialisation of A_log, dt_bias and D is the Mamba-2 reference's
(the tree's, not this file's); a chip's share of the vocabulary and the ten
layers are the configuration's cut. `switch_off` (a set of names) drops one
piece of the mathematics at a time, for the tests that show each matters:
"embedding_multiplier", "residual_multiplier", "attention_multiplier",
"logits_scaling" (each taken as the model without it: 1, 1, 1 / sqrt(head_dim),
1), "nope" (rope of theta 10000 on q and k, as a model WITH positions would).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
TOKEN_BLOCK = 64


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotate_half(x, positions, theta=10000.0):
    """HF rotate_half convention on (S, heads, dims): only `switch_off` "nope" runs it."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[:, None].astype(jnp.float32) * inv_freq
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _causal_attention(q, k, v, scale):
    """(S, heads, d), (S, kv heads, d) x 2 -> (S, heads, d): a head at a time,
    a block of queries at a time against all keys."""
    s, heads = q.shape[0], q.shape[1]
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
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


def _attention(lp, y, positions, fields, off):
    hd = lp["wq"]["kernel"].shape[-1]
    q = jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"])
    kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"])
    k = kv[0]
    if "nope" in off:
        q, k = _rotate_half(q, positions), _rotate_half(k, positions)
    scale = hd ** -0.5 if "attention_multiplier" in off else fields["attention_multiplier"]
    out = _causal_attention(q, k, kv[1], scale)
    return out.reshape(out.shape[0], -1) @ lp["wo"]["kernel"]


def conv_shifted(x, taps, bias=None):
    """(S, C), (C, K), (C,) -> (S, C): c_t = sum_j taps[:, j] x_{t - (K - 1) + j} + bias."""
    s, k = x.shape[0], taps.shape[1]
    out = jnp.zeros_like(x) if bias is None else jnp.broadcast_to(bias, x.shape)
    for j in range(k):
        back = k - 1 - j  # tap j reads `back` tokens ago
        out = out + taps[:, j] * jnp.concatenate([jnp.zeros((back, x.shape[1]), x.dtype), x[:s - back]])
    return out


def ssm_scan(x, dt, a, bm, cm, d, state_dtype=jnp.float32):
    """Mamba-2's recurrence token by token. x (S, H, P), dt (S, H) > 0, a (H,)
    < 0, bm, cm (S, N) shared by the heads, d (H,) -> y (S, H, P) and the
    final states (H, P, N). `state_dtype`: the dtype the carried state is
    rounded to after every token (float32 here; the controls carry it in
    bfloat16)."""
    s, heads, p = x.shape
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0, (s, block)

    def token(state, t):
        xt, dtt, bt, ct = t
        state = jnp.exp(dtt * a)[:, None, None] * state + (dtt[:, None] * xt)[:, :, None] * bt
        state = state.astype(state_dtype).astype(jnp.float32)
        return state, state @ ct + d[:, None] * xt

    @jax.checkpoint
    def tokens(state, ts):
        return jax.lax.scan(token, state, ts)

    ts = tuple(t.reshape((s // block, block) + t.shape[1:]) for t in (x, dt, bm, cm))
    state, y = jax.lax.scan(tokens, jnp.zeros((heads, p, bm.shape[-1]), jnp.float32), ts)
    return y.reshape(x.shape), state


def _ssm(lp, y, fields):
    lp = lp["ssm"]
    heads = lp["A_log"].shape[0]
    inner = lp["norm"]["scale"].shape[0]
    state = (lp["conv"]["kernel"].shape[0] - inner) // 2
    s = y.shape[0]
    zxbcdt = y @ lp["win"]["kernel"]
    z = zxbcdt[:, :inner]
    xbc = jax.nn.silu(conv_shifted(zxbcdt[:, inner:2 * inner + 2 * state], lp["conv"]["kernel"],
                                   lp["conv"].get("bias")))
    dt = jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * state:] + lp["dt_bias"])
    out, _ = ssm_scan(xbc[:, :inner].reshape(s, heads, inner // heads), dt, -jnp.exp(lp["A_log"]),
                      xbc[:, inner:inner + state], xbc[:, inner + state:], lp["D"])
    out = _rms(out.reshape(s, inner) * jax.nn.silu(z), lp["norm"]["scale"], fields["layernorm_eps"])
    return out @ lp["wout"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def _block(lp, x, positions, fields, off):
    """One layer; recomputed in a backward pass (`jax.checkpoint`), so that a
    gradient of the whole sequence at the published widths fits a chip."""
    def once(lp, x):
        eps = fields["layernorm_eps"]
        res = 1.0 if "residual_multiplier" in off else fields["residual_multiplier"]
        y = _rms(x, lp["ln1"]["scale"], eps)
        x = x + res * (_ssm(lp, y, fields) if "ssm" in lp else _attention(lp, y, positions, fields, off))
        return x + res * _swiglu(lp, _rms(x, lp["ln2"]["scale"], eps))

    return jax.checkpoint(once)(lp, x)


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, off, tokens, positions, labels):
    """One sequence's tokens' cross entropies (S,)."""
    table = params["embed"]["wte"]
    x = table[tokens] * (1.0 if "embedding_multiplier" in off else fields["embedding_multiplier"])
    for lp in params["layers"]:
        x = _block(lp, x, positions, fields, off)
    x = _rms(x, params["final_norm"]["scale"], fields["layernorm_eps"])
    logits = x @ table.T / (1.0 if "logits_scaling" in off else fields["logits_scaling"])
    return _nll(logits, labels)


def loss(params, batch, fields, switch_off=()):
    """The objective of the batch, float32."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    off = frozenset(switch_off)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        ce = jax.lax.map(lambda row: _sequence(params, fields, off, *row),
                         (batch["tokens"], batch["positions"], batch["labels"]))
        mask = batch["loss_mask"].astype(jnp.float32) if "loss_mask" in batch else jnp.ones_like(ce)
        return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
