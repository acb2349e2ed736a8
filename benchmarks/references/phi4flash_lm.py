"""Plain reference of Phi-4-mini-flash-reasoning's training loss (HF
`Phi4FlashForCausalLM`, `model_type: phi4flash`; SambaY, arXiv:2507.06607;
differential attention, arXiv:2410.05258; Mamba-1, arXiv:2312.00752): a
self-decoder of Mamba-1 layers and differential window attention that ends in
one full differential attention layer, and a cross-decoder whose gated memory
units read the LAST Mamba-1 layer's scan output and whose cross layers read
the ONE full layer's keys and values.

Straightforward float32 `jax.numpy` at `highest` matmul precision, none of the
program's model code and none of its algorithms: **the recurrence runs token by
token** (`selective_scan`: one `lax.scan` step a token on the (channels, N)
state, no chunk; blocks of `TOKEN_BLOCK` steps are recomputed in a backward
pass so that a gradient at 8192 tokens fits a chip), the convolution is one
`pad` and four slices, each softmax map is computed on its own with an
explicit mask, a pair of heads and a block of `QUERY_BLOCK` queries at a time,
and the memory and the keys and values pass from layer to layer as plain
arguments. It reads the program's parameter tree (`models/base.py:
init_layer_params`, the one coupling): a Mamba-1 layer's `mamba.{win (h, [x |
z]), conv.{kernel (channels, taps), bias}, wx (channels, [dt_r | B | C]), wdt.{
kernel (R, channels), bias}, A_log (channels, N), D, wout}`; an attention layer's
`wq.{kernel (h, nh, hd), bias}`, `wkv.{kernel (h, 2, nkv, hd), bias}`, `wo.{kernel,
bias}` and `diff.{lq1, lk1, lq2, lk2 (hd,), subln.scale (2 hd,)}`; a cross layer's
`wq`, `wo`, `diff`; a gated memory unit's `gmu.{win, wout}`; every layer's `ln1`,
`ln2` (scale, bias), `wi` (h, 2, F) the gate's F columns beside the up
projection's, `wo_mlp` (F, h); `embed.wte` (V, h), which is also the head, and
`final_norm`. WHICH layer is of which kind is `fields`' to say (`layer_types` at
`layer_indices`: a window layer and a full layer hold the same leaves), and
`lambda_init` is computed HERE from the published index, not read off the tree.

The equations (x a token's row; LN(x; w, b) over the hidden dims, eps
`layernorm_eps`; L the published depth, i the published index):

- x_0 = E[token]; every layer: x <- x + Mixer_i(LN(x; ln1)); x <- x + MLP(LN(x;
  ln2)), MLP(y) = (silu(y Wg) * (y Wu)) Wd; logits = LN(x_L; final_norm) E^T; the
  loss the mean cross entropy. No position enters any layer.
- Mamba-1: [x | z] = y Win; x = silu(conv4(x) + b), `c_t = sum_j taps_j x_{t-3+j}`,
  zeros before the start; [dt_r | B | C] = x Wx; dt = softplus(dt_r Wdt + b_dt); A =
  -exp(A_log) a (channel, state); from h = 0: `h = exp(dt_t A) h + dt_t x_t B_t^T;
  m_t = h C_t + D x_t`; out = (m silu(z)) Wout. **The memory is m**: with the D
  skip, before the gate.
- differential attention: q, k, v = y Wq + b, y Wkv + b; query heads (2j, 2j + 1)
  are a pair (q1, q2) on the key pair (k1, k2) = key heads (2m, 2m + 1), m = j //
  (query pairs a key pair), v = [v_2m | v_2m+1]; a_s = softmax(q_s k_s^T / sqrt(hd) +
  mask) v; lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i), lambda_init(i) =
  0.8 - 0.6 exp(-0.3 i); o = RMS(a_1 - lambda a_2; w) (1 - lambda_init(i)) over the
  pair's 2 hd dims; out = concat(o) Wo + b. The mask is causal; a window layer's
  query t sees the keys `t - sliding_window < j <= t`. **K and V as projected are
  what a full layer hands on.**
- gated memory unit: out = (m silu(y W1)) W2, m the memory handed on.
- cross layer: q = y Wq + b alone; K, V the full layer's; causal; the rest as above.

Departures from HF's code, each also in the configuration's `assumed` /
`not_modelled`: the sizes and biases the published file has no key for are HF
`Phi4FlashConfig`'s defaults; the pairing of heads is the Diff Transformer's
`multihead_flashdiff_1`; dropout is 0 as published. `switch_off` (a set of names)
changes one piece of the mathematics at a time, for the tests and the chip
check that show each matters: "reader_cotangents" (the memory and K, V reach
their readers behind a `stop_gradient`: what a step that dropped a reader's
cotangent would compute), "memory_d_skip" (the memory WITHOUT the D skip: the
other candidate), "pairing" (heads (j, j + nh / 2) a pair: the other candidate),
"window_reach" (`t - sliding_window <= j`: one key more), "lambda_index"
(lambda_init from the index in the layers RUN, not the published one),
"sub_norm" (no RMS of the difference).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
TOKEN_BLOCK = 64
WINDOW_KINDS = ("sliding_attention", "window")
FULL_KINDS = ("full_attention", "attention")


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def conv_padded(x, taps, bias):
    """(S, C), (C, K), (C,) -> (S, C): c_t = sum_j taps[:, j] x_{t - (K - 1) + j} +
    bias: ONE pad, then K slices (XLA:TPU shifts a concatenation wrongly within
    1024-row tiles: PERF.md section 7; this is not that form)."""
    s, k = x.shape[0], taps.shape[1]
    padded = jnp.pad(x, ((k - 1, 0), (0, 0)))
    out = jnp.broadcast_to(bias, x.shape)
    for j in range(k):
        out = out + taps[:, j] * padded[j:j + s]
    return out


def selective_scan(x, dt, a, bm, cm, d, state_dtype=jnp.float32):
    """Mamba-1's recurrence token by token. x, dt (S, C), dt > 0; a (C, N) < 0;
    bm, cm (S, N) shared by the channels; d (C,) -> m (S, C) and the final state
    (C, N). `state_dtype`: what the carried state is rounded to after every
    token (float32 here; the controls carry it in bfloat16)."""
    s = x.shape[0]
    block = min(TOKEN_BLOCK, s)
    assert s % block == 0, (s, block)

    def token(state, t):
        xt, dtt, bt, ct = t
        state = jnp.exp(dtt[:, None] * a) * state + (dtt * xt)[:, None] * bt
        state = state.astype(state_dtype).astype(jnp.float32)
        return state, state @ ct + d * xt

    @jax.checkpoint
    def tokens(state, ts):
        return jax.lax.scan(token, state, ts)

    ts = tuple(t.reshape((s // block, block) + t.shape[1:]) for t in (x, dt, bm, cm))
    state, m = jax.lax.scan(tokens, jnp.zeros(a.shape, jnp.float32), ts)
    return m.reshape(x.shape), state


def _mamba(lp, y, off):
    """-> the mixer's output and the layer's memory."""
    p = lp["mamba"]
    inner, n = p["A_log"].shape
    r = p["wdt"]["kernel"].shape[0]
    xz = y @ p["win"]["kernel"]
    x = jax.nn.silu(conv_padded(xz[:, :inner], p["conv"]["kernel"], p["conv"]["bias"]))
    dbc = x @ p["wx"]["kernel"]
    dt = jax.nn.softplus(dbc[:, :r] @ p["wdt"]["kernel"] + p["wdt"]["bias"])
    m, _ = selective_scan(x, dt, -jnp.exp(p["A_log"]), dbc[:, r:r + n], dbc[:, r + n:], p["D"])
    out = (m * jax.nn.silu(xz[:, inner:])) @ p["wout"]["kernel"]
    return out, (m - p["D"] * x if "memory_d_skip" in off else m)


def _softmax_map(q, k, v, window):
    """One softmax map: (S, d), (S, d), (S, dv) -> (S, dv), a block of queries at
    a time against all keys under an explicit mask: key j is seen by query t where
    `j <= t` and, over a window, `t - window < j`."""
    s = q.shape[0]
    block = min(QUERY_BLOCK, s)
    assert s % block == 0, (s, block)
    key_pos = jnp.arange(s)
    scale = q.shape[1] ** -0.5

    @jax.checkpoint
    def one_block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=0)
        query_pos = (start + jnp.arange(block))[:, None]
        seen = key_pos[None, :] <= query_pos
        if window is not None:
            seen = seen & (key_pos[None, :] > query_pos - window)
        return jax.nn.softmax(jnp.where(seen, qb @ k.T * scale, -jnp.inf), axis=-1) @ v

    return jax.lax.map(one_block, jnp.arange(0, s, block)).reshape(s, -1)


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _differential(lp, q, k, v, index, window, eps, off):
    """q (S, nh, hd), k, v (S, nkv, hd) -> (S, nh x hd) before the output projection."""
    p = lp["diff"]
    s, nh, hd = q.shape
    nkv = k.shape[1]
    serves = (nh // 2) // (nkv // 2)  # query pairs a key pair

    def pairs(t):  # (S, n, hd) -> (n / 2, 2, S, hd): a pair's two heads
        if "pairing" in off:  # heads (j, j + n / 2) a pair
            both = jnp.stack([t[:, :t.shape[1] // 2], t[:, t.shape[1] // 2:]], axis=2)
        else:  # consecutive heads (2j, 2j + 1) a pair
            both = t.reshape(s, t.shape[1] // 2, 2, hd)
        return both.transpose(1, 2, 0, 3)

    init = lambda_init(index)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + init

    def one_pair(qkv):
        qs, ks, vs = qkv  # (2, S, hd) each
        value = jnp.concatenate([vs[0], vs[1]], axis=-1)
        diff = _softmax_map(qs[0], ks[0], value, window) - lam * _softmax_map(qs[1], ks[1], value, window)
        if "sub_norm" not in off:
            diff = diff / jnp.sqrt(jnp.mean(diff * diff, axis=-1, keepdims=True) + eps) * p["subln"]["scale"]
        return diff * (1.0 - init)

    # query pair j reads key pair j // serves
    out = jax.lax.map(one_pair, (pairs(q), jnp.repeat(pairs(k), serves, axis=0), jnp.repeat(pairs(v), serves, axis=0)))
    return out.transpose(1, 0, 2).reshape(s, nh * hd)


def _queries(lp, y):
    return jnp.einsum("sh,hnd->snd", y, lp["wq"]["kernel"]) + lp["wq"]["bias"]


def _attention(lp, y, index, window, eps, off):
    """-> the mixer's output and the layer's keys and values as projected."""
    kv = jnp.einsum("sh,hcnd->csnd", y, lp["wkv"]["kernel"]) + lp["wkv"]["bias"][:, None]
    out = _differential(lp, _queries(lp, y), kv[0], kv[1], index, window, eps, off)
    return out @ lp["wo"]["kernel"] + lp["wo"]["bias"], (kv[0], kv[1])


def _cross(lp, y, keys_values, index, eps, off):
    out = _differential(lp, _queries(lp, y), keys_values[0], keys_values[1], index, None, eps, off)
    return out @ lp["wo"]["kernel"] + lp["wo"]["bias"]


def _gmu(lp, y, memory):
    return (memory * jax.nn.silu(y @ lp["gmu"]["win"]["kernel"])) @ lp["gmu"]["wout"]["kernel"]


def _swiglu(p, y):
    gate_up = jnp.einsum("sh,hcf->csf", y, p["wi"]["kernel"])
    return (jax.nn.silu(gate_up[0]) * gate_up[1]) @ p["wo_mlp"]["kernel"]


def layer_plan(fields):
    """(kind, published index) of each layer run: `layer_types` at `layer_indices`."""
    indices = fields.get("layer_indices")
    indices = list(range(fields["num_layers"])) if indices is None else list(indices)
    return [(fields["layer_types"][i], i) for i in indices]


def block(lp, x, memory, keys_values, kind, index, run_index, fields, off):
    """One layer: -> x, and the memory and the keys and values as they stand after
    it (its own where it publishes, else those handed in). Recomputed in a backward
    pass (`jax.checkpoint`), so that a gradient of the whole sequence at the
    published widths fits a chip."""
    eps = fields["layernorm_eps"]
    place = run_index if "lambda_index" in off else index
    window = fields["sliding_window"] + (1 if "window_reach" in off else 0)
    if "reader_cotangents" in off:
        memory, keys_values = jax.lax.stop_gradient((memory, keys_values))

    def once(lp, x, memory, keys_values):
        y = _ln(x, lp["ln1"], eps)
        if "mamba" in lp:
            out, memory = _mamba(lp, y, off)
        elif "gmu" in lp:
            out = _gmu(lp, y, memory)
        elif "wkv" not in lp:
            out = _cross(lp, y, keys_values, place, eps, off)
        elif kind in WINDOW_KINDS:
            out, _ = _attention(lp, y, place, window, eps, off)  # a window layer hands nothing on
        else:
            assert kind in FULL_KINDS, kind
            out, keys_values = _attention(lp, y, place, None, eps, off)
        x = x + out
        return x + _swiglu(lp, _ln(x, lp["ln2"], eps)), memory, keys_values

    return jax.checkpoint(once)(lp, x, memory, keys_values)


def _nll(logits, labels):
    return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
        logits, labels[:, None], axis=-1)[:, 0]


def _sequence(params, fields, off, tokens, labels):
    """One sequence's tokens' cross entropies (S,)."""
    table = params["embed"]["wte"]
    x = table[tokens]
    memory = keys_values = None
    for run_index, (lp, (kind, index)) in enumerate(zip(params["layers"], layer_plan(fields))):
        x, memory, keys_values = block(lp, x, memory, keys_values, kind, index, run_index, fields, off)
    x = _ln(x, params["final_norm"], fields["layernorm_eps"])
    return _nll(x @ table.T, labels)


def loss(params, batch, fields, switch_off=()):
    """The objective of the batch, float32."""
    if "layers" not in params:
        raise ValueError("the reference reads the per-layer tree (`layers`); "
                         "this tree has %s" % sorted(params))
    off = frozenset(switch_off)
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        ce = jax.lax.map(lambda row: _sequence(params, fields, off, *row), (batch["tokens"], batch["labels"]))
        mask = batch["loss_mask"].astype(jnp.float32) if "loss_mask" in batch else jnp.ones_like(ce)
        return jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
