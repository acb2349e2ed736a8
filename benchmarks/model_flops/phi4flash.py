"""Phi-4-mini-flash's model FLOPs a token, and the selective scan's least
operations and bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token, by the kind of
each layer run (`layer_types` at `layer_indices`):

- a Mamba-1 layer's mixer ("mamba1"): hidden -> [x | z] (2 x inner, inner =
  mamba_expand x hidden), inner -> [dt_r | B | C] (dt_rank + 2 x d_state),
  dt_rank -> inner, inner -> hidden; and **the scan as the recurrence needs it:
  a multiply-add into the state and one out of it a (channel, state) a token**
  (`4 inner d_state`), whatever chunk an implementation cuts the sequence into,
  so a change of the scan's form cannot move `mfu`. The convolution's four taps
  a channel, the decay and the D skip are no matmul;
- a differential attention layer's mixer ("sliding_attention",
  "full_attention"): q and out on the query heads, k and v on the KV heads;
  **the core as its mathematics needs it**: two score maps of head_dim a pair of
  heads (as many as ordinary attention's) and two `p v` products at the pair's
  2 x head_dim (twice ordinary attention's), 1.5 x an ordinary core of the same
  heads, over the causal half (full) or the exact band (window: query i sees
  min(i + 1, W) keys), whatever heads an implementation pads: the program's one
  call at 128-wide heads does 2 x an ordinary core and is priced as 1.5;
- a gated memory unit's ("gmu"): hidden -> inner and inner -> hidden; the gate
  on another layer's memory is no matmul;
- a cross layer's ("cross_attention"): q's and the output's projections alone,
  and a differential core over the causal half on ANOTHER layer's keys;
- every layer's MLP half: a dense SwiGLU, hidden -> 2 x ffn and ffn -> hidden;
- the head once (the tied table transposed).

At the published widths, 25008 vocabulary rows, the six layers of the cut
(0, 1, 16, 17, 18, 19) and 8192 tokens, forward MFLOP a token: six MLP halves
943.7 (61.8 %), two Mamba-1 mixers 164.5 of projections (10.8 %) + 0.7 of scan,
the gated memory unit 52.4 (3.4 %), three attention-like mixers' projections
104.9 (6.9 %), the full and the cross differential cores 125.8 (8.2 %), the
band 7.6, the head 128.0 (8.4 %): 1527.6 in all, 4.583 GFLOP with the backward.
`tests/benchmarks/test_flops.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, List, Mapping

BWD_FWD_RATIO = 2.0
MAMBA, WINDOW, FULL, GMU, CROSS = "mamba1", "sliding_attention", "full_attention", "gmu", "cross_attention"


def kinds_run(fields: Mapping) -> List[str]:
    """The mixer of each layer run: `layer_types` at `layer_indices` (None: the first `num_layers`)."""
    indices = fields.get("layer_indices")
    indices = range(fields["num_layers"]) if indices is None else indices
    return [fields["layer_types"][i] for i in indices]


def inner(fields: Mapping) -> int:
    return fields["mamba_expand"] * fields["hidden_size"]


def mamba_mixer_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    hidden, ch, n, r = fields["hidden_size"], inner(fields), fields["mamba_d_state"], fields["mamba_dt_rank"]
    proj = 2.0 * hidden * (2 * ch) + 2.0 * ch * (r + 2 * n) + 2.0 * r * ch + 2.0 * ch * hidden
    return {"projections": proj, "core": 4.0 * ch * n}


def gmu_fwd_flops_a_token(fields: Mapping) -> float:
    return 2.0 * fields["hidden_size"] * inner(fields) + 2.0 * inner(fields) * fields["hidden_size"]


def diff_core_fwd_flops_a_token(fields: Mapping, keys_seen: float) -> float:
    """Two score maps of head_dim and two `p v` products of 2 x head_dim a pair of
    heads, over `keys_seen` keys a query (the mean over the sequence)."""
    q_dim = fields["num_heads"] * fields["head_dim"]
    return 3.0 * 2.0 * keys_seen * q_dim


def attention_mixer_fwd_flops_a_token(fields: Mapping, seq_len: int, kind: str) -> Dict[str, float]:
    hidden, hd = fields["hidden_size"], fields["head_dim"]
    q_dim, kv_dim = fields["num_heads"] * hd, fields["num_kv_heads"] * hd
    own_kv = 0.0 if kind == CROSS else 2.0 * hidden * (2 * kv_dim)
    if kind == WINDOW:  # the exact band: query i sees min(i + 1, W) keys
        w = min(fields["sliding_window"], seq_len)
        seen = (w * seq_len - w * (w - 1) / 2.0) / seq_len
    else:  # the causal half
        seen = seq_len / 2.0
    return {"projections": 2.0 * hidden * q_dim + own_kv + 2.0 * q_dim * hidden,
            "core": diff_core_fwd_flops_a_token(fields, seen)}


def mlp_fwd_flops_a_token(fields: Mapping) -> float:
    hidden, ffn = fields["hidden_size"], fields["ffn_hidden"]
    return 2.0 * hidden * (2 * ffn) + 2.0 * ffn * hidden


def mixer_fwd_flops_a_token(fields: Mapping, seq_len: int, kind: str) -> float:
    if kind == MAMBA:
        return sum(mamba_mixer_fwd_flops_a_token(fields).values())
    if kind == GMU:
        return gmu_fwd_flops_a_token(fields)
    return sum(attention_mixer_fwd_flops_a_token(fields, seq_len, kind).values())


def mamba_layers(fields: Mapping) -> int:
    return kinds_run(fields).count(MAMBA)


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    fwd = (sum(mixer_fwd_flops_a_token(fields, seq_len, kind) for kind in kinds_run(fields))
           + fields["num_layers"] * mlp_fwd_flops_a_token(fields)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------ the selective scan
# One Mamba-1 layer's scan over `tokens` tokens (ops/selective_scan.py; scope
# `gt.attn.selscan`). The floor ANY implementation must meet, chunked or not, in
# XLA or in a kernel: the recurrence's two multiply-adds a (channel, state) a
# token forward and twice that backward, and each operand and result moved once
# at the configuration's dtypes: x and m on the channels and B and C in the
# compute dtype, dt one float32 a channel; the backward reads those and m's
# cotangent and writes the four gradients (A's and D's are a float a (channel,
# state) and a channel). The states carried through HBM, the chunks' sums, the
# states kept a chunk and a recomputed forward are the implementation's own
# cost, so the share of this floor cannot pass 100 %.
def selscan_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's scan."""
    ch, n = inner(fields), fields["mamba_d_state"]
    x = m = ch * dtype_bytes
    bc = 2 * n * dtype_bytes  # B and C a token
    dt = ch * 4  # float32
    fwd = {"flops": 4.0 * ch * n * tokens, "bytes": float(x + dt + bc + m) * tokens}
    if which == "fwd":
        return fwd
    return {"flops": BWD_FWD_RATIO * fwd["flops"],
            "bytes": float(x + dt + bc + m + x + dt + bc) * tokens}
