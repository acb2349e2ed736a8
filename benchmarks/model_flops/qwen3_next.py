"""Qwen3-Next's model FLOPs a token, and the gated delta rule's least
operations and bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token:

- a linear layer's mixer (`full_attention_interval` - 1 of every
  `full_attention_interval` layers): hidden -> [q | k | v | z] (2 x key + 2 x
  value dims), hidden -> [b | a] (2 x value heads), value dims -> hidden; and
  **the core as the recurrence needs it: three (d_k, d_v) products a value
  head a token** (S^T k, k u^T, S^T q: `6 d_k d_v`), whatever chunk an
  implementation cuts the sequence into, so a change of chunk cannot move
  `mfu`. The convolution's four taps a channel are no matmul;
- an attention layer's mixer: q beside its gate (hidden -> 2 x heads x
  head_dim), k and v on the KV heads, out; q k^T and p v, the causal half once;
- every layer's MLP half: the router's matmul over ALL experts, the shared
  expert and its (hidden, 1) gate, and the routed experts at
  `experts_per_token` x `experts_held` / `num_experts` experts a token: the EVEN
  share of a token's experts that falls to the experts held here (a constant,
  whatever the routing; the rows a step really sends are the counter
  `expert_rows_held`);
- the head once.

At the published widths, 32 of 512 experts, 18992 vocabulary rows, 4 layers
and 8192 tokens, forward MFLOP a token: a linear mixer 67.37 of projections +
3.15 of core (three), the attention mixer 54.53 + 67.11 of scores, an MLP half
12.32 (four), the head 77.79: 460.3 in all, 1.381 GFLOP with the backward.
`tests/benchmarks/test_flops.py`-style, `tests/benchmarks/test_qwen3next_cell.py`
holds this count to the program's own (`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def linear_mixer_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    hidden, nv = fields["hidden_size"], fields["linear_num_value_heads"]
    dk, dv = fields["linear_key_head_dim"], fields["linear_value_head_dim"]
    key_dim, value_dim = fields["linear_num_key_heads"] * dk, nv * dv
    proj = (2.0 * hidden * (2 * key_dim + 2 * value_dim) + 2.0 * hidden * (2 * nv)
            + 2.0 * value_dim * hidden)
    return {"projections": proj, "core": 6.0 * nv * dk * dv}


def attention_mixer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, hd = fields["hidden_size"], fields["head_dim"]
    q_dim, kv_dim = fields["num_heads"] * hd, fields["num_kv_heads"] * hd
    proj = 2.0 * hidden * (2 * q_dim) + 2.0 * hidden * (2 * kv_dim) + 2.0 * q_dim * hidden
    return {"projections": proj, "core": 2.0 * (2.0 * seq_len * q_dim) * 0.5}  # causal


def moe_fwd_flops_a_token(fields: Mapping) -> float:
    hidden = fields["hidden_size"]
    expert = 2.0 * hidden * (2 * fields["ffn_hidden"]) + 2.0 * fields["ffn_hidden"] * hidden
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return ((sent_here + fields["num_shared_experts"]) * expert
            + 2.0 * hidden * fields["num_experts"] + 2.0 * hidden)


def linear_layers(fields: Mapping) -> int:
    """Layers whose mixer is linear: all but every `full_attention_interval`-th."""
    return fields["num_layers"] - fields["num_layers"] // fields["full_attention_interval"]


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    linear = linear_layers(fields)
    fwd = (linear * sum(linear_mixer_fwd_flops_a_token(fields).values())
           + (fields["num_layers"] - linear)
           * sum(attention_mixer_fwd_flops_a_token(fields, seq_len).values())
           + fields["num_layers"] * moe_fwd_flops_a_token(fields)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------- the gated delta rule
# One linear layer's core over `tokens` tokens (ops/linear_attention.py
# `gated_delta_rule`; scope `gt.attn.delta`). The floor ANY implementation must
# meet, chunked or not: the recurrence's three products a value head a token
# forward and twice that backward, and each operand and result moved once: q
# and k on the key heads, v and o on the value heads in the compute dtype, g
# and beta one float32 a value head; the backward reads those and o's
# cotangent and writes the five gradients. The chunks' triangular solves, the
# states kept a chunk and a recomputed forward are the implementation's own
# cost, so the share of this floor cannot pass 100 %.
def gdn_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's core."""
    nk, nv = fields["linear_num_key_heads"], fields["linear_num_value_heads"]
    dk, dv = fields["linear_key_head_dim"], fields["linear_value_head_dim"]
    qkv = (2 * nk * dk + nv * dv) * dtype_bytes  # q, k, v a token
    o = nv * dv * dtype_bytes
    gates = 2 * nv * 4  # g and beta, float32
    fwd = {"flops": 6.0 * nv * dk * dv * tokens, "bytes": float(qkv + gates + o) * tokens}
    if which == "fwd":
        return fwd
    return {"flops": BWD_FWD_RATIO * fwd["flops"],
            "bytes": float(qkv + gates + o + qkv + gates) * tokens}
