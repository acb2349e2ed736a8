"""Kimi-Linear's model FLOPs a token, and the per-channel delta rule's least
operations and bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token:

- a KDA layer's mixer (the layers `layer_types` names "kda"): hidden -> [q | k
  | v] (2 x key + value dims), the gate's and the output gate's low-rank pairs
  (hidden -> d_v -> key dims, hidden -> d_v -> value dims), hidden -> beta a
  head, value dims -> hidden; and **the core as the recurrence needs it: three
  (d_k, d_v) products a head a token** (S^T k, k u^T, S^T q: `6 d_k d_v`),
  whatever chunk or sub-block an implementation cuts the sequence into, so a
  change of either cannot move `mfu`. A gate that is a vector scales the
  state's rows: no matmul. Nor are the convolution's four taps a channel;
- an attention layer's mixer (latent attention with no low-rank q): hidden ->
  heads x (nope + rope), hidden -> kv_lora + rope, kv_lora -> heads x (nope +
  v), heads x v -> hidden; q k^T at nope + rope dims and p v at v dims a head,
  the causal half once, whatever width the one attention call pads them to;
- the leading dense layers' SwiGLU at `dense_ffn_hidden`;
- a routed layer's shared expert(s), its router's matmul over ALL experts, and
  the routed experts at `experts_per_token` x `experts_held` / `num_experts`
  experts a token: the EVEN share of a token's experts that falls to the
  experts held here (a constant, whatever the routing; the rows a step really
  sends are the counter `expert_rows_held`);
- the head once.

At the published widths, 8 of 256 experts, 20480 vocabulary rows, 1 + 4 layers
and 8192 tokens, forward MFLOP a token: a KDA mixer 78.92 of projections +
3.15 of core (four), the attention mixer 58.23 + 83.89 of scores and sums, the
dense MLP 127.40, a routed half 14.16 of the shared expert + 3.54 of the held
share + 1.18 of the router = 18.87 (four), the head 94.37: 767.7 in all, 2.303
GFLOP with the backward. `tests/benchmarks/test_flops.py` holds this count to
the program's own (`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def kda_dims(fields: Mapping):
    """(heads, d_k, d_v) of a KDA layer: keys and values have the same heads."""
    return (fields["linear_num_value_heads"], fields["linear_key_head_dim"],
            fields["linear_value_head_dim"])


def kda_mixer_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    hidden, (heads, dk, dv) = fields["hidden_size"], kda_dims(fields)
    key_dim, value_dim = heads * dk, heads * dv
    proj = (2.0 * hidden * (2 * key_dim + value_dim)
            + 2.0 * hidden * dv + 2.0 * dv * key_dim  # the gate's pair
            + 2.0 * hidden * dv + 2.0 * dv * value_dim  # the output gate's pair
            + 2.0 * hidden * heads + 2.0 * value_dim * hidden)
    return {"projections": proj, "core": 6.0 * heads * dk * dv}


def attention_mixer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, heads, kvl = fields["hidden_size"], fields["num_heads"], fields["kv_lora_rank"]
    nope, rope, v = fields["qk_nope_head_dim"], fields["qk_rope_head_dim"], fields["v_head_dim"]
    proj = (2.0 * hidden * heads * (nope + rope) + 2.0 * hidden * (kvl + rope)
            + 2.0 * kvl * heads * (nope + v) + 2.0 * heads * v * hidden)
    return {"projections": proj, "core": 2.0 * seq_len * heads * ((nope + rope) + v) * 0.5}  # causal


def swiglu_fwd_flops_a_token(hidden: int, width: int) -> float:
    return 2.0 * hidden * (2 * width) + 2.0 * width * hidden


def mlp_fwd_flops_a_token(fields: Mapping, routed: bool) -> float:
    hidden = fields["hidden_size"]
    if not routed:
        return swiglu_fwd_flops_a_token(hidden, fields["dense_ffn_hidden"])
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return ((sent_here + fields["num_shared_experts"]) * swiglu_fwd_flops_a_token(hidden, fields["ffn_hidden"])
            + 2.0 * hidden * fields["num_experts"])


def kda_layers(fields: Mapping) -> int:
    """Layers whose mixer is KDA: those of the pattern's first `num_layers`."""
    return fields["layer_types"][:fields["num_layers"]].count("kda")


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    kda = kda_layers(fields)
    dense = min(fields["first_dense_layers"], fields["num_layers"])
    fwd = (kda * sum(kda_mixer_fwd_flops_a_token(fields).values())
           + (fields["num_layers"] - kda) * sum(attention_mixer_fwd_flops_a_token(fields, seq_len).values())
           + dense * mlp_fwd_flops_a_token(fields, False)
           + (fields["num_layers"] - dense) * mlp_fwd_flops_a_token(fields, True)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------- the per-channel delta rule
# One KDA layer's core over `tokens` tokens (ops/linear_attention.py
# `kda_rule`; scope `gt.attn.kda_rule`). The floor ANY implementation must
# meet, chunked or not, kernel or not: the recurrence's three products a head a
# token forward and twice that backward, and each operand and result moved
# once: q, k, v and o in the compute dtype, the gate g one float32 a head AND
# key channel (at 128 channels it weighs as much as q + k + v + o together
# would in float32's half: 16 KB a token of 32 heads against 32 KB), beta one
# float32 a head; the backward reads those and o's cotangent and writes the
# five gradients. The sub-blocks' decays, the chunks' triangular solves, the
# states kept a chunk and a recomputed forward are the implementation's own
# cost, so the share of this floor cannot pass 100 %.
def kda_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's core."""
    heads, dk, dv = kda_dims(fields)
    qkv = (2 * heads * dk + heads * dv) * dtype_bytes  # q, k, v a token
    o = heads * dv * dtype_bytes
    gates = heads * dk * 4 + heads * 4  # g a channel and beta a head, float32
    fwd = {"flops": 6.0 * heads * dk * dv * tokens, "bytes": float(qkv + gates + o) * tokens}
    if which == "fwd":
        return fwd
    return {"flops": BWD_FWD_RATIO * fwd["flops"],
            "bytes": float(qkv + gates + o + qkv + gates) * tokens}
