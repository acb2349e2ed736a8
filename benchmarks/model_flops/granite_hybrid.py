"""Granite-4.0-H's model FLOPs a token, and the Mamba-2 scan's least
operations and bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token:

- a state-space layer's mixer (the layers `layer_types` names "ssm"): hidden ->
  [z | x | B | C | dt] (2 x inner + 2 x d_state + heads columns, inner = heads x
  d_head), inner -> hidden; and **the scan as the recurrence needs it: two
  (d_head, d_state) products a head a token** (dt x B^T into the state, h C out
  of it: `4 d_head d_state`), whatever chunk an implementation cuts the
  sequence into, so a change of chunk cannot move `mfu`. The convolution's
  four taps a channel, the decay and the D skip are no matmul;
- an attention layer's mixer: q and out on the query heads, k and v on the KV
  heads; q k^T and p v, the causal half once;
- every layer's MLP half: a dense SwiGLU, hidden -> 2 x ffn and ffn -> hidden;
- the head once (the tied table transposed).

At the published widths, 12544 vocabulary rows, 10 layers (9 + 1) and 4096
tokens, forward MFLOP a token: a state-space mixer 51.64 of projections +
2.10 of scan (nine), the attention mixer 20.97 + 16.78 of scores, an MLP half
100.66 (ten), the head 51.38: 1579.4 in all, 4.738 GFLOP with the backward
(MLPs 63.7 %, state-space mixers 30.6 %, the attention mixer 2.4 %, the head
3.3 %).
`tests/benchmarks/test_flops.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def ssm_mixer_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    hidden, heads = fields["hidden_size"], fields["ssm_num_heads"]
    inner, state = heads * fields["ssm_head_dim"], fields["ssm_state_dim"]
    proj = 2.0 * hidden * (2 * inner + 2 * state + heads) + 2.0 * inner * hidden
    return {"projections": proj, "core": 4.0 * heads * fields["ssm_head_dim"] * state}


def attention_mixer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, hd = fields["hidden_size"], fields["head_dim"]
    q_dim, kv_dim = fields["num_heads"] * hd, fields["num_kv_heads"] * hd
    proj = 2.0 * hidden * q_dim + 2.0 * hidden * (2 * kv_dim) + 2.0 * q_dim * hidden
    return {"projections": proj, "core": 2.0 * (2.0 * seq_len * q_dim) * 0.5}  # causal


def mlp_fwd_flops_a_token(fields: Mapping) -> float:
    hidden, ffn = fields["hidden_size"], fields["ffn_hidden"]
    return 2.0 * hidden * (2 * ffn) + 2.0 * ffn * hidden


def ssm_layers(fields: Mapping) -> int:
    """Layers whose mixer is a state-space one: HF's `layer_types` cut to the
    layers run, "mamba" there."""
    return sum(t != "attention" for t in fields["layer_types"][:fields["num_layers"]])


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    ssm = ssm_layers(fields)
    fwd = (ssm * sum(ssm_mixer_fwd_flops_a_token(fields).values())
           + (fields["num_layers"] - ssm)
           * sum(attention_mixer_fwd_flops_a_token(fields, seq_len).values())
           + fields["num_layers"] * mlp_fwd_flops_a_token(fields)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------ the Mamba-2 scan
# One state-space layer's scan over `tokens` tokens (ops/ssd.py `ssd_scan`;
# scope `gt.attn.ssd`). The floor ANY implementation must meet, chunked or
# not: the recurrence's two products a head a token forward and twice that
# backward, and each operand and result moved once: x and y on the heads and
# B and C (one group) in the compute dtype, dt one float32 a head; the
# backward reads those and y's cotangent and writes the four gradients (A's
# and D's are a float a head). The chunks' decay masks, the C B^T products,
# the states kept a chunk and a recomputed forward are the implementation's
# own cost, so the share of this floor cannot pass 100 %.
def ssd_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's scan."""
    heads, hd, state = fields["ssm_num_heads"], fields["ssm_head_dim"], fields["ssm_state_dim"]
    xbc = (heads * hd + 2 * state) * dtype_bytes  # x, B, C a token
    y = heads * hd * dtype_bytes
    dt = heads * 4  # float32
    fwd = {"flops": 4.0 * heads * hd * state * tokens, "bytes": float(xbc + dt + y) * tokens}
    if which == "fwd":
        return fwd
    return {"flops": BWD_FWD_RATIO * fwd["flops"],
            "bytes": float(xbc + dt + y + xbc + dt) * tokens}
