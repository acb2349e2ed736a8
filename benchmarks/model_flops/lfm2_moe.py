"""LFM2-MoE's model FLOPs a token, the gated short convolution's gate pass's
least operations and bytes, and the held experts' grouped matmul's, for their
rooflines.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token:

- a convolution layer's mixer (the layers `layer_types` names "conv"): hidden
  -> [B | C | u] (3 x hidden) and hidden -> hidden. The two gates and the taps
  are no matmul: they are `conv_gate_cost`'s, which `mfu` does not count;
- an attention layer's mixer: hidden -> heads x head_dim, hidden -> 2 x kv
  heads x head_dim, heads x head_dim -> hidden; q k^T and p v a head, the causal
  half once;
- the leading dense layers' SwiGLU at `dense_ffn_hidden`;
- a routed layer's router's matmul over ALL experts, and the routed experts at
  `experts_per_token` x `experts_held` / `num_experts` experts a token: the
  EVEN share of a token's experts that falls to the experts held here (a
  constant, whatever the routing; the rows a step really sends are the counter
  `expert_rows_held`). No shared expert;
- the head once.

At the published widths, 8 of 32 experts, 16384 vocabulary rows, 1 + 4 layers
and 8192 tokens, forward MFLOP a token: a convolution mixer 33.55 (four), the
attention mixer 20.97 of projections + 33.55 of scores and sums, the dense MLP
88.08, a routed half 22.02 of the held share (one expert's worth: 4 x 8 / 32) +
0.13 of the router = 22.15 (four), the head 67.11: 432.5 in all, 1.2975 GFLOP
with the backward. `tests/benchmarks/test_lfm2moe_cell.py` holds this count to
the program's own (`galvatron_tpu/obs/flops.py`).
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def conv_mixer_fwd_flops_a_token(fields: Mapping) -> float:
    hidden = fields["hidden_size"]
    return 2.0 * hidden * (3 * hidden) + 2.0 * hidden * hidden


def attention_mixer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, heads, kv = fields["hidden_size"], fields["num_heads"], fields["num_kv_heads"]
    hd = fields.get("head_dim") or hidden // heads
    q_dim = heads * hd
    proj = 2.0 * hidden * q_dim + 2.0 * hidden * (2 * kv * hd) + 2.0 * q_dim * hidden
    return {"projections": proj, "core": 2.0 * (2.0 * seq_len * q_dim) * 0.5}  # causal


def swiglu_fwd_flops_a_token(hidden: int, width: int) -> float:
    return 2.0 * hidden * (2 * width) + 2.0 * width * hidden


def mlp_fwd_flops_a_token(fields: Mapping, routed: bool) -> float:
    hidden = fields["hidden_size"]
    if not routed:
        return swiglu_fwd_flops_a_token(hidden, fields["dense_ffn_hidden"])
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return sent_here * swiglu_fwd_flops_a_token(hidden, fields["ffn_hidden"]) + 2.0 * hidden * fields["num_experts"]


def conv_layers(fields: Mapping) -> int:
    """Layers whose mixer is the short convolution: those of the pattern's first `num_layers`."""
    return fields["layer_types"][:fields["num_layers"]].count("conv")


def routed_blocks(fields: Mapping) -> int:
    """Routed blocks a step runs: the layers after the leading dense ones."""
    return fields["num_layers"] - min(fields["first_dense_layers"], fields["num_layers"])


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    conv, routed = conv_layers(fields), routed_blocks(fields)
    fwd = (conv * conv_mixer_fwd_flops_a_token(fields)
           + (fields["num_layers"] - conv) * sum(attention_mixer_fwd_flops_a_token(fields, seq_len).values())
           + (fields["num_layers"] - routed) * mlp_fwd_flops_a_token(fields, False)
           + routed * mlp_fwd_flops_a_token(fields, True)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------- the gate pass
# One convolution layer's pass between its two matmuls over `tokens` tokens
# (models/parts/conv.py; scope `gt.attn.conv_gate`): `B * u`, the K taps, `C *
# v`. The floor ANY implementation must meet, fused or not, kernel or not: each
# operand and result moved once in the compute dtype. Forward: [B | C | u] read
# (3 x hidden a token), `C v` written (hidden). Backward: [B | C | u] read
# again, the cotangent of `C v` read, d[B | C | u] written (7 x hidden), and the
# taps' gradient written once, float32. The operations: a multiply a gate and
# a multiply-add a tap forward ((2 K + 2) a channel a token); backward `v`
# again for dC, dv, the transposed taps, the two products of the first gate and
# the taps' own gradient ((6 K + 4)). At 16 KB a token against 16 KFLOP the
# pass is memory bound by two hundred times. A recomputed forward and whatever
# an implementation writes between its fusions are in the time and not in the
# floor, so the share of this floor cannot pass 100 %.
def conv_gate_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's gate pass."""
    hidden, taps = fields["hidden_size"], fields["short_conv_kernel"]
    if which == "fwd":
        return {"flops": (2.0 * taps + 2) * hidden * tokens, "bytes": 4.0 * hidden * dtype_bytes * tokens}
    return {"flops": (6.0 * taps + 4) * hidden * tokens,
            "bytes": 7.0 * hidden * dtype_bytes * tokens + 4.0 * hidden * taps}


# ------------------------------------------------------- the grouped matmul
# One call multiplies the rows sent to the experts HELD here, sorted by
# expert, by the kernel of each row's expert: (rows, K) x (held, K, N) ->
# (rows, N); the other experts' rows are skipped (megablox's `group_offset`).
# Two kinds of call a pass: "in" (K = hidden, N = 2 x width: gate and up) and
# "out" (K = width, N = hidden). The backward's two calls a kind do the same
# multiply-adds over the same three operands with another one as the result,
# so one cost serves a kind's four calls.
def gmm_dims(fields: Mapping, kind: str):
    hidden, width = fields["hidden_size"], fields["ffn_hidden"]
    return {"in": (hidden, 2 * width), "out": (width, hidden)}[kind]


def gmm_cost(fields: Mapping, kind: str, rows: float, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE grouped-matmul call of this kind over
    `rows` rows: the rows the held experts are really sent (the program's
    counter `expert_rows_held`, a block), not the even share. Bytes: the rows
    in, the rows out and ONE expert's kernel, in the compute dtype: the least
    any routing of that many rows must move (the counter says how many rows
    the held experts got and not which of them got any, and a kernel whose
    group is empty is never read). At the even share, 2048 rows an expert, the
    call is compute bound either way."""
    k, n = gmm_dims(fields, kind)
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((k * n if rows > 0 else 0) + rows * k + rows * n) * dtype_bytes}
