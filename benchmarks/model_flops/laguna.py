"""Laguna's model FLOPs a token, the window kernels' least operations and
bytes, and the held experts' grouped matmul's, for their rooflines.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token:

- a full layer's mixer (the layers `layer_types` does not name a window
  layer): hidden -> `num_heads` x head_dim, hidden -> 2 x kv heads x head_dim,
  the per-head gate hidden -> `num_heads`, heads x head_dim -> hidden; q k^T and
  p v a head, the causal half once;
- a window layer's mixer: the same projections at `window_num_heads`, and q
  k^T and p v over the keys a query SEES, the exact band: query i sees min(i +
  1, W) keys, a mean of (W S - W (W - 1) / 2) / S over a sequence (496.03 at W =
  512 and 8192 tokens). A kernel that multiplied the whole causal triangle
  would do 8.3 times that, none of it counted;
- the leading dense layers' SwiGLU at `dense_ffn_hidden`;
- a routed layer's router's matmul over ALL experts, the routed experts at
  `experts_per_token` x `experts_held` / `num_experts` experts a token (the
  EVEN share of a token's experts that falls to the experts held here: a
  constant, whatever the routing; the rows a step really sends are the counter
  `expert_rows_held`), and the shared expert(s) whole;
- the head once.

At the published widths, 32 of 256 experts, 12544 vocabulary rows, 1 + 4
layers and 8192 tokens, forward MFLOP a token: a full layer's mixer 59.18 of
projections + 100.66 of scores and sums (two), a window mixer 75.76 + 16.25
(three), the dense MLP 100.66, a routed half 3.15 of the held share (one
expert's worth: 8 x 32 / 256) + 3.15 shared + 1.05 of the router = 7.34 (four),
the head 51.38: 801.8 in all, 2.405 GFLOP with the backward.
`tests/benchmarks/test_laguna_cell.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`).
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0
WINDOW_TYPES = ("sliding_attention", "window")


def band_keys(window: int, seq_len: int) -> float:
    """The keys a query sees under `i - window < j <= i`, the mean over a sequence."""
    w = min(window, seq_len)
    return (w * seq_len - w * (w - 1) / 2.0) / seq_len


def mixer_fwd_flops_a_token(fields: Mapping, seq_len: int, windowed: bool) -> Dict[str, float]:
    hidden, kv, hd = fields["hidden_size"], fields["num_kv_heads"], fields["head_dim"]
    heads = fields["window_num_heads"] if windowed else fields["num_heads"]
    q_dim = heads * hd
    proj = (2.0 * hidden * q_dim + 2.0 * hidden * (2 * kv * hd) + 2.0 * q_dim * hidden
            + (2.0 * hidden * heads if fields["attn_head_gate"] else 0.0))
    keys = band_keys(fields["sliding_window"], seq_len) if windowed else seq_len / 2.0  # causal
    return {"projections": proj, "core": 2.0 * 2.0 * keys * q_dim}


def swiglu_fwd_flops_a_token(hidden: int, width: int) -> float:
    return 2.0 * hidden * (2 * width) + 2.0 * width * hidden


def mlp_fwd_flops_a_token(fields: Mapping, routed: bool) -> float:
    hidden = fields["hidden_size"]
    if not routed:
        return swiglu_fwd_flops_a_token(hidden, fields["dense_ffn_hidden"])
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return ((sent_here + fields["num_shared_experts"]) * swiglu_fwd_flops_a_token(hidden, fields["ffn_hidden"])
            + 2.0 * hidden * fields["num_experts"])


def window_layers(fields: Mapping) -> int:
    """Layers that attend over the window: those of the pattern's first `num_layers`."""
    return sum(t in WINDOW_TYPES for t in fields["layer_types"][:fields["num_layers"]])


def routed_blocks(fields: Mapping) -> int:
    """Routed blocks a step runs: the layers after the leading dense ones."""
    return fields["num_layers"] - min(fields["first_dense_layers"], fields["num_layers"])


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    windows, routed = window_layers(fields), routed_blocks(fields)
    fwd = (windows * sum(mixer_fwd_flops_a_token(fields, seq_len, True).values())
           + (fields["num_layers"] - windows) * sum(mixer_fwd_flops_a_token(fields, seq_len, False).values())
           + (fields["num_layers"] - routed) * mlp_fwd_flops_a_token(fields, False)
           + routed * mlp_fwd_flops_a_token(fields, True)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------- the window kernels
# The repo's band kernels (ops/window_attention.py): a forward kernel and ONE
# backward kernel, a grid step a block of queries of one query head beside the
# key blocks its band touches. Matmuls over the band a (batch, query head), as
# each kernel does them:
#   fwd  q k^T, p v                                          2
#   bwd  q k^T again, do v^T, ds k, ds^T q, p^T do           5
# each 2 x tokens x band_keys x head_dim: the EXACT band, although a kernel
# runs whole blocks along its two edges. What the algorithm has to move, each
# operand once in the compute dtype: fwd reads q and writes o at the query
# heads and reads k, v at the KEY heads (GQA is indexed, not repeated); bwd
# reads q, do and writes dq at the query heads and reads k, v and writes dk,
# dv at the key heads. Nothing of the forward is kept but q, k, v: the rows are
# whole in a step, so the backward's softmax needs no statistics.
WINDOW_KERNEL_MATMULS = {"fwd": 2, "bwd": 5}
WINDOW_KERNEL_TENSORS = {"fwd": (2, 2), "bwd": (3, 4)}  # (at the query heads, at the key heads)


def window_kernel_cost(fields: Mapping, kind: str, batch: int, seq_len: int,
                       dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE call of a window kernel ("fwd" | "bwd") on
    `batch` sequences of `seq_len` tokens at the window layers' heads."""
    heads, kv, hd = fields["window_num_heads"], fields["num_kv_heads"], fields["head_dim"]
    keys = band_keys(fields["sliding_window"], seq_len)
    at_q, at_kv = WINDOW_KERNEL_TENSORS[kind]
    return {"flops": WINDOW_KERNEL_MATMULS[kind] * 2.0 * batch * heads * seq_len * keys * hd,
            "bytes": float((at_q * heads + at_kv * kv) * batch * seq_len * hd) * dtype_bytes}


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
    group is empty is never read). At the even share, 256 rows an expert of
    width 512, the "in" call is memory bound."""
    k, n = gmm_dims(fields, kind)
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((k * n if rows > 0 else 0) + rows * k + rows * n) * dtype_bytes}
