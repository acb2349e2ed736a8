"""EvaByte's model FLOPs a token, and the EVA aggregation's least operations
and bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, backward = 2 x forward, recomputation not counted). What is
counted, forward, a token, a layer:

- the mixer's projections: q, k, v and out, `num_heads x head_dim` each way (as
  many key heads as query heads, no bias);
- **the aggregation as its mathematics needs it**: scores and weighted sum over
  the (query, key) pairs a query MEETS, counted EXACTLY (`eva_pairs`): query t of
  window `n = t // W` meets the `(t mod W) + 1` keys of its own window up to
  itself and the `n C` pooled keys of every earlier window, `C = W / chunk`;
  whatever blocks an implementation runs along the diagonal and whatever pooled
  keys it multiplies and masks, so a change of its form cannot move `mfu`;
- the pooling's two weighted sums, of a chunk's keys and of its values (2
  head_dim multiply-adds a position a head); the chunk's weights `<phi, k>` are a
  dot product a position on the vector unit and no matmul;
- the MLP half: a dense SwiGLU, hidden -> 2 x ffn and ffn -> hidden;

and once the head: hidden -> `pred_heads` x vocabulary columns, one matmul.

At the published widths, four layers and 8192 positions a query meets 1216.5
pairs on average (1024.5 of its window, 192 pooled), and forward MFLOP a token:
the MLP halves 1082.1 (62.9 %), the projections 536.9 (31.2 %), the aggregation
79.7 (4.6 %), the pooling 0.07, the head 21.0 (1.2 %): 1719.8 in all, 5.159 GFLOP
with the backward. `tests/benchmarks/test_flops.py` holds this count to the
program's own (`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def eva_pairs(seq_len: int, window: int, chunk: int) -> int:
    """The (query, key) pairs ONE head scores over one sequence, exactly:
    `sum_t [(t mod W) + 1 + (t // W) C]`, in closed form over `full` whole
    windows and a last one of `rest` positions."""
    full, rest = divmod(seq_len, window)
    own = full * (window * (window + 1) // 2) + rest * (rest + 1) // 2
    return own + (window // chunk) * (window * (full * (full - 1) // 2) + rest * full)


def q_dim(fields: Mapping) -> int:
    return fields["num_heads"] * fields["head_dim"]


def mixer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    pairs_a_token = eva_pairs(seq_len, fields["eva_window"], fields["eva_chunk"]) / float(seq_len)
    return {"projections": 2.0 * fields["hidden_size"] * q_dim(fields) * 4,
            "aggregation": 2.0 * (2.0 * pairs_a_token * q_dim(fields)),
            "pooling": 2.0 * (2.0 * q_dim(fields))}


def mlp_fwd_flops_a_token(fields: Mapping) -> float:
    hidden, ffn = fields["hidden_size"], fields["ffn_hidden"]
    return 2.0 * hidden * (2 * ffn) + 2.0 * ffn * hidden


def head_fwd_flops_a_token(fields: Mapping) -> float:
    return 2.0 * fields["hidden_size"] * (fields["vocab_size"] * fields["pred_heads"])


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    mixer = mixer_fwd_flops_a_token(fields, seq_len)
    layer = mixer["projections"] + (mixer["aggregation"] + mixer["pooling"]) + mlp_fwd_flops_a_token(fields)
    return (fields["num_layers"] * layer + head_fwd_flops_a_token(fields)) * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------ the aggregation
# One EVA layer's aggregation over `tokens` tokens in sequences of `seq_len`
# (ops/eva_attention.py `aggregate`; scope `gt.attn.eva_agg`). The floor ANY
# implementation must meet, kernels or XLA: over the exact pairs the two products
# of a forward (q k^T, p v) and the five of a backward (the scores again, dP = do
# v^T, dV = p^T do, dQ = ds k, dK = ds^T q), and each operand and result moved
# once in the compute dtype: forward reads q, k, v, K~, V~ and writes the output;
# backward reads those six and the output's cotangent and writes the five
# gradients. The row statistics are a float or two a row and are left out. Whole
# blocks along the diagonal, pooled keys multiplied and masked, a recomputed
# forward and the statistics' traffic are the implementation's own cost, so the
# share of this floor cannot pass 100 %.
MATMULS = {"fwd": 2, "bwd": 5}
TENSORS = {"fwd": (4, 2), "bwd": (8, 4)}  # (arrays of a row a token, arrays of a row a chunk) moved


def eva_cost(fields: Mapping, tokens: float, which: str, seq_len: int = 0, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's
    aggregation over `tokens` tokens, in sequences of `seq_len` (0: one sequence)."""
    seq_len = int(seq_len or tokens)
    pairs = eva_pairs(seq_len, fields["eva_window"], fields["eva_chunk"]) * (tokens / seq_len)
    a_token, a_chunk = TENSORS[which]
    return {"flops": MATMULS[which] * 2.0 * pairs * q_dim(fields),
            "bytes": (a_token + a_chunk / float(fields["eva_chunk"])) * tokens * q_dim(fields) * dtype_bytes}
