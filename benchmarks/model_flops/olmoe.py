"""OLMoE's model FLOPs a token, and the grouped matmul's operations and
bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted), with the block's MLP half replaced by what a
routed token costs: the experts it is SENT to (`experts_per_token` SwiGLU
MLPs of width `ffn_hidden`), not the experts held, plus the router's matmul.
At the published widths and 4096 tokens, forward: projections 33.6 M, scores
16.8 M, experts 100.7 M, router 0.26 M a layer, head 206.0 M.
`tests/benchmarks/test_flops.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def layer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> float:
    hidden, heads, hd = fields["hidden_size"], fields["num_heads"], fields["head_dim"]
    nkv = fields.get("num_kv_heads") or heads
    q_dim = heads * hd
    proj = 2.0 * hidden * q_dim + 2.0 * hidden * (2 * nkv * hd) + 2.0 * q_dim * hidden
    scores = 2.0 * (2.0 * seq_len * q_dim) * 0.5  # causal
    expert = 2.0 * hidden * (2 * fields["ffn_hidden"]) + 2.0 * fields["ffn_hidden"] * hidden
    router = 2.0 * hidden * fields["num_experts"]
    return proj + scores + fields["experts_per_token"] * expert + router


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    fwd = (fields["num_layers"] * layer_fwd_flops_a_token(fields, seq_len)
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------- the grouped matmul
# One call multiplies `rows` rows, sorted by expert, by the kernel of each
# row's expert: (rows, K) x (E, K, N) -> (rows, N). The block makes two kinds
# of call a pass: "in" (K = hidden, N = 2 x width: gate and up) and "out"
# (K = width, N = hidden). The backward's two calls a kind do the same
# multiply-adds over the same three operands with another one as the result
# (the rows' gradient: (rows, N) x (E, K, N)^T; the kernels': (rows, K)^T x
# (rows, N) a group), so one cost serves a kind's four calls.
def gmm_dims(fields: Mapping, kind: str):
    hidden, width = fields["hidden_size"], fields["ffn_hidden"]
    return {"in": (hidden, 2 * width), "out": (width, hidden)}[kind]


def gmm_cost(fields: Mapping, kind: str, tokens: int, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE grouped-matmul call of this kind over one
    device's `tokens` tokens. Rows actually sent: tokens x experts a token,
    exact under dropless dispatch whatever the routing. Bytes: every held
    expert's kernel once, the rows in and the rows out, in the compute dtype."""
    k, n = gmm_dims(fields, kind)
    rows = tokens * fields["experts_per_token"]
    return {"flops": 2.0 * rows * k * n,
            "bytes": float(fields["num_experts"] * k * n + rows * k + rows * n) * dtype_bytes}
