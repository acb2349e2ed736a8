"""GLM-4.7-Flash's model FLOPs a token, and the held experts' grouped
matmul's operations and bytes for its roofline.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). What is counted, forward, a token:

- latent attention's five projections by their shapes: hidden -> q_lora ->
  heads x (nope + rope); hidden -> kv_lora + rope; kv_lora -> heads x (nope +
  v); heads x v -> hidden; and the core, q k^T at nope + rope and p v at v
  dims a head, the causal half once;
- the leading dense layers' SwiGLU at `dense_ffn_hidden`;
- a routed layer's shared expert(s), its router's matmul, and the routed
  experts at `experts_per_token` x `experts_held` / `num_experts` experts a
  token: the EVEN share of a token's experts that falls to the experts held
  here. A constant, whatever the routing, so `mfu` stays a fixed multiple of
  the rate; the rows a step really sends are the counter `expert_rows_held`;
- the multi-token-prediction module: the (2 hidden, hidden) projection, one
  more routed block, and the head a second time; the head once for the model.

At the published widths, 8 of 64 experts, 19360 vocabulary rows, 1 + 4 layers
and 8192 tokens, forward GFLOP a token: a block's attention core 0.0839 and
projections 0.0435 (six blocks), the dense MLP 0.1258, a shared expert 0.0189
and the held routed share 0.0094 (five blocks), `Weh` 0.0168, the head 0.0793
twice: 1.208 in all, 3.62 with the backward.
`tests/benchmarks/test_flops.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def attention_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, heads = fields["hidden_size"], fields["num_heads"]
    ql, kvl = fields["q_lora_rank"], fields["kv_lora_rank"]
    nope, rope, v = fields["qk_nope_head_dim"], fields["qk_rope_head_dim"], fields["v_head_dim"]
    proj = (2.0 * hidden * ql + 2.0 * ql * heads * (nope + rope)
            + 2.0 * hidden * (kvl + rope) + 2.0 * kvl * heads * (nope + v)
            + 2.0 * heads * v * hidden)
    core = 2.0 * seq_len * heads * ((nope + rope) + v) * 0.5  # causal
    return {"projections": proj, "core": core}


def swiglu_fwd_flops_a_token(hidden: int, width: int) -> float:
    return 2.0 * hidden * (2 * width) + 2.0 * width * hidden


def block_fwd_flops_a_token(fields: Mapping, seq_len: int, routed: bool) -> float:
    hidden = fields["hidden_size"]
    attn = sum(attention_fwd_flops_a_token(fields, seq_len).values())
    if not routed:
        return attn + swiglu_fwd_flops_a_token(hidden, fields["dense_ffn_hidden"])
    expert = swiglu_fwd_flops_a_token(hidden, fields["ffn_hidden"])
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return (attn + (sent_here + fields["num_shared_experts"]) * expert
            + 2.0 * hidden * fields["num_experts"])


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    hidden = fields["hidden_size"]
    dense = min(fields["first_dense_layers"], fields["num_layers"])
    routed = fields["num_layers"] - dense
    head = 2.0 * hidden * fields["vocab_size"]
    fwd = (block_fwd_flops_a_token(fields, seq_len, False) * dense
           + block_fwd_flops_a_token(fields, seq_len, True) * routed + head)
    if fields["mtp_layers"]:
        fwd += head + 2.0 * (2 * hidden) * hidden + block_fwd_flops_a_token(fields, seq_len, True)
    return fwd * (1.0 + BWD_FWD_RATIO)


def routed_blocks(fields: Mapping) -> int:
    """Routed blocks a step runs: the stack's and the MTP module's."""
    dense = min(fields["first_dense_layers"], fields["num_layers"])
    return fields["num_layers"] - dense + fields["mtp_layers"]


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
    any routing of that many rows must move. The counter says how many rows
    the held experts got and not which of them got any; a kernel whose group
    is empty is never read (megablox visits no tile of it), and once the
    routing has collapsed (PERF.md section 6, PR 32) most held groups are
    empty, so counting every held kernel would put the least time above the
    time taken. At the even share the call is compute bound either way."""
    k, n = gmm_dims(fields, kind)
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((k * n if rows > 0 else 0) + rows * k + rows * n) * dtype_bytes}
