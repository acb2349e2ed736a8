"""Nemotron-H's model FLOPs a token, counted BY BLOCKS OF ONE HALF, and the
least operations and bytes of its two kernel-level parts, the grouped Mamba-2
scan and the held experts' two-matrix grouped matmuls, for their rooflines.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a
multiply-add, the causal half of the scores once, backward = 2 x forward,
recomputation not counted). A published block is ONE of three kinds
(`layer_types` / `mlp_types`, a block an entry, cut to the blocks run), and
what is counted, forward, a token, a block:

- an `M` block ("mamba" in `layer_types`): hidden -> [z | x | B | C | dt] (2 x
  inner + 2 x groups x d_state + heads columns, inner = heads x d_head: B and C
  a GROUP), inner -> hidden; and **the scan as the recurrence needs it: two
  (d_head, d_state) products a head a token** (`4 d_head d_state`), whatever
  chunk and whatever batch of heads an implementation cuts it into. The
  convolution's four taps a channel, the decay, the gate, the group norm and
  the D skip are no matmul;
- a `*` block ("attention"): q and out on the query heads, k and v on the KV
  heads; q k^T and p v, the causal half once;
- an `E` block ("routed" in `mlp_types`): the router's matmul over ALL the
  experts, the shared expert (`shared_expert_ffn` wide, TWO matrices: up and
  down, no gate), and the routed experts, two matrices each, at
  `experts_per_token` x `experts_held` / `num_experts` experts a token: the EVEN
  share of a token's experts that falls to the experts held here. A constant,
  whatever the routing, so `mfu` stays a fixed multiple of the rate; the rows a
  step really sends are the counter `expert_rows_held`;
- the untied head once.

At the published widths, 8 of 128 experts, 16384 vocabulary rows, the first
nine blocks (MEMEM*EME) and 8192 tokens, forward MFLOP a token: an `M` block
55.39 + 22.02 of projections + 2.10 of scan = 79.51 (four: 318.05, 44.6 %); an
`E` block 0.69 router + 39.91 shared + 0.375 x 19.96 held = 48.08 (four:
192.33, 27.0 %); the `*` block 46.79 + 67.11 of scores = 113.90 (16.0 %); the
head 88.08 (12.4 %): 712.36 in all, 2.137 GFLOP with the backward.
`tests/benchmarks/test_flops.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

BWD_FWD_RATIO = 2.0


def blocks(fields: Mapping) -> Tuple[Tuple[str, str], ...]:
    """(mixer, MLP half) of each block run: the two lists cut to `num_layers`."""
    n = fields["num_layers"]
    return tuple(zip(fields["layer_types"][:n], fields["mlp_types"][:n]))


def ssm_mixer_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    hidden, heads = fields["hidden_size"], fields["ssm_num_heads"]
    inner, state = heads * fields["ssm_head_dim"], fields["ssm_state_dim"]
    proj = 2.0 * hidden * (2 * inner + 2 * fields["ssm_groups"] * state + heads) + 2.0 * inner * hidden
    return {"projections": proj, "core": 4.0 * heads * fields["ssm_head_dim"] * state}


def attention_mixer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, hd = fields["hidden_size"], fields["head_dim"]
    q_dim, kv_dim = fields["num_heads"] * hd, fields["num_kv_heads"] * hd
    proj = 2.0 * hidden * q_dim + 2.0 * hidden * (2 * kv_dim) + 2.0 * q_dim * hidden
    return {"projections": proj, "core": 2.0 * (2.0 * seq_len * q_dim) * 0.5}  # causal


def two_matrix_mlp_fwd_flops_a_token(hidden: int, width: int) -> float:
    """down(relu(up x)^2): hidden -> width -> hidden, no gate."""
    return 2.0 * hidden * width + 2.0 * width * hidden


def routed_block_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    hidden = fields["hidden_size"]
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return {"router": 2.0 * hidden * fields["num_experts"],
            "shared": two_matrix_mlp_fwd_flops_a_token(hidden, fields["shared_expert_ffn"]),
            "held": sent_here * two_matrix_mlp_fwd_flops_a_token(hidden, fields["ffn_hidden"])}


def ssm_layers(fields: Mapping) -> int:
    """Blocks whose mixer is a state-space one."""
    return sum(mixer == "mamba" for mixer, _ in blocks(fields))


def routed_blocks(fields: Mapping) -> int:
    """Routed blocks a step runs (the model has no MTP module)."""
    return sum(mlp == "routed" for _, mlp in blocks(fields))


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    kinds = blocks(fields)
    attention = sum(mixer == "attention" for mixer, _ in kinds)
    fwd = (ssm_layers(fields) * sum(ssm_mixer_fwd_flops_a_token(fields).values())
           + attention * sum(attention_mixer_fwd_flops_a_token(fields, seq_len).values())
           + routed_blocks(fields) * sum(routed_block_fwd_flops_a_token(fields).values())
           + 2.0 * fields["hidden_size"] * fields["vocab_size"])
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------ the Mamba-2 scan
# One `M` block's scan over `tokens` tokens (ops/ssd.py `ssd_scan`; scope
# `gt.attn.ssd`). The floor ANY implementation must meet, chunked or not,
# whatever heads it works at once: the recurrence's two products a head a token
# forward and twice that backward, and each operand and result moved once: x
# and y on the heads and B and C BY GROUP (`ssm_groups` x d_state columns
# each) in the compute dtype, dt one float32 a head; the backward reads those
# and y's cotangent and writes the four gradients (A's and D's are a float a
# head). The chunks' decay masks, the C B^T products a group, the states kept
# a chunk and a recomputed forward are the implementation's own cost, so the
# share of this floor cannot pass 100 %.
def ssd_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE block's scan."""
    heads, hd, state = fields["ssm_num_heads"], fields["ssm_head_dim"], fields["ssm_state_dim"]
    xbc = (heads * hd + 2 * fields["ssm_groups"] * state) * dtype_bytes  # x, B, C a token
    y = heads * hd * dtype_bytes
    dt = heads * 4  # float32
    fwd = {"flops": 4.0 * heads * hd * state * tokens, "bytes": float(xbc + dt + y) * tokens}
    if which == "fwd":
        return fwd
    return {"flops": BWD_FWD_RATIO * fwd["flops"],
            "bytes": float(xbc + dt + y + xbc + dt) * tokens}


# ------------------------------------------------------- the grouped matmul
# One call multiplies the rows sent to the experts HELD here, sorted by
# expert, by the kernel of each row's expert: (rows, K) x (held, K, N) ->
# (rows, N); the other experts' rows are skipped (megablox's `group_offset`).
# TWO matrices an expert, so two kinds of call a pass: "in" (K = hidden, N =
# width: the up projection alone, no gate beside it) and "out" (K = width, N =
# hidden). The backward's two calls a kind do the same multiply-adds over the
# same three operands with another one as the result, so one cost serves a
# kind's four calls. The dims are the MODEL's (1856 wide): a tile's masked
# rest or a kernel padded with zeros is the implementation's own cost.
def gmm_dims(fields: Mapping, kind: str):
    hidden, width = fields["hidden_size"], fields["ffn_hidden"]
    return {"in": (hidden, width), "out": (width, hidden)}[kind]


def gmm_cost(fields: Mapping, kind: str, rows: float, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE grouped-matmul call of this kind over
    `rows` rows: the rows the held experts are really sent (the program's
    counter `expert_rows_held`, a block), not the even share. Bytes: the rows
    in, the rows out and ONE expert's kernel, in the compute dtype: the least
    any routing of that many rows must move (the counter says how many rows
    the held experts got and not which of them got any, and a kernel whose
    group is empty is never read)."""
    k, n = gmm_dims(fields, kind)
    return {"flops": 2.0 * rows * k * n,
            "bytes": float((k * n if rows > 0 else 0) + rows * k + rows * n) * dtype_bytes}
