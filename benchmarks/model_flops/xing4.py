"""Xing4.0-29B-A4B's model FLOPs a token, and the floors of its cell's two roofline readers: the
hyper-connections by their least BYTES, and the attention call by the model's own products.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a multiply-add, the causal half of the
scores once, backward = 2 x forward, recomputation not counted). What is counted, forward, a token:

- latent attention's five projections by their shapes (hidden -> q_lora -> heads x (nope + rope); hidden ->
  kv_lora + rope; kv_lora -> heads x (nope + v); heads x v -> hidden) and the core, q k^T at nope + rope = 192
  and p v at v = 128 dims a head over the causal half: the MODEL's products, not the 256 the one padded call runs;
- the leading dense layers' SwiGLU at `dense_ffn_hidden`; a routed layer's shared expert(s), its router's
  matmul, and the routed experts at `experts_per_token` x `experts_held` / `num_experts` experts a token (the
  EVEN share of a token's experts that falls to the experts held here: a constant, whatever the routing);
- hyper-connections around every layer (`hc_mult` = n streams, two halves a layer): a half's coefficients
  `x~ Phi` 2 n hidden (n^2 + 2n), its read 2 n hidden, its write 2 n^2 hidden + 2 n hidden; the Sinkhorn steps
  and the sigmoids are no matmul;
- the head once. No multi-token-prediction module (`mtp_layers` 0: refused beside hyper-connections).

At the published widths, 8 of 64 experts, 16384 vocabulary rows, 1 + 4 layers and 4096 tokens, forward MFLOP a
token: MLA's projections 56.8 a layer (29.8 % of the model), its cores 41.9 (22.0 %), the dense SwiGLU 198.2
(20.8 %), the head 117.4 (12.3 %), a shared expert 22.0 (9.3 %), the held experts' even share 11.0 (4.6 %), a
router 0.46, the hyper-connections 1.72 a layer (0.9 %): 952.0 in all, 2.856 GFLOP with the backward.
`tests/benchmarks/test_flops.py` holds this count to the program's own (`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def attention_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    hidden, heads = fields["hidden_size"], fields["num_heads"]
    ql, kvl = fields["q_lora_rank"], fields["kv_lora_rank"]
    nope, rope, v = fields["qk_nope_head_dim"], fields["qk_rope_head_dim"], fields["v_head_dim"]
    proj = (2.0 * hidden * ql + 2.0 * ql * heads * (nope + rope) + 2.0 * hidden * (kvl + rope)
            + 2.0 * kvl * heads * (nope + v) + 2.0 * heads * v * hidden)
    return {"projections": proj, "core": 2.0 * seq_len * heads * ((nope + rope) + v) * 0.5}  # causal


def swiglu_fwd_flops_a_token(hidden: int, width: int) -> float:
    return 2.0 * hidden * (2 * width) + 2.0 * width * hidden


def hyper_fwd_flops_a_token(fields: Mapping) -> Dict[str, float]:
    """Hyper-connections around ONE layer (two halves), by part; nothing for one stream."""
    n, hidden = fields.get("hc_mult", 1), fields["hidden_size"]
    if n <= 1:
        return {"coefficients": 0.0, "mix": 0.0}
    return {"coefficients": 2 * 2.0 * n * hidden * (n * n + 2 * n),
            "mix": 2 * (2.0 * n * hidden + 2.0 * n * n * hidden + 2.0 * n * hidden)}


def layers(fields: Mapping):
    """(leading dense layers, routed layers) of the stack as run."""
    dense = min(fields["first_dense_layers"], fields["num_layers"])
    return dense, fields["num_layers"] - dense


def fwd_flops_a_token_by_part(fields: Mapping, seq_len: int) -> Dict[str, float]:
    """Forward model FLOPs a token of the whole model, by part."""
    hidden = fields["hidden_size"]
    dense, routed = layers(fields)
    attn = attention_fwd_flops_a_token(fields, seq_len)
    expert = swiglu_fwd_flops_a_token(hidden, fields["ffn_hidden"])
    sent_here = fields["experts_per_token"] * fields["experts_held"] / fields["num_experts"]
    return {
        "mla_projections": fields["num_layers"] * attn["projections"],
        "mla_core": fields["num_layers"] * attn["core"],
        "dense_mlp": dense * swiglu_fwd_flops_a_token(hidden, fields["dense_ffn_hidden"]),
        "shared_experts": routed * fields["num_shared_experts"] * expert,
        "held_experts": routed * sent_here * expert,
        "router": routed * 2.0 * hidden * fields["num_experts"],
        "hyper": fields["num_layers"] * sum(hyper_fwd_flops_a_token(fields).values()),
        "head": 2.0 * hidden * fields["vocab_size"],
    }


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    if fields.get("mtp_layers"):
        raise ValueError("no count of a multi-token-prediction module beside hyper-connections")
    return sum(fwd_flops_a_token_by_part(fields, seq_len).values()) * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------ the readers' floors
def hc_cost(fields: Mapping, tokens: float, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and the LEAST HBM bytes of `which` ("fwd" | "remat" | "bwd") pass of ONE layer's hyper-connections
    (both halves, ALL of `gt.hc`: coefficients, Sinkhorn steps and mixes) over `tokens` tokens, whatever
    implements them and however it is fused, given only that the n-stream array (117 MB at 4096 tokens) does not
    stay on the chip across a half's body. Elements a token at the streams' dtype, X the n C streams, a row C wide:

    - a forward half: ONE pass reads X, makes the coefficients (they need all of a token's X, and the read needs
      them) and writes the row the body reads; after the body ONE pass reads X and the body's row and writes X':
      3 n C + 2 C;
    - a recomputed layer (`--checkpoint 1`): its first half whole (X between the halves is made again), of its
      second half the first pass alone (the body's row is made again; nothing reads the layer's output):
      (3 n C + 2 C) + (n C + C);
    - a backward half: before the body's backward ONE pass reads dX', X and the body's row and writes the row's
      cotangent (dH_post and dH_res are sums inside it); after it ONE pass reads dX' and X again with the read
      row's cotangent (dH_pre = du . X, so the coefficients' own cotangent exists only now) and writes dX:
      5 n C + 3 C;
    - and, each pass that makes coefficients, Phi once (float32).

    The coefficients' FLOPs are 24 a stream element read; memory bound throughout."""
    n, c = fields["hc_mult"], fields["hidden_size"]
    wide, row = n * c, c
    phi = 4.0 * wide * (n * n + 2 * n)
    half = sum(hyper_fwd_flops_a_token(fields).values()) / 2  # one half's, forward
    elements = {"fwd": 2 * (3 * wide + 2 * row), "remat": (3 * wide + 2 * row) + (wide + row),
                "bwd": 2 * (5 * wide + 3 * row)}[which]
    return {"flops": tokens * 2 * half * (BWD_FWD_RATIO if which == "bwd" else 1.0),
            "bytes": float(tokens * elements * dtype_bytes) + 2 * phi}


def attn_layers(fields: Mapping) -> int:
    """Layers that run the attention call `attn_cost` prices: every one."""
    return fields["num_layers"]


# ONE layer's causal attention over `rows` rows of `seq_len` as the MODEL needs it, whatever call implements
# it (the program pads q, k and v to ONE call at 256 and jax's three flash kernels run 2 + 4 + 3 products at
# that width): a forward's two products, q k^T at nope + rope and p v at v dims, and a backward's five (the
# scores again, dQ and dK at nope + rope; dP and dV at v) over the causal half; forward reads q, k, v and writes
# o, backward reads q, k, v, o, do and writes dq, dk, dv, each once at its own width in the compute dtype
def attn_cost(fields: Mapping, rows: int, seq_len: int, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE layer's attention."""
    heads, qk, v = fields["num_heads"], fields["qk_nope_head_dim"] + fields["qk_rope_head_dim"], fields["v_head_dim"]
    dims = {"fwd": qk + v, "bwd": 3 * qk + 2 * v}[which]
    widths = {"fwd": 2 * qk + 2 * v, "bwd": 4 * qk + 4 * v}[which]
    return {"flops": 2.0 * rows * heads * seq_len * seq_len * dims * 0.5,
            "bytes": float(rows * seq_len * heads * widths) * dtype_bytes}
