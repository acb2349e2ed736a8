"""Ouro's (LoopLM's) model FLOPs a token, and what its readers price a step's attention kernels and SwiGLUs at.

The convention is `benchmarks/flops.py`'s (matmul terms only, 2 FLOPs a multiply-add, the causal half of
the score matrix counted once, backward = 2 x forward, recomputation not counted). **A looped stack applies
every layer `loop_steps` = T times a step over the same weights, and the head once a pass**: what is counted
is APPLICATIONS (`applications`: `num_layers` x T), not layers. Forward, a token, a layer application:

- the projections: q, k, v and out, `num_heads x head_dim` each way (as many key heads as query heads);
- the causal scores and weighted sum: 2 x 2 x S x q_dim, half of it under the mask;
- a dense SwiGLU: hidden -> 2 x ffn and ffn -> hidden;

and a pass: the head, hidden -> vocabulary columns. The sandwich norms, the norm between passes and the exit
gate (a dot product a position) are no matmul. The embedding's lookup runs once.

At the published widths, six layers, T = 4 and 4096 positions, forward MFLOP a token: an application 33.55
(projections) + 16.78 (scores) + 69.21 (SwiGLU) = 119.54, x 24 = 2868.9; the head 201.33 x 4 = 805.3; 3674.2
in all, 11.02 GFLOP with the backward: SwiGLUs 45.2 %, projections 21.9 %, scores 11.0 %, the four heads
21.9 %. `tests/benchmarks/test_ouro_cell.py` holds this count to the program's own
(`galvatron_tpu/obs/flops.py`) to 1e-12.
"""

from __future__ import annotations

from typing import Dict, Mapping

BWD_FWD_RATIO = 2.0


def applications(fields: Mapping) -> int:
    """Layer applications a step: every layer once a pass."""
    return fields["num_layers"] * fields.get("loop_steps", 1)


def q_dim(fields: Mapping) -> int:
    return fields["num_heads"] * fields["head_dim"]


def layer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> Dict[str, float]:
    """ONE application of a layer, by part."""
    hidden, ffn = fields["hidden_size"], fields["ffn_hidden"]
    kv_dim = fields["num_kv_heads"] * fields["head_dim"]
    return {"projections": 2.0 * hidden * q_dim(fields) * 2 + 2.0 * hidden * kv_dim * 2,
            "scores": 2.0 * (2.0 * seq_len * q_dim(fields)) * 0.5,
            "mlp": 2.0 * hidden * (2 * ffn) + 2.0 * ffn * hidden}


def head_fwd_flops_a_token(fields: Mapping) -> float:
    """ONE pass's head."""
    return 2.0 * fields["hidden_size"] * fields["vocab_size"]


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length: every application and every pass's head."""
    layer = sum(layer_fwd_flops_a_token(fields, seq_len).values())
    fwd = applications(fields) * layer + fields.get("loop_steps", 1) * head_fwd_flops_a_token(fields)
    return fwd * (1.0 + BWD_FWD_RATIO)


# ------------------------------------------------------------ the readers' floors
# ONE application's causal attention over `rows` rows of `seq_len` (the kernels under `gt.attn.core`), as the
# MODEL needs it whatever implements it (`benchmarks/flops.py` FLASH_KERNEL_*'s "core" rows): a forward's two
# products (q k^T, p v) and a backward's five (the scores again, dP, dV, dQ, dK) over the causal half; forward
# reads q, k, v and writes o, backward reads q, k, v, o, do and writes dq, dk, dv, each once in the compute dtype
ATTN_MATMULS = {"fwd": 2, "bwd": 5}
ATTN_TENSORS = {"fwd": 4, "bwd": 8}


def attn_cost(fields: Mapping, rows: int, seq_len: int, which: str, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of `which` ("fwd" | "bwd") pass of ONE application's attention."""
    return {"flops": ATTN_MATMULS[which] * 2.0 * rows * q_dim(fields) * seq_len * seq_len * 0.5,
            "bytes": ATTN_TENSORS[which] * float(rows * seq_len * q_dim(fields)) * dtype_bytes}


def mlp_train_flops(fields: Mapping, tokens: float) -> float:
    """Forward + backward FLOPs of the SwiGLUs' matmuls of ALL applications of a step over `tokens` tokens."""
    fwd = 3 * 2.0 * fields["hidden_size"] * fields["ffn_hidden"]
    return applications(fields) * tokens * fwd * (1.0 + BWD_FWD_RATIO)
