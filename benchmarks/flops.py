"""The benchmark's own arithmetic: model FLOPs a token, MFU, and the flash
kernels' operations and bytes from their shapes.

The model FLOPs are a copy of galvatron_tpu/obs/flops.py's (PaLM appendix-B
convention: matmul terms only, 2 FLOPs a multiply-add, the causal half of the
score matrix counted once, backward = 2 x forward, recomputation NOT counted),
kept here so that a later PR cannot move the yardstick. `fields` is the
configuration as the program is given it (benchmarks/cells.config_fields):
hidden_size, num_heads, num_kv_heads, head_dim, ffn_hidden, num_layers,
vocab_size, activation, causal. There is no CPU row and no override: the peak
comes from benchmarks/peaks.json or the run is refused.
"""

from __future__ import annotations

from typing import Dict, Mapping, Tuple

BWD_FWD_RATIO = 2.0


def _sizes(fields: Mapping) -> Tuple[int, int, int, int, int]:
    hidden = fields["hidden_size"]
    heads = fields["num_heads"]
    hd = fields.get("head_dim") or hidden // heads
    nkv = fields.get("num_kv_heads") or heads
    ffn = fields.get("ffn_hidden") or 4 * hidden
    return hidden, heads, hd, nkv, ffn


def layer_fwd_flops_a_token(fields: Mapping, seq_len: int) -> float:
    hidden, heads, hd, nkv, ffn = _sizes(fields)
    q_dim = heads * hd
    proj = 2.0 * hidden * q_dim + 2.0 * hidden * (2 * nkv * hd) + 2.0 * q_dim * hidden
    attn = 2.0 * (2.0 * seq_len * q_dim) * (0.5 if fields.get("causal", True) else 1.0)
    if fields.get("activation") == "swiglu":
        mlp = 2.0 * hidden * (2 * ffn) + 2.0 * ffn * hidden
    else:
        mlp = 2.0 * hidden * ffn + 2.0 * ffn * hidden
    return proj + attn + mlp


def head_fwd_flops_a_token(fields: Mapping) -> float:
    return 2.0 * fields["hidden_size"] * fields["vocab_size"]


def train_flops_a_token(fields: Mapping, seq_len: int) -> float:
    """Forward + backward model FLOPs a token at this sequence length."""
    fwd = (fields["num_layers"] * layer_fwd_flops_a_token(fields, seq_len)
           + head_fwd_flops_a_token(fields))
    return fwd * (1.0 + BWD_FWD_RATIO)


def mfu_pct(tokens_per_s_chip: float, flops_a_token: float, peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s_chip * flops_a_token / peak_flops_per_s


# ------------------------------------------------------------ flash kernels
# jax.experimental.pallas.ops.tpu.flash_attention, as ops/attention.py calls
# it: one forward kernel and two backward kernels, each on (batch, heads, seq,
# head_dim) operands in the compute dtype, K and V already expanded to the
# query heads. Matmuls of S x S x head_dim a (batch, head), as each kernel
# does them:
#   forward  q k^T, p v                                   2
#   dkv      q k^T again, p^T do, do v^T, ds^T q          4
#   dq       q k^T again, do v^T, ds k                    3
# The causal half is counted once (what the model needs), although the
# kernels run whole blocks on the diagonal.
# Beside jax's three kernels stands the MODEL's form, for a kernel of another
# cut (`gt.attn.core`, benchmarks/layer_metrics/flash_ms.py): what attention
# needs whatever implements it, one forward 2 (q k^T, p v) and one backward 5
# (the scores again, dP = do v^T, dV = p^T do, dQ = ds k, dK = ds^T q). jax's
# two backward kernels make the scores and dP twice, 7: a kernel that does
# less than they do cannot pass 100 % on the model's count.
FLASH_KERNEL_MATMULS = {"fwd": 2, "dkv": 4, "dq": 3, "core_fwd": 2, "core_bwd": 5}
# what the algorithm has to read and write, in (batch, heads, seq, head_dim)
# tensors of the compute dtype: forward reads q k v and writes o; dkv reads
# q k v do and writes dk dv; dq reads q k v do and writes dq. The per-row
# softmax statistics (l, m, di) are one float a row to the algorithm and are
# left out; the kernel as written moves them broadcast over 128 or 512 lanes
# (fp32[b,h,s,128]: as many bytes again as q k v o), which is its own cost,
# not the algorithm's
# the model's backward reads q k v o do and writes dq dk dv
FLASH_KERNEL_TENSORS = {"fwd": 4, "dkv": 6, "dq": 5, "core_fwd": 4, "core_bwd": 8}


def flash_kernel_cost(kind: str, batch: int, heads: int, seq: int, head_dim: int,
                      causal: bool = True, dtype_bytes: int = 2) -> Dict[str, float]:
    """FLOPs and HBM bytes of ONE call of a flash kernel at these shapes
    (`core_fwd`, `core_bwd`: of one forward or backward of one layer's
    attention over `batch` rows)."""
    flops = (FLASH_KERNEL_MATMULS[kind] * 2.0 * batch * heads * seq * seq * head_dim
             * (0.5 if causal else 1.0))
    nbytes = FLASH_KERNEL_TENSORS[kind] * float(batch * heads * seq * head_dim) * dtype_bytes
    return {"flops": flops, "bytes": nbytes}


def least_time_s(cost: Mapping[str, float], peak: Mapping[str, float]) -> Tuple[float, str]:
    """The least time the chip could take for `cost`, and which bound holds."""
    by_flops = cost["flops"] / peak["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / peak["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes, "memory")
