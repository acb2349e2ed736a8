"""Rotary position embeddings, shard-aware.

The reference computes RoPE with CP/SP-aware position offsets so each rank
rotates by its *global* positions (models/llama_hf/LlamaModel_tensor_parallel.py:49-76,
zigzag CP offsets :16-39). Under GSPMD we instead pass the full `positions`
array (B, S) through the same shardings as the tokens — each shard then holds
exactly its global positions, including zigzag CP layouts, with no
rank-arithmetic in model code."""

import math

import jax.numpy as jnp

# the numbers a yarn scaling states beside `rope_type` (HF `rope_parameters`); theta and the
# rotary share are the model's own fields
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "attention_factor")


def checked_scaling(scaling):
    """`scaling` as `rope_frequencies` takes it, or a ValueError that names
    what it has no form of: None, or a mapping whose `rope_type` is "yarn" with
    yarn's numbers (`YARN_KEYS`) and nothing else. A raw DeepSeek-style mapping
    (`type`, `mscale`, `mscale_all_dim`) is refused with where to map it."""
    if scaling is None:
        return None
    kind = scaling.get("rope_type")
    if kind is None and "type" in scaling:
        # DeepSeek's spelling (`type`, `mscale`, `mscale_all_dim`) is a family file's to map: `mscale_all_dim`
        # scales the SOFTMAX, which is no number of the rotation's
        raise ValueError("rope_scaling states type=%r in DeepSeek's spelling (type, mscale, mscale_all_dim): a family "
                         "file maps it onto rope_type, %s and the config's attention_multiplier "
                         "(models/xing4.yarn_from_deepseek)" % (scaling["type"], ", ".join(YARN_KEYS)))
    if kind != "yarn":
        raise ValueError("rope_scaling rope_type=%r has no form here: ops/rope.py knows \"yarn\" "
                         "(and no scaling at all, rope_scaling=None)" % (kind,))
    unknown, missing = sorted(set(scaling) - set(YARN_KEYS) - {"rope_type"}), sorted(set(YARN_KEYS) - set(scaling))
    if unknown or missing:
        raise ValueError("a yarn rope_scaling states %s; missing %r, not modelled %r"
                         % (", ".join(YARN_KEYS), missing, unknown))
    return scaling


def rope_frequencies(head_dim: int, theta: float = 10000.0, scaling=None):
    """Inverse frequencies, shape (head_dim//2,). `scaling`: yarn
    (arXiv:2309.00071, as HF's `_compute_yarn_parameters`): frequency i is
    `theta^(-2i/d)` where it turns more than `beta_fast` times over the
    original context, that divided by `factor` where it turns fewer than
    `beta_slow` times, and a linear ramp between the two dims in between
    (floor and ceil of the correction dims)."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (theta ** exponents)
    if checked_scaling(scaling) is None:
        return inv_freq

    def correction_dim(turns):
        return (head_dim * math.log(scaling["original_max_position_embeddings"] / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001  # HF's guard against a ramp of no width
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    return inv_freq * (1.0 - ramp) + inv_freq / scaling["factor"] * ramp


def apply_rotary(x, positions, theta: float = 10000.0, interleaved: bool = False,
                 rotary_dim=None, scaling=None):
    """Rotate (B, S, n_heads, head_dim) by per-token positions (B, S).

    `interleaved=False` is the HF/LLaMA half-split convention
    (rotate_half); `interleaved=True` pairs adjacent dims (GPT-NeoX style).
    fp32 math, result cast back to x.dtype. `rotary_dim` (HF's
    `partial_rotary_factor` x head_dim): the leading `rotary_dim` dims of a
    head are rotated, at the frequencies of a head of that size, and the
    rest pass as they are. `scaling` (yarn, `rope_frequencies`): the scaled
    frequencies, and cos and sin x its `attention_factor`, so the turned dims
    alone carry that factor."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = apply_rotary(x[..., :rotary_dim], positions, theta, interleaved, scaling=scaling)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    dtype = x.dtype
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta, scaling)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        cos, sin = cos * scaling["attention_factor"], sin * scaling["attention_factor"]
    x32 = x.astype(jnp.float32)
    if interleaved:
        x1 = x32[..., 0::2]
        x2 = x32[..., 1::2]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    else:
        x1 = x32[..., : head_dim // 2]
        x2 = x32[..., head_dim // 2 :]
        r1 = x1 * cos - x2 * sin
        r2 = x2 * cos + x1 * sin
        out = jnp.concatenate([r1, r2], axis=-1)
    return out.astype(dtype)


def half_split_tables(positions, head_dim: int, theta: float = 10000.0, interleaved: bool = False,
                      rotary_dim=None, scaling=None):
    """`apply_rotary(x, positions, theta, ...)` of heads `head_dim` wide as two
    float32 (B, S, head_dim) tables, [cos | cos] and [-sin | sin], under which
    it reads `x * cos + roll(x, head_dim / 2) * sin` (what the window kernels
    do to a block of q in VMEM: ops/window_attention.py), or None where the
    rotation is no such product of whole heads at the plain frequencies:
    interleaved pairs, a share of a head's dims, scaled frequencies."""
    if interleaved or rotary_dim not in (None, head_dim) or scaling is not None:
        return None
    angles = positions[..., None].astype(jnp.float32) * rope_frequencies(head_dim, theta)  # (B, S, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)
