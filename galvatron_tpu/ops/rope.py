"""Rotary position embeddings, shard-aware.

The reference computes RoPE with CP/SP-aware position offsets so each rank
rotates by its *global* positions (models/llama_hf/LlamaModel_tensor_parallel.py:49-76,
zigzag CP offsets :16-39). Under GSPMD we instead pass the full `positions`
array (B, S) through the same shardings as the tokens — each shard then holds
exactly its global positions, including zigzag CP layouts, with no
rank-arithmetic in model code."""

import jax.numpy as jnp


def rope_frequencies(head_dim: int, theta: float = 10000.0):
    """Inverse frequencies, shape (head_dim//2,)."""
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponents)


def apply_rotary(x, positions, theta: float = 10000.0, interleaved: bool = False,
                 rotary_dim=None):
    """Rotate (B, S, n_heads, head_dim) by per-token positions (B, S).

    `interleaved=False` is the HF/LLaMA half-split convention
    (rotate_half); `interleaved=True` pairs adjacent dims (GPT-NeoX style).
    fp32 math, result cast back to x.dtype. `rotary_dim` (HF's
    `partial_rotary_factor` x head_dim): the leading `rotary_dim` dims of a
    head are rotated, at the frequencies of a head of that size, and the
    rest pass as they are."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = apply_rotary(x[..., :rotary_dim], positions, theta, interleaved)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    dtype = x.dtype
    head_dim = x.shape[-1]
    inv_freq = rope_frequencies(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # (B,S,hd/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
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
