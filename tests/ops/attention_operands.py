"""What the attention tests share (tests/ops/test_attention.py, test_ring_attention.py, test_window_attention.py,
test_window_as_projected.py): their random operands."""

import jax
import jax.numpy as jnp


def _rand_qkv(rng, b=2, s=32, nh=4, nkv=None, hd=16):
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, s, nh, hd), jnp.float32)
    k = jax.random.normal(kk, (b, s, nkv or nh, hd), jnp.float32)
    v = jax.random.normal(kv, (b, s, nkv or nh, hd), jnp.float32)
    return q, k, v
