"""The window kernels on their operands AS PROJECTED (PR 50: a head a block of lanes, q turned in the kernel from
two tables, the head's gate in the epilogue), interpreted, against XLA's band between rope and gate."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests.ops.attention_operands import _rand_qkv

pytestmark = [pytest.mark.parallel]


# --------------------------------- the window kernels read q as projected (PR 50)
def _as_projected_cases():
    """(window, block) pairs the kernels have a form of at 768 tokens (a window
    of 512 at 128-token blocks would need four blocks before a step's own),
    each with and without a group, the gate and the rope in the kernel: all
    eight at a window of 512, and each of the three factors both ways at the
    other windows."""
    every = [(g, gate, rope) for g in (1, 8) for gate in (False, True) for rope in (False, True)]
    some = [(8, True, True), (1, False, True), (8, True, False), (1, True, True), (8, False, False)]
    return ([(512, 256) + c for c in every] + [(128, 128) + c for c in some] + [(128, 256) + c for c in some[:3]]
            + [(640, 256) + c for c in some])


@pytest.mark.parametrize("window,block,group,gate,rope", _as_projected_cases())
def test_the_as_projected_window_kernels_are_xlas_band_between_rope_and_gate(window, block, group, gate, rope):
    """`ops/window_attention.py` on operands as the projections wrote them
    ((batch, seq, heads x 128), interpret mode), q unturned with its tables
    (`rope`) or turned before the call, the head's gate in the epilogue (`gate`)
    or multiplied after the call: output and the gradients of q, k, v and the
    gate logits against `apply_rotary`, the band mask on XLA's logits and the
    gate's product, float32, rows at positions that differ by row."""
    import jax.experimental.pallas.tpu as pltpu

    from galvatron_tpu.ops import attention as A
    from galvatron_tpu.ops import rope as R
    from galvatron_tpu.ops import window_attention as W

    b, s, nkv, hd, scale = 2, 768, 1, 128, 0.05
    q, k, v = _rand_qkv(jax.random.PRNGKey(window + group), b=b, s=s, nh=nkv * group, nkv=nkv, hd=hd)
    logits = jax.random.normal(jax.random.PRNGKey(3), (b, s, nkv * group))
    positions = jnp.arange(s)[None] + jnp.array([[0], [11]])
    assert W.block_for(s, window, block) == block

    def grads(kernel):
        def f(q, k, v, logits):
            k = R.apply_rotary(k, positions)
            if not kernel:
                out = A.core_attention(R.apply_rotary(q, positions), k, v, window=window, sm_scale=scale, impl="xla")
                out = out * jax.nn.sigmoid(logits)[..., None]
                return jnp.sum(jnp.sin(out)), out
            tables = R.half_split_tables(positions, hd) if rope else None
            q = q if rope else R.apply_rotary(q, positions)
            out = W.window_attention(*(t.reshape(b, s, -1) for t in (q, k, v)), tables, logits if gate else None,
                                     window, scale, block, hd).reshape(q.shape)
            out = out if gate else out * jax.nn.sigmoid(logits)[..., None]
            return jnp.sum(jnp.sin(out)), out
        return jax.grad(f, argnums=(0, 1, 2, 3), has_aux=True)(q, k, v, logits)

    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got, want = grads(True), grads(False)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=3e-5)
