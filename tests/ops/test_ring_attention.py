"""Ring / zigzag context parallelism (ops/ring_attention.py) on the virtual CPU mesh: the forward against dense
attention, and the hand-scheduled backward (`use_custom_vjp`) against autodiff through the unrolled ring."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.ops.attention import core_attention
from galvatron_tpu.ops.ring_attention import (
    inverse_permutation,
    ring_attention,
    zigzag_permutation,
)
from galvatron_tpu.parallel.mesh import LayerAxes
from tests.ops.attention_operands import _rand_qkv

pytestmark = [pytest.mark.parallel]


@pytest.mark.parametrize("zigzag", [False, True])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_dense(devices8, zigzag, causal):
    b, s, nh, hd = 2, 32, 4, 16
    cp = 4
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), b=b, s=s, nh=nh, hd=hd)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    dense = core_attention(q, k, v, causal=causal, impl="xla")

    if zigzag:
        idx = zigzag_permutation(s, cp)
        qp, kp, vp = q[:, idx], k[:, idx], v[:, idx]
        pos_p = positions[:, idx]
    else:
        qp, kp, vp, pos_p = q, k, v, positions

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("m0", "m1"))
    axes = LayerAxes(dp=("m0",), cp=("m1",), tp=())
    sharded = lambda t, spec: jax.device_put(t, NamedSharding(mesh, spec))
    out = ring_attention(
        sharded(qp, P("m0", "m1", None, None)),
        sharded(kp, P("m0", "m1", None, None)),
        sharded(vp, P("m0", "m1", None, None)),
        sharded(pos_p, P("m0", "m1")),
        mesh=mesh, axes=axes, causal=causal,
    )
    out = np.asarray(out)
    if zigzag:
        inv = inverse_permutation(zigzag_permutation(s, cp))
        out = out[:, inv]
    np.testing.assert_allclose(out, np.asarray(dense), atol=3e-5)


def test_ring_attention_padding_bias_matches_dense(devices8):
    """BERT-style padded batches under CP: the additive key bias rotates with
    K/V around the ring (the reference's ring path is causal-only,
    transformer.py:2335-2670 — this is a capability beyond it)."""
    b, s, nh, hd = 2, 32, 4, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), b=b, s=s, nh=nh, hd=hd)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    mask = np.ones((b, s), np.float32)
    mask[:, -8:] = 0.0
    bias = jnp.asarray((1.0 - mask)[:, None, None, :] * -1e9)
    dense = core_attention(q, k, v, causal=False, bias=bias, impl="xla")

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("m0", "m1"))
    axes = LayerAxes(dp=("m0",), cp=("m1",), tp=())
    sharded = lambda t, spec: jax.device_put(t, NamedSharding(mesh, spec))
    out = ring_attention(
        sharded(q, P("m0", "m1", None, None)),
        sharded(k, P("m0", "m1", None, None)),
        sharded(v, P("m0", "m1", None, None)),
        sharded(positions, P("m0", "m1")),
        mesh=mesh, axes=axes, causal=False, bias=sharded(bias, P("m0", None, None, "m1")),
    )
    # padded queries attend to garbage (all keys masked would be fully
    # masked rows) — compare only valid query positions
    np.testing.assert_allclose(
        np.asarray(out)[:, :24], np.asarray(dense)[:, :24], atol=3e-5
    )


def _ring_mem_setup(devices8):
    """Shared scaffolding for the ring-attention compiled-memory gates: one
    mesh/axes/abstract-input recipe so both tests measure the same config."""
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("m0", "m1"))
    axes = LayerAxes(dp=("m0",), cp=("m1",), tp=())

    def structs(s, b=2, nh=4, hd=16):
        q = jax.ShapeDtypeStruct((b, s, nh, hd), jnp.float32,
                                 sharding=NamedSharding(mesh, P("m0", "m1", None, None)))
        pos = jax.ShapeDtypeStruct((b, s), jnp.int32,
                                   sharding=NamedSharding(mesh, P("m0", "m1")))
        return q, pos

    return mesh, axes, structs


def test_ring_attention_blockwise_memory_scales_linearly(devices8):
    """The per-step working set must be O(sq * key_chunk), not O(S^2/cp):
    doubling S must scale the compiled temp bytes ~linearly (the round-2
    full-logits implementation scaled quadratically)."""
    from galvatron_tpu.ops import ring_attention as R

    mesh, axes, structs = _ring_mem_setup(devices8)

    def temp_bytes(s):
        q, pos = structs(s)

        def f(q, k, v, pos):
            return R.ring_attention(q, k, v, pos, mesh=mesh, axes=axes, causal=True)

        compiled = jax.jit(f).lower(q, q, q, pos).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    t1 = temp_bytes(2048)
    t2 = temp_bytes(4096)
    assert t2 < 3.0 * t1, (t1, t2)


def test_zigzag_permutation_roundtrip():
    idx = zigzag_permutation(32, 4)
    inv = inverse_permutation(idx)
    x = np.arange(32)
    assert (x[idx][inv] == x).all()
    # shard 0 holds chunks 0 and 7 (balanced causal load)
    chunk = 32 // 8
    shard0 = idx[: 2 * chunk]
    assert set(shard0) == set(range(0, chunk)) | set(range(7 * chunk, 32))


@pytest.mark.parametrize("mode", ["causal", "bias", "gqa_zigzag"])
def test_ring_custom_vjp_matches_autodiff(devices8, mode):
    """The hand-scheduled ring backward (custom_vjp re-walking the ring with
    rotating dk/dv/dbias accumulators, the reference's zigzag backward
    pattern transformer.py:2423-2553) must produce the same gradients as
    autodiff through the unrolled forward — for causal, padded-bias, and
    GQA+zigzag compositions."""
    b, s, nh, hd = 2, 32, 4, 16
    nkv = 2 if mode == "gqa_zigzag" else None
    q, k, v = _rand_qkv(jax.random.PRNGKey(7), b=b, s=s, nh=nh, nkv=nkv, hd=hd)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    causal = mode != "bias"
    bias = None
    if mode == "bias":
        m = np.ones((b, s), np.float32)
        m[:, -8:] = 0.0
        bias = jnp.asarray((1.0 - m)[:, None, None, :] * -1e9)
    if mode == "gqa_zigzag":
        idx = zigzag_permutation(s, 4)
        q, k, v, positions = q[:, idx], k[:, idx], v[:, idx], positions[:, idx]

    mesh = Mesh(np.array(devices8).reshape(2, 4), ("m0", "m1"))
    axes = LayerAxes(dp=("m0",), cp=("m1",), tp=())
    sharded = lambda t, spec: jax.device_put(t, NamedSharding(mesh, spec))
    args = [
        sharded(q, P("m0", "m1", None, None)),
        sharded(k, P("m0", "m1", None, None)),
        sharded(v, P("m0", "m1", None, None)),
    ]
    pos_s = sharded(positions, P("m0", "m1"))
    bias_s = sharded(bias, P("m0", None, None, "m1")) if bias is not None else None
    # downstream-style scalar loss with a non-uniform cotangent
    w = jax.random.normal(jax.random.PRNGKey(9), (b, s, nh, hd))

    def loss(qkv, use_custom):
        out = ring_attention(
            *qkv, pos_s, mesh=mesh, axes=axes, causal=causal, bias=bias_s,
            use_custom_vjp=use_custom,
        )
        return jnp.sum(out.astype(jnp.float32) * w)

    # (jitted: op by op the unrolled ring's autodiff dispatches for a minute)
    l_c, g_c = jax.jit(jax.value_and_grad(lambda t: loss(t, True)))(tuple(args))
    l_a, g_a = jax.jit(jax.value_and_grad(lambda t: loss(t, False)))(tuple(args))
    np.testing.assert_allclose(float(l_c), float(l_a), rtol=1e-6)
    for name, gc, ga in zip("qkv", g_c, g_a):
        np.testing.assert_allclose(
            np.asarray(gc), np.asarray(ga), atol=2e-4, rtol=1e-4,
            err_msg="grad mismatch for %s (%s)" % (name, mode),
        )


def test_ring_custom_vjp_bias_grad_matches_autodiff(devices8):
    """The rotating dbias accumulator: gradient w.r.t. the additive key bias
    itself (a trainable-relative-bias shape) matches autodiff."""
    b, s, nh, hd = 2, 32, 4, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), b=b, s=s, nh=nh, hd=hd)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    bias = jax.random.normal(jax.random.PRNGKey(12), (b, 1, 1, s)) * 0.5
    mesh = Mesh(np.array(devices8).reshape(2, 4), ("m0", "m1"))
    axes = LayerAxes(dp=("m0",), cp=("m1",), tp=())
    sharded = lambda t, spec: jax.device_put(t, NamedSharding(mesh, spec))
    qs = sharded(q, P("m0", "m1", None, None))
    ks = sharded(k, P("m0", "m1", None, None))
    vs = sharded(v, P("m0", "m1", None, None))
    pos_s = sharded(positions, P("m0", "m1"))
    w = jax.random.normal(jax.random.PRNGKey(13), (b, s, nh, hd))

    def loss(bb, use_custom):
        out = ring_attention(
            qs, ks, vs, pos_s, mesh=mesh, axes=axes, causal=False,
            bias=sharded(bb, P("m0", None, None, "m1")), use_custom_vjp=use_custom,
        )
        return jnp.sum(out.astype(jnp.float32) * w)

    g_c = jax.jit(jax.grad(lambda bb: loss(bb, True)))(bias)
    g_a = jax.jit(jax.grad(lambda bb: loss(bb, False)))(bias)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_a),
                               atol=2e-4, rtol=1e-4)


def test_ring_custom_vjp_bias_grad_with_tp_sharded_heads(devices8):
    """tp x cp compose: heads are tp-sharded while the bias enters the
    shard_map tp-invariant, so the custom backward must psum the local
    head-sum over tp (autodiff inserts that reduction automatically — the
    hand-written rule has to match it)."""
    b, s, nh, hd = 2, 32, 4, 16
    q, k, v = _rand_qkv(jax.random.PRNGKey(21), b=b, s=s, nh=nh, hd=hd)
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    bias = jax.random.normal(jax.random.PRNGKey(22), (b, 1, 1, s)) * 0.5
    mesh = Mesh(np.array(devices8).reshape(2, 2, 2), ("m0", "m1", "m2"))
    axes = LayerAxes(dp=("m0",), cp=("m1",), tp=("m2",))
    sharded = lambda t, spec: jax.device_put(t, NamedSharding(mesh, spec))
    qs = sharded(q, P("m0", "m1", "m2", None))
    ks = sharded(k, P("m0", "m1", "m2", None))
    vs = sharded(v, P("m0", "m1", "m2", None))
    pos_s = sharded(positions, P("m0", "m1"))
    w = jax.random.normal(jax.random.PRNGKey(23), (b, s, nh, hd))

    def loss(bb, use_custom):
        out = ring_attention(
            qs, ks, vs, pos_s, mesh=mesh, axes=axes, causal=True,
            bias=sharded(bb, P("m0", None, None, "m1")), use_custom_vjp=use_custom,
        )
        return jnp.sum(out.astype(jnp.float32) * w)

    g_c = jax.jit(jax.grad(lambda bb: loss(bb, True)))(bias)
    g_a = jax.jit(jax.grad(lambda bb: loss(bb, False)))(bias)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_a),
                               atol=2e-4, rtol=1e-4)


def test_ring_custom_vjp_backward_memory_beats_autodiff(devices8):
    """The point of the hand-written ring backward: probabilities recompute
    from the saved lse, so no per-chunk residuals survive the forward.
    Compiled temp bytes of the gradient program must stay bounded where
    autodiff's transpose-of-scan residuals grow superlinearly (measured on
    this mesh: S=4096 custom 28 MB vs autodiff 247 MB)."""
    from galvatron_tpu.ops import ring_attention as R

    mesh, axes, structs = _ring_mem_setup(devices8)

    def temp_bytes(s, use_custom):
        q, pos = structs(s)

        def loss(q_, k_, v_, pos_):
            out = R.ring_attention(q_, k_, v_, pos_, mesh=mesh, axes=axes,
                                   causal=True, use_custom_vjp=use_custom)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        return g.lower(q, q, q, pos).compile().memory_analysis().temp_size_in_bytes

    big_custom = temp_bytes(4096, True)
    big_auto = temp_bytes(4096, False)
    assert big_custom < 0.4 * big_auto, (big_custom, big_auto)
    # and the custom backward never costs meaningfully MORE than autodiff
    small_custom, small_auto = temp_bytes(2048, True), temp_bytes(2048, False)
    assert small_custom < 1.1 * small_auto, (small_custom, small_auto)
