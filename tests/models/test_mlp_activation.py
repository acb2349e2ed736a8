"""The dense MLP half's two forms (models/parts/mlp.dense_mlp): a GELU is a
pass of its own in the forward, written out on both sides (it reads the
pre-activation behind `attention._written_out`, and the down projection's
forward matmul reads it behind an optimization barrier under a
`jax.custom_vjp`); SiLU x gate and ReLU stay folded into that matmul. A barrier
changes no value and the rule's backward is the matmul's own transpose, so
either form is plain autodiff of the half as it was written before the rule, to
the bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from galvatron_tpu import HybridParallelConfig
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.parts import mlp
from galvatron_tpu.models.parts.common import _activation, _dense
from galvatron_tpu.obs import forms
from galvatron_tpu.parallel.mesh import build_mesh, layer_axes

FORM = {"gelu_exact": "written_out", "gelu": "written_out", "relu": "folded", "swiglu": "folded"}


def _plain_mlp(p, y, cfg, dtype):
    """The half without the rule: three lines and plain autodiff."""
    wi_out = jnp.einsum("bsh,h...->bs...", y, p["wi"]["kernel"].astype(dtype)) + p["wi"]["bias"].astype(dtype)
    swiglu = cfg.activation == "swiglu"
    hmid = jax.nn.silu(wi_out[:, :, 0]) * wi_out[:, :, 1] if swiglu else _activation(wi_out, cfg)
    return _dense(hmid, p["wo_mlp"], dtype)


def _half(activation, dtype):
    """-> (config, parameters with biases that are not zero, normed input)."""
    cfg = gpt_config("gpt-0.3b", num_layers=1, hidden_size=64, num_heads=4, ffn_hidden=128, vocab_size=256,
                     max_seq_len=32, activation=activation, compute_dtype=dtype)
    ks = list(jax.random.split(jax.random.PRNGKey(0), 6))
    p = mlp._init_dense(ks, cfg)
    p["wi"]["bias"] = 0.1 * jax.random.normal(ks[4], p["wi"]["bias"].shape, cfg.param_dtype)
    p["wo_mlp"]["bias"] = 0.1 * jax.random.normal(ks[5], p["wo_mlp"]["bias"].shape, cfg.param_dtype)
    return cfg, p, jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), dtype)


def _value_and_grads(half, cfg, dtype, checkpoint):
    """The jitted loss of a residual layer around `half`, and its gradients for
    the parameters and the input, the layer under `jax.checkpoint` or not."""
    def layer(p, y):
        return y + half(p, y, cfg, dtype)

    def loss(p, y):
        out = (jax.checkpoint(layer) if checkpoint else layer)(p, y)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


@pytest.mark.parametrize("checkpoint", [False, True], ids=["kept", "recomputed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("activation", list(FORM))
def test_either_form_is_plain_autodiff_to_the_bit(activation, dtype, checkpoint):
    """`dense_mlp`'s value and its gradients for y, both kernels and both
    biases equal plain autodiff's of the half without the rule, in float32 and
    in bf16 compute, the layer recomputed or not; and the trace says which form
    the activation took."""
    cfg, p, y = _half(activation, dtype)
    with forms.recording() as took:
        got = _value_and_grads(mlp.dense_mlp, cfg, dtype, checkpoint)(p, y)
    assert took[forms.MLP_ACTIVATION][FORM[activation]] >= 1
    assert set(took[forms.MLP_ACTIVATION]) == {FORM[activation]}
    want = _value_and_grads(_plain_mlp, cfg, dtype, checkpoint)(p, y)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert {"kernel", "bias"} == set(got[1][0]["wi"]) == set(got[1][0]["wo_mlp"])
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.dtype == b.dtype and float(jnp.abs(a.astype(jnp.float32)).max()) > 0
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("activation", list(FORM))
def test_under_tp_the_down_kernels_gradient_comes_as_the_specs_say(devices8, activation):
    """tp 2 x dp 4 on the eight-device CPU mesh: the rule's backward leaves the
    down kernel's gradient where `_dense_specs` puts the kernel (the ffn dim
    over tp: the sum over the tokens is autodiff's own), every gradient is
    sharded as plain autodiff's, and the values agree with one device's."""
    dtype = jnp.float32
    cfg, p, y = _half(activation, dtype)
    hp = HybridParallelConfig.uniform(8, 1, tp=2, global_bsz=8)
    mesh, axes = build_mesh(hp, devices8), layer_axes(hp, 0)
    specs = mlp._dense_specs(cfg, axes)
    assert specs["wo_mlp"]["kernel"] == P(axes.tp[0], None)
    y = jnp.concatenate([y] * 4)  # 8 rows over dp 4
    placed = jax.tree.map(lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), p, specs,
                          is_leaf=lambda x: isinstance(x, P))
    y_placed = jax.device_put(y, NamedSharding(mesh, P(axes.dp, None, None)))
    got = _value_and_grads(mlp.dense_mlp, cfg, dtype, True)(placed, y_placed)
    want = _value_and_grads(_plain_mlp, cfg, dtype, True)(placed, y_placed)
    down = got[1][0]["wo_mlp"]["kernel"]
    assert down.sharding.is_equivalent_to(NamedSharding(mesh, specs["wo_mlp"]["kernel"]), down.ndim), down.sharding
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim), (a.shape, a.sharding, b.sharding)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    one = _value_and_grads(mlp.dense_mlp, cfg, dtype, True)(p, y)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one), strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5)
