"""The chunked gated delta rule and the causal convolution
(ops/linear_attention.py) on the CPU in float32, against the recurrence run
token by token and against shifted adds, each written here in a few lines.

Tolerances, and why: in float32 the chunked form does the recurrence's
arithmetic in another order (a chunk's triangular solve and batched matmuls
against 16 to 320 dependent rank-one updates): measured worst relative error
3e-6 of the output's largest magnitude, 5e-6 of a gradient's; the limit is
5e-5.

The Pallas kernels (the form a TPU takes) run here in interpret mode at the
widths they need (d_k = d_v = 128), against the XLA form AND the recurrence:
float32 under the same 5e-5 (measured 2.5e-6); bf16 operands no further from
the recurrence than the XLA form is on the same operands (the products on the
way to the output round to bf16 in both), or inside the float32 limit where
both are (the gates' gradients).

The passes around the core (`conv_norm_*`, `gated_norm_*`, and the per-channel
rule's `kda_gate_*`) run interpreted too, at both mixers' layouts, against the
XLA form of models/parts/linear.linear_mixer and parts/kda.kda_mixer written here in a few
lines from `causal_conv`, SiLU, `unit`, `rms_norm`, `softplus`: float32 under
the same 5e-5 (measured 4e-7), bf16 no further from the float32 XLA form than
the bf16 XLA form is.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.ops import linear_attention as L
from galvatron_tpu.ops.norms import rms_norm


TOL = 5e-5


B, HK, HV, DK, DV = 2, 2, 4, 16, 8


def recurrence(q, k, v, g, beta):
    """The rule token by token: S' = e^g S; u = beta (v - S'^T k); S = S' + k u^T; o = S^T q."""
    serves = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, serves, axis=2), jnp.repeat(k, serves, axis=2)

    def token(state, x):
        qt, kt, vt, gt, bt = x
        state = jnp.exp(gt)[..., None, None] * state
        u = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", state, kt))
        state = state + kt[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    state, o = jax.lax.scan(token, jnp.zeros((v.shape[0], v.shape[2], q.shape[-1], v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1), state


def operands(tokens, seed=0, hv=HV, hk=HK, dk=DK, dv=DV):
    """Unit keys, queries / sqrt(d_k), decays from 1e-3 to 1.6 a token."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, tokens, hk, dk))) / dk ** 0.5
    k = unit(jax.random.normal(ks[1], (B, tokens, hk, dk)))
    v = jax.random.normal(ks[2], (B, tokens, hv, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (B, tokens, hv), minval=np.log(1e-3), maxval=np.log(1.6)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, tokens, hv)))
    return q, k, v, g, beta


def objective(rule):
    return lambda *ops: jnp.sum(jnp.sin(rule(*ops)[0]))


KERNEL = dict(hv=2, hk=1, dk=128, dv=128)  # one key head serving two value heads, unrepeated


def kernel_rule(*ops, **kw):
    return L.gated_delta_rule(*ops, impl="pallas", **kw)


def worst(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


QWEN3_NEXT = L.linear_layout(L.Heads(16, 128, 32, 128))  # 16 key heads serving 32 value heads, 128 wide


SMALL = L.linear_layout(L.Heads(1, 128, 2, 128))


KIMI = L.kda_layout(L.Heads(4, 128, 4, 128))  # equal key and value heads, three segments, z its own array


KIMI_SMALL = L.kda_layout(L.Heads(2, 128, 2, 128))


LAYOUTS = pytest.mark.parametrize("layout", [QWEN3_NEXT, KIMI], ids=["qwen3_next", "kimi"])


SMALL_LAYOUTS = pytest.mark.parametrize("layout", [SMALL, KIMI_SMALL], ids=["qwen3_next", "kimi"])


EPS = 1e-6


def per_channel(layout):
    return "kda_gate" in layout.counted


def around(layout, tokens, dtype, seed=0, batch=1):
    """A projection's output x ([q | k | v | z], or [q | k | v] and z an
    array of its own: `within` is the array z lies in), the taps, the gated
    norm's scale, a core's output, and cotangents for q, k, v and the gated
    result; for the per-channel rule also its gate's operands f, dt_bias,
    a_log and a cotangent for g."""
    heads = layout.heads
    keys, values = heads.key_heads * heads.d_k, heads.value_heads * heads.d_v
    ks = jax.random.split(jax.random.PRNGKey(seed), 13)
    normal = lambda key, width: jax.random.normal(key, (batch, tokens, width)).astype(dtype)  # noqa: E731
    inside = layout.z.start > 0
    given = dict(x=normal(ks[0], 2 * keys + values + values * inside),
                 taps=jax.random.uniform(ks[1], (2 * keys + values, 4), minval=-0.5, maxval=0.5),
                 scale=1.0 + 0.1 * jax.random.normal(ks[2], (heads.d_v,)), o=normal(ks[3], values),
                 dq=normal(ks[4], keys), dk=normal(ks[5], keys), dv=normal(ks[6], values),
                 dout=normal(ks[7], values))
    given["within"] = given["x"] if inside else normal(ks[8], values)
    if per_channel(layout):
        given.update(f=normal(ks[9], keys), dt_bias=jax.random.normal(ks[10], (keys,)),
                     a_log=jnp.log(jax.random.uniform(ks[11], (heads.key_heads,), minval=0.05, maxval=4.0)),
                     dg=jax.random.normal(ks[12], (batch, tokens, keys)))
    return given


def xla_before(layout, x, taps):
    """models/parts/linear.linear_mixer and parts/kda.kda_mixer before the core: -> q, k, v, flat."""
    heads, (b, s, _) = layout.heads, x.shape
    keys, values = heads.key_heads * heads.d_k, heads.value_heads * heads.d_v

    def unit(t):
        t32 = t.astype(jnp.float32).reshape(b, s, heads.key_heads, heads.d_k)
        return (t32 * jax.lax.rsqrt(jnp.sum(jnp.square(t32), axis=-1, keepdims=True) + 1e-6)).reshape(t.shape)

    qkv = jax.nn.silu(L.causal_conv(x[..., :2 * keys + values], taps))
    return ((unit(qkv[..., :keys]) * heads.d_k ** -0.5).astype(x.dtype),
            unit(qkv[..., keys:2 * keys]).astype(x.dtype), qkv[..., 2 * keys:])


def xla_after(layout, o, within, scale):
    """After the core: RMSNorm(o) a head x the output gate of z, `within`'s last columns."""
    heads, (b, s, values) = layout.heads, o.shape
    z = within[..., -values:].reshape(b, s, heads.value_heads, heads.d_v).astype(jnp.float32)
    normed = rms_norm(o.reshape(z.shape).astype(jnp.float32), scale, EPS)
    return (normed * getattr(jax.nn, layout.gate)(z)).astype(o.dtype).reshape(o.shape)


def xla_gate(layout, f, dt_bias, a_log):
    """models/parts/kda.kda_mixer's gate: -exp(A_log) a head x softplus(f + dt_bias), float32, flat."""
    heads, (b, s, _) = layout.heads, f.shape
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * jax.nn.softplus(
        f.astype(jnp.float32) + dt_bias.astype(jnp.float32)).reshape(b, s, heads.key_heads, heads.d_k)
    return g.reshape(f.shape)


def value_heads_shares(layout, x):
    """A key head's cotangent cut into its value heads' unequal shares, side
    by side, as the core's backward kernel hands dq and dk on."""
    heads = layout.heads
    serves = heads.value_heads // heads.key_heads
    weights = jnp.arange(1.0, serves + 1) / sum(range(1, serves + 1))
    x5 = x.astype(jnp.float32).reshape(x.shape[:2] + (heads.key_heads, 1, heads.d_k))
    return (x5 * weights[:, None]).reshape(x.shape[:2] + (-1,)).astype(x.dtype)
