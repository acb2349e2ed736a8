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
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from galvatron_tpu.ops import linear_attention as L
from galvatron_tpu.ops.attention import KernelSharding
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


@pytest.mark.parametrize("chunks", [2, 5])
@pytest.mark.parametrize("chunk", [16, 32, 64])
def test_the_chunked_rule_is_the_recurrence(chunk, chunks):
    ops = operands(chunk * chunks, seed=chunk + chunks)
    with jax.default_matmul_precision("highest"):
        o, state = L.gated_delta_rule(*ops, chunk=chunk)
        want_o, want_state = recurrence(*ops)
        grads = jax.grad(objective(lambda *a: L.gated_delta_rule(*a, chunk=chunk)), range(5))(*ops)
        want = jax.grad(objective(recurrence), range(5))(*ops)
    assert o.shape == want_o.shape and state.shape == (B, HV, DK, DV)
    assert float(jnp.max(jnp.abs(o - want_o))) < TOL * float(jnp.max(jnp.abs(want_o)))
    assert float(jnp.max(jnp.abs(state - want_state))) < TOL * float(jnp.max(jnp.abs(want_state)))
    for name, got, ref in zip("q k v g beta".split(), grads, want):
        assert float(jnp.max(jnp.abs(got - ref))) < TOL * float(jnp.max(jnp.abs(ref))), name


def test_equal_neighbouring_keys_do_not_break_the_solve():
    """A run of one repeated key with beta near 1 and no decay: I + A is the
    all-ones lower triangle, whose inverse is bidiagonal while the powers of A
    grow as binomials (the product form of the inverse loses every digit)."""
    tokens = 64
    q, k, v, g, beta = operands(tokens, seed=9)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.full_like(g, -1e-6), jnp.full_like(beta, 0.999)
    with jax.default_matmul_precision("highest"):
        o, _ = L.gated_delta_rule(q, k, v, g, beta, chunk=64)
        want, _ = recurrence(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(o - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


# --- the kernel form, interpreted -------------------------------------------

KERNEL = dict(hv=2, hk=1, dk=128, dv=128)  # one key head serving two value heads, unrepeated


def kernel_rule(*ops, **kw):
    return L.gated_delta_rule(*ops, impl="pallas", **kw)


def worst(got, want):
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("chunks", [2, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_kernels_are_the_recurrence_and_the_xla_form(dtype, chunks, monkeypatch):
    """o, the final states and all five gradients, batch 2. Two tiles a grid
    step (one in float32): 5 chunks are 3 tiles behind a chunk of zeros, so
    the last step's block is not whole, and 2 chunks are one tile, less than a
    block."""
    monkeypatch.setattr(L, "_BLOCK", 2)
    ops = operands(64 * chunks, seed=chunks, **KERNEL)
    cast = tuple(x.astype(dtype) for x in ops[:3]) + ops[3:]
    exact = tuple(x.astype(jnp.float32) for x in cast)

    def objective(rule):  # the final states' gradient enters too
        def of(*a):
            o, states = rule(*a)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))) + jnp.sum(jnp.cos(states))
        return of

    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got = kernel_rule(*cast) + jax.grad(objective(kernel_rule), range(5))(*cast)
        xla = (L.gated_delta_rule(*cast, impl="xla")
               + jax.grad(objective(lambda *a: L.gated_delta_rule(*a, impl="xla")), range(5))(*cast))
        want = recurrence(*exact) + jax.grad(objective(recurrence), range(5))(*exact)
    assert got[0].dtype == dtype and got[0].shape == want[0].shape
    assert got[1].dtype == jnp.float32 and got[1].shape == (B, 2, 128, 128)
    for name, g, x, w in zip("o states dq dk dv dg dbeta".split(), got, xla, want):
        limit = TOL if dtype == jnp.float32 else max(TOL, worst(x, w))
        assert worst(g, w) <= limit, (name, worst(g, w), worst(x, w))
        assert worst(g, x.astype(jnp.float32)) <= 2 * limit, name


def test_the_kernels_solve_a_run_of_one_repeated_key():
    """`test_equal_neighbouring_keys_do_not_break_the_solve`, through the
    kernels' elimination and merges on a tile of 128."""
    q, k, v, g, beta = operands(128, seed=9, **KERNEL)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.full_like(g, -1e-6), jnp.full_like(beta, 0.999)
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        o, _ = kernel_rule(q, k, v, g, beta)
        want, _ = recurrence(q, k, v, g, beta)
    assert worst(o, want) < 1e-4


def test_the_kernels_run_a_device_on_its_rows_of_the_batch():
    """Under `sharding` the kernels sit in a manual region over the batch:
    two devices, a row each, the same numbers as one device on both."""
    ops = operands(128, seed=3, **KERNEL)
    sharding = KernelSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_axes=("dp",))
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        o, state = jax.jit(lambda *a: kernel_rule(*a, sharding=sharding))(*ops)
        want_o, want_state = kernel_rule(*ops)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want_o), atol=1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(want_state), atol=1e-6)


def test_off_a_tpu_the_choice_is_the_xla_form_and_it_is_counted():
    ops = operands(64, seed=1)  # heads of 16 x 8: no kernel could take them
    before = dict(L.TOOK)
    got = L.gated_delta_rule(*ops)
    assert L.TOOK["xla"] == before.get("xla", 0) + 1 and L.TOOK["pallas"] == before.get("pallas", 0)
    wide = operands(64, seed=1, **KERNEL)  # the kernels' widths, but this is a CPU
    L.gated_delta_rule(*wide)
    assert L.TOOK["xla"] == before.get("xla", 0) + 2 and L.TOOK["pallas"] == before.get("pallas", 0)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(L.gated_delta_rule(*ops, impl="xla")[0]))


def test_a_length_that_is_no_multiple_of_the_chunk_is_refused_by_name():
    with pytest.raises(ValueError, match="100 tokens is no multiple of the chunk of 64"):
        L.gated_delta_rule(*operands(100))


def test_the_unit_lower_inverse_is_the_inverse():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(0), (3, 2, 64, 64)), -1) * 0.3
    with jax.default_matmul_precision("highest"):
        inverse = L.unit_lower_inverse(a)
        product = jnp.matmul(jnp.eye(64) + a, inverse)
    np.testing.assert_allclose(np.asarray(product), np.broadcast_to(np.eye(64), product.shape), atol=2e-5)
    assert float(jnp.max(jnp.abs(jnp.triu(inverse, 1)))) == 0.0


@pytest.mark.parametrize("taps", [1, 4])
def test_the_convolution_is_shifted_adds(taps):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 6))
    w = jax.random.uniform(jax.random.PRNGKey(2), (6, taps), minval=-0.5, maxval=0.5)
    want = np.zeros(x.shape, np.float32)
    for t in range(x.shape[1]):
        for j in range(taps):  # tap j reads the token (taps - 1 - j) back; none before the start
            if t - (taps - 1 - j) >= 0:
                want[:, t] += np.asarray(w[:, j]) * np.asarray(x[:, t - (taps - 1 - j)])
    np.testing.assert_allclose(np.asarray(L.causal_conv(x, w)), want, atol=1e-6)
    # causal: a later token moves no earlier output
    moved = L.causal_conv(x.at[:, 7].add(1.0), w)
    np.testing.assert_array_equal(np.asarray(moved[:, :7]), np.asarray(L.causal_conv(x, w)[:, :7]))


# --- the passes around the core, interpreted ---------------------------------
#
# One set of kernels, two mixers' layouts: Qwen3-Next's gated DeltaNet
# (`Wqkvz`'s [q | k | v | z], a key head serving two value heads, SiLU(z)) and
# Kimi Delta Attention (`Wqkv`'s [q | k | v], a head each, the output gate an
# array of its own behind a sigmoid, and the per-channel gate's pass).

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


def passes_against_the_xla_form(layout, tokens, dtype, monkeypatch, **kw):
    """Every pass, forward and backward: the worst leaf of each kind,
    kernels against the float32 XLA form, and the XLA form in `dtype`
    against it."""
    monkeypatch.setattr(L, "_TOKENS", 128)
    given = around(layout, tokens, dtype, **kw)
    exact = {name: x.astype(jnp.float32) for name, x in given.items()}
    cut = given["taps"].shape[0]  # where q, k, v end
    inside = layout.z.start > 0

    def xla(x):
        before, pull_before = jax.vjp(lambda a, b: xla_before(layout, a, b), x["x"], x["taps"])
        after, pull_after = jax.vjp(lambda a, b, c: xla_after(layout, a, b, c), x["o"], x["within"], x["scale"])
        dqkv, dtaps = pull_before((x["dq"], x["dk"], x["dv"]))
        do, dz, dscale = pull_after(x["dout"])
        out = dict(zip("q k v".split(), before), gated=after, dqkv=dqkv[..., :cut], dtaps=dtaps, do=do,
                   dz=dz[..., -x["o"].shape[-1]:], dscale=dscale)
        if per_channel(layout):
            g, pull_gate = jax.vjp(lambda *a: xla_gate(layout, *a), x["f"], x["dt_bias"], x["a_log"])
            out.update(g=g, **dict(zip("df ddt_bias da_log".split(), pull_gate(x["dg"]))))
        return out

    with pltpu.force_tpu_interpret_mode():
        q, k, v = L._conv_norm(layout.qkv, given["x"], given["taps"])
        dwithin, do, dscale = L._gated_norm_bwd(layout, EPS, given["o"], given["within"], given["scale"],
                                                given["dout"])
        assert dwithin.shape == given["within"].shape and dwithin.dtype == dtype
        dz = dwithin[..., -given["o"].shape[-1]:]
        dx, dtaps = L._conv_norm_bwd(
            layout.qkv, given["x"], given["taps"], (value_heads_shares(layout, given["dq"]),
                                                    value_heads_shares(layout, given["dk"]), given["dv"]),
            dwithin if inside else None)
        assert dx.shape == given["x"].shape and dx.dtype == dtype
        got = dict(q=q, k=k, v=v, gated=L._gated_norm(layout, EPS, given["o"], given["within"], given["scale"]),
                   dqkv=dx[..., :cut], dtaps=dtaps, do=do, dz=dz, dscale=dscale)
        if per_channel(layout):
            got["g"] = L._channel_gate(layout, given["f"], given["dt_bias"], given["a_log"])
            assert got["g"].dtype == jnp.float32  # whatever f came in
            got.update(zip("df ddt_bias da_log".split(), L._channel_gate_bwd(
                layout, given["f"], given["dt_bias"], given["a_log"], given["dg"])))
            assert got["df"].dtype == dtype
    if inside:  # two backwards filled one array: the second left the first's columns as they were
        np.testing.assert_array_equal(np.asarray(dx[..., cut:], np.float32), np.asarray(dz, np.float32))
    want, rounded = xla(exact), xla(given)
    for name in want:
        assert got[name].shape == want[name].shape, name
        limit = TOL if dtype == jnp.float32 else max(TOL, 1.25 * worst(rounded[name], want[name]))
        assert worst(got[name], want[name]) <= limit, (name, worst(got[name], want[name]), limit)


@LAYOUTS
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_passes_around_the_core_are_the_xla_form(dtype, layout, monkeypatch):
    """Three tiles of 128 tokens, so the convolution's halo is crossed twice
    forward and twice backward: q, k, v, the gated result, and the gradients
    to the projection's output (q, k, v's columns and, where it lies there,
    z's, filled into one array by two backwards), the taps, o, z and the
    scale; at Kimi's structure also the per-channel gate g (float32 from a
    bf16 f) and the gradients to f, `dt_bias` and `A_log`."""
    passes_against_the_xla_form(layout, 384, dtype, monkeypatch)


@SMALL_LAYOUTS
def test_a_sequences_first_tile_sees_zeros_before_it(layout, monkeypatch):
    """One tile alone, two rows of the batch: the block before the tile is
    the tile itself (the index map stops at 0) and must read as zeros; the
    block after it likewise."""
    passes_against_the_xla_form(layout, 128, jnp.float32, monkeypatch, batch=2, seed=4)


def mixer_operands(layout, tokens, seed=0):
    """What the mixer's rule takes, in its order: the scalar rule's (qkvz,
    taps, scale, g, beta), the per-channel rule's (qkv, taps, scale, f,
    dt_bias, a_log, z, beta)."""
    given = around(layout, tokens, jnp.float32, seed=seed, batch=2)
    ks = jax.random.split(jax.random.PRNGKey(seed + 100), 2)
    heads = layout.heads.value_heads
    beta = jax.nn.sigmoid(jax.random.normal(ks[1], (2, tokens, heads)))
    if per_channel(layout):
        return (given["x"], given["taps"], given["scale"], given["f"], given["dt_bias"], given["a_log"],
                given["within"], beta)
    g = -jnp.exp(jax.random.uniform(ks[0], (2, tokens, heads), minval=np.log(1e-3), maxval=np.log(1.6)))
    return given["x"], given["taps"], given["scale"], g, beta


def xla_mixer(layout, *ops):
    heads, (b, s, _) = layout.heads, ops[0].shape
    by_heads = lambda t, n, d: t.reshape(b, s, n, d)  # noqa: E731
    q, k, v = xla_before(layout, ops[0], ops[1])
    q, k = by_heads(q, heads.key_heads, heads.d_k), by_heads(k, heads.key_heads, heads.d_k)
    v = by_heads(v, heads.value_heads, heads.d_v)
    if per_channel(layout):
        x, taps, scale, f, dt_bias, a_log, z, beta = ops
        o, state = L.kda_rule(q, k, v, by_heads(xla_gate(layout, f, dt_bias, a_log), heads.key_heads, heads.d_k),
                              beta, impl="xla")
        return xla_after(layout, o.reshape(b, s, -1), z, scale), state
    x, taps, scale, g, beta = ops
    o, state = L.gated_delta_rule(q, k, v, g, beta, impl="xla")
    return xla_after(layout, o.reshape(b, s, -1), x, scale), state


def kernel_mixer(layout, **where):
    """The mixer's rule through the kernels -> (out, states)."""
    if per_channel(layout):
        return lambda *a: L.kda_kernel_mixer(*a, layout, eps=EPS, **where)[:2]
    return lambda *a: L.kernel_mixer(*a, layout, eps=EPS, **where)


def mixer_objective(rule):
    def of(*a):
        out, states = rule(*a)
        return jnp.sum(jnp.sin(out)) + jnp.sum(jnp.cos(states))
    return of


NAMES = {False: "out states dqkvz dtaps dscale dg dbeta".split(),
         True: "out states dqkv dtaps dscale df ddt_bias da_log dz dbeta".split()}


@SMALL_LAYOUTS
def test_the_kernel_mixer_is_the_xla_form_through_the_core(layout, monkeypatch):
    """Convolution and norms, (the per-channel gate,) the core's kernels, the
    gated norm as ONE rule (`kernel_mixer`, `kda_kernel_mixer`): the result,
    the final states and the gradients to every operand, the cotangent of the
    projection's output written once."""
    monkeypatch.setattr(L, "_TOKENS", 128)
    ops = mixer_operands(layout, 256)
    kernel = kernel_mixer(layout)
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        got = kernel(*ops) + jax.grad(mixer_objective(kernel), range(len(ops)))(*ops)
        xla = lambda *a: xla_mixer(layout, *a)  # noqa: E731
        want = xla(*ops) + jax.grad(mixer_objective(xla), range(len(ops)))(*ops)
        if per_channel(layout):  # the counter: the mean of exp(g) a row of the batch
            decay = L.kda_kernel_mixer(*ops, layout, eps=EPS)[2]
            np.testing.assert_allclose(np.asarray(decay), np.asarray(jnp.mean(jnp.exp(xla_gate(
                layout, *ops[3:6])), axis=(1, 2))), rtol=1e-6)
    for name, g, w in zip(NAMES[per_channel(layout)], got, want):
        assert g.shape == w.shape and worst(g, w) <= TOL, (name, worst(g, w))


@SMALL_LAYOUTS
def test_the_kernel_mixer_runs_a_device_on_its_rows_of_the_batch(layout, monkeypatch):
    """Under `sharding` the whole rule sits in one manual region over the
    batch; the weights' gradients (the taps', the scale's, `dt_bias`'s and
    `A_log`'s) are summed over the devices."""
    monkeypatch.setattr(L, "_TOKENS", 128)
    ops = mixer_operands(layout, 128, seed=2)
    weights = (0, 1, 2, 4, 5) if per_channel(layout) else (0, 1, 2)
    sharding = KernelSharding(Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_axes=("dp",))
    with pltpu.force_tpu_interpret_mode(), jax.default_matmul_precision("highest"):
        sharded = kernel_mixer(layout, sharding=sharding)
        got = jax.jit(lambda *a: sharded(*a) + jax.grad(mixer_objective(sharded), weights)(*a))(*ops)
        alone = kernel_mixer(layout)
        want = alone(*ops) + jax.grad(mixer_objective(alone), weights)(*ops)
    for name, g, w in zip(["out", "states"] + [NAMES[per_channel(layout)][2 + i] for i in weights], got, want):
        # (a sum over two devices' halves is float32's in another order: 1.6e-7 of `A_log`'s gradient of 400)
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-6, rtol=1e-6, err_msg=name)


@pytest.mark.parametrize("layout_of", [L.linear_layout, L.kda_layout], ids=["qwen3_next", "kimi"])
def test_off_a_tpu_and_at_heads_of_64_the_passes_take_the_xla_form_and_it_is_counted(layout_of):
    narrow = layout_of(L.Heads(2, 64, 2, 64))  # no block of whole lanes holds a head of 64
    wide = layout_of(L.Heads(2, 128, 2, 128))  # the kernels' widths, but this is a CPU
    assert len(wide.counted) == 2 + per_channel(wide)
    given, fits = around(narrow, 128, jnp.float32), around(wide, 128, jnp.float32)
    before = dict(L.TOOK)
    assert L.mixer_form(given["x"], given["taps"], narrow) == "xla"
    assert L.mixer_form(fits["x"], fits["taps"], wide) == "xla"
    for name in wide.counted:
        assert L.TOOK[name + "_xla"] == before.get(name + "_xla", 0) + 2
        assert L.TOOK[name + "_pallas"] == before.get(name + "_pallas", 0)
    assert L.mixer_form(fits["x"], fits["taps"], wide, impl="pallas") == "pallas"
    for name in wide.counted:
        assert L.TOOK[name + "_pallas"] == before.get(name + "_pallas", 0) + 1


@SMALL_LAYOUTS
def test_what_the_passes_cannot_tile_is_left_to_the_xla_form(layout):
    """On a TPU (told so by a mesh of one) the kernels take whole heads of
    128 lanes, tiles of 128 tokens and at most eight taps; anything else is
    the XLA form's."""
    class OnTpu(KernelSharding):
        on_tpu = True

    sharding = OnTpu(Mesh(np.array(jax.devices()[:1]), ("dp",)), batch_axes=("dp",))
    fits = around(layout, 128, jnp.float32)
    assert L.mixer_form(fits["x"], fits["taps"], layout, sharding=sharding) == "pallas"
    assert L.mixer_form(fits["x"][:, :64], fits["taps"], layout, sharding=sharding) == "xla"  # half a tile
    assert L.mixer_form(fits["x"], jnp.zeros((fits["taps"].shape[0], 9)), layout, sharding=sharding) == "xla"
    narrow = L.kda_layout(L.Heads(2, 64, 2, 64)) if per_channel(layout) else L.linear_layout(L.Heads(2, 64, 4, 64))
    given = around(narrow, 128, jnp.float32)
    assert L.mixer_form(given["x"], given["taps"], narrow, sharding=sharding) == "xla"
    two = OnTpu(Mesh(np.array(jax.devices()[:2]), ("dp",)), batch_axes=("dp",))  # a device needs whole rows
    assert L.mixer_form(fits["x"], fits["taps"], layout, sharding=two) == "xla"
    rows = around(layout, 128, jnp.float32, batch=2)
    assert L.mixer_form(rows["x"], rows["taps"], layout, sharding=two) == "pallas"
