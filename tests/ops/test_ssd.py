"""Mamba-2's chunked scan (ops/ssd.py) on the CPU in float32, against the
recurrence run token by token, written here in a few lines.

Tolerances, and why: in float32 the chunked form does the recurrence's
arithmetic in another order (a chunk's decay mask `exp(G_t - G_j)` and batched
matmuls against 40 to 200 dependent multiply-adds of the state). The running
sum `G` of `dt A` reaches -30 inside a chunk at these decays, so a difference
of two of its values carries `30 x 6e-8 = 2e-6` of absolute error into the
exponent: measured worst error 4e-6 of the output's largest magnitude, 6e-6
of a gradient's (the gradients of `A` and `dt`, sums of such differences over
all tokens, are the worst); the limit is 5e-5. The same core with the carried
state rounded to bfloat16 a chunk lies 1e-3 off: twenty times the limit.

The kernel form (`impl="pallas"`: `ssd_fwd`, `ssd_bwd`) runs the same cases
under `pltpu.force_tpu_interpret_mode()` on one row of the batch in blocks of
`KERNEL_BLOCK` heads (4 heads: two blocks of one group, a block each of two
groups, or two groups of one head a block), and is held to the recurrence AND
to the XLA form by the same limit (measured worst 1e-6, a gradient of `A`). Its backward is written (`jax.custom_vjp`) and reads y's
cotangent alone, so the kernels' gradient cases weigh y and not the final state.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import ssd as op
from galvatron_tpu.ops.ssd import CHUNK, HEADS_AT_ONCE, ssd_scan

TOL = 5e-5
B, H, P, N = 2, 4, 8, 16
KERNEL_BLOCK = 2  # heads a grid step of the interpreted kernels holds
FORMS = ["xla", "pallas"]


def recurrence(x, dt, a, bm, cm, d):
    """h = exp(dt A) h + dt x B^T; y = h C + D x, token by token; B and C one
    group's (B, S, N) or a group's a head (B, S, groups, N)."""
    if bm.ndim == 4:
        bm, cm = (jnp.repeat(t, H // t.shape[2], axis=2) for t in (bm, cm))
    else:
        bm, cm = (jnp.broadcast_to(t[:, :, None], t.shape[:2] + (H, N)) for t in (bm, cm))

    def token(state, t):
        xt, dtt, bt, ct = t
        state = (jnp.exp(dtt * a)[..., None, None] * state
                 + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return state, jnp.einsum("bhps,bhs->bhp", state, ct) + d[:, None] * xt

    ts = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm))
    state, y = jax.lax.scan(token, jnp.zeros((x.shape[0], H, P, N)), ts)
    return jnp.moveaxis(y, 0, 1), state


def operands(tokens, seed=0, groups=1, form="xla"):
    """Decays exp(dt A) from 0.2 to 0.999 a token over the heads, as the
    Mamba-2 initialisation gives them: dt log-uniform in [1e-3, 0.1], A in [1,
    16]. One row of the batch for the interpreted kernels (a grid step a row,
    block and chunk, each interpreted), `B` for the XLA form."""
    rows = 1 if form == "pallas" else B
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (rows, tokens, H, P))
    dt = jnp.exp(jax.random.uniform(ks[1], (rows, tokens, H), minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    bm, cm = (jax.random.normal(k, (rows, tokens, N) if groups == 1 else (rows, tokens, groups, N)) for k in ks[3:5])
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, a, bm, cm, d


@contextlib.contextmanager
def taking(form, monkeypatch):
    """The scan in `form`: the XLA form as the CPU runs it, or the kernels
    interpreted, in blocks of `KERNEL_BLOCK` heads."""
    if form == "xla":
        yield
        return
    monkeypatch.setattr(op, "CHANNELS", KERNEL_BLOCK * P)
    with pltpu.force_tpu_interpret_mode():
        yield


def worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


SIZES = [(64, 16), (64, 64), (50, 16), (37, 10), (200, 64), (48, CHUNK)]
IDS = ["4_chunks", "1_chunk", "50_by_16", "37_by_10", "200_by_64", "shorter_than_a_chunk"]
# whole chunks, one group in two blocks of heads; a padded rest, a block each of two groups; three chunks, two groups a block
KERNEL_SIZES = [(64, 16, 1, "pallas"), (50, 16, 2, "pallas"), (48, 16, 4, "pallas")]
CASES = [size + (1, "xla") for size in SIZES] + KERNEL_SIZES
CASE_IDS = IDS + ["kernels_4_chunks", "kernels_50_by_16_2_groups", "kernels_3_chunks_4_groups"]


@pytest.mark.parametrize("tokens,chunk,groups,form", CASES, ids=CASE_IDS)
def test_the_chunked_scan_is_the_recurrence(tokens, chunk, groups, form, monkeypatch):
    """Chunks that do and do not divide the length (the rest is padded with
    dt = 0 and cut off), outputs, final states and the counter; the kernels
    against the XLA form too, and what each says it took."""
    ops = operands(tokens, groups=groups, form=form)
    with jax.default_matmul_precision("highest"):
        with forms.recording() as took, taking(form, monkeypatch):
            y, last, peak = jax.jit(lambda *o: ssd_scan(*o, chunk=chunk, heads_at_once=2, impl=form))(*ops)
        want_y, want_last = recurrence(*ops)
    plural = "" if groups == 1 else "s"
    said = ("pallas: %d group%s x %d heads a block" % (groups, plural, KERNEL_BLOCK)
            if form == "pallas" else "%d group%s x 2 heads at once" % (groups, plural))
    assert took == {forms.SSD: {said: 1}}
    assert y.shape == want_y.shape and last.shape == (ops[0].shape[0], H, P, N)
    assert worst(y, want_y) < TOL and worst(last, want_last) < TOL
    assert float(peak) >= float(jnp.max(jnp.abs(want_last))) * (1 - TOL)
    if form == "pallas":
        with jax.default_matmul_precision("highest"):
            xla_y, xla_last, xla_peak = jax.jit(lambda *o: ssd_scan(*o, chunk=chunk, impl="xla"))(*ops)
        assert worst(y, xla_y) < TOL and worst(last, xla_last) < TOL and abs(float(peak) / float(xla_peak) - 1) < TOL


@pytest.mark.parametrize("tokens,chunk,groups,form",
                         [(64, 16, 1, "xla"), (50, 16, 1, "xla"), (64, 16, 1, "pallas"), (50, 16, 2, "pallas")],
                         ids=["divides", "does_not_divide", "kernels_divides", "kernels_does_not_divide_2_groups"])
def test_every_gradient_is_the_recurrences(tokens, chunk, groups, form, monkeypatch):
    """x, dt, A, B, C and D, through the kept chunk-start states and the
    recomputation within a chunk (the kernels': all six from `ssd_bwd` and
    what XLA adds up of its shares); A_log and dt_bias reach the scan as A
    and dt. The XLA form's through the final state too."""
    ops = operands(tokens, seed=3, groups=groups, form=form)
    weights = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)

    def through(scan):
        def loss(x, dt_raw, a_log, bm, cm, d):
            y, last = scan(x, jax.nn.softplus(dt_raw), -jnp.exp(a_log), bm, cm, d)[:2]
            return jnp.sum(weights * y) + (jnp.sum(jnp.sin(last)) if form == "xla" else 0.0)

        return jax.jit(jax.grad(loss, argnums=tuple(range(6))))

    x, dt, a, bm, cm, d = ops
    raw = (x, jnp.log(jnp.expm1(dt)), jnp.log(-a), bm, cm, d)
    with jax.default_matmul_precision("highest"):
        with taking(form, monkeypatch):
            got = through(lambda *o: ssd_scan(*o, chunk=chunk, heads_at_once=2, impl=form))(*raw)
        want = through(recurrence)(*raw)
    for name, g, w in zip(("x", "dt_bias", "A_log", "B", "C", "D"), got, want):
        assert g.shape == w.shape and worst(g, w) < TOL, (name, worst(g, w))


@pytest.mark.parametrize("form", FORMS)
def test_state_crosses_the_chunk_edges(form, monkeypatch):
    """With the state dropped at every chunk's start the outputs past the
    first chunk are far off: what the scan carries is in the result."""
    ops = operands(64, form=form)
    with jax.default_matmul_precision("highest"), taking(form, monkeypatch):
        scan = jax.jit(lambda *o: ssd_scan(*o, chunk=16, impl=form)[0])
        y = scan(*ops)
        alone = jnp.concatenate([scan(*(t[:, i:i + 16] if t.ndim > 1 else t for t in ops))
                                 for i in range(0, 64, 16)], axis=1)
    assert worst(alone[:, :16], y[:, :16]) < TOL
    assert worst(alone[:, 16:], y[:, 16:]) > 0.05


def test_a_bfloat16_state_fails_the_tolerance():
    """The control: the same core with the carried state rounded to bfloat16 a
    chunk is outside the limit the float32 state meets (the XLA form's alone:
    the kernels refuse one by name)."""
    ops = operands(200)
    with jax.default_matmul_precision("highest"):
        want_y, want_last = recurrence(*ops)
        y32, last32, _ = ssd_scan(*ops, chunk=16)
        y16, last16, _ = ssd_scan(*ops, chunk=16, state_dtype=jnp.bfloat16)
    assert worst(y32, want_y) < TOL and worst(last32, want_last) < TOL
    assert worst(y16, want_y) > 10 * TOL and worst(last16, want_last) > 10 * TOL
    with pytest.raises(ValueError, match="float32 state"):
        ssd_scan(*ops, chunk=16, state_dtype=jnp.bfloat16, impl="pallas")


@pytest.mark.parametrize("at_once", [1, 2, 3, 4, HEADS_AT_ONCE])
def test_the_heads_worked_at_once_do_not_change_the_result(at_once):
    """`heads_at_once` takes the largest divisor of the heads up to it (3 of 4
    heads: 2), and a group's result is its heads' own."""
    ops = operands(64)
    with jax.default_matmul_precision("highest"):
        y, last, _ = ssd_scan(*ops, chunk=16, heads_at_once=at_once)
        want_y, want_last = recurrence(*ops)
    assert worst(y, want_y) < TOL and worst(last, want_last) < TOL


@pytest.mark.parametrize("form", FORMS)
def test_bfloat16_operands_keep_a_float32_state(form, monkeypatch):
    """bf16 x, B, C (the compute dtype of a bf16 model): the output comes back
    in bf16, the final state in float32 and within bf16 rounding of the
    recurrence on the same operands (2^-8 of its magnitude)."""
    x, dt, a, bm, cm, d = operands(128, form=form)
    x16, b16, c16 = (t.astype(jnp.bfloat16) for t in (x, bm, cm))
    with taking(form, monkeypatch):
        y, last, _ = ssd_scan(x16, dt, a, b16, c16, d, chunk=32, impl=form)
    with jax.default_matmul_precision("highest"):
        want_y, want_last = recurrence(*(t.astype(jnp.float32) for t in (x16, dt, a, b16, c16, d)))
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert worst(last, want_last) < 1e-4  # float32 operands at the highest precision
    assert worst(y.astype(jnp.float32), want_y) < 2 ** -6


@pytest.fixture(scope="module")
def kernel_bf16_gradients():
    """The kernels' six gradients of a weighted sum of y on bf16 x, B, C (two
    chunks of 128), beside the operands and the weights: made once for the two
    cases that read them (an interpreted backward is 5 s a case)."""
    x, dt, a, bm, cm, d = operands(256, form="pallas")
    ops = (x.astype(jnp.bfloat16), dt, a, bm.astype(jnp.bfloat16), cm.astype(jnp.bfloat16), d)
    weights = jax.random.normal(jax.random.PRNGKey(6), x.shape)
    with pytest.MonkeyPatch.context() as patch, taking("pallas", patch):
        grads = jax.jit(jax.grad(lambda *o: jnp.sum(ssd_scan(*o, impl="pallas")[0].astype(jnp.float32) * weights),
                                 argnums=tuple(range(6))))(*ops)
    return ops, weights, grads


def test_what_cancels_in_the_decays_gradient_cancels_under_bf16_operands(kernel_bf16_gradients):
    """dt's and A's gradients sum, over the pairs of tokens of a chunk, the
    rows' sums less the columns' sums of ONE matrix (dW * W), of which all but
    the pairs on either side of a token cancels. The kernels make the two sums
    as two products; they must read the same rounded operands, or what should
    cancel is left as rounding: with bf16 x, B, C at chunks of 128 the kernels'
    gradients lie as near the float32 ones as the XLA form's (measured 0.0026
    and 0.0023 of their norms, the XLA form's 0.0031 and 0.0017; with a float32
    `dt x` in the columns' sums alone A's read 0.030)."""
    ops, weights, got = kernel_bf16_gradients
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda *o: jnp.sum(ssd_scan(*o, impl="xla")[0] * weights), argnums=(1, 2)))(
            *(t.astype(jnp.float32) for t in ops))
    off = [float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) for g, w in zip(got[1:3], want)]
    assert max(off) < 6e-3, off


@pytest.mark.parametrize("form", FORMS)
def test_gradients_come_in_the_operands_dtypes(form, request):
    if form == "pallas":
        ops, _, grads = request.getfixturevalue("kernel_bf16_gradients")
    else:
        x, dt, a, bm, cm, d = operands(32, seed=3, groups=2)
        ops = (x.astype(jnp.bfloat16), dt, a, bm.astype(jnp.bfloat16), cm.astype(jnp.bfloat16), d)
        grads = jax.jit(jax.grad(lambda *o: jnp.sum(ssd_scan(*o, chunk=16, impl="xla")[0].astype(jnp.float32)),
                                 argnums=tuple(range(6))))(*ops)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
                                        jnp.float32]
    assert [g.shape for g in grads] == [t.shape for t in ops]
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16], ids=["float32_state", "bf16_state"])
def test_auto_takes_the_xla_form_off_a_tpu_and_says_so(state_dtype):
    """On the CPU, and for a state that is not float32 anywhere, `impl="auto"`
    is the XLA form at widths the kernels would take (8 heads of 64 with
    states of 128, a chunk of 128), under the string it always said."""
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (1, 128, 8, 64))
    dt = jnp.exp(jax.random.uniform(ks[1], (1, 128, 8), minval=np.log(1e-3), maxval=np.log(0.1)))
    bm, cm = (jax.random.normal(k, (1, 128, 128)) for k in ks[2:])
    ops = (x, dt, -jnp.arange(1.0, 9.0), bm, cm, jnp.ones((8,)))
    with forms.recording() as took:
        got = jax.jit(lambda *o: ssd_scan(*o, state_dtype=state_dtype))(*ops)
        want = jax.jit(lambda *o: ssd_scan(*o, state_dtype=state_dtype, impl="xla"))(*ops)
    assert took == {forms.SSD: {"1 group x 8 heads at once": 2}}  # both calls, under the one string
    assert all(bool(jnp.all(g == w)) for g, w in zip(got, want))
