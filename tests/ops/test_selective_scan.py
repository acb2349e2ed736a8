"""Mamba-1's selective scan (ops/selective_scan.py) on the CPU in float32,
against the recurrence run token by token, written here in a few lines, and
its written backward against autodiff through that recurrence.

Tolerances, and why: in float32 the chunked form does the recurrence's
arithmetic in another order only where a chunk's END state is made (a sum of
`exp(A (D_last - D_p))` terms against a chunk's dependent multiply-adds) and
where the chunk starts are carried; inside a chunk it IS the recurrence from
the chunk's start. Measured worst error 1.1e-7 of the output's largest
magnitude, 2.9e-7 of a final state's and 4.7e-7 of a gradient's (A's and
dt's, sums over all tokens, are the worst); the limit is 2e-5. The same scan
with the carried state rounded to bfloat16 a token lies 2.1e-3 off: a hundred
times the limit.

The kernel form (`impl="pallas"`: `selscan_fwd`, `selscan_bwd`) runs the same
cases under `pltpu.force_tpu_interpret_mode()` at 256 channels in blocks of
128 and states of 8, the smallest the kernels' tiles take, and is held to the
recurrence AND to the XLA form by the same limit (measured worst 3.9e-7).
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import selective_scan as op
from galvatron_tpu.ops.selective_scan import CHUNK, selective_scan

TOL = 2e-5
B = 2
NAMES = ("x", "dt", "a", "b", "c", "d")


def recurrence(x, dt, a, b, c, d):
    """h = exp(dt A) h + dt x B^T; m = h C + D x, token by token."""
    def token(state, t):
        xt, dtt, bt, ct = t
        state = jnp.exp(dtt[..., None] * a) * state + (dtt * xt)[..., None] * bt[:, None, :]
        return state, jnp.einsum("bcn,bn->bc", state, ct) + d * xt

    ts = tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c))
    state, m = jax.lax.scan(token, jnp.zeros((x.shape[0], x.shape[2], a.shape[1])), ts)
    return jnp.moveaxis(m, 0, 1), state


KERNEL_C, KERNEL_N, KERNEL_BLOCK = 256, 8, 128  # two blocks of channels, a tile of sublanes of states


def operands(tokens, seed=0, form="xla"):
    """Decays exp(dt A) from 0.67 to 0.999 a token over channels and states,
    as Mamba-1's initialisation gives them (dt log-uniform in [1e-3, 0.1], A =
    -(1 .. N)): most of a chunk's state crosses its edge."""
    C, N = (KERNEL_C, KERNEL_N) if form == "pallas" else (24, 4)
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, tokens, C))
    dt = jnp.exp(jax.random.uniform(ks[1], (B, tokens, C), minval=np.log(1e-3), maxval=np.log(0.1)))
    a = -jnp.broadcast_to(jnp.arange(1.0, N + 1), (C, N)) * (1.0 + 0.1 * jax.random.uniform(ks[2], (C, N)))
    b, c = (jax.random.normal(k, (B, tokens, N)) for k in ks[3:5])
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (C,))
    return x, dt, a, b, c, d


@contextlib.contextmanager
def taking(form, monkeypatch):
    """The scan in `form`: the XLA form as the CPU runs it, or the kernels
    interpreted, in blocks of `KERNEL_BLOCK` channels."""
    if form == "xla":
        yield
        return
    monkeypatch.setattr(op, "CHANNELS", KERNEL_BLOCK)
    with pltpu.force_tpu_interpret_mode():
        yield


def worst(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


SIZES = [(64, 16), (64, 64), (50, 16), (37, 10), (200, CHUNK), (48, CHUNK)]
IDS = ["4_chunks", "1_chunk", "50_by_16", "37_by_10", "200_by_the_chunk", "shorter_than_a_chunk"]
# whole chunks; a padded rest; the chunk the layers run
KERNEL_SIZES = [(64, 16, "pallas"), (50, 16, "pallas"), (200, CHUNK, "pallas")]
CASES = [size + ("xla",) for size in SIZES] + KERNEL_SIZES
CASE_IDS = IDS + ["kernels_4_chunks", "kernels_50_by_16", "kernels_200_by_the_chunk"]


@pytest.mark.parametrize("tokens,chunk,form", CASES, ids=CASE_IDS)
def test_the_chunked_scan_is_the_recurrence(tokens, chunk, form, monkeypatch):
    """Chunks that do and do not divide the length (the rest is padded with
    dt = 0 and cut off), and chunks that are and are not whole blocks of the
    backward's `BLOCK`: outputs, final states and the counter. The kernels on
    a batch of 2 and two blocks of channels, against the XLA form too."""
    ops = operands(tokens, form=form)
    with forms.recording() as took, taking(form, monkeypatch):
        m, last, peak = selective_scan(*ops, chunk=chunk, impl=form)
    assert took == {forms.SELECTIVE_SCAN: {form: 1}}
    want_m, want_last = recurrence(*ops)
    assert m.shape == want_m.shape and last.shape == want_last.shape
    assert worst(m, want_m) < TOL and worst(last, want_last) < TOL
    assert float(peak) >= float(jnp.max(jnp.abs(want_last))) * (1 - TOL)
    if form == "pallas":
        xla_m, xla_last, xla_peak = selective_scan(*ops, chunk=chunk, impl="xla")
        assert worst(m, xla_m) < TOL and worst(last, xla_last) < TOL and abs(float(peak) / float(xla_peak) - 1) < TOL
    # state crosses chunk edges: the same tokens with the state cut at every edge read otherwise
    if tokens > chunk:
        cut = jnp.concatenate([selective_scan(*(t[:, i:i + chunk] if t.ndim == 3 else t for t in ops))[0]
                               for i in range(0, tokens, chunk)], axis=1)
        assert worst(cut, want_m) > 100 * TOL


@pytest.mark.parametrize("tokens,chunk,form",
                         [(50, 16, "xla"), (64, 64, "xla"), (37, 10, "xla"), (96, 32, "xla"),
                          (50, 16, "pallas"), (64, 64, "pallas"), (96, 32, "pallas")],
                         ids=["50_by_16", "1_chunk", "37_by_10", "3_chunks_of_4_blocks",
                              "kernels_50_by_16", "kernels_1_chunk", "kernels_3_chunks"])
def test_the_written_backward_is_autodiff_through_the_recurrence(tokens, chunk, form, monkeypatch):
    """Every operand's gradient of a random projection of m (the kernels': all
    six from `selscan_bwd` and what XLA adds up of its shares)."""
    ops = operands(tokens, seed=1, form=form)
    weight = jax.random.normal(jax.random.PRNGKey(9), ops[0].shape)
    with taking(form, monkeypatch):
        got = jax.grad(lambda *o: jnp.sum(selective_scan(*o, chunk=chunk, impl=form)[0] * weight),
                       argnums=tuple(range(6)))(*ops)
    want = jax.grad(lambda *o: jnp.sum(recurrence(*o)[0] * weight), argnums=tuple(range(6)))(*ops)
    off = {name: worst(g, w) for name, g, w in zip(NAMES, got, want)}
    assert max(off.values()) < TOL, off


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_carried_state_is_float32_under_bf16_compute(form, monkeypatch):
    """x and m in bfloat16, the state and what it is made of float32: the
    final state is float32 and the output lies within bfloat16's rounding of
    the float32 recurrence on the same (rounded) x; a state rounded to
    bfloat16 a token lies further off (the XLA form's alone: the kernels
    refuse one by name)."""
    x, dt, a, b, c, d = operands(128, seed=2, form=form)
    xb = x.astype(jnp.bfloat16)
    want_m, want_last = recurrence(xb.astype(jnp.float32), dt, a, b, c, d)
    with taking(form, monkeypatch):
        m, last, _ = selective_scan(xb, dt, a, b, c, d, impl=form)
        if form == "pallas":
            with pytest.raises(ValueError, match="float32 state"):
                selective_scan(xb, dt, a, b, c, d, impl=form, state_dtype=jnp.bfloat16)
    assert m.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert worst(last, want_last) < TOL  # the state never saw bfloat16
    assert worst(m.astype(jnp.float32), want_m) < 2 ** -8
    _, rounded, _ = selective_scan(xb, dt, a, b, c, d, state_dtype=jnp.bfloat16)
    assert worst(rounded, want_last) > 50 * TOL


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16], ids=["float32_state", "bf16_state"])
def test_auto_takes_the_xla_form_off_a_tpu_and_says_so(state_dtype, monkeypatch):
    """On the CPU, and for a state that is not float32 anywhere, `impl="auto"`
    is the XLA form at widths the kernels would take, and says so."""
    monkeypatch.setattr(op, "CHANNELS", KERNEL_BLOCK)
    ops = operands(CHUNK, seed=4, form="pallas")
    with forms.recording() as took:
        got = selective_scan(*ops, state_dtype=state_dtype)
    assert took == {forms.SELECTIVE_SCAN: {"xla": 1}}
    want = selective_scan(*ops, state_dtype=state_dtype, impl="xla")
    assert all(bool(jnp.all(g == w)) for g, w in zip(got, want))


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_gradients_come_in_the_operands_dtypes(form, monkeypatch):
    x, dt, a, b, c, d = operands(32, seed=3, form=form)
    xb, bb, cb = (t.astype(jnp.bfloat16) for t in (x, b, c))
    with taking(form, monkeypatch):
        grads = jax.grad(lambda *o: jnp.sum(selective_scan(*o, impl=form)[0].astype(jnp.float32)),
                         argnums=tuple(range(6)))(xb, dt, a, bb, cb, d)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32, jnp.float32, jnp.bfloat16, jnp.bfloat16,
                                        jnp.float32]
    assert all(bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))) for g in grads)
