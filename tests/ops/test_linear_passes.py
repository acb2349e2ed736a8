"""The Pallas passes around a delta rule's core (`conv_norm_*`, `gated_norm_*`, `kda_gate_*`), interpreted, at both
mixers' layouts against the XLA form (operands, oracles and tolerances: tests/ops/linear_attention_cases.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import linear_attention as L
from galvatron_tpu.ops.attention import KernelSharding
from tests.ops.linear_attention_cases import (EPS, LAYOUTS, SMALL_LAYOUTS, TOL, around, per_channel,
                                              value_heads_shares, worst, xla_after, xla_before, xla_gate)


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
        q, k, v = jax.jit(L._conv_norm, static_argnums=0)(layout.qkv, given["x"], given["taps"])
        dwithin, do, dscale = jax.jit(L._gated_norm_bwd, static_argnums=(0, 1))(
            layout, EPS, given["o"], given["within"], given["scale"], given["dout"])
        assert dwithin.shape == given["within"].shape and dwithin.dtype == dtype
        dz = dwithin[..., -given["o"].shape[-1]:]
        dx, dtaps = jax.jit(L._conv_norm_bwd, static_argnums=0)(
            layout.qkv, given["x"], given["taps"], (value_heads_shares(layout, given["dq"]),
                                                    value_heads_shares(layout, given["dk"]), given["dv"]),
            dwithin if inside else None)
        assert dx.shape == given["x"].shape and dx.dtype == dtype
        got = dict(q=q, k=k, v=v, gated=jax.jit(L._gated_norm, static_argnums=(0, 1))(layout, EPS, given["o"], given["within"], given["scale"]),
                   dqkv=dx[..., :cut], dtaps=dtaps, do=do, dz=dz, dscale=dscale)
        if per_channel(layout):
            got["g"] = L._channel_gate(layout, given["f"], given["dt_bias"], given["a_log"])
            assert got["g"].dtype == jnp.float32  # whatever f came in
            got.update(zip("df ddt_bias da_log".split(), L._channel_gate_bwd(
                layout, given["f"], given["dt_bias"], given["a_log"], given["dg"])))
            assert got["df"].dtype == dtype
    if inside:  # two backwards filled one array: the second left the first's columns as they were
        np.testing.assert_array_equal(np.asarray(dx[..., cut:], np.float32), np.asarray(dz, np.float32))
    want, rounded = jax.jit(xla)(exact), jax.jit(xla)(given)
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


@pytest.mark.parametrize("layout_of", [L.linear_layout, L.kda_layout], ids=["qwen3_next", "kimi"])
def test_off_a_tpu_and_at_heads_of_64_the_passes_take_the_xla_form_and_it_is_counted(layout_of):
    narrow = layout_of(L.Heads(2, 64, 2, 64))  # no block of whole lanes holds a head of 64
    wide = layout_of(L.Heads(2, 128, 2, 128))  # the kernels' widths, but this is a CPU
    assert len(wide.counted) == 2 + per_channel(wide)
    given, fits = around(narrow, 128, jnp.float32), around(wide, 128, jnp.float32)
    with forms.recording() as took:
        assert L.mixer_form(given["x"], given["taps"], narrow) == "xla"
        assert L.mixer_form(fits["x"], fits["taps"], wide) == "xla"
        assert took == {part: {"xla": 2} for part in wide.counted}
        assert L.mixer_form(fits["x"], fits["taps"], wide, impl="pallas") == "pallas"
    assert took == {part: {"xla": 2, "pallas": 1} for part in wide.counted}


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
