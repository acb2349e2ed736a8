"""The vocabulary-split embedding (models/parts/embed_head.vocab_parallel_lookup): a
masked local gather and one sum over the vocabulary's tp axes must give the
unsplit ``wte[tokens]`` and its table gradient, whatever the layout around
it, and the compiled program must hold the lookup and not a one-hot matmul.
Its second form, for a table stored split over the ZeRO axes on its hidden
dim (ZeRO-3's, and the one ZeRO-2's step keeps in the moments' layout), must
give the first form's rows to the bit and its gradient to float32 rounding,
laid out as the table is, with nothing of the table's size crossing dp."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.parts.embed_head import table_split_axes, vocab_parallel_lookup
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import build_mesh, mesh_axis_size, pipeline_vocab_axes
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]

V, H, B, SEQ = 64, 16, 4, 8


def _layout(devices8, vtp, dp, **kw):
    hp = HybridParallelConfig.uniform(
        vtp * dp * kw.get("pp", 1) * kw.get("vocab_cp", 1), max(2, kw.get("pp", 1)), tp=vtp, vocab_tp=vtp,
        cp=kw.get("vocab_cp", 1), global_bsz=B, **kw)
    # (the axes the vocabulary layers compute under: `vocab_axes` at pp = 1)
    return build_mesh(hp, devices8[: hp.world_size]), pipeline_vocab_axes(hp)


def _tokens(vtp, shape=(B, SEQ)):
    """Random ids, then both ends of every shard's rows, then one id repeated
    over a whole row (the gradient's accumulation)."""
    tok = np.array(jax.random.randint(jax.random.PRNGKey(1), shape, 0, V))
    flat = tok.reshape(-1)
    rows = V // vtp
    ends = [e for r in range(vtp) for e in (r * rows, (r + 1) * rows - 1)]
    flat[: len(ends)] = ends[: flat.size]
    tok = flat.reshape(shape)
    if shape[0] > 1:
        tok[1] = rows + 3
    return jnp.asarray(tok)


def _check(mesh, vax, tokens, dtype, table_spec=None):
    """The lookup of a table stored as `table_spec` (None: as `param_specs`
    places it) against the unsplit gather -> (compiled gradient's text, rows,
    gradient)."""
    wte = jax.random.normal(jax.random.PRNGKey(0), (V, H), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(2), tokens.shape + (H,), jnp.float32)
    w_sh = jax.device_put(wte, NamedSharding(mesh, table_spec or S.vocab_embed_spec(vax)))

    def split(w, t):
        return vocab_parallel_lookup(w, t, dtype, mesh, vax, table_spec)

    def whole(w, t):  # gather, then cast: the gradient accumulates in float32
        return w[t].astype(dtype)

    def pulled(f):
        return lambda w, t: jnp.sum(f(w, t).astype(jnp.float32) * ct)

    out, ref = jax.jit(split)(w_sh, tokens), whole(wte, tokens)
    assert out.dtype == dtype and out.shape == ref.shape
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(ref, np.float32))
    grad_fn = jax.jit(jax.grad(pulled(split))).lower(w_sh, tokens).compile()
    grad = grad_fn(w_sh, tokens)
    assert grad.dtype == jnp.float32
    np.testing.assert_allclose(grad, jax.grad(pulled(whole))(wte, tokens), rtol=1e-5, atol=1e-5)
    return grad_fn.as_text(), out, grad


def _count(hlo, op):
    return len(re.findall(r" %s(?:-start)?\(" % re.escape(op), hlo))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("dp", [1, 2])
@pytest.mark.parametrize("vtp", [2, 4])
def test_split_lookup_matches_whole_table(devices8, vtp, dp, dtype):
    mesh, vax = _layout(devices8, vtp, dp, sequence_parallel=False)
    hlo = _check(mesh, vax, _tokens(vtp), dtype)[0]
    # the lookup it stands for: no matmul, one local scatter-add, and the only
    # collectives are sums (tp forward, dp for the table), never a permute
    assert _count(hlo, "dot") == 0 and _count(hlo, "scatter") == 1
    assert _count(hlo, "collective-permute") == 0 and _count(hlo, "all-to-all") == 0


LAYOUTS = {
    "megatron_sp": dict(sequence_parallel=True),  # sum lands in sequence shards
    "embed_sdp": dict(embed_sdp=1),               # ZeRO-3 on the table's hidden dim
    "vocab_cp2": dict(vocab_cp=2),                # tokens split over the sequence too
}


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_split_lookup_under_layout(devices8, name):
    mesh, vax = _layout(devices8, 2, 2, **LAYOUTS[name])
    hlo = _check(mesh, vax, _tokens(2), jnp.bfloat16)[0]
    assert _count(hlo, "dot") == 0 and _count(hlo, "collective-permute") == 0


# name -> (vocab_tp, dp, the pipeline): the scan pipeline's table is split over
# ('pp',) + vocab_tp (mesh.pipeline_vocab_axes) and looked up on the full mesh
OVER_PP = {
    "pp2_vtp2_dp2": (2, 2, dict(pp=2, chunks=2)),                            # the benchmark cell's, with a dp axis
    "pp2_vtp1_dp2_zero2": (1, 2, dict(pp=2, chunks=2, default_dp_type="zero2")),  # pp alone
    "pp4_vtp2": (2, 1, dict(pp=4, chunks=4)),
    "pp2_vtp2_dp2_embed_sdp": (2, 2, dict(pp=2, chunks=2, embed_sdp=1)),     # and its rows over dp
    "pp2_vtp2_dp2_megatron_sp": (2, 2, dict(pp=2, chunks=2, sequence_parallel=True)),
}


@pytest.mark.parametrize("name", list(OVER_PP))
def test_split_lookup_over_pp_and_vocab_tp(devices8, name):
    """Ids of every shard's first and last row: each chip owns 1/(pp x vocab_tp)
    of the rows, the sum runs over pp and tp, and neither the table nor its
    gradient is gathered, summed over pp or permuted."""
    vtp, dp, kw = OVER_PP[name]
    mesh, vax = _layout(devices8, vtp, dp, **kw)
    assert vax.tp[0] == "pp" and len(vax.tp) == (2 if vtp > 1 else 1)
    shards = mesh_axis_size(mesh, vax.tp)
    assert shards == kw["pp"] * vtp
    hlo, _, grad = _check(mesh, vax, _tokens(shards), jnp.bfloat16)
    assert grad.sharding.is_equivalent_to(NamedSharding(mesh, S.vocab_embed_spec(vax)), 2), grad.sharding
    assert _count(hlo, "dot") == 0 and _count(hlo, "scatter") == 1
    assert _count(hlo, "collective-permute") == 0 and _count(hlo, "all-gather") == (1 if vax.zero3 else 0)


# ------------------------------------------ the table split over the ZeRO axes
def _split_spec(vax):
    return P(S._ax(vax.tp), S._ax(vax.dp))


ROWS_OVER_DP = {
    "zero2_tp2dp2": dict(dp=2, default_dp_type="zero2"),       # the four-chip cell's layout
    "zero2_tp2dp4": dict(dp=4, default_dp_type="zero2"),       # dp of two mesh axes
    "zero2_no_sp": dict(dp=2, default_dp_type="zero2", sequence_parallel=False),
    "zero3_embed_sdp": dict(dp=2, embed_sdp=1),                # `param_specs` has the table so
    "zero2_vocab_cp2": dict(dp=2, default_dp_type="zero2", vocab_cp=2),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
@pytest.mark.parametrize("name", list(ROWS_OVER_DP))
def test_rows_over_dp_match_the_whole_table_form(devices8, name, dtype):
    """Repeated ids and ids of both tp halves, the table stored `P(tp, dp)`:
    the rows to the bit of today's form and of `wte.astype(dtype)[tokens]`,
    the gradient within float32 rounding of today's and laid out as the table,
    and in the compiled gradient no sum of anything over dp: ids gathered,
    cotangents exchanged, one local scatter-add."""
    kw = dict(ROWS_OVER_DP[name])
    mesh, vax = _layout(devices8, 2, kw.pop("dp"), **kw)
    tokens, spec = _tokens(2), _split_spec(vax)
    assert table_split_axes(spec, vax) == tuple(vax.dp)
    assert table_split_axes(P(S._ax(vax.tp), None), vax) == ()
    _, rows_whole, grad_whole = _check(mesh, vax, tokens, dtype, P(S._ax(vax.tp), None))
    hlo, rows, grad = _check(mesh, vax, tokens, dtype, spec)
    np.testing.assert_array_equal(np.asarray(rows, np.float32), np.asarray(rows_whole, np.float32))
    np.testing.assert_allclose(grad, grad_whole, rtol=1e-6, atol=1e-6)
    assert grad.sharding.is_equivalent_to(NamedSharding(mesh, spec), 2), grad.sharding
    assert _count(hlo, "dot") == 0 and _count(hlo, "scatter") == 1
    assert _count(hlo, "all-to-all") == 1 and _count(hlo, "collective-permute") == 0
    assert _count(hlo, "reduce-scatter") == 0
    # (tokens split over cp too: the table is whole over cp, and its cotangent summed there)
    assert _count(hlo, "all-reduce") == (1 if vax.cp else 0)


def test_a_zero3_table_is_looked_up_as_it_is_stored(devices8):
    """No spec handed in: `param_specs`' own decides, and ZeRO-3's splits the
    hidden dim over dp, so its table is no longer gathered whole in float32
    before the lookup. Under ZeRO-2 and ddp `param_specs` keeps it whole."""
    mesh, vax = _layout(devices8, 2, 2, embed_sdp=1)
    assert table_split_axes(S.vocab_embed_spec(vax), vax) == tuple(vax.dp)
    hlo = _check(mesh, vax, _tokens(2), jnp.bfloat16)[0]
    assert _count(hlo, "all-to-all") == 1 and _count(hlo, "all-reduce") == 0
    for kw in (dict(default_dp_type="zero2"), dict()):
        mesh, vax = _layout(devices8, 2, 2, **kw)
        assert table_split_axes(S.vocab_embed_spec(vax), vax) == ()


@pytest.mark.parametrize("shape", [(1, 8), (3, 8), (1, 1)], ids=["prefill_one_row", "three_rows", "one_token"])
def test_rows_over_dp_take_tokens_the_dp_axes_do_not_divide(devices8, shape):
    """Such tokens are whole on every replica: no id gather, no all_to_all,
    and the rows leave the region split over dp on the hidden dim."""
    mesh, vax = _layout(devices8, 2, 2, default_dp_type="zero2")
    hlo = _check(mesh, vax, _tokens(2, shape), jnp.float32, _split_spec(vax))[0]
    assert _count(hlo, "all-to-all") == 0 and _count(hlo, "collective-permute") == 0


@pytest.mark.parametrize("shape", [(4, 1), (1, 8), (1, 1)],
                         ids=["decode_slots", "prefill_one_row", "one_token"])
def test_split_lookup_takes_serve_shapes(devices8, shape):
    """serve/engine.py embeds (slots, 1) and (1, ctx): rows the batch axes do
    not divide stay whole on every device."""
    mesh, vax = _layout(devices8, 2, 2)
    _check(mesh, vax, _tokens(2, shape), jnp.float32)


@pytest.mark.parametrize("kw", [dict(), dict(embed_sdp=1), dict(default_dp_type="zero2")],
                         ids=["ddp", "embed_sdp", "zero2"])
def test_lm_loss_and_table_gradient_match_single_device(devices8, gpt_cfg, gpt_params, kw):
    from tests.conftest import gpt_batch

    batch = gpt_batch(0)
    batch["tokens"] = batch["tokens"].at[0].set(5)  # one id, a whole row
    want, want_g = jax.value_and_grad(M.lm_loss_fn)(gpt_params, batch, gpt_cfg)
    hp = HybridParallelConfig.uniform(8, gpt_cfg.num_layers, tp=2, vocab_tp=2,
                                      global_bsz=batch["tokens"].shape[0], **kw)
    m = construct_hybrid_parallel_model(gpt_cfg, hp, devices8)
    got, got_g = jax.jit(jax.value_and_grad(m.loss_fn))(
        jax.device_put(gpt_params, m.shardings()), m.shard_batch(batch))
    assert abs(float(got) - float(want)) < 2e-5
    np.testing.assert_allclose(got_g["embed"]["wte"], want_g["embed"]["wte"], atol=2e-6)
