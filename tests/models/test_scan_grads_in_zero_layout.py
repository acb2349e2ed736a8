"""A scanned run's parameter gradient is summed over dp where ZeRO stores it
(PR 55): `models/base.run_layers` reads a run's stacked leaves through
`spec.constrain_grad_as`, whose backward asks for the cotangent in
`stacked_layer_grad_specs`' layout, the one `grad_accum_specs` gives the
leaf (`spec.zero_split_spec`, stated once).

Held here on the CPU mesh: the values (a scanned step against the unrolled
one, whose gradients reach `to_accum` unconstrained, as before), the state's
layout, and the rule itself for every family of the registry. What the
COMPILER makes of the request, a reduce-scatter a layer and no all-reduce,
is a TPU compile's to show: tests/ops/test_scan_grad_sums.py."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import base as M
from galvatron_tpu.models import registry
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.obs import forms
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import layer_axes
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler, opt_state_specs

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]

B, SEQ, V = 8, 32, 256


def tiny_qwen(dtype, layers=4):
    """The Qwen2.5 test config: a LLaMA stack with a q, k, v bias, an untied head."""
    return llama_config("llama-0.3b", num_layers=layers, hidden_size=64, num_heads=4, ffn_hidden=128,
                        vocab_size=V, max_seq_len=SEQ, compute_dtype=dtype, qkv_bias=True)


def uniform(world, layers=4, **kw):
    kw.setdefault("default_dp_type", "zero2")
    return HybridParallelConfig.uniform(world, layers, global_bsz=B, **kw)


def two_runs(world):
    """Two scanned runs of different axes: tp 2 x dp 2, then dp 4."""
    return HybridParallelConfig(world_size=world, pp=1, layers=[LayerStrategy(tp=2)] * 2 + [LayerStrategy()] * 2,
                                global_bsz=B, default_dp_type="zero2", vocab_tp=2)


# name -> (layout, devices): ZeRO-2 layouts with a scanned run and no benchmark cell
LAYOUTS = {
    "tp2dp2": (lambda: uniform(4, tp=2, vocab_tp=2), 4),
    "dp4": (lambda: uniform(4), 4),
    "tp2dp2_no_megatron_sp": (lambda: uniform(4, tp=2, vocab_tp=2, sequence_parallel=False), 4),
    "cp2dp2": (lambda: uniform(4, cp=2), 4),
    "two_runs_of_different_axes": (lambda: two_runs(4), 4),
    "dp8_of_two_axes_chunks2": (lambda: uniform(8, chunks=2), 8),
    "tp2dp2_full_recomputation": (lambda: uniform(4, tp=2, vocab_tp=2, checkpoint=1), 4),
}


def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, SEQ), 0, V)
    return dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(SEQ), (B, SEQ)),
                labels=jnp.roll(tokens, -1, 1))


def one_sgd_step(cfg, hp, devices):
    """One step of the model's own train step under plain SGD at rate 1: the
    state it returns, and `params - new_params`, the gradient as the step
    accumulated it (`to_accum`, the microbatch loop and all)."""
    m = construct_hybrid_parallel_model(cfg, hp, devices)
    params = m.init_params(jax.random.PRNGKey(0))
    tx = optax.sgd(1.0)
    step = m.make_train_step(tx, donate=False)
    new, _, mets = step(params, m.init_opt_state(tx, params), m.shard_batch(batch()))
    return m, float(mets["loss"]), new, jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b), params, new)


def leaf_paths(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(tree)}


@pytest.mark.parametrize("name", list(LAYOUTS))
def test_a_scanned_step_gives_the_unrolled_steps_loss_and_gradient(name, devices8):
    """float32 compute, one step: under ZeRO-2 a scanned run's stacked
    cotangent is asked for in ZeRO's layout, the unrolled layers' reach
    `to_accum` unconstrained (`scan_layers=False`: the program before PR 55,
    layer for layer). Loss and every leaf of the gradient agree within
    tests/models/test_parallel_correctness.py's and
    tests/models/test_scan_layers.py's tolerances, and the scanned step hands
    the state back in `state_specs`."""
    make_hp, n = LAYOUTS[name]
    cfg = tiny_qwen(jnp.float32)
    with forms.recording() as took:
        m, loss, new, grads = one_sgd_step(cfg, make_hp(), devices8[:n])
    runs = 2 if name.startswith("two_runs") else 1
    # a Qwen layer's leaves: two norm scales, wqkv and its bias, wo, wi, wo_mlp: each has a dim to split
    # (a leaf the microbatch loop traces a second time is the same leaf)
    assert took[forms.SCAN_GRADS] == {"zero_layout": 7 * runs}
    unrolled = make_hp()
    unrolled.scan_layers = False
    with forms.recording() as took:
        _, want_loss, _, want = one_sgd_step(cfg, unrolled, devices8[:n])
    assert forms.SCAN_GRADS not in took
    assert abs(loss - want_loss) < 2e-5, (loss, want_loss)
    for (path, a), b in zip(leaf_paths(grads).items(), jax.tree.leaves(want)):
        assert np.abs(b).max() > 0, path
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-5, err_msg=path)
    for (path, leaf), spec in zip(leaf_paths(new).items(),
                                  jax.tree.leaves(m.state_specs(), is_leaf=lambda x: isinstance(x, P))):
        assert leaf.sharding.is_equivalent_to(NamedSharding(m.mesh, spec), leaf.ndim), (path, leaf.sharding, spec)


def test_a_scanned_bf16_step_stays_within_a_step_of_bf16_of_the_unrolled_one(devices8):
    """The four-chip cell's flags in bf16 (tp 2 x dp 2, ZeRO-2, the copy):
    the sums are bf16 sums partitioned otherwise, so a leaf's gradient may
    differ from the unrolled step's in a last bit of bf16 at the leaf's
    scale, and by no more; the first loss, whose forward nothing touched, is
    the unrolled step's."""
    cfg, make_hp = tiny_qwen(jnp.bfloat16), LAYOUTS["tp2dp2_full_recomputation"][0]
    _, loss, _, grads = one_sgd_step(cfg, make_hp(), devices8[:4])
    unrolled = make_hp()
    unrolled.scan_layers = False
    _, want_loss, _, want = one_sgd_step(cfg, unrolled, devices8[:4])
    assert abs(loss - want_loss) < 2e-5, (loss, want_loss)
    for (path, a), b in zip(leaf_paths(grads).items(), jax.tree.leaves(want)):
        one_step = 2.0 ** (np.floor(np.log2(np.abs(b).max())) - 7)
        assert np.abs(a - b).max() <= one_step, (path, np.abs(a - b).max(), one_step)


# ------------------------------------------------------------- the rule, once
def rule_before_pr55(param_spec, shape, dp_axes, mesh_shape):
    """`runtime/optimizer._shard_moment_spec` as it stood before it moved to
    `parallel/spec.zero_split_spec`, word for word: the oracle."""
    if not dp_axes:
        return param_spec
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh_shape[a]
    used = set()
    for e in entries:
        if e is None:
            continue
        for x in (e if isinstance(e, tuple) else (e,)):
            used.add(x)
    if any(a in used for a in dp_axes):
        return param_spec
    for i, e in enumerate(entries):
        if e is None and shape[i] % dp_size == 0:
            entries[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            return P(*entries)
    return param_spec


def family_model(name, devices, dp_type):
    """A family of the registry at its default size's widths, cut to two
    layers where its config takes the cut (the encoder-decoder and the
    windowed vision family at their test sizes), under dp 8 over two mesh
    axes: shapes and specs only, nothing is initialised."""
    fam = registry.get_family(name)
    if name in ("t5", "swin"):
        cfg = fam.config_fn("%s-test" % name)
        layers = cfg.num_enc_layers + cfg.num_dec_layers if name == "t5" else sum(cfg.depths)
    else:
        cfg = fam.config_fn(fam.default_size, num_layers=2)
        layers = cfg.num_layers
    hp = HybridParallelConfig.uniform(8, layers, global_bsz=8, default_dp_type=dp_type, vocab_tp=1)
    return (fam.build or construct_hybrid_parallel_model)(cfg, hp, devices)


@pytest.mark.parametrize("dp_type", ["zero2", "zero3", "ddp"])
@pytest.mark.parametrize("name", registry.family_names())
def test_the_moved_zero_rule_places_every_familys_leaves_as_before(name, dp_type, devices8):
    """`grad_accum_specs` and `opt_state_specs`, the callers of the rule at
    its old address, give every leaf of every family's `param_specs` the
    spec the old function gave it; and a scanned run's cotangent specs
    (`stacked_layer_grad_specs`) are those very specs behind the stack axis:
    what the scan's body is asked for is what `to_accum` asks for."""
    m = family_model(name, devices8, dp_type)
    shapes, mesh_shape = m.abstract_params(), dict(m.mesh.shape)
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    want = jax.tree.map(lambda spec, a, zax: rule_before_pr55(spec, a.shape, tuple(zax), mesh_shape),
                        m.param_specs, shapes, m.zero_axes_tree(), is_leaf=is_spec)
    accum = m.grad_accum_specs()
    assert leaf_paths(jax.tree.map(str, accum, is_leaf=is_spec)) == leaf_paths(jax.tree.map(str, want, is_leaf=is_spec))
    split = sum(a != b for a, b in zip(jax.tree.leaves(accum, is_leaf=is_spec),
                                       jax.tree.leaves(m.param_specs, is_leaf=is_spec)))
    assert (split > 0) == (dp_type != "ddp"), split

    tx, _ = get_optimizer_and_scheduler(OptimizerArgs())
    state = opt_state_specs(jax.eval_shape(tx.init, shapes), m.param_specs, shapes, m.zero_axes_tree(), m.mesh)
    mu = next(s.mu for s in jax.tree.leaves(state, is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
              if isinstance(s, optax.ScaleByAdamState))
    moments = leaf_paths(jax.tree.map(str, mu, is_leaf=is_spec))
    assert moments and all(leaf_paths(jax.tree.map(str, want, is_leaf=is_spec))[p] == s for p, s in moments.items())

    if "layers" not in m.param_specs:
        return  # the encoder-decoder and the windowed family run no scan
    kinds = m.cfg.layer_kinds()
    for i, layer in enumerate(shapes["layers"]):
        stacked = jax.tree.map(lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype), layer)
        got = M.stacked_layer_grad_specs(m.cfg.layer_config(kinds[i]), layer_axes(m.hp, i), stacked, m.mesh)
        assert jax.tree.leaves(got, is_leaf=is_spec) == [
            P(None, *s) for s in jax.tree.leaves(accum["layers"][i], is_leaf=is_spec)], (name, i)


def test_equal_specs_give_the_plain_constraint(devices8):
    """`constrain_grad_as` with one spec for both ways traces what
    `constrain` traces: a layout ZeRO does not split keeps its jaxpr."""
    mesh = jax.sharding.Mesh(np.array(devices8[:4]).reshape(2, 2), ("a", "b"))
    x = jnp.ones((4, 4))
    plain = jax.make_jaxpr(lambda t: S.constrain(t, mesh, P(None, "b")))(x)
    same = jax.make_jaxpr(lambda t: S.constrain_grad_as(t, mesh, P(None, "b"), P(None, "b")))(x)
    assert str(plain) == str(same) and "custom_vjp" not in str(same)
    other = jax.make_jaxpr(jax.grad(lambda t: jnp.sum(S.constrain_grad_as(t, mesh, P(None, "b"), P("a", "b")) ** 2)))(x)
    assert "custom_vjp_call" in str(other) and "PartitionSpec('a', 'b')" in str(other)
