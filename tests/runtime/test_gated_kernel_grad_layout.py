"""The update reads its state where it lies: a gated (hidden, 2, ffn) up
kernel is read through `parts/mlp.grad_as_stored` (`models/base.run_layers`
decides where), whose backward holds the gradient as the matmul yields it and
asks for the leaf's own layout after that, so that the compiler reads the
gradient into the Adam update in the state's tiling and no longer copies
parameter, `mu` and `nu` into the gradient's and back every step. The state,
its shardings and a checkpoint are untouched. Compile-only checks against a
described v5e (no chip: nothing runs there) and value checks on the CPU."""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy, layer_runs
from galvatron_tpu.models import base as M
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.parts import mlp
from galvatron_tpu.obs import forms, report, telemetry
from galvatron_tpu.parallel.mesh import build_mesh
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

H, F, S = 512, 1024, 256  # whole (8, 128) tiles, S apart from H; the step compiles in about 6 s

_spec = importlib.util.spec_from_file_location(
    "compiled_steps", os.path.join(os.path.dirname(__file__), "..", "..", "scripts", "compiled_steps.py"))
compiled_steps = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(compiled_steps)


@pytest.fixture(scope="module")
def v5e():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this host
        pytest.skip("cannot describe a TPU topology here: %s" % e)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _swiglu(layers=2, **kw):
    return llama_config("llama-0.3b", num_layers=layers, hidden_size=H, num_heads=H // 128, ffn_hidden=F,
                        vocab_size=1024, max_seq_len=S, compute_dtype=jnp.bfloat16, **kw)


def _alone(n=2, **kw):
    """Every layer a run of its own (the remat flag alternates): unrolled."""
    return HybridParallelConfig(world_size=1, pp=1, global_bsz=1,
                                layers=[LayerStrategy(checkpoint=i % 2) for i in range(n)], **kw)


def _scanned(n=2, **kw):
    return HybridParallelConfig.uniform(1, n, global_bsz=1, checkpoint=1, **kw)


def _tx():
    return get_optimizer_and_scheduler(OptimizerArgs(lr=1e-2, warmup_steps=1, total_steps=10))[0]


def _compiled(model, tx, batch=1):
    def sds(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    params = model.abstract_params()
    one = jax.ShapeDtypeStruct((batch, S), jnp.int32)
    batch = {k: jax.ShapeDtypeStruct(one.shape, jnp.int32, sharding=NamedSharding(model.mesh, model._batch_spec_for(one)))
             for k in ("tokens", "positions", "labels")}
    return model.make_train_step(tx).lower(
        sds(params, model.shardings()),
        sds(jax.eval_shape(tx.init, params), model.opt_state_shardings(tx, params)), batch).compile()


def _moved(text, dims):
    """The float32 `copy` ops of an array of `dims` that stand alone: relayouts
    that read and write the array beside everybody else's work."""
    return [op for op in compiled_steps.copy_ops(text) if op[0] == "f32" and op[1] == dims and op[4]]


def _left_to_the_compiler(monkeypatch):
    monkeypatch.setattr(M, "_gated_grads_as_stored", lambda layers, *a, **k: layers)


def _yielded_in(text, dims):
    """The dtypes the backward's matmuls yield a `dims` gradient in, sorted."""
    return sorted(re.findall(r"= (\w+)\[%s\]\{[^ ]*\} convolution\(" % dims, text))


@pytest.mark.parametrize("relaid", [True, False], ids=["relaid", "left_to_the_compiler"])
def test_unrolled_swiglu_layers_no_longer_move_their_state_for_v5e(v5e, monkeypatch, relaid):
    """Two layers, each alone in its run. Left to the compiler the step
    copies parameter, `mu` and `nu` of both into the gradient's tiling (fused
    into the update's read) and back (6 `copy` ops that stand alone: the fact
    this PR starts from); read through `grad_as_stored` none is left, the six
    `wi`-shaped entry parameters are stored as the parent stored them, the
    state is donated whole as before, and the temporaries do not grow."""
    if not relaid:
        _left_to_the_compiler(monkeypatch)
    model = construct_hybrid_parallel_model(_swiglu(), _alone(), v5e[:1])
    tx = _tx()
    with forms.recording() as took:
        step = _compiled(model, tx)
    text = step.as_text()
    assert took[forms.GATED_KERNEL_GRADS]["as_stored"] == (2 if relaid else 0)
    assert len(_moved(text, "%d,2,%d" % (H, F))) == (0 if relaid else 6)
    entry = re.findall(r"%%(?:params|opt_state)\S* = f32\[%d,2,%d\](\{[^ ]*\}) parameter\(" % (H, F), text)
    assert len(entry) == 6 and all(lay.startswith("{2,1,0:T(2,128)") for lay in entry), entry
    memory = step.memory_analysis()
    params = model.abstract_params()
    state = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves((params, jax.eval_shape(tx.init, params))))
    # the parent's formula: every leaf of the state aliases its output (the
    # compiler counts a scalar's 4 bytes as a 512-byte tile and a [2] as 1 KiB)
    assert memory.output_size_in_bytes - memory.alias_size_in_bytes <= 2048
    assert 0 <= memory.alias_size_in_bytes - state <= 64 * 512
    TEMPS[relaid] = memory.temp_size_in_bytes
    YIELDED[relaid] = _yielded_in(text, "%d,2,%d" % (H, F))
    if len(TEMPS) == 2:
        assert TEMPS[True] <= TEMPS[False] + (1 << 20), TEMPS
        # an unrolled layer's gradient leaves the matmul in the compute dtype
        # with and without the rule: `held_in` states what the compiler did
        assert YIELDED[True] == YIELDED[False] == ["bf16", "bf16"], YIELDED


TEMPS, YIELDED = {}, {}


def test_a_scanned_run_is_left_as_it_is_for_v5e(v5e):
    """Both layers in ONE scanned run (the two one-chip Qwen cells' shape of
    program): the gradient arrives in the scan's buffer, which the compiler
    lays out after the state by itself (one fused relayout of a layer's
    gradient in the loop, no copy of the state): nothing is asked for."""
    model = construct_hybrid_parallel_model(_swiglu(), _scanned(), v5e[:1])
    with forms.recording() as took:
        text = _compiled(model, _tx()).as_text()
    assert forms.GATED_KERNEL_GRADS not in took
    assert not _moved(text, "%d,2,%d" % (H, F))  # no copy of a leaf of the state
    in_the_loop = [op for op in compiled_steps.copy_ops(text) if op[:2] == ("f32", "1,%d,2,%d" % (H, F))]
    assert len(in_the_loop) == 1 and not in_the_loop[0][4]  # the layer's gradient, fused into the stack's write


@pytest.mark.parametrize("relaid", [True, False], ids=["relaid", "left_to_the_compiler"])
def test_a_layer_beside_a_scanned_run_keeps_the_runs_float32_stack_for_v5e(v5e, monkeypatch, relaid):
    """Granite's shape of program: a scanned run of two and a layer alone.
    Left to the compiler EVERY layer's state moves (9 `copy` ops back into the
    state's tiling); read through `grad_as_stored` none does, the scanned
    run's gradient is stacked in float32 straight from the matmul as before
    (no relayout, no transient in the loop) and every matmul yields its
    gradient in the dtype it did."""
    if not relaid:
        _left_to_the_compiler(monkeypatch)
    hp = HybridParallelConfig(world_size=1, pp=1, global_bsz=1, layers=[LayerStrategy(checkpoint=c) for c in (1, 1, 0)])
    text = _compiled(construct_hybrid_parallel_model(_swiglu(3), hp, v5e[:1]), _tx()).as_text()
    assert len(_moved(text, "%d,2,%d" % (H, F))) == (0 if relaid else 9)
    assert not _moved(text, "1,%d,2,%d" % (H, F)) and not _moved(text, "2,%d,2,%d" % (H, F))
    BESIDE[relaid] = _yielded_in(text, "%d,2,%d" % (H, F))  # the lone layer's, the scanned body's
    assert len(BESIDE[relaid]) == 2 and len(set(map(tuple, BESIDE.values()))) == 1, BESIDE


BESIDE = {}


@pytest.mark.parametrize("relaid", [True, False], ids=["relaid", "left_to_the_compiler"])
def test_tp2_dp2_zero2_with_unrolled_layers_compiles_for_a_v5e_2x2(v5e, monkeypatch, relaid):
    """The rule is not one device's: under tp 2 x dp 2 with ZeRO-2 a rank's
    share of the kernel is (hidden, 2, ffn / 2), Adam's moments lie split over
    dp, the step reads a gathered bf16 copy, and the gradient is
    reduce-scattered after the constraint. Compile-only: the relaid step holds
    no stand-alone float32 copy of a share (left to the compiler: 6), and its
    temporaries stay within four shares of the other's (read: 3.4, 6.8 MiB,
    at this size; `memory_analysis` reads the other's as 0)."""
    if not relaid:
        _left_to_the_compiler(monkeypatch)
    hp = HybridParallelConfig(world_size=4, pp=1, global_bsz=4, default_dp_type="zero2",
                              layers=[LayerStrategy(tp=2, checkpoint=i % 2) for i in range(2)])
    model = construct_hybrid_parallel_model(_swiglu(), hp, v5e)
    with forms.recording() as took:
        step = _compiled(model, _tx(), batch=4)
    assert took[forms.GATED_KERNEL_GRADS]["as_stored"] == (2 if relaid else 0)
    shares = [op for op in compiled_steps.copy_ops(step.as_text())
              if op[0] == "f32" and op[4] and op[1].endswith(",2,%d" % (F // 2))]
    SHARDED[relaid] = (len(shares), step.memory_analysis().temp_size_in_bytes)
    if len(SHARDED) == 2:
        assert SHARDED[True][0] <= SHARDED[False][0] and SHARDED[True][0] == 0, SHARDED
        assert SHARDED[True][1] <= SHARDED[False][1] + 4 * (H * 2 * (F // 2) * 4), SHARDED


SHARDED = {}


def _glm(**kw):
    return glm4_moe_lite_config("glm-4.7-flash", num_layers=3, hidden_size=64, num_heads=2, num_kv_heads=2,
                                ffn_hidden=32, vocab_size=128, max_seq_len=64, **kw)


CASES = {
    # name: (config, layout, whether some layer with a gated kernel runs unrolled, gated kernels)
    "swiglu_layers_alone": (_swiglu, _alone, True, 2),
    "swiglu_one_scanned_run": (_swiglu, _scanned, False, 2),
    "swiglu_scan_off": (_swiglu, lambda: _scanned(scan_layers=False), True, 2),
    "swiglu_one_layer_beside_a_scanned_run": (
        lambda: _swiglu(3), lambda: HybridParallelConfig(
            world_size=1, pp=1, global_bsz=1, layers=[LayerStrategy(checkpoint=c) for c in (1, 1, 0)]), True, 3),
    "gelu": (lambda: gpt_config("gpt-0.3b", num_layers=2, hidden_size=H, num_heads=4, vocab_size=1024,
                                max_seq_len=S), _alone, False, 0),
    "routed_without_a_shared_expert": (
        lambda: olmoe_config("olmoe-1b-7b", num_layers=2, hidden_size=H, num_heads=4, num_kv_heads=4,
                             ffn_hidden=256, vocab_size=1024, max_seq_len=S), _alone, False, 0),
    # a leading dense layer alone; the routed layers' and the MTP block's shared experts are left to the compiler
    "dense_then_shared_experts_and_mtp": (_glm, lambda: _scanned(3), True, 1),
    "shared_experts_alone": (lambda: _glm(first_dense_layers=0), lambda: _alone(3), False, 0),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_rule_reads_the_parts_statement_the_runs_and_the_platform(v5e, name):
    make_cfg, make_hp, unrolled, kernels = CASES[name]
    cfg, hp = make_cfg(), make_hp()
    layers = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))["layers"]
    runs = layer_runs(hp, M.model_layer_kinds(cfg))

    def relaid(mesh, scan=hp.scan_layers):
        with forms.recording() as took:
            out = jax.eval_shape(lambda ls: M._gated_grads_as_stored(
                ls, runs, lambda run: scan and run.length >= 2, cfg, mesh), layers)
        assert jax.tree.structure(out) == jax.tree.structure(layers)
        return took[forms.GATED_KERNEL_GRADS]["as_stored"]

    on_chip = build_mesh(hp, v5e[:1])
    assert relaid(on_chip) == (kernels if unrolled else 0)  # all of a model's gated kernels or none
    assert relaid(build_mesh(hp, jax.devices()[:1])) == 0  # the CPU's path is the parent's
    assert relaid(None) == 0  # no layout: the constraint-free local loss
    assert relaid(on_chip, scan=False) == kernels


def test_the_statement_is_the_parts():
    assert mlp.DENSE.gated_kernels(_swiglu()) == (("wi", "kernel"),)
    assert mlp.DENSE.gated_kernels(gpt_config("gpt-0.3b", num_layers=1)) == ()
    routed = _glm().layer_config("routed")
    assert mlp.ROUTED.gated_kernels(routed) == ()  # the experts' kernels are flat, the shared expert's is small


@pytest.mark.parametrize("held_in", [None, jnp.bfloat16], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_a_relaid_gradient_is_the_gradient_the_jaxpr_states(dtype, held_in):
    """Read through `grad_as_stored` the dense half's output and its input's
    gradient are the plain ones to the bit. The kernel's gradient is too with
    `held_in` None (a scanned run: a layout is no value); with `held_in` the
    compute dtype (an unrolled layer) it is the plain one ROUNDED to that
    dtype: what the matmul's jaxpr yields before the cast's transpose widens
    it, and what a TPU's compiler holds of such a layer without the rule
    (`test_unrolled_swiglu_layers_...`: `bf16` either way), whether or not
    this backend kept that rounding."""
    cfg = llama_config("llama-0.3b", num_layers=1, hidden_size=64, num_heads=4, ffn_hidden=128,
                       vocab_size=256, max_seq_len=32, compute_dtype=dtype)
    p = mlp._init_dense(list(jax.random.split(jax.random.PRNGKey(0), 4)), cfg)
    y = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64), dtype)

    def loss(p, y, as_stored):
        if as_stored:
            p = M._at(p, ("wi", "kernel"), lambda k: mlp.grad_as_stored(k, None if held_in is None else dtype))
        return jnp.sum(mlp.dense_mlp(p, y, cfg, dtype).astype(jnp.float32) ** 2)

    plain = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)), static_argnums=2)(p, y, False)
    relaid = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)), static_argnums=2)(p, y, True)
    if held_in is not None:
        plain[1][0]["wi"]["kernel"] = plain[1][0]["wi"]["kernel"].astype(dtype).astype(jnp.float32)
    for a, b in zip(jax.tree.leaves(relaid), jax.tree.leaves(plain), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert relaid[1][0]["wi"]["kernel"].dtype == jnp.float32  # the leaf's gradient, widened by the cast's transpose


def test_the_compile_event_counts_the_kernels_and_the_report_prints_it():
    assert "forms" in telemetry.EVENT_SCHEMAS["compile"][1]
    events = [{"v": 1, "t": 0.0, "seq": 0, "type": "compile", "trace_ms": 1.0, "compile_ms": 2.0,
               "forms": {forms.GATED_KERNEL_GRADS: {"as_stored": 10}}}]
    text = report.render(report.analyze(events))
    assert "gated_kernel_grads: as_stored x 10" in text
