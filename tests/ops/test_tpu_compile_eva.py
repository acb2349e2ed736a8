"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
the EVA aggregation's kernels (ops/eva_attention.py) at the EvaByte cell's shapes and at the published context, on
a dp4 mesh, and a whole EVA layer's mixer under its scopes."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops import eva_attention as E
from galvatron_tpu.ops.kernels import KernelSharding
from tests.ops.tpu_compile import v5e_2x2  # noqa: F401  (the fixture)

FLASH_PATTERNS = (r"^flash_attention[.:]", r"^flash_mha_bwd_dkv", r"^flash_mha_bwd_dq", r"^splash_m")  # flash_ms.py


def _custom_calls(text):
    return sorted(line.split("=")[0].strip().lstrip("%") for line in text.splitlines()
                  if "custom_call_target=\"tpu_custom_call\"" in line)


def _loss(sharding):
    def loss(q, k, v, phi, mu):
        with jax.named_scope(tracing.layers_scope(0)), jax.named_scope(tracing.ATTN_EVA_AGG):
            out, mass = E.eva_attention(q, k, v, phi, mu, window=2048, chunk=16, sm_scale=128 ** -0.5,
                                        sharding=sharding)
        return jnp.sum(out.astype(jnp.float32) ** 2) + jnp.mean(mass)

    return loss


@pytest.mark.parametrize("tokens", [8192, 32768])
def test_the_eva_kernels_compile_at_the_cells_shapes_for_v5e(v5e_2x2, tokens):
    """32 heads of 128 under a window of 2048 over chunks of 16, the EvaByte cell's 8192 positions and the
    published 32768 (1920 pooled keys a head in VMEM), through `impl="auto"`: two Mosaic calls, `eva_agg_fwd` and
    `eva_agg_bwd`, whose names none of `flash_ms`'s patterns match, each on ONE line of the compiled text with its
    `op_name` under the scope, and no (S, S) or per-window array of scores among the temporaries."""
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, tokens, 32, 128), jnp.bfloat16, sharding=one)
    vec = jax.ShapeDtypeStruct((32, 128), jnp.float32, sharding=one)
    fn = jax.grad(_loss(KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",)))), argnums=(0, 1, 2, 3, 4))
    with forms.recording() as took:
        compiled = jax.jit(fn).lower(q, q, q, vec, vec).compile()
    text = compiled.as_text()
    names = _custom_calls(text)
    assert [n.rsplit(".", 1)[0] for n in names] == ["eva_agg_bwd", "eva_agg_fwd"]
    assert not any(re.search(rx, name) for rx in FLASH_PATTERNS for name in names)
    for line in text.splitlines():
        if "custom_call_target=\"tpu_custom_call\"" in line:
            assert tracing.ATTN_EVA_AGG in re.search(r'op_name="([^"]*)"', line).group(1)
    assert took == {forms.EVA_ATTENTION: {"pallas": 1}}
    # q, k, v, the output and their cotangents are 8 MiB a thousand positions each; one window's float32 scores
    # of 32 heads alone would be 512 MiB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.45 * 2**30 * tokens / 8192


def test_the_eva_kernels_run_in_a_manual_region_on_a_dp4_mesh(v5e_2x2):
    """Four sequences over four chips (dp with ZeRO runs the family): each chip its own row through the
    kernels, phi and mu whole on every chip, no collective but the sum of their two cotangents over the rows."""
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("pp", "m0"))
    q = jax.ShapeDtypeStruct((4, 4096, 8, 128), jnp.bfloat16, sharding=NamedSharding(mesh, P("m0", None, None, None)))
    vec = jax.ShapeDtypeStruct((8, 128), jnp.float32, sharding=NamedSharding(mesh, P(None, None)))
    fn = jax.grad(_loss(KernelSharding(mesh, ("m0",), ())), argnums=(0, 1, 2, 3, 4))
    with forms.recording() as took:
        text = jax.jit(fn).lower(q, q, q, vec, vec).compile().as_text()
    assert len(_custom_calls(text)) == 2 and took == {forms.EVA_ATTENTION: {"pallas": 1}}
    for collective in ("all-gather", "all-to-all", "collective-permute"):
        assert collective not in text, collective


def test_a_shape_the_kernels_do_not_take_runs_the_xla_form_on_a_tpu(v5e_2x2):
    """A last, partial window (8192 + 1024 positions): `fits` refuses, the call says "xla" and compiles a window
    at a time, with no Mosaic call."""
    one = SingleDeviceSharding(v5e_2x2[0])
    q = jax.ShapeDtypeStruct((1, 9216, 4, 128), jnp.bfloat16, sharding=one)
    vec = jax.ShapeDtypeStruct((4, 128), jnp.float32, sharding=one)
    with forms.recording() as took:
        text = jax.jit(_loss(KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("x",))))).lower(q, q, q, vec, vec).compile().as_text()
    assert took == {forms.EVA_ATTENTION: {"xla": 1}} and not _custom_calls(text)


V5E_BYTES = int(15.75 * 2 ** 30)  # what a v5e's allocator hands out (the compiler's "15.75G hbm")


def _built(workload, devices):
    """(model, the optimizer, the step's abstract operands) of a cell as `cli train` builds them."""
    from benchmarks import cells
    from galvatron_tpu.cli.arguments import hp_config_from_args, initialize_galvatron, model_config_from_args
    from galvatron_tpu.cli.train import optimizer_args_from
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import get_optimizer_and_scheduler

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    cell = cells.load_cell(root, workload)
    cells.register_family(cell)
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, 0))
    _, cfg = model_config_from_args(args)
    model = construct_hybrid_parallel_model(cfg, hp_config_from_args(args, cfg.num_layers, cell.chips),
                                            devices[:cell.chips])
    tx, _ = get_optimizer_and_scheduler(optimizer_args_from(args))

    def abstract(tree, shardings):
        return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    params = model.abstract_params()
    shape = (cell.traffic["global_batch"], cell.traffic["seq_length"])
    batch = {k: jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(
        model.mesh, model._batch_spec_for(jax.ShapeDtypeStruct(shape, dt))))
        for k, dt in (("tokens", jnp.int32), ("positions", jnp.int32), ("labels", jnp.int32),
                      ("loss_mask", jnp.float32))}
    return model, tx, (abstract(params, model.shardings()),
                       abstract(jax.eval_shape(tx.init, params), model.opt_state_shardings(tx, params)), batch)


def _workloads():
    import json

    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    return [w["name"] for w in json.load(open(os.path.join(root, "BENCHMARK.json")))["workloads"]]


@pytest.mark.parametrize("workload", _workloads())
def test_only_the_evabyte_cell_is_tight_on_a_v5e(v5e_2x2, workload):
    """`scan_stacks_are_tight` at a v5e's memory, for every cell of the manifest as the trainer builds it: the
    fourteen cells that stood before PR 61 keep their float32 stacks (their compiled steps are the parent's),
    EvaByte's four layers stack their cotangents in bf16."""
    from galvatron_tpu.runtime.model_api import scan_stacks_are_tight

    model, tx, _ = _built(workload, v5e_2x2)
    assert scan_stacks_are_tight(model, tx, V5E_BYTES) is (workload == "evabyte-c1-s8k")
    assert not scan_stacks_are_tight(model, tx)  # a described device does not say what it holds


def test_the_cells_scanned_step_is_refused_wide_and_fits_with_narrow_stacks(v5e_2x2):
    """`evabyte-c1-s8k` as `cli train` builds it (four layers at the published widths, 8192 positions,
    `--checkpoint 1`, the layers scanned) for one described v5e chip: the chip's compiler refuses the step whose
    scan stacks float32 cotangents (3.06 GiB beside a 1.53 GiB bf16 copy of the layers and 9.18 of state: 15.95
    of 15.75); the launch's rule says so beforehand (13.77 GiB of state and stacks leave 1.98, under 15 %), and
    the step it then builds stacks them in bf16 and compiles, with the EVA kernels in the scan's body."""
    import jax.errors

    from galvatron_tpu.runtime.model_api import scan_stacks_are_tight

    model, tx, operands = _built("evabyte-c1-s8k", v5e_2x2)
    assert model.hp.scan_layers and not model.hp.narrow_scan_grads
    with pytest.raises(jax.errors.JaxRuntimeError, match="Ran out of memory in memory space hbm"):
        model.make_train_step(tx).lower(*operands).compile()
    model.hp.narrow_scan_grads = scan_stacks_are_tight(model, tx, V5E_BYTES)
    with forms.recording() as took:
        text = model.make_train_step(tx).lower(*operands).compile().as_text()
    assert took[forms.SCAN_GRADS] == {"compute_dtype": 1} and took[forms.EVA_ATTENTION] == {"pallas": 1}
    assert sorted({n.rsplit(".", 1)[0] for n in _custom_calls(text)}) == ["eva_agg_bwd", "eva_agg_fwd"]
    # the SwiGLU up kernels' cotangents: one bf16 stack the backward's loop writes a layer of at a time, no float32 one
    assert re.search(r"bf16\[4,4096,2,11008\]\S* fusion\(.*dynamic_update_slice", text)
    assert not re.search(r"= f32\[4,4096,2,11008\]\S* (fusion|broadcast)\(", text)
