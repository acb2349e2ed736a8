"""A scanned run that stacks its cotangents in the compute dtype (`hp.narrow_scan_grads`, set by the launch
where state and float32 stacks would leave the device too little: `runtime/model_api.scan_stacks_are_tight`): the run
casts the leaves it reads through a cast BEFORE it stacks them (`models/base.run_layers`). Held here on the
CPU: the forward's values, the gradient against the unrolled layers' (which reach the update in the compute
dtype too), which leaves are cast and which are read as stored, that the traced stack is the narrow one, and
the rule itself."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.evabyte import evabyte_config
from galvatron_tpu.obs import forms
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model, device_memory_limit, scan_stacks_are_tight
from tests.models.test_scan_grads_in_zero_layout import B, batch, leaf_paths, one_sgd_step, tiny_qwen

def one_chip(**kw):
    return HybridParallelConfig.uniform(1, 4, global_bsz=B, checkpoint=1, **kw)


def test_a_narrow_scanned_bf16_step_is_the_unrolled_one_to_a_step_of_bf16(devices8):
    """bf16 compute, one chip, full recomputation (the EvaByte cell's layout): the first loss is the wide
    scan's bit for bit (the forward reads the same rounded values), and every leaf of the gradient lies within
    four steps of bf16 (2^-6 of the leaf's norm) of the unrolled layers', whose matmuls hand the update their
    gradient in bf16 as well."""
    cfg = tiny_qwen(jnp.bfloat16)
    with forms.recording() as took:
        _, loss, _, grads = one_sgd_step(cfg, one_chip(narrow_scan_grads=True), devices8[:1])
    assert took[forms.SCAN_GRADS] == {"compute_dtype": 1}
    with forms.recording() as took:
        _, wide_loss, _, _ = one_sgd_step(cfg, one_chip(), devices8[:1])
    assert forms.SCAN_GRADS not in took and loss == wide_loss
    _, want_loss, _, want = one_sgd_step(cfg, one_chip(scan_layers=False), devices8[:1])
    assert abs(loss - want_loss) < 2e-5
    for (path, a), b in zip(leaf_paths(grads).items(), jax.tree.leaves(want)):
        # bf16 holds 8 bits, and a leaf's two gradients are roundings of sums taken in another order, a bias's over every row: four of its steps
        assert 0 < np.linalg.norm(a - b) <= 2.0 ** -6 * np.linalg.norm(b), (path, np.linalg.norm(a - b), np.linalg.norm(b))


def test_the_narrow_stack_holds_the_kernels_in_bf16_and_what_is_read_as_stored_in_float32(devices8):
    """What the scan of an EVA layer's run takes as its stacked operands: the four kernels in the compute dtype,
    the norms' scales and the pooling's `phi` and `mu` (float32 arithmetic: `spec.cast_first_tree`) as stored."""
    cfg = evabyte_config(num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2, head_dim=32, ffn_hidden=96,
                         max_seq_len=128, eva_window=64, eva_chunk=8, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(1, 2, global_bsz=1, narrow_scan_grads=True)
    m = construct_hybrid_parallel_model(cfg, hp, devices8[:1])
    tokens = jnp.zeros((1, 128), jnp.int32)
    data = dict(tokens=tokens, positions=jnp.arange(128)[None], labels=tokens)
    jaxpr = jax.make_jaxpr(m.loss_fn)(m.abstract_params(), data)
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1
    stacked = sorted((v.aval.dtype.name, v.aval.shape) for v in scans[0].invars if v.aval.shape[:1] == (2,))
    assert stacked == sorted([
        ("bfloat16", (2, 64, 3, 2, 32)), ("bfloat16", (2, 64, 64)),  # wqkv, wo
        ("bfloat16", (2, 64, 2, 96)), ("bfloat16", (2, 96, 64)),  # wi, wo_mlp
        ("float32", (2, 64)), ("float32", (2, 64)),  # the two norms
        ("float32", (2, 2, 32)), ("float32", (2, 2, 32))])  # phi, mu


def tight(cfg, hp, devices, limit):
    return scan_stacks_are_tight(construct_hybrid_parallel_model(cfg, hp, devices), optax.adam(1e-3), limit)


def test_the_rule_weighs_state_and_stacks_against_the_devices_memory(devices8):
    """Four Qwen layers under Adam on one device: the state is three float32 copies of every leaf, a scanned
    run stacks a float32 cotangent and (bf16 compute) a bf16 copy of the kernels beside it. The rule answers
    True where the sum passes 85 % of the limit, and never where nothing is scanned, under pp, or where the
    device does not say what it holds (the CPU)."""
    cfg = tiny_qwen(jnp.bfloat16)
    m = construct_hybrid_parallel_model(cfg, one_chip(), devices8[:1])
    params = m.abstract_params()
    every = sum(x.size * 4 for x in jax.tree.leaves(params))
    layers = sum(x.size * 4 for x in jax.tree.leaves(params["layers"]))
    scales = sum(x.size * 4 for lp in params["layers"] for x in (lp["ln1"]["scale"], lp["ln2"]["scale"]))
    held = 3 * every + layers + (layers - scales) // 2 + scales  # state, cotangents, the copy (norms as stored)
    assert tight(cfg, one_chip(), devices8[:1], int(held / 0.85) - 8)
    assert not tight(cfg, one_chip(), devices8[:1], int(held / 0.85) + 8)
    assert not tight(cfg, one_chip(scan_layers=False), devices8[:1], 1)
    assert not tight(cfg, HybridParallelConfig.uniform(2, 4, global_bsz=B, pp=2, chunks=2), devices8[:2], 1)
    assert device_memory_limit(devices8[0]) is None and not tight(cfg, one_chip(), devices8[:1], None)
    # two devices of dp under ZeRO-3: a device holds half of every split leaf
    z3 = HybridParallelConfig.uniform(2, 4, global_bsz=B, default_dp_type="zero3")
    assert tight(cfg, z3, devices8[:2], int(held / 0.85 * 0.49)) and not tight(cfg, z3, devices8[:2], int(held / 0.85 * 0.75))
