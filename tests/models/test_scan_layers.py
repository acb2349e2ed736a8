"""Scan-over-layer-runs compilation (ISSUE 3): run partitioning, scan-vs-
unrolled parity (outputs AND grads) for uniform / piecewise-uniform / fully
heterogeneous strategies, remat policies, and depth-constant trace cost.

The parity tolerances are deliberately tight: on this jax the scanned body
compiles to the same per-layer program as the unrolled path, and the suite
historically caught a real GSPMD miscompilation (reshape-splitting a
tp-sharded dim inside a scan silently corrupts the row-parallel kernels —
why stack_layer_run uses jnp.stack; see its docstring)."""

import functools

import jax
import jax.numpy as jnp
import pytest

from galvatron_tpu.config.strategy import (
    HybridParallelConfig,
    LayerStrategy,
    layer_runs,
)
from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.parallel.mesh import build_mesh, layer_axes

B, S, H = 8, 32, 64


def make_cfg(n_layers, **kw):
    kw.setdefault("compute_dtype", jnp.float32)
    return TransformerConfig(
        hidden_size=H, num_heads=4, num_layers=n_layers, vocab_size=128,
        max_seq_len=S, **kw,
    )


def make_inputs(seed=1):
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, S, H), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    return x, positions


def make_layers(cfg):
    keys = jax.random.split(jax.random.PRNGKey(0), cfg.num_layers)
    return {"layers": [M.init_layer_params(k, cfg) for k in keys]}


# ------------------------------------------------------------ run partitioning
class TestLayerRuns:
    def test_uniform_is_one_run(self):
        hp = HybridParallelConfig.uniform(8, 6, tp=2, global_bsz=8)
        runs = layer_runs(hp)
        assert [(r.start, r.stop) for r in runs] == [(0, 6)]
        assert runs[0].length == 6 and list(runs[0].layer_indices) == list(range(6))

    def test_piecewise_uniform(self):
        layers = ([LayerStrategy(tp=2)] * 3 + [LayerStrategy(tp=4, sp=1)] * 2
                  + [LayerStrategy(tp=2)] * 1)
        hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
        assert [(r.start, r.stop) for r in layer_runs(hp)] == [(0, 3), (3, 5), (5, 6)]

    def test_checkpoint_flag_partitions(self):
        layers = [LayerStrategy(checkpoint=1)] * 2 + [LayerStrategy()] * 2
        hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
        runs = layer_runs(hp)
        assert [(r.start, r.stop) for r in runs] == [(0, 2), (2, 4)]
        assert runs[0].strategy.checkpoint == 1 and runs[1].strategy.checkpoint == 0

    def test_inert_flags_do_not_split(self):
        # sp/tp_consec are inert at tp=1: same LayerAxes => one run, even
        # though the raw LayerStrategy tuples differ
        layers = [LayerStrategy(tp=1, sp=0, tp_consec=1),
                  LayerStrategy(tp=1, sp=1, tp_consec=0)]
        hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
        assert len(layer_runs(hp)) == 1

    def test_stage_boundary_splits(self):
        hp = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8)
        assert [(r.start, r.stop) for r in layer_runs(hp)] == [(0, 2), (2, 4)]

    def test_fully_heterogeneous(self):
        layers = [LayerStrategy(tp=2), LayerStrategy(tp=4), LayerStrategy(tp=1),
                  LayerStrategy(tp=2, checkpoint=1)]
        hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
        assert [r.length for r in layer_runs(hp)] == [1, 1, 1, 1]

    def test_remat_policy_partitions(self):
        # same axes, same checkpoint flag — a differing per-layer remat
        # policy still wraps the scanned body in a different jax.checkpoint
        # program, so it must split the run
        layers = ([LayerStrategy(checkpoint=1, remat_policy="dots_saveable")] * 2
                  + [LayerStrategy(checkpoint=1)] * 2)
        hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
        runs = layer_runs(hp)
        assert [(r.start, r.stop) for r in runs] == [(0, 2), (2, 4)]
        assert [r.strategy.effective_remat_policy for r in runs] == \
            ["dots_saveable", "full"]

    def test_remat_policy_inert_without_checkpoint(self):
        # checkpoint=0 layers never wrap: their serialized policy is inert,
        # and cpt=1 + rp='none' is effectively cpt=0 — one run throughout
        layers = [LayerStrategy(remat_policy="dots_saveable"),
                  LayerStrategy(remat_policy="nothing_saveable"),
                  LayerStrategy(checkpoint=1, remat_policy="none")]
        hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=8)
        assert len(layer_runs(hp)) == 1


# ------------------------------------------------------------------ parity
# uniform: one run of 4; piecewise: runs of 2+2; hetero: four length-1 runs
# (the scan path must fall back to unrolled per layer)
STRATEGIES = {
    "uniform_tp2": [LayerStrategy(tp=2)] * 4,
    "uniform_zero3": [LayerStrategy(fsdp=1)] * 4,
    "piecewise_tp2_ulysses": [LayerStrategy(tp=2)] * 2 + [LayerStrategy(tp=4, sp=1)] * 2,
    "piecewise_ckpt": [LayerStrategy(tp=2, checkpoint=1)] * 2 + [LayerStrategy(tp=2)] * 2,
    "hetero": [LayerStrategy(tp=2), LayerStrategy(tp=4, sp=1),
               LayerStrategy(fsdp=1), LayerStrategy(tp=2, checkpoint=1)],
}


def _loss_and_grads(cfg, hp, mesh, params, x, positions, scan):
    def loss(p):
        y = M.run_layers(p, x, positions, cfg, hp, mesh, scan=scan)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("name", list(STRATEGIES))
def test_scan_matches_unrolled(name, devices8):
    cfg = make_cfg(4)
    hp = HybridParallelConfig(world_size=8, pp=1, layers=STRATEGIES[name], global_bsz=B)
    mesh = build_mesh(hp, devices8)
    params = make_layers(cfg)
    x, positions = make_inputs()
    ref, ref_g = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=False)
    got, got_g = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=True)
    assert abs(float(ref) - float(got)) < 1e-6, name
    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(got_g)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5, name


def test_scan_matches_unrolled_llama_features(devices8):
    """rope + rmsnorm + swiglu (the feature set that exposed the GSPMD
    stacking miscompilation) under tp4+zero3."""
    cfg = make_cfg(4, position_type="rope", norm_type="rmsnorm",
                   activation="swiglu", qkv_bias=False, mlp_bias=False,
                   out_bias=False)
    hp = HybridParallelConfig.uniform(8, 4, tp=4, sdp=1, global_bsz=B)
    mesh = build_mesh(hp, devices8)
    params = jax.device_put(
        make_layers(cfg),
        jax.tree.map(
            lambda sp: jax.sharding.NamedSharding(mesh, sp),
            {"layers": [M.layer_param_specs(cfg, layer_axes(hp, i)) for i in range(4)]},
            is_leaf=lambda t: isinstance(t, jax.sharding.PartitionSpec),
        ),
    )
    # small-magnitude activations: attention probs stay diffuse, so a wrong
    # weight stacking shows up instead of saturating away
    x, positions = make_inputs()
    x = 0.02 * x
    ref, ref_g = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=False)
    got, got_g = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=True)
    assert abs(float(ref) - float(got)) < 1e-6
    for a, b in zip(jax.tree.leaves(ref_g), jax.tree.leaves(got_g)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5


def test_no_hp_path_scans_and_matches():
    """hp=None (plain model) treats the whole stack as one run."""
    cfg = make_cfg(3)
    params = make_layers(cfg)
    x, positions = make_inputs()
    a = jax.jit(functools.partial(M.run_layers, cfg=cfg, scan=False))(params, x, positions)
    b = jax.jit(functools.partial(M.run_layers, cfg=cfg, scan=True))(params, x, positions)
    assert float(jnp.max(jnp.abs(a - b))) < 1e-6


def test_scan_layers_escape_hatch(devices8):
    """hp.scan_layers=False (--no_scan_layers) reproduces the unrolled trace:
    no scan primitive appears in the jaxpr."""
    cfg = make_cfg(4)
    hp = HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=B, scan_layers=False)
    mesh = build_mesh(hp, devices8)
    params = make_layers(cfg)
    x, positions = make_inputs()
    jaxpr = jax.make_jaxpr(
        lambda p, xx: M.run_layers(p, xx, positions, cfg, hp, mesh)
    )(params, x)
    assert all(e.primitive.name != "scan" for e in jaxpr.eqns)


@pytest.mark.parametrize("policy", ["none", "full", "dots_saveable", "nothing_saveable"])
def test_remat_policy_parity(policy, devices8):
    """Every remat policy computes the same loss/grads as the default, on
    BOTH execution paths — the scanned run body and the per-layer unrolled
    wrap; the policy only moves the memory/recompute tradeoff."""
    cfg = make_cfg(4)
    hp = HybridParallelConfig.uniform(
        8, 4, tp=2, checkpoint=1, global_bsz=B, remat_policy=policy,
    )
    mesh = build_mesh(hp, devices8)
    params = make_layers(cfg)
    x, positions = make_inputs()
    ref_hp = HybridParallelConfig.uniform(8, 4, tp=2, checkpoint=1, global_bsz=B)
    ref, ref_g = _loss_and_grads(cfg, ref_hp, mesh, params, x, positions, scan=True)
    got, got_g = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=True)
    got_u, got_ug = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=False)
    assert abs(float(ref) - float(got)) < 1e-6, policy
    assert abs(float(got) - float(got_u)) < 1e-6, policy
    for a, b, c in zip(jax.tree.leaves(ref_g), jax.tree.leaves(got_g),
                       jax.tree.leaves(got_ug)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5, policy
        assert float(jnp.max(jnp.abs(b - c))) < 1e-5, policy


def test_remat_mixed_policy_piecewise_parity(devices8):
    """A MIXED per-layer remat plan (the searched shape: some layers under
    dots_saveable, some full, some unwrapped) splits into piecewise runs and
    still computes the default's loss/grads on both execution paths."""
    import dataclasses

    cfg = make_cfg(4)
    hp = HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=B)
    hp = dataclasses.replace(hp, layers=[
        dataclasses.replace(s, checkpoint=c, remat_policy=rp)
        for s, (c, rp) in zip(hp.layers, [
            (1, "dots_saveable"), (1, "dots_saveable"), (1, "full"),
            (0, "full")])])
    runs = layer_runs(hp)
    assert [(r.start, r.stop) for r in runs] == [(0, 2), (2, 3), (3, 4)]
    assert [r.strategy.effective_remat_policy for r in runs] == \
        ["dots_saveable", "full", "none"]
    mesh = build_mesh(hp, devices8)
    params = make_layers(cfg)
    x, positions = make_inputs()
    ref_hp = HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=B)
    ref, ref_g = _loss_and_grads(cfg, ref_hp, mesh, params, x, positions, scan=True)
    got, got_g = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=True)
    got_u, got_ug = _loss_and_grads(cfg, hp, mesh, params, x, positions, scan=False)
    assert abs(float(ref) - float(got)) < 1e-6
    assert abs(float(got) - float(got_u)) < 1e-6
    for a, b, c in zip(jax.tree.leaves(ref_g), jax.tree.leaves(got_g),
                       jax.tree.leaves(got_ug)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5
        assert float(jnp.max(jnp.abs(b - c))) < 1e-5


def test_remat_policy_validated():
    # the per-layer field validates eagerly in LayerStrategy.__post_init__
    # (remat_policy is a serialized strategy field since the remat search
    # dimension), so a bogus value dies before the GLS005 layer ever runs
    with pytest.raises(ValueError, match="remat_policy"):
        HybridParallelConfig.uniform(8, 2, remat_policy="bogus")


# -------------------------------------------------------------- trace cost
# Pure layout/metadata primitives: the per-layer expand_dims+concatenate that
# stack_layer_run emits (jnp.stack — see its docstring for why the
# 2-equation concat+reshape form is off the table on this jax). XLA compile
# cost is governed by the remaining compute equations, which must be
# depth-CONSTANT under scan for a uniform strategy.
LAYOUT_PRIMS = {"broadcast_in_dim", "reshape", "concatenate", "transpose", "squeeze"}


def _eqn_counts(n_layers, devices, scan):
    cfg = make_cfg(n_layers)
    hp = HybridParallelConfig.uniform(8, n_layers, tp=2, global_bsz=B)
    mesh = build_mesh(hp, devices)
    params = make_layers(cfg)
    x, positions = make_inputs()
    jaxpr = jax.make_jaxpr(
        lambda p, xx: M.run_layers(p, xx, positions, cfg, hp, mesh, scan=scan)
    )(params, x)
    total = len(jaxpr.eqns)
    compute = sum(1 for e in jaxpr.eqns if e.primitive.name not in LAYOUT_PRIMS)
    return total, compute


def test_trace_cost_depth_constant_under_scan(devices8):
    total2, compute2 = _eqn_counts(2, devices8, scan=True)
    total8, compute8 = _eqn_counts(8, devices8, scan=True)
    # the compute trace is depth-constant: the scanned body is traced once
    # per RUN, and a uniform strategy is a single run at any depth
    assert compute2 == compute8, (compute2, compute8)
    # what little grows is the per-leaf param stacking — pure layout
    # equations, bounded by the leaf count of one layer
    n_leaves = len(jax.tree.leaves(make_layers(make_cfg(1))))
    assert total8 - total2 <= 2 * n_leaves * (8 - 2), (total2, total8)


def test_trace_cost_depth_linear_when_unrolled(devices8):
    """Sanity contrast: the unrolled path's compute trace grows ~linearly
    with depth (this is the cost the scan path removes)."""
    _, compute2 = _eqn_counts(2, devices8, scan=False)
    _, compute8 = _eqn_counts(8, devices8, scan=False)
    assert compute8 >= compute2 + 3 * (compute2 // 2)


# -------------------------------------------------------------- stacking
def test_stack_layer_run_layout():
    cfg = make_cfg(3)
    layers = make_layers(cfg)["layers"]
    stacked = M.stack_layer_run(layers)
    for i in range(3):
        for a, b in zip(jax.tree.leaves(jax.tree.map(lambda t, _i=i: t[_i], stacked)),
                        jax.tree.leaves(layers[i])):
            assert a.shape == b.shape
            assert float(jnp.max(jnp.abs(a - b))) == 0.0

    single = M.stack_layer_run(layers[:1])
    assert all(t.shape[0] == 1 for t in jax.tree.leaves(single))


def test_stacked_specs_match_stacked_shapes():
    cfg = make_cfg(2)
    hp = HybridParallelConfig.uniform(8, 2, tp=2, sdp=1, global_bsz=B)
    stacked = M.stack_layer_run(make_layers(cfg)["layers"])
    specs = M.stacked_layer_param_specs(cfg, layer_axes(hp, 0))
    flat_t, tdef = jax.tree.flatten(stacked)
    flat_s, sdef = jax.tree.flatten(
        specs, is_leaf=lambda t: isinstance(t, jax.sharding.PartitionSpec))
    assert tdef == sdef
    for t, sp in zip(flat_t, flat_s):
        assert len(sp) <= t.ndim
        assert sp[0] is None  # the stacked layer axis is never sharded


# ------------------------------------------- a config that publishes nothing
# `run_layers` carries what layers PUBLISH for later layers in a dict beside x
# (Phi-4-mini-flash's family, PR 57). For a config none of whose mixers reads,
# the dict is empty and nothing of it is traced: the equations of the stack's
# gradient, scanned and unrolled, are the counts recorded on PR 57's PARENT
# (scripts of that PR printed both trees' jaxprs: their texts were equal).
# Since PR 67 a GELU is written out on both sides under two `jax.custom_vjp`s
# (models/parts/mlp.dense_mlp: `_written_out`, `_matmul_of_written_out`):
# `dense4` unrolled counts, a layer, the rules' calls in its forward where the
# layer is kept (tp2) and the product that `jax.vjp` leaves dead in its
# backward, 12 and 8 more than PR 57's 958 and 350; the scanned counts, a body
# an equation, are PR 57's.
def _dense4():
    return make_cfg(4)


def _hybrid10():
    from galvatron_tpu.models.granite_hybrid import granite_hybrid_config

    return granite_hybrid_config(hidden_size=64, num_heads=4, num_kv_heads=2, ffn_hidden=96, num_layers=10,
                                 vocab_size=128, max_seq_len=S, ssm_num_heads=4, ssm_head_dim=32, ssm_state_dim=16,
                                 compute_dtype=jnp.float32, attn_impl="xla")


RECORDED = {  # (config, layout): (equations scanned, equations unrolled)
    ("dense4", "tp2"): (160, 970),
    ("dense4", "dp_remat"): (159, 358),
    ("hybrid10", "dp_remat"): (405, 1020),
}


@pytest.mark.parametrize("name,layout", sorted(RECORDED))
def test_a_config_that_publishes_nothing_traces_what_it_did(name, layout, devices8):
    cfg = {"dense4": _dense4, "hybrid10": _hybrid10}[name]()
    n = cfg.num_layers
    assert cfg.shared() == (((), ()),) * n  # no layer hands a tensor on, none reads one
    hp = (HybridParallelConfig.uniform(8, n, tp=2, global_bsz=B) if layout == "tp2"
          else HybridParallelConfig.uniform(8, n, global_bsz=B, checkpoint=1))
    mesh = build_mesh(hp, devices8)
    params = {"layers": [M.init_layer_params(jax.random.PRNGKey(i), cfg.layer_config(kind))
                         for i, kind in enumerate(cfg.layer_kinds())]}
    x, positions = make_inputs()

    def equations(scan):
        def out(p, xx):
            y = M.run_layers(p, xx, positions, cfg, hp, mesh, scan=scan)
            return jnp.sum((y[0] if cfg.layer_aux else y) ** 2)

        return len(jax.make_jaxpr(jax.grad(out))(params, x).eqns)

    assert (equations(True), equations(False)) == RECORDED[(name, layout)]
