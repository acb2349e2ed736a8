"""Parity regression net for the TP execution paths (ISSUE 8): the manual
shard_map / overlap paths must match the GSPMD path (loss AND grads) on
every supported tp/zero3/scan combination, and every path must match the
UNSHARDED single-device reference — the sharded-vs-unsharded net that has
caught three real GSPMD miscompiles in this repo (explicit layout pins via
the conftest 8-virtual-device CPU backend). Unsupported configs refuse with
GLS012 at trace time, never silently fall back.

Budget: the tier-1 matrix shares one GSPMD reference per config through a
module-level memo; the heavier cross product is marked ``slow``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.parallel.mesh import build_mesh

# full-layer value_and_grad programs recur identically across tests in this
# module (shared GSPMD references): keep them out of the session's
# persistent compile cache — the second identical >1s compile would execute
# a deserialized XLA:CPU executable (tests/conftest.py hazard)
pytestmark = pytest.mark.usefixtures("disable_persistent_compile_cache")

B, S, H = 8, 32, 32


def make_cfg(**kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("hidden_size", H)
    kw.setdefault("num_heads", 4)
    kw.setdefault("num_layers", 2)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_seq_len", S)
    return TransformerConfig(**kw)


def make_params(cfg):
    keys = jax.random.split(jax.random.PRNGKey(0), cfg.num_layers)
    return {"layers": [M.init_layer_params(k, cfg) for k in keys]}


def make_inputs(cfg):
    x = 0.05 * jax.random.normal(
        jax.random.PRNGKey(1), (B, cfg.max_seq_len, cfg.hidden_size), jnp.float32)
    positions = jnp.broadcast_to(
        jnp.arange(cfg.max_seq_len), (B, cfg.max_seq_len))
    return x, positions


def loss_and_grads(cfg, hp, mesh, scan, attn_bias=None):
    params = make_params(cfg)
    x, positions = make_inputs(cfg)

    def loss(p):
        y = M.run_layers(p, x, positions, cfg, hp, mesh, attn_bias=attn_bias,
                         scan=scan)
        return jnp.mean(y.astype(jnp.float32) ** 2)

    return jax.jit(jax.value_and_grad(loss))(params)


def assert_close(ref, refg, got, gotg, tag, tol=2e-5):
    assert abs(float(ref) - float(got)) < tol, tag
    for a, b in zip(jax.tree.leaves(refg), jax.tree.leaves(gotg)):
        assert float(jnp.max(jnp.abs(a - b))) < tol, tag


# config name -> (cfg kwargs, hp kwargs, scan)
CONFIGS = {
    "tp2_scan": ({}, dict(tp=2), True),
    "tp2_noscan": ({}, dict(tp=2), False),
    "tp4_zero3_scan": ({}, dict(tp=4, sdp=1), True),
    "tp2_remat_scan": ({}, dict(tp=2, checkpoint=1), True),
    "llama_tp2_scan": (
        dict(position_type="rope", norm_type="rmsnorm", activation="swiglu",
             num_kv_heads=2, qkv_bias=False, mlp_bias=False, out_bias=False),
        dict(tp=2), True),
}
# the rest of the tp x zero3 x scan cross product; functionally redundant
# with the tier-1 rows (same code paths, different degrees) so marked slow
SLOW_CONFIGS = {
    "tp2_zero3_scan": ({}, dict(tp=2, sdp=1), True),
    "tp2_zero3_noscan": ({}, dict(tp=2, sdp=1), False),
    "tp4_scan": ({}, dict(tp=4), True),
    "tp4_noscan": ({}, dict(tp=4), False),
    "tp4_zero3_noscan": ({}, dict(tp=4, sdp=1), False),
    "postnorm_bias_tp2_scan": (dict(pre_norm=False, causal=False),
                               dict(tp=2), True),
}

_REF_MEMO = {}


def _case(name, table, devices8, mode):
    cfg_kw, hp_kw, scan = table[name]
    cfg = make_cfg(**cfg_kw)
    attn_bias = None
    if name.startswith("postnorm_bias"):
        mask = np.ones((B, cfg.max_seq_len), np.float32)
        mask[:, -cfg.max_seq_len // 4:] = 0.0
        attn_bias = M.padding_attn_bias(jnp.asarray(mask))
    if name not in _REF_MEMO:
        hp_ref = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=B,
                                              **hp_kw)
        _REF_MEMO[name] = loss_and_grads(cfg, hp_ref, build_mesh(hp_ref, devices8),
                                         scan, attn_bias)
    ref, refg = _REF_MEMO[name]
    hp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=B,
                                      tp_comm_mode=mode, **hp_kw)
    got, gotg = loss_and_grads(cfg, hp, build_mesh(hp, devices8), scan, attn_bias)
    assert_close(ref, refg, got, gotg, "%s/%s" % (name, mode))


@pytest.mark.parametrize("mode", ["shard_map", "overlap"])
@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_manual_path_matches_gspmd(name, mode, devices8):
    _case(name, CONFIGS, devices8, mode)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["shard_map", "overlap"])
@pytest.mark.parametrize("name", sorted(SLOW_CONFIGS))
def test_manual_path_matches_gspmd_full_matrix(name, mode, devices8):
    _case(name, SLOW_CONFIGS, devices8, mode)


def test_sharded_paths_match_unsharded_reference(devices8):
    """The miscompile-class net: every execution path (GSPMD, manual,
    overlapped) against the UNSHARDED single-host reference — a silently
    wrong collective or layout decision diverges here even if the sharded
    paths agree with each other."""
    cfg = make_cfg()
    params = make_params(cfg)
    x, positions = make_inputs(cfg)

    def unsharded_loss(p):
        y = M.run_layers(p, x, positions, cfg)  # no hp/mesh: plain local run
        return jnp.mean(y.astype(jnp.float32) ** 2)

    ref, refg = jax.jit(jax.value_and_grad(unsharded_loss))(params)
    for mode in ("gspmd", "shard_map", "overlap"):
        hp = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2, sdp=1,
                                          global_bsz=B, tp_comm_mode=mode)
        got, gotg = loss_and_grads(cfg, hp, build_mesh(hp, devices8), scan=True)
        assert_close(ref, refg, got, gotg, "unsharded-vs-%s" % mode)


def test_piecewise_runs_mix_manual_and_gspmd(devices8):
    """A piecewise strategy under the knob: tp runs go manual, tp=1 runs
    keep GSPMD — and the composite still matches the all-GSPMD trajectory."""
    from galvatron_tpu.config.strategy import LayerStrategy

    cfg = make_cfg(num_layers=4)
    layers = [LayerStrategy(tp=2)] * 2 + [LayerStrategy()] * 2
    ref_hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=B)
    hp = HybridParallelConfig(world_size=8, pp=1, layers=layers, global_bsz=B,
                              tp_comm_mode="overlap")
    mesh = build_mesh(ref_hp, devices8)
    ref, refg = loss_and_grads(cfg, ref_hp, mesh, scan=True)
    got, gotg = loss_and_grads(cfg, hp, build_mesh(hp, devices8), scan=True)
    assert_close(ref, refg, got, gotg, "piecewise")


# ------------------------------------------------------------------ refusal
@pytest.mark.parametrize("hp_kw", [
    dict(tp=2, sp=1),                       # ulysses
    dict(tp=2, sequence_parallel=False),    # no megatron-sp
])
def test_unsupported_configs_refuse_loudly(hp_kw, devices8):
    cfg = make_cfg()
    hp = HybridParallelConfig.uniform(8, cfg.num_layers, global_bsz=B,
                                      tp_comm_mode="overlap", **hp_kw)
    mesh = build_mesh(hp, devices8)
    params = make_params(cfg)
    x, positions = make_inputs(cfg)
    with pytest.raises(DiagnosticError, match="GLS012"):
        jax.jit(lambda p: M.run_layers(p, x, positions, cfg, hp, mesh))(params)


def test_gqa_indivisible_refuses(devices8):
    cfg = make_cfg(num_kv_heads=2)
    hp = HybridParallelConfig.uniform(8, cfg.num_layers, tp=4, global_bsz=B,
                                      tp_comm_mode="shard_map")
    mesh = build_mesh(hp, devices8)
    params = make_params(cfg)
    x, positions = make_inputs(cfg)
    with pytest.raises(DiagnosticError, match="GLS012"):
        jax.jit(lambda p: M.run_layers(p, x, positions, cfg, hp, mesh))(params)


# -------------------------------------------------------------- train step
@pytest.mark.slow
@pytest.mark.parametrize("mode", ["shard_map", "overlap"])
def test_train_step_trajectory_matches_gspmd(mode, devices8):
    """Driver-level: 3 optimizer steps through model_api under the manual
    paths track the GSPMD trajectory (the prototype measured bit-identical
    on this jax; the assert allows tolerance for other backends)."""
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import (
        OptimizerArgs,
        get_optimizer_and_scheduler,
    )

    cfg = make_cfg(max_seq_len=16)

    def traj(tp_mode):
        hp = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2, global_bsz=8,
                                          tp_comm_mode=tp_mode)
        m = construct_hybrid_parallel_model(cfg, hp, devices8)
        tx, _ = get_optimizer_and_scheduler(
            OptimizerArgs(lr=1e-3, warmup_steps=0, total_steps=8))
        p = m.init_params(jax.random.PRNGKey(0))
        st = m.init_opt_state(tx, p)
        step = m.make_train_step(tx, donate=False)
        out = []
        for i in range(3):
            tokens = jax.random.randint(jax.random.PRNGKey(i), (8, 16), 0, 64)
            b = dict(tokens=tokens,
                     positions=jnp.broadcast_to(jnp.arange(16), (8, 16)),
                     labels=jnp.roll(tokens, -1, 1))
            p, st, mets = step(p, st, m.shard_batch(b))
            out.append(float(mets["loss"]))
        return out

    ref = traj("gspmd")
    got = traj(mode)
    np.testing.assert_allclose(got, ref, atol=1e-5)
