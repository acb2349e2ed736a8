"""Live in-memory strategy migration (runtime/elastic.migrate): the
no-checkpoint-round-trip recovery path.

The parity contract under test: a run that hot-swaps strategies at step k
must continue BITWISE-identical (params, opt_state, subsequent losses) to a
run that checkpointed at step k and resumed under the target strategy via
the cross-layout restore (`load_checkpoint(target=)`). Both paths move the
same global arrays through the same `_relayout_tree` family — migration
just skips the disk.

Driver-level coverage: SIGUSR1 mid-run triggers resolve+migrate inside
cli/train.py (drain, prefetch teardown/reopen, step-fn rebuild), and GLS207
refusals keep infeasible migrations from garbling live state."""

import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.runtime import checkpoint as ck
from galvatron_tpu.runtime import elastic as els
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler


@pytest.fixture(autouse=True)
def _no_persistent_compile_cache(disable_persistent_compile_cache):
    """This module compiles full-size train steps via PLAIN jit (no driver,
    so no _STEP_EXECUTABLES bypass) — the shared conftest guard keeps those
    compiles out of the session's persistent cache (deserialized-executable
    heap corruption, see tests/conftest.py)."""
    yield


def tiny_cfg(**kw):
    kw.setdefault("compute_dtype", jnp.float32)
    kw.setdefault("hidden_size", 32)
    kw.setdefault("num_heads", 2)
    kw.setdefault("num_layers", 4)
    kw.setdefault("vocab_size", 64)
    kw.setdefault("max_seq_len", 16)
    return TransformerConfig(**kw)


def make_tx():
    return get_optimizer_and_scheduler(
        OptimizerArgs(lr=1e-3, warmup_steps=0, total_steps=8))[0]


def batch_for(hp, cfg, seed):
    tokens = jax.random.randint(
        jax.random.PRNGKey(seed), (hp.global_bsz, cfg.max_seq_len), 0, cfg.vocab_size)
    return dict(
        tokens=np.asarray(tokens),
        positions=np.broadcast_to(
            np.arange(cfg.max_seq_len), (hp.global_bsz, cfg.max_seq_len)),
        labels=np.asarray(jnp.roll(tokens, -1, 1)),
    )


def train_steps(model, tx, params, opt_state, cfg, start, n, step=None):
    # donate=False: the parity branches re-execute one compiled step on
    # arrays from three different producers (init, on-device migration,
    # orbax restore); donating orbax-restored buffers after earlier orbax
    # activity in the session segfaults XLA:CPU 0.4.37 (double-free class)
    step = model.make_train_step(tx, donate=False) if step is None else step
    losses = []
    for i in range(start, start + n):
        params, opt_state, mets = step(
            params, opt_state, model.shard_batch(batch_for(model.hp, cfg, i)))
        losses.append(float(mets["loss"]))
    return params, opt_state, losses


def assert_global_equal(a, b):
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree_util.tree_flatten_with_path(b)[0]
    assert len(fa) == len(fb)
    for (ka, va), (kb, vb) in zip(fa, fb):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(va)), np.asarray(jax.device_get(vb)),
            err_msg=jax.tree_util.keystr(ka))


STRATS = {
    "dp": lambda: HybridParallelConfig.uniform(8, 4, global_bsz=8),
    "tp": lambda: HybridParallelConfig.uniform(8, 4, tp=2, global_bsz=8),
    "pp2": lambda: HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8, chunks=2),
}


@pytest.mark.parametrize("swap", ["dp->tp", "tp->dp", "pp2->dp"])
def test_mid_run_swap_matches_checkpoint_resume_bitwise(devices8, tmp_path, swap):
    """Acceptance: train k=2 steps under A, hot-swap to B in memory, train 2
    more — params/opt_state/losses must be bitwise-identical to saving at k
    and resuming under B from disk."""
    src, dst = swap.split("->")
    cfg = tiny_cfg()
    hp_a, hp_b = STRATS[src](), STRATS[dst]()
    tx = make_tx()

    model_a = construct_hybrid_parallel_model(cfg, hp_a, devices8)
    params = model_a.init_params(jax.random.PRNGKey(0))
    opt_state = model_a.init_opt_state(tx, params)
    params, opt_state, pre_losses = train_steps(
        model_a, tx, params, opt_state, cfg, start=0, n=2)

    # reference path: checkpoint at k, cross-strategy restore under B
    d = str(tmp_path / "ck")
    prov = els.build_provenance(hp_a, cfg, mesh=model_a.mesh)
    ck.save_checkpoint(d, 2, params, opt_state, hp_a, provenance=prov)
    model_ref = construct_hybrid_parallel_model(cfg, hp_b, devices8)
    p_ref, st_ref, _ = ck.load_checkpoint(
        d, target=model_ref, tx=tx, strict_strategy=False)

    # live path: in-memory migration, no disk round-trip
    result = els.migrate(model_a, params, opt_state, tx, hp_b,
                         devices=devices8, iteration=2)
    assert result.same_layout == (src != "pp2" and dst != "pp2")

    # the migrated state IS the restored state, bit for bit
    assert_global_equal(result.params, p_ref)
    assert_global_equal(result.opt_state, st_ref)
    # and the restored arrays live in the target's shardings
    want = jax.tree.leaves(result.model.shardings())
    got = jax.tree.leaves(jax.tree.map(lambda x: x.sharding, result.params))
    for w, g in zip(want, got):
        assert w.spec == g.spec, (w, g)

    # subsequent training is bitwise-identical too: both branches continue
    # through ONE compiled target-strategy step (the HLO is identical, and
    # one compile halves the dominant suite cost)
    step_b = model_ref.make_train_step(tx, donate=False)
    p_mig, st_mig, mig_losses = train_steps(
        result.model, tx, result.params, result.opt_state, cfg, start=2, n=2,
        step=step_b)
    p_res, st_res, res_losses = train_steps(
        model_ref, tx, p_ref, st_ref, cfg, start=2, n=2, step=step_b)
    assert mig_losses == res_losses
    assert_global_equal(p_mig, p_res)
    assert_global_equal(st_mig, st_res)


# ------------------------------------------------------------------ refusals
def test_custom_tree_family_cross_layout_refused(devices8):
    cfg = tiny_cfg()
    hp_a = STRATS["pp2"]()
    model = construct_hybrid_parallel_model(cfg, hp_a, devices8)
    model.init_fn = lambda rng: {}  # pretend t5/swin-style custom tree
    with pytest.raises(DiagnosticError, match="GLS207"):
        els.migrate(model, {}, None, None, STRATS["dp"](), devices=devices8)


def test_global_bsz_change_refused(devices8):
    cfg = tiny_cfg()
    model = construct_hybrid_parallel_model(cfg, STRATS["dp"](), devices8)
    bigger = HybridParallelConfig.uniform(8, 4, global_bsz=16)
    with pytest.raises(DiagnosticError, match="GLS207"):
        els.migrate(model, {}, None, None, bigger, devices=devices8)


def test_resolve_migration_strategy_file_and_bsz_guard(devices8, tmp_path):
    cfg = tiny_cfg()
    current = STRATS["dp"]()
    target = STRATS["tp"]()
    spath = str(tmp_path / "target.json")
    target.save(spath)

    class A:
        elastic_strategy = spath
        elastic_memory_gb = None
        model_type = "llama"
        config_dir = None

    hp, action = els.resolve_migration_strategy(A(), cfg, 8, current)
    assert action == "strategy_file" and hp.layers[0].tp == 2
    # propagates the running exec knobs, not the file's defaults
    assert hp.scan_layers == current.scan_layers

    forked = HybridParallelConfig.uniform(8, 4, global_bsz=16)
    forked.save(spath)
    with pytest.raises(DiagnosticError, match="GLS207"):
        els.resolve_migration_strategy(A(), cfg, 8, current)


def test_resolve_migration_search_respects_budget(devices8):
    """No strategy fits an absurd budget: GLS203, not a doomed plan."""
    cfg = tiny_cfg(hidden_size=256, num_heads=4, vocab_size=4096, max_seq_len=512)

    class A:
        elastic_strategy = None
        elastic_memory_gb = 1e-4
        model_type = "llama"
        config_dir = None

    with pytest.raises(DiagnosticError, match="GLS203"):
        els.resolve_migration_strategy(
            A(), cfg, 2, HybridParallelConfig.uniform(8, 4, global_bsz=8))


# ------------------------------------------------------- driver-level SIGUSR1
def test_driver_sigusr1_migration_matches_checkpoint_resume(devices8, tmp_path):
    """The full driver path: SIGUSR1 at step 2 hot-swaps dp -> tp2 (target
    from --elastic_strategy) inside cli/train.py — drain, prefetch
    teardown/reopen, step-fn rebuild — and the losses continue exactly as a
    checkpoint-resume under the target strategy would."""
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train
    from galvatron_tpu.runtime.resilience import FaultHooks

    TINY = [
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "32", "--num_attention_heads", "2", "--num_layers", "2",
        "--vocab_size", "64", "--seq_length", "16", "--mixed_precision", "fp32",
        "--global_train_batch_size", "8", "--lr", "1e-3", "--world_size", "8",
    ]

    def run(extra, hooks=None):
        args = initialize_galvatron(mode="train_dist", argv=TINY + extra)
        if hooks is not None:
            args.fault_hooks = hooks
        return train(args)

    target = HybridParallelConfig.uniform(8, 2, tp=2, global_bsz=8)
    spath = str(tmp_path / "target.json")
    target.save(spath)

    ck_dir = str(tmp_path / "ck")
    # reference: 2 steps under dp, checkpoint, resume under the target
    run(["--train_iters", "2", "--save", ck_dir])
    resumed = run(["--train_iters", "4", "--load", ck_dir,
                   "--elastic_strategy", spath, "--elastic", "resume"])

    # live: one process, SIGUSR1 ONCE at the same boundary (on_step re-fires
    # for the same iteration after the post-migration continue)
    sent = {"done": False}

    def fire_once(i):
        if i == 2 and not sent["done"]:
            sent["done"] = True
            os.kill(os.getpid(), signal.SIGUSR1)

    live = run(["--train_iters", "4", "--elastic_strategy", spath],
               hooks=FaultHooks(on_step=fire_once))

    assert len(live["losses"]) == 4
    np.testing.assert_array_equal(
        np.asarray(live["losses"][2:]), np.asarray(resumed["losses"]))
