"""Phi-4-mini-flash's layers and objective (models/base.py, models/parts/mamba.py,
cross.py, attention.py's differential form, ops/selective_scan.py,
models/phi4flash.py) against the plain reference
(benchmarks/references/phi4flash_lm.py) on seeded random weights at a small
size: hidden 64, the benchmark's cut in kinds (published layers 0, 1, 16, 17,
18, 19: Mamba-1, window, Mamba-1 that publishes, full that publishes, gated
memory unit, cross), 4 query heads on 2 KV heads of 16 (two query pairs on one
KV pair), 128 channels with states of 4, dt rank 4, a window of 8 keys, dense
SwiGLUs of 96, a 128-row tied table, 48 tokens (no multiple of the scan's chunk).

Tolerances, and why. In float32 compute program and reference do the same
arithmetic in another order (the chunked scan against the recurrence token by
token, one padded attention call against a softmax map a pair and a block of
queries at a time). Measured: loss 1.4e-6 apart; the worst leaves are the
four lambda vectors a layer, whose gradients are sums over every token and
pair that nearly cancel (3.9e-5 relative: their limit is 5e-4); every other
leaf lies within the Granite test's 5e-5. A reference whose readers'
cotangents are dropped lies 0.43 to 1.2 off on the publishers' leaves.

The weights are drawn with a wider `init_std` (0.2) than a model starts with
and the norms' scales, biases, D and the lambda vectors moved off their starts,
so that every piece moves the loss by far more than the tolerance.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells
from galvatron_tpu import HybridParallelConfig, LayerStrategy
from galvatron_tpu.analysis import strategy_lint
from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.config.strategy import layer_runs, model_layer_kinds
from galvatron_tpu.models import base as M
from galvatron_tpu.models import phi4flash as F
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.parts import MIXERS, unsupported_reason
from galvatron_tpu.models.parts.attention import LAMBDA_INIT, lambda_init
from galvatron_tpu.models.parts.mamba import mamba_mixer
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.obs import telemetry, tracing
from galvatron_tpu.runtime import construct_hybrid_parallel_model, get_optimizer_and_scheduler
from galvatron_tpu.runtime.optimizer import OptimizerArgs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/phi4flash_lm.py")

F32_TOL = 5e-5  # loss, worst-leaf relative gradient error
LAMBDA_TOL = 5e-4  # the lambda vectors' gradients: near-cancelling sums over all tokens and pairs
BATCH, SEQ, VOCAB = 2, 48, 128
CUT = [0, 1, 16, 17, 18, 19]
KINDS = ("mamba1.dense", "window.dense", "mamba1.dense", "dense", "gmu.dense", "cross.dense")
MAMBA_PUBLISHER, FULL_PUBLISHER, GMU_READER, CROSS_READER = 2, 3, 4, 5  # in the layers run


def tiny(dtype=jnp.float32, **kw):
    fields = dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, ffn_hidden=96, vocab_size=VOCAB,
        max_seq_len=SEQ, mamba_d_state=4, sliding_window=8, layer_indices=CUT, init_std=0.2,
        compute_dtype=dtype, attn_impl="xla")
    fields.update(kw)
    return F.phi4flash_config("phi-4-mini-flash-reasoning", **fields)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, VOCAB)
    mask = jnp.ones((batch, SEQ), jnp.float32).at[:, -1].set(0.0)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (batch, SEQ)),
                labels=jnp.roll(tok, -1, 1), loss_mask=mask)


def params_of(cfg, seed=0):
    """Seeded weights with norm scales, biases, D and the lambda vectors off their starts
    (`lambda_init` stays what the layer's index makes it)."""
    params = M.init_model_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(key, leaf.shape)
             if any(n in jax.tree_util.keystr(path) for n in ("scale", "bias", "['D']", "['lq", "['lk")) else leaf
             for (path, leaf), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, moved)


def leaf_errors(grads, ref_grads):
    def rel(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        norm = float(np.linalg.norm(b))
        diff = float(np.linalg.norm(a - b))
        return diff / norm if norm else diff

    tree = jax.tree.map(rel, grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def assert_within(errors):
    """Every leaf within `F32_TOL`, the lambda vectors within `LAMBDA_TOL`; `lambda_init` is no parameter."""
    errors = {k: v for k, v in errors.items() if LAMBDA_INIT not in k}
    lambdas = {k: v for k, v in errors.items() if "['lq" in k or "['lk" in k}
    others = {k: v for k, v in errors.items() if k not in lambdas}
    assert max(others.values()) < F32_TOL, max(others, key=others.get)
    assert max(lambdas.values()) < LAMBDA_TOL, max(lambdas, key=lambdas.get)


def reference_grads(cfg, params, batch, switch_off=()):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: REF.loss(p, batch, fields_of(cfg), switch_off=switch_off)))(params)


@pytest.fixture(scope="module")
def case():
    cfg = tiny()
    params, batch = params_of(cfg), batch_of()
    with jax.default_matmul_precision("highest"):
        program = jax.jit(jax.value_and_grad(
            lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)
    return cfg, params, batch, program, reference_grads(cfg, params, batch)


# ------------------------------------------------------------ the configuration
def test_the_config_is_the_published_one():
    cfg = F.phi4flash_config()
    pub = F.PUBLISHED["phi-4-mini-flash-reasoning"]
    assert pub["source"] == F.PHI_4_MINI_FLASH_SOURCE and get_family("phi4flash").meta_configs is F.PUBLISHED
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2560, 40, 20, 64)
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_expand, cfg.mamba_dt_rank) == (16, 4, 2, 160)
    assert (cfg.ffn_hidden, cfg.vocab_size, cfg.layernorm_eps, cfg.sliding_window) == (10240, 200064, 1e-5, 512)
    assert cfg.position_type == "none" and cfg.tie_embeddings and cfg.diff_attention and cfg.norm_type == "layernorm"
    assert cfg.qkv_bias and cfg.out_bias and not cfg.mlp_bias
    kinds = cfg.layer_kinds()
    assert [kinds.count(k) for k in ("mamba1.dense", "window.dense", "dense", "gmu.dense", "cross.dense")] == [
        9, 8, 1, 7, 7]
    assert kinds[16] == "mamba1.dense" and kinds[17] == "dense" and kinds[18:20] == ("gmu.dense", "cross.dense")
    # ONE Mamba-1 layer's memory and ONE full layer's keys and values are handed on, seven readers each
    shares = cfg.shared()
    assert [i for i, (out, _) in enumerate(shares) if out] == [16, 17]
    assert shares[16] == (("memory",), ()) and shares[17] == (("k", "v"), ())
    assert sum(read == ("memory",) for _, read in shares) == 7 and sum(read == ("k", "v") for _, read in shares) == 7


def test_a_cut_names_the_published_layers_it_runs():
    cfg = tiny()
    assert cfg.layer_kinds() == KINDS and cfg.published_indices() == tuple(CUT)
    # the runs' keys tell a layer that hands a tensor on from a plain one of its kind; `layer_kinds` does not
    assert model_layer_kinds(cfg) == KINDS[:2] + ("mamba1.dense -> memory", "dense -> k, v") + KINDS[4:]
    assert len(cfg.layer_types) == 32 and cfg.num_layers == 6
    hp = HybridParallelConfig.uniform(1, 6, global_bsz=BATCH, checkpoint=1)
    assert [(r.start, r.stop) for r in layer_runs(hp, model_layer_kinds(cfg))] == [(i, i + 1) for i in range(6)]
    # a model of another DEPTH is that depth's pattern; HF's constructor wants whole pairs in both decoders
    eight = tiny(layer_indices=None, num_layers=8)
    assert eight.layer_kinds() == ("mamba1.dense", "window.dense") * 2 + KINDS[2:]
    # any other depth runs the published stack's first layers, as the other families' cuts do
    assert tiny(layer_indices=None, num_layers=6).layer_kinds() == ("mamba1.dense", "window.dense") * 3
    for wrong in ([0, 1, 16, 17, 18], [1, 0, 16, 17, 18, 19], [0, 1, 16, 17, 18, 32]):
        with pytest.raises(ValueError, match="layer_indices"):
            tiny(layer_indices=wrong, num_layers=6)
    with pytest.raises(ValueError, match="Mamba-1 layers"):
        tiny(mamba_d_state=0)
    with pytest.raises(ValueError, match="diff_attention"):
        tiny(diff_attention=False)  # a cross layer reads a DIFFERENTIAL full layer's keys and values


@pytest.mark.parametrize("indices,named", [
    ([1, 3, 18, 19], 'layer 2 (published 18, mixer \'gmu\') reads "memory"'),
    ([16, 18, 19, 21], 'layer 2 (published 19, mixer \'cross\') reads "k" and "v"'),
    ([1, 17, 18, 19], 'layer 2 (published 18, mixer \'gmu\') reads "memory"')], ids=["gmu", "cross", "no_mamba"])
def test_a_layer_that_reads_what_nothing_publishes_is_refused_by_name(indices, named):
    with pytest.raises(ValueError) as e:
        tiny(layer_indices=indices)
    assert named in str(e.value) and "no earlier layer publishes" in str(e.value)


@pytest.mark.parametrize("key,value", [("mb_per_layer", 4), ("mlp_bias", True), ("lm_head_bias", True),
                                       ("mamba_conv_bias", False), ("mamba_proj_bias", True), ("resid_pdrop", 0.1)])
def test_what_is_not_modelled_is_refused_not_dropped(key, value):
    from types import SimpleNamespace

    with pytest.raises(ValueError, match="not modelled"):
        F.phi4flash_config_from_hf(SimpleNamespace(**{**F.PUBLISHED["phi-4-mini-flash-reasoning"], key: value}))


def test_the_published_cut_counts_697_094_272_parameters():
    """The benchmark's configuration counted leaf by leaf, ISSUE 57's table; and three floats more that are no
    parameters: the attention layers' `lambda_init`, set from the published index and moved by nothing."""
    cfg = F.phi4flash_config(layer_indices=CUT, vocab_size=25008)
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))

    def count(tree):
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        return sum(int(np.prod(leaf.shape)) for path, leaf in leaves if LAMBDA_INIT not in jax.tree_util.keystr(path))

    mamba, window, full, gmu, cross = (shapes["layers"][i] for i in (0, 1, 3, 4, 5))
    assert mamba["mamba"]["win"]["kernel"].shape == (2560, 10240) and mamba["mamba"]["A_log"].shape == (5120, 16)
    assert mamba["mamba"]["wx"]["kernel"].shape == (5120, 192) and mamba["mamba"]["wdt"]["kernel"].shape == (160, 5120)
    assert count(mamba["mamba"]) == 41_241_600 and count(gmu["gmu"]) == 26_214_400
    mixer = lambda layer: count({k: v for k, v in layer.items() if k in ("wq", "wkv", "wo", "diff")})  # noqa: E731
    assert mixer(window) == mixer(full) == 19_668_864 and mixer(cross) == 13_112_704 and "wkv" not in cross
    assert count(mamba["wi"]) + count(mamba["wo_mlp"]) == 78_643_200 and count(mamba["ln1"]) == 5120
    assert [count(layer) for layer in (mamba, window, gmu, cross)] == [
        119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert count(shapes["layers"]) == 633_068_672
    assert count(shapes["embed"]) + count(shapes["final_norm"]) == 64_025_600 and "lm_head" not in shapes
    assert count(shapes) == 697_094_272
    assert sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(shapes)) == 697_094_272 + 3


def test_lambda_init_is_of_the_published_index_and_nothing_moves_it():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    got = {i: float(lp["diff"][LAMBDA_INIT]) for i, lp in zip(CUT, params["layers"]) if "diff" in lp}
    assert got == pytest.approx({1: 0.8 - 0.6 * np.exp(-0.3), 17: 0.8 - 0.6 * np.exp(-5.1), 19: 0.8 - 0.6 * np.exp(-5.7)})
    assert (round(lambda_init(1), 4), round(lambda_init(17), 4), round(lambda_init(19), 4)) == (0.3555, 0.7963, 0.798)
    # two window layers of one scanned run differ in it: the constant is a leaf of the LAYER
    two = tiny(layer_indices=[1, 3, 16, 17, 18, 19])
    leaves = [lp["diff"][LAMBDA_INIT] for lp in M.init_model_params(jax.random.PRNGKey(0), two)["layers"][:2]]
    assert float(leaves[0]) == pytest.approx(lambda_init(1)) and float(leaves[1]) == pytest.approx(lambda_init(3))
    # no gradient reaches it, and Adam with weight decay leaves it where it is
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-2, weight_decay=0.1, warmup_steps=0, total_steps=4))
    grads = jax.jit(jax.grad(lambda p: M.lm_loss_fn(p, batch_of(), cfg)))(params)
    assert all(float(g["diff"][LAMBDA_INIT]) == 0.0 for g in grads["layers"] if "diff" in g)
    state = tx.init(params)
    assert "'%s': None" % LAMBDA_INIT in str(jax.tree_util.tree_structure(state))  # no moment of it
    _, state = tx.update(grads, state, params)  # (the schedule's first rate is 0)
    updates, _ = tx.update(grads, state, params)
    assert all(float(u["diff"][LAMBDA_INIT]) == 0.0 for u in updates["layers"] if "diff" in u)
    assert float(jnp.max(jnp.abs(updates["layers"][1]["diff"]["lq1"]))) > 0.0


# ------------------------------------------------- the whole model, float32
def test_the_loss_is_the_references(case):
    cfg, _, _, ((loss, parts), _), (ref_loss, _) = case
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-5)
    assert set(parts) == {"loss_ce"} | set(telemetry.SHARED_STEP_FIELDS)
    assert float(parts["selscan_state_abs_max"]) > 0.0
    # layer 16's memory (B, S, 128) and layer 17's k and v (B, S, 2, 16) each, float32: nothing of layer 0 or 1
    assert float(parts["published_mib"]) == pytest.approx(BATCH * SEQ * (128 + 2 * 32) * 4 / 2 ** 20)


def test_every_leafs_gradient_is_the_references(case):
    _, _, _, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    lam = "['layers'][%d]['diff']['%s']" % (FULL_PUBLISHER, LAMBDA_INIT)
    assert errors.pop(lam) == 0.0  # a constant on both sides: the reference never reads the leaf
    assert {"['layers'][0]['mamba']['A_log']", "['layers'][2]['mamba']['wdt']['bias']", "['layers'][2]['mamba']['D']",
            "['layers'][1]['diff']['lq1']", "['layers'][3]['diff']['subln']['scale']", "['layers'][3]['wkv']['bias']",
            "['layers'][4]['gmu']['win']['kernel']", "['layers'][5]['wq']['kernel']", "['embed']['wte']"} <= set(errors)
    assert_within(errors)


def test_a_step_that_dropped_a_readers_cotangent_would_fail_the_comparison(case):
    """The leaves that the READERS reach: layer 17's K and V projection gets gradient from layer 19's queries too,
    layer 16's scan from layer 18's gate. Against a reference whose readers see the memory and K, V behind a
    `stop_gradient` the publishers' leaves lie far off, the readers' own do not move."""
    cfg, params, batch, (_, grads), _ = case
    errors = leaf_errors(grads, reference_grads(cfg, params, batch, ("reader_cotangents",))[1])
    mamba, full = "['layers'][%d]['mamba']" % MAMBA_PUBLISHER, "['layers'][%d]" % FULL_PUBLISHER
    for leaf in (mamba + "['wx']['kernel']", mamba + "['wdt']['kernel']", mamba + "['A_log']", mamba + "['D']",
                 full + "['wkv']['kernel']", full + "['wkv']['bias']"):
        assert errors[leaf] > 1000 * F32_TOL, (leaf, errors[leaf])
    for leaf in ("['layers'][%d]['gmu']['wout']['kernel']" % GMU_READER, "['layers'][%d]['wo']['kernel']" % CROSS_READER):
        assert errors[leaf] < F32_TOL, (leaf, errors[leaf])
    # the program with a reader's contribution zeroed: the publishers' leaves' gradients change
    def without(path_head, key):
        layers = list(params["layers"])
        layers[path_head] = {**layers[path_head], key: jax.tree.map(jnp.zeros_like, layers[path_head][key])}
        return jax.jit(jax.grad(lambda p: M.lm_loss_fn(p, batch, cfg)))({**params, "layers": layers})

    with jax.default_matmul_precision("highest"):
        no_gmu, no_cross = without(GMU_READER, "gmu"), without(CROSS_READER, "wo")
    assert leaf_errors(no_gmu, grads)[mamba + "['wx']['kernel']"] > 0.05
    assert leaf_errors(no_cross, grads)[full + "['wkv']['kernel']"] > 0.05


@pytest.mark.parametrize("piece", ["memory_d_skip", "pairing", "window_reach", "lambda_index", "sub_norm"])
def test_each_assumed_piece_matters(case, piece):
    """Each `assumed` of the configuration that has another candidate, flipped in the reference alone: the
    agreement breaks by far (the gradients by the worst leaf; the loss by more than its tolerance)."""
    cfg, params, batch, ((loss, _), grads), _ = case
    off_loss, off_grads = reference_grads(cfg, params, batch, (piece,))
    assert max(leaf_errors(grads, off_grads).values()) > 0.01
    assert abs(float(off_loss) - float(loss)) > 2e-5


def test_bf16_compute_stays_within_the_cells_limit_of_the_reference():
    start = tiny(jnp.bfloat16, init_std=0.02)
    p0, batch = M.init_model_params(jax.random.PRNGKey(0), start), batch_of()
    with jax.default_matmul_precision("highest"):
        want = REF.loss(p0, batch, fields_of(start))
    assert float(jax.jit(lambda p: M.lm_loss_fn(p, batch, start))(p0)) == pytest.approx(float(want), abs=2e-3)


# --------------------------------------------- the stack carries what layers publish
def test_the_model_through_the_normal_path_is_the_plain_loss(case):
    """`HybridParallelModel`, `run_layers`, full recomputation: the published tensors are outputs of one
    checkpointed layer and inputs of others, and the step's gradient is the plain one."""
    cfg, params, batch, ((loss, _), grads), _ = case
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(model.loss_fn))(params, model.shard_batch(batch))
    assert float(got[0]) == pytest.approx(float(loss), abs=1e-6)
    assert_within(leaf_errors(got[1], grads))


def test_a_scanned_run_of_readers_closes_over_what_was_published():
    """Two gated memory units side by side are ONE scanned run that reads the same memory; a publishing layer
    is never scanned, and two plain Mamba-1 layers are."""
    cfg = tiny(layer_types=["mamba1", "mamba1", "mamba1", "full_attention", "gmu", "gmu", "cross_attention",
                            "cross_attention"], layer_indices=None, num_layers=8)
    assert cfg.shared()[2][0] == ("memory",) and cfg.shared()[0][0] == cfg.shared()[1][0] == ()
    params, batch = params_of(cfg), batch_of()
    with jax.default_matmul_precision("highest"):
        scanned = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg)))(params)
        ref = reference_grads(cfg, params, batch)
    # three scanned runs (the two plain Mamba-1 layers, the two units, the two cross layers): a shorter trace
    x = jnp.zeros((BATCH, SEQ, 64))
    traced = {scan: str(jax.make_jaxpr(lambda p: M.run_layers(p, x, batch["positions"], cfg, scan=scan)[0])(params))
              for scan in (True, False)}
    assert (traced[True].count("custom_vjp_call"), traced[False].count("custom_vjp_call")) == (2, 3)  # the scans
    assert traced[True].count("dot_general") < traced[False].count("dot_general")
    assert float(scanned[0]) == pytest.approx(float(ref[0]), abs=1e-5)
    assert_within(leaf_errors(scanned[1], ref[1]))


@pytest.mark.parametrize("zero,sdp", [("zero2", 0), ("zero3", 1)])
def test_dp4_with_zero_agrees_with_one_device(case, zero, sdp):
    cfg, params, _, _, _ = case
    batch = batch_of(batch=4)
    one = construct_hybrid_parallel_model(
        cfg, HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=4, checkpoint=1), jax.devices()[:1])
    hp = HybridParallelConfig.uniform(4, cfg.num_layers, global_bsz=4, checkpoint=1, sdp=sdp, default_dp_type=zero)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:4])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(one.loss_fn))(params, one.shard_batch(batch))
        got = jax.jit(jax.value_and_grad(model.loss_fn))(
            jax.device_put(params, model.shardings()), model.shard_batch(batch))
    assert float(got[0]) == pytest.approx(float(want[0]), abs=1e-5)
    assert_within(leaf_errors(got[1], want[1]))


def test_the_step_hands_back_the_counters_and_the_event_takes_them():
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=0, total_steps=4))
    params = model.init_params(jax.random.PRNGKey(0))
    before = [float(lp["diff"][LAMBDA_INIT]) for lp in params["layers"] if "diff" in lp]
    params, _, metrics = model.make_train_step(tx)(params, model.init_opt_state(tx, params),
                                                   model.shard_batch(batch_of()))
    assert float(metrics["selscan_state_abs_max"]) > 0.0 and float(metrics["published_mib"]) > 0.0
    assert set(telemetry.SHARED_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    assert {"mamba_layers", "shared_readers"} <= set(telemetry.EVENT_SCHEMAS["compile"][1])
    assert [float(lp["diff"][LAMBDA_INIT]) for lp in params["layers"] if "diff" in lp] == before  # a step later


# --------------------------------------------- each new piece against a formula
def test_the_mamba_mixer_is_its_few_lines_and_its_memory_is_the_scans_output():
    lcfg = tiny().layer_config("mamba1.dense")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        got, kv, counters, handed = mamba_mixer(lp, y, None, lcfg, publish=("memory",))
        want, memory = REF._mamba(lp, y[0], frozenset())
        quiet = mamba_mixer(lp, y, None, lcfg)
    assert kv is None and len(quiet) == 3 and set(handed) == {"memory"}
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(handed["memory"][0]), np.asarray(memory), atol=2e-5)
    assert float(counters["selscan_state_abs_max"]) > 0.0


def test_the_mamba_leaves_start_as_the_mamba_reference_starts_them():
    lp = M.init_layer_params(jax.random.PRNGKey(3), F.phi4flash_config().layer_config("mamba1.dense"))["mamba"]
    np.testing.assert_allclose(np.exp(np.asarray(lp["A_log"])), np.broadcast_to(np.arange(1, 17), (5120, 16)), rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(lp["wdt"]["bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001 and dt.shape == (5120,)
    np.testing.assert_array_equal(np.asarray(lp["D"]), np.ones(5120))
    assert float(jnp.max(jnp.abs(lp["wdt"]["kernel"]))) <= 160 ** -0.5 and lp["wdt"]["kernel"].shape == (160, 5120)
    assert lp["conv"]["kernel"].shape == (5120, 4) and float(jnp.max(jnp.abs(lp["conv"]["kernel"]))) <= 0.5
    assert "bias" not in lp["win"] and "bias" not in lp["wx"] and "bias" not in lp["wout"]


def test_one_table_maps_the_mixers_to_what_they_bring():
    assert MIXERS["mamba1"].scopes == (tracing.ATTN_MAMBA, tracing.ATTN_SELSCAN) == ("gt.attn.mamba", "gt.attn.selscan")
    assert MIXERS["gmu"].scopes == ("gt.attn.gmu",) and MIXERS["cross"].scopes == ("gt.attn.cross", "gt.attn.diff")
    assert "gt.attn.diff" in MIXERS["attention"].scopes and "gt.attn.diff" in MIXERS["window"].scopes
    assert (MIXERS["mamba1"].publishes, MIXERS["attention"].publishes) == (("memory",), ("k", "v"))
    assert (MIXERS["gmu"].reads, MIXERS["cross"].reads, MIXERS["window"].publishes) == (("memory",), ("k", "v"), ())
    # every other part neither publishes nor reads: its call is what it was
    assert all(not (part.publishes or part.reads) for name, part in MIXERS.items()
               if name not in ("mamba1", "attention", "gmu", "cross"))
    llama = llama_config("llama-0.3b", num_layers=2)
    assert llama.shared() == (((), ()), ((), ()))


# ------------------------------------------------------------ GLS018, by name
def _layers(n, **kw):
    return [LayerStrategy(**kw) for _ in range(n)]


REFUSED = {
    "tp2": (dict(world_size=2, layers=_layers(6, tp=2)), "Mamba-1 layers (the scan's state runs along the whole"),
    "sp": (dict(world_size=2, layers=_layers(6, tp=2, sp=1)), "gated memory units"),
    "cp2": (dict(world_size=2, layers=_layers(6, cp=2)), "cross layers (the keys and values are one full"),
    "pp2": (dict(world_size=2, pp=2, layers=_layers(6), chunks=2),
            "carry no tensor a layer publishes for later layers across stages"),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_form_of_the_new_parts_is_refused_by_name(layout):
    """GLS018 from the parts' own table, by `strategy_lint` before trace time and by the constructor: neither
    was edited for the family."""
    cfg = tiny()
    kw, named = REFUSED[layout]
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": 4, **kw})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert any(d.code == "GLS018" and named in d.message for d in report.errors)
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    assert "GLS018" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("kwargs,named", [
    (dict(mode="serve"), "no convolution window or scan state of a Mamba-1 layer"),
    (dict(mode="serve"), "no cache that one layer writes and later layers read"),
    (dict(mode="train", autotune="observe"), "a Mamba-1 layer as softmax attention")],
    ids=["serve_scan", "serve_shared_cache", "autotune"])
def test_serve_and_the_autotuner_refuse_it_and_name_the_parts(kwargs, named):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    errors = strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors
    assert any(d.code == "GLS018" and named in d.message for d in errors)
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok
    said = unsupported_reason(cfg, asker="search")
    assert all(name in said for name in ("cost models", "Mamba-1 layers", "gated memory units", "cross layers",
                                         "differential attention layers"))
    assert unsupported_reason(llama_config("llama-0.3b"), asker="search") is None


@pytest.mark.parametrize("surface", ["search", "profile"])
def test_search_and_profile_refuse_it_by_name(surface):
    from galvatron_tpu.cli.arguments import initialize_galvatron

    if surface == "search":
        from galvatron_tpu.cli.search import search as run
        mode = "search"
    else:
        from galvatron_tpu.cli.profile import profile_model as run
        mode = "profile"
    args = initialize_galvatron(mode=mode, argv=["--model_type", "phi4flash"])
    with pytest.raises(DiagnosticError) as e:
        run(args)
    assert "GLS018" in str(e.value) and "Mamba-1 layers" in str(e.value)
