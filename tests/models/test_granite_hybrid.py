"""Granite-4.0-H's layers and objective (models/base.py, ops/ssd.py,
models/granite_hybrid.py) against the plain reference
(benchmarks/references/granite_hybrid_lm.py) on seeded random weights at a
small size: hidden 64, ten layers in the published pattern (five Mamba-2
layers, an attention layer, four Mamba-2 layers; 4 heads of 32 with states of
16, 4 taps and a bias; 4 query heads on 2 KV heads of 16, no positions), dense
SwiGLUs of 96, a 128-row tied table, the four published multipliers.

Tolerances, and why. In float32 compute program and reference do the same
arithmetic in another order (the chunked scan against the recurrence token by
token, attention whole against a block of queries at a time). Every weight
matrix's gradient agrees to 1e-5 relative; the worst leaves are the scan's
own scalars a head, `A_log` and `dt_bias`, whose gradients are sums over all
tokens of differences of the decay's running sums (tests/ops/test_ssd.py):
measured 3e-5, the limit 5e-5, the Qwen3-Next test's. In bf16 compute the
loss is held to 2e-3 of the float32 reference, the benchmark's own limit for
every cell: two unrelated forwards differ by more than 1e-2, so a forward
that is off by more than a few roundings fails.

The weights are drawn with a wider `init_std` (0.2) than a model starts with
and the norms' scales moved off 1, so that the attention's logits, the
positions and each multiplier move the loss by far more than the tolerance.
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
from galvatron_tpu.models.parts import unsupported_reason
from galvatron_tpu.models.parts.attention import attention_mixer
from galvatron_tpu.models.parts.ssm import ssm_mixer
from galvatron_tpu.models import granite_hybrid as G
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.qwen3_next import qwen3_next_config
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import telemetry, tracing
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/granite_hybrid_lm.py")

F32_TOL = 5e-5  # loss, worst-leaf relative gradient error (the scan's scalars a head)
MATRIX_TOL = 1e-5  # every weight matrix's gradient
BATCH, SEQ, VOCAB = 2, 128, 128
PATTERN = ("ssm.dense",) * 5 + ("dense",) + ("ssm.dense",) * 4


def tiny(dtype=jnp.float32, **kw):
    fields = dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, ffn_hidden=96, num_layers=10, vocab_size=VOCAB,
        max_seq_len=SEQ, ssm_num_heads=4, ssm_head_dim=32, ssm_state_dim=16, init_std=0.2,
        compute_dtype=dtype, attn_impl="xla")
    fields.update(kw)
    return G.granite_hybrid_config("granite-4.0-h-micro", **fields)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, VOCAB)
    mask = jnp.ones((batch, SEQ), jnp.float32).at[:, -1].set(0.0)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (batch, SEQ)),
                labels=jnp.roll(tok, -1, 1), loss_mask=mask)


def params_of(cfg, seed=0):
    """Seeded weights with norm scales and D that are not at their start."""
    params = M.init_model_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(key, leaf.shape)
             if any(n in jax.tree_util.keystr(path) for n in ("scale", "['D']")) else leaf
             for (path, leaf), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, moved)


def leaf_errors(grads, ref_grads):
    def rel(a, b):
        norm = float(jnp.linalg.norm(b))
        diff = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        return diff / norm if norm else diff

    tree = jax.tree.map(rel, grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def case():
    cfg = tiny()
    params, batch = params_of(cfg), batch_of()
    with jax.default_matmul_precision("highest"):
        program = jax.jit(jax.value_and_grad(
            lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)
        reference = jax.jit(jax.value_and_grad(lambda p: REF.loss(p, batch, fields_of(cfg))))(params)
    return cfg, params, batch, program, reference


# ------------------------------------------------- the whole model, float32
def test_the_config_is_the_published_one():
    cfg = G.granite_hybrid_config()
    pub = G.PUBLISHED["granite-4.0-h-micro"]
    assert pub["source"] == G.GRANITE_4_H_MICRO_SOURCE and get_family("granite_hybrid").meta_configs is G.PUBLISHED
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        40, 2048, 32, 8, 64)
    assert (cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_dim, cfg.ssm_conv_kernel) == (64, 64, 128, 4)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling) == (12.0, 0.22, 0.015625, 8.0)
    assert (cfg.ffn_hidden, cfg.vocab_size, cfg.layernorm_eps) == (8192, 100352, 1e-5)
    assert cfg.position_type == "none" and cfg.tie_embeddings and not cfg.routed
    kinds = cfg.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "dense"] == [5, 15, 25, 35]
    assert kinds.count("ssm.dense") == 36 and cfg.layer_types == pub["layer_types"]


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 8), ("mamba_n_groups", 8), ("position_embedding_type", "rope"),
    ("attention_bias", True), ("mamba_proj_bias", True), ("mamba_conv_bias", False)])
def test_what_is_not_modelled_is_refused_not_dropped(key, value):
    from types import SimpleNamespace

    with pytest.raises(ValueError, match="not modelled"):
        G.granite_hybrid_config_from_hf(SimpleNamespace(**{**G.PUBLISHED["granite-4.0-h-micro"], key: value}))


def test_a_pattern_given_as_a_list_gives_the_three_runs():
    """The first ten entries of the published list: M M M M M A M M M M, which
    no interval says, split unedited by `layer_runs` into 5 scanned, 1, 4 scanned."""
    cfg = tiny()
    assert cfg.layer_kinds() == PATTERN and model_layer_kinds(cfg) == PATTERN
    hp = HybridParallelConfig.uniform(1, 10, global_bsz=BATCH, checkpoint=1)
    runs = layer_runs(hp, model_layer_kinds(cfg))
    assert [(r.start, r.stop) for r in runs] == [(0, 5), (5, 6), (6, 10)]
    ssm, attention = cfg.layer_config("ssm.dense"), cfg.layer_config("dense")
    assert (ssm.mixer, attention.mixer) == ("ssm", "attention")
    assert ssm.layer_types is None and ssm.layer_aux and cfg.layer_aux and not attention.layer_aux
    # a cut in depth keeps the list whole and runs its first so many entries
    assert tiny(num_layers=6).layer_kinds() == PATTERN[:6] and len(tiny(num_layers=6).layer_types) == 40
    with pytest.raises(ValueError, match="layer_types"):
        tiny(layer_types=["mamba"] * 3)
    with pytest.raises(ValueError, match="layer_types"):
        tiny(layer_types=["mamba"] * 9 + ["windowed"])
    with pytest.raises(ValueError, match="state-space layers"):
        tiny(ssm_state_dim=0)


def test_the_published_cut_counts_772_160_448_parameters():
    """The benchmark's configuration counted leaf by leaf, ISSUE 39's table."""
    cfg = G.granite_hybrid_config(num_layers=10, vocab_size=12544)
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))  # noqa: E731
    mamba, attention = shapes["layers"][0], shapes["layers"][5]
    assert shapes["layers"][0]["ssm"]["win"]["kernel"].shape == (2048, 8512)
    assert count(mamba["ssm"]) == 25_847_232 and "wq" not in mamba
    assert count({k: attention[k] for k in ("wq", "wkv", "wo")}) == 10_485_760 and "ssm" not in attention
    assert count(mamba["wi"]) + count(mamba["wo_mlp"]) == 50_331_648
    assert (count(mamba), count(attention)) == (76_182_976, 60_821_504)
    assert count(shapes["embed"]) + count(shapes["final_norm"]) == 25_692_160 and "lm_head" not in shapes
    assert count(shapes) == 772_160_448


def test_the_loss_is_the_references(case):
    cfg, _, _, ((loss, parts), _), (ref_loss, _) = case
    assert float(loss) == pytest.approx(float(ref_loss), abs=F32_TOL)
    assert set(parts) == {"loss_ce"} | set(telemetry.SSM_STEP_FIELDS)
    assert float(parts["ssm_state_abs_max"]) > 0.0


def test_every_leafs_gradient_is_the_references(case):
    _, _, _, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    assert {"['layers'][0]['ssm']['A_log']", "['layers'][0]['ssm']['dt_bias']", "['layers'][0]['ssm']['D']",
            "['layers'][0]['ssm']['conv']['bias']", "['layers'][9]['ssm']['norm']['scale']",
            "['layers'][5]['wq']['kernel']", "['embed']['wte']"} <= set(errors)
    assert max(errors.values()) < F32_TOL, max(errors, key=errors.get)
    matrices = {k: v for k, v in errors.items() if "kernel" in k or "wte" in k}
    assert max(matrices.values()) < MATRIX_TOL, max(matrices, key=matrices.get)


@pytest.mark.parametrize("piece", ["embedding_multiplier", "residual_multiplier", "attention_multiplier",
                                   "logits_scaling", "nope"])
def test_each_multiplier_and_the_absent_positions_matter(case, piece):
    """Switched off in the reference alone (a multiplier taken as the model
    without it, positions turned on), the agreement breaks by far."""
    cfg, params, batch, ((loss, _), grads), _ = case
    # the gradients of the leaves from `first` up alone, so the reference's backward stops there (6 s a
    # case through all ten layers, PR 72): the attention layer and what follows it where the piece is the
    # attention's, the last layer and the final norm for the others; the largest of a part of the leaves
    # is no more than the largest of all
    first = 5 if piece in ("attention_multiplier", "nope") else 9
    top = lambda tree: {"layers": tree["layers"][first:], "final_norm": tree["final_norm"]}  # noqa: E731

    def off(leaves):
        p = {**params, "layers": params["layers"][:first] + leaves["layers"], "final_norm": leaves["final_norm"]}
        return REF.loss(p, batch, fields_of(cfg), switch_off=(piece,))

    with jax.default_matmul_precision("highest"):
        off_loss, off_grads = jax.jit(jax.value_and_grad(off))(top(params))
    # the gradients by far (measured 0.29 to 101 by the worst of these leaves); the loss too, but for
    # the positions: at a model's start a rotation of q and k hardly moves a softmax
    assert max(leaf_errors(top(grads), off_grads).values()) > 0.1
    assert piece == "nope" or abs(float(off_loss) - float(loss)) > 2 * F32_TOL


def test_the_tied_head_matters(case):
    """The head is the table: an untied program with a head of its own is
    another model, and the reference reads no `lm_head`."""
    cfg, params, batch, ((loss, _), _), _ = case
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    own = M.init_model_params(jax.random.PRNGKey(0), untied)
    assert "lm_head" in own and "lm_head" not in params
    with jax.default_matmul_precision("highest"):
        other = M.lm_loss_fn({**params, "lm_head": own["lm_head"]}, batch, untied)
    assert abs(float(other) - float(loss)) > 100 * F32_TOL


def test_bf16_compute_stays_within_the_cells_limit_of_the_reference(case):
    _, params, batch, _, (ref_loss, _) = case
    cfg = tiny(jnp.bfloat16)
    loss = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    # wide weights (init_std 0.2) make a bf16 forward scatter more than a model's start does
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-2)
    start = tiny(jnp.bfloat16, init_std=0.02)
    p0 = M.init_model_params(jax.random.PRNGKey(0), start)
    with jax.default_matmul_precision("highest"):
        want = REF.loss(p0, batch, fields_of(start))
    assert float(jax.jit(lambda p: M.lm_loss_fn(p, batch, start))(p0)) == pytest.approx(float(want), abs=2e-3)


def test_the_scanned_stack_is_the_unrolled_one(case):
    cfg, params, batch, ((loss, _), grads), _ = case
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        scanned = jax.jit(jax.value_and_grad(model.loss_fn))(params, model.shard_batch(batch))
    assert float(scanned[0]) == pytest.approx(float(loss), abs=1e-6)
    assert max(leaf_errors(scanned[1], grads).values()) < F32_TOL  # A_log's, recomputed in another order


def test_dp_with_zero3_runs_it_and_agrees(case):
    cfg, params, batch, ((loss, _), _), _ = case
    hp = HybridParallelConfig.uniform(2, cfg.num_layers, global_bsz=BATCH, checkpoint=1, sdp=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:2])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(model.loss_fn)(jax.device_put(params, model.shardings()), model.shard_batch(batch))
    assert float(got) == pytest.approx(float(loss), abs=1e-5)


# --------------------------------------------- each new piece against a formula
def test_the_ssm_mixer_is_its_few_lines():
    """[z | xBC | dt] from one projection, conv + bias + SiLU, the scan, the
    gate BEFORE a norm over all channels, the output projection."""
    lcfg = tiny().layer_config("ssm.dense")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    lp["ssm"]["norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (128,))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    p = lp["ssm"]
    with jax.default_matmul_precision("highest"):
        got, kv, counters = ssm_mixer(lp, y, None, lcfg)
        zxbcdt = y[0] @ p["win"]["kernel"]
        z, xbc, dt = zxbcdt[:, :128], zxbcdt[:, 128:288], zxbcdt[:, 288:]
        xbc = jax.nn.silu(REF.conv_shifted(xbc, p["conv"]["kernel"], p["conv"]["bias"]))
        o, last = REF.ssm_scan(xbc[:, :128].reshape(SEQ, 4, 32), jax.nn.softplus(dt + p["dt_bias"]),
                               -jnp.exp(p["A_log"]), xbc[:, 128:144], xbc[:, 144:], p["D"])
        gated = o.reshape(SEQ, 128) * jax.nn.silu(z)
        normed = gated / jnp.sqrt(jnp.mean(gated * gated, axis=-1, keepdims=True) + 1e-5) * p["norm"]["scale"]
        want = normed @ p["wout"]["kernel"]
    assert kv is None and p["win"]["kernel"].shape == (64, 128 + 160 + 4)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5)
    assert float(counters["ssm_state_abs_max"]) >= float(jnp.max(jnp.abs(last))) * 0.999


def test_the_mamba_leaves_start_as_the_mamba2_reference_starts_them():
    lp = M.init_layer_params(jax.random.PRNGKey(3), G.granite_hybrid_config().layer_config("ssm.dense"))["ssm"]
    a, dt = np.exp(np.asarray(lp["A_log"])), np.asarray(jax.nn.softplus(lp["dt_bias"]))
    assert a.shape == (64,) and 1.0 <= a.min() and a.max() <= 16.0
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    np.testing.assert_array_equal(np.asarray(lp["D"]), np.ones(64))
    assert lp["conv"]["kernel"].shape == (4352, 4) and lp["conv"]["bias"].shape == (4352,)
    assert float(jnp.max(jnp.abs(lp["conv"]["kernel"]))) <= 0.5 and lp["norm"]["scale"].shape == (4096,)


def test_the_attention_layer_takes_granites_scale_and_no_positions():
    lcfg = tiny().layer_config("dense")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    assert set(lp) == {"ln1", "ln2", "wq", "wkv", "wo", "wi", "wo_mlp"} and "bias" not in lp["wq"]
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    run = lambda cfg, pos: attention_mixer(  # noqa: E731
        lp, y, pos, cfg, mesh=None, axes=None, attn_bias=None, attn_sharding=None, return_kv=False)[0]
    pos = jnp.arange(SEQ)[None]
    with jax.default_matmul_precision("highest"):
        got = run(lcfg, pos)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(run(lcfg, pos + 7)))  # no position enters
        default_scale = run(dataclasses.replace(lcfg, attention_multiplier=None), pos)
        quarter = run(dataclasses.replace(lcfg, attention_multiplier=16 ** -0.5), pos)
    np.testing.assert_allclose(np.asarray(default_scale), np.asarray(quarter), atol=1e-6)
    assert float(jnp.max(jnp.abs(got - default_scale))) > 1e-3


def _first_loss_and_count(cfg, seq=64):  # the delta rule's chunk
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, seq), 0, cfg.vocab_size)
    batch = dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(seq), (2, seq)), labels=jnp.roll(tok, -1, 1))
    digest = sum(float(jnp.sum(jnp.abs(leaf))) for leaf in jax.tree.leaves(params))
    # jitted: the delta rule's and the routed block's ops dispatched one by one took 10 s a family (PR 72)
    return float(jax.jit(lambda p, b: M.lm_loss_fn(p, b, cfg))(params, batch)), digest, len(jax.tree.leaves(params))


SMALL = dict(num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2, ffn_hidden=32, vocab_size=128,
             max_seq_len=64, compute_dtype=jnp.float32, attn_impl="xla")
FAMILIES = {
    "gpt": lambda **kw: gpt_config("gpt-0.3b", **{**SMALL, "num_kv_heads": 2, **kw}),
    "llama": lambda **kw: llama_config("llama-0.3b", **{**SMALL, **kw}),
    "olmoe": lambda **kw: olmoe_config(**{**SMALL, **kw}),
    "glm4_moe_lite": lambda **kw: glm4_moe_lite_config(**{**SMALL, "num_layers": 3, **kw}),
    "qwen3_next": lambda **kw: qwen3_next_config(**{**SMALL, "num_layers": 4, "num_kv_heads": 1, **kw}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_other_families_are_what_they_were_under_the_new_fields_defaults(family):
    """The new fields' defaults are the model without them, and a default is
    not multiplied by: parameters and first loss are bit for bit those of the
    same config with every new field stated at its default, and the step's
    jaxpr holds no multiplication the fields could have added."""
    cfg = FAMILIES[family]()
    assert (cfg.embedding_multiplier, cfg.residual_multiplier, cfg.attention_multiplier,
            cfg.logits_scaling, cfg.layer_types, cfg.ssm_num_heads) == (1.0, 1.0, None, 1.0, None, 0)
    stated = FAMILIES[family](embedding_multiplier=1.0, residual_multiplier=1.0, attention_multiplier=None,
                              logits_scaling=1.0, layer_types=None)
    assert _first_loss_and_count(cfg) == _first_loss_and_count(stated)
    assert "ssm" not in " ".join(cfg.layer_kinds()) and "ssm" not in cfg.mixers()
    params = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    batch = dict(tokens=tok, positions=tok, labels=tok)
    plain = str(jax.make_jaxpr(lambda p, b: M.lm_loss_fn(p, b, cfg))(params, batch))
    scaled = str(jax.make_jaxpr(lambda p, b: M.lm_loss_fn(
        p, b, dataclasses.replace(cfg, residual_multiplier=0.5, logits_scaling=2.0,
                                  embedding_multiplier=3.0)))(params, batch))
    assert plain.count(" mul ") < scaled.count(" mul ") and plain.count(" div ") < scaled.count(" div ")


def test_one_table_maps_the_mixer_to_what_it_brings():
    assert M.MIXERS["ssm"].scopes == (tracing.ATTN_SSM, tracing.ATTN_SSD) == ("gt.attn.ssm", "gt.attn.ssd")
    assert callable(obs_flops.MIXER_FWD_FLOPS["ssm"][0])
    cfg = tiny()
    kinds = obs_flops.layer_kind_fwd_flops(cfg, 1.0)
    proj, core = obs_flops.ssm_fwd_flops_a_token(hidden=64, num_heads=4, head_dim=32, state_dim=16)
    assert (proj, core) == (2 * 64 * (2 * 128 + 2 * 16 + 4) + 2 * 128 * 64, 4 * 4 * 32 * 16)
    mlp = 3 * 2 * 64 * 96
    assert kinds["ssm.dense"] == proj + core + mlp
    assert kinds["dense"] == 2 * 64 * 64 + 2 * 64 * 64 + 2 * 64 * 64 + 2 * 2 * SEQ * 64 * 0.5 + mlp
    head = 2 * 64 * VOCAB
    assert obs_flops.train_step_flops(cfg, 1) == 3 * SEQ * (9 * kinds["ssm.dense"] + kinds["dense"] + head)


def test_the_step_hands_back_the_counter_and_the_event_takes_it():
    cfg = tiny(num_layers=2)  # two Mamba-2 layers, one scanned run: the counter's way out of a step knows no depth
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    import optax

    tx = optax.adam(1e-3)
    params = model.init_params(jax.random.PRNGKey(0))
    step = model.make_train_step(tx)
    _, _, metrics = step(params, model.init_opt_state(tx, params), model.shard_batch(batch_of()))
    assert float(metrics["ssm_state_abs_max"]) > 0.0
    assert "ssm_state_abs_max" in telemetry.EVENT_SCHEMAS["step"][1]


# ------------------------------------------------------------ GLS018, by name
def _layers(n, **kw):
    return [LayerStrategy(**kw) for _ in range(n)]


REFUSED = {
    "tp2": (dict(world_size=2, layers=_layers(10, tp=2)), "state-space layers"),
    "sp": (dict(world_size=2, layers=_layers(10, tp=2, sp=1)), "state-space layers"),
    "cp2": (dict(world_size=2, layers=_layers(10, cp=2)), "state-space layers"),
    "pp2": (dict(world_size=2, pp=2, layers=_layers(10), chunks=2), "not state-space layers among attention"),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_form_of_the_state_space_layers_is_refused_by_name(layout):
    cfg = tiny()
    kw, named = REFUSED[layout]
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": 4, **kw})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert any(d.code == "GLS018" and named in d.message for d in report.errors)
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    assert "GLS018" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("kwargs,named", [
    (dict(mode="serve"), "scan state of a state-space layer"),
    (dict(mode="train", autotune="observe"), "a state-space layer as softmax attention")],
    ids=["serve", "autotune"])
def test_serve_and_the_autotuner_refuse_it_and_name_the_state_space_layers(kwargs, named):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    errors = strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors
    assert any(d.code == "GLS018" and named in d.message for d in errors)
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok
    assert "state-space layers" in unsupported_reason(cfg, asker="search")
    assert "cost models" in unsupported_reason(cfg, asker="search")
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
    args = initialize_galvatron(mode=mode, argv=["--model_type", "granite_hybrid"])
    with pytest.raises(DiagnosticError) as e:
        run(args)
    assert "GLS018" in str(e.value) and "state-space layers" in str(e.value)
