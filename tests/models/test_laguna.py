"""Laguna's layers and objective (models/parts/window.py `window_mixer`, full
attention under yarn on half a head's dims, the per-head output gate, the
softmax router renormalised over its pick x 2.5 beside a shared expert,
models/laguna.py) against the plain reference
(benchmarks/references/laguna_lm.py) on seeded random weights at a small size:
hidden 64, five layers that hold all three kinds (full + dense MLP, window +
experts three times, full + experts: the benchmark's cut), 4 query heads on a
full layer and 6 on a window layer over 2 KV heads of 16, a window of 5 keys, a
dense SwiGLU of 96, 8 experts of 32 with 2 a token and a shared one, 128-row
untied tables, and **37 tokens a sequence, a multiple of nothing**; yarn with a
factor of 8 over an original 16 positions, so that all three regimes of its
ramp lie among a head's 4 frequencies.

Tolerances, and why. In float32 compute program and reference do the same
arithmetic in another order (attention whole against a block of rows at a
time, a sort and grouped matmuls against every expert densely). Every leaf's
gradient agrees to 2e-5 relative (measured 7.8e-6, a norm's scale), the logits
to 5e-5 absolute at a spread of 3. In bf16 compute the loss is held to 5e-4 of
the float32 reference, a quarter of the benchmark's own limit for every cell.

The weights are drawn with a wider `init_std` (0.2) than a model starts with
and the norms' scales moved off their start, so that the attention's logits,
the positions and the gate move the loss by far more than the tolerance.
"""

import dataclasses
import math
import os
from types import SimpleNamespace

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
from galvatron_tpu.models import laguna as L
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.parts import unsupported_reason
from galvatron_tpu.models.parts.common import ASKERS
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import forms, telemetry, tracing
from galvatron_tpu.ops.moe import moe_ffn
from galvatron_tpu.ops.rope import apply_rotary, rope_frequencies
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/laguna_lm.py")
PUB = L.PUBLISHED["laguna-xs.2"]

F32_TOL = 2e-5  # loss, worst-leaf relative gradient error
BATCH, SEQ, VOCAB, WINDOW = 2, 37, 128, 5
PATTERN = ("dense", "window.routed", "window.routed", "window.routed", "routed")
YARN = {"rope_type": "yarn", "factor": 8, "original_max_position_embeddings": 16, "beta_fast": 4, "beta_slow": 1,
        "attention_factor": 1.2}


def tiny(dtype=jnp.float32, **kw):
    fields = dict(
        hidden_size=64, num_heads=4, window_num_heads=6, num_kv_heads=2, head_dim=16, ffn_hidden=32,
        dense_ffn_hidden=96, num_layers=5, vocab_size=VOCAB, max_seq_len=SEQ, num_experts=8, experts_per_token=2,
        sliding_window=WINDOW, rope_scaling=YARN, init_std=0.2, compute_dtype=dtype, attn_impl="xla")
    fields.update(kw)
    return L.laguna_config("laguna-xs.2", **fields)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH, seq=SEQ):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0, VOCAB)
    mask = jnp.ones((batch, seq), jnp.float32).at[:, -1].set(0.0)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(seq), (batch, seq)),
                labels=jnp.roll(tok, -1, 1), loss_mask=mask)


def params_of(cfg, seed=0):
    """Seeded weights with norm scales that are not at their start."""
    params = M.init_model_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(key, leaf.shape) if "scale" in jax.tree_util.keystr(path) else leaf
             for (path, leaf), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, moved)


def leaf_errors(grads, ref_grads):
    def rel(a, b):
        norm = float(jnp.linalg.norm(b))
        diff = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        return diff / norm if norm else diff

    tree = jax.tree.map(rel, grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module", params=[0, 2], ids=["all_held", "2_of_8_held"])
def case(request):
    cfg = tiny(experts_held=request.param, experts_held_start=4 if request.param else 0)
    params, batch = params_of(cfg), batch_of()
    with jax.default_matmul_precision("highest"):
        program = jax.jit(jax.value_and_grad(
            lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)
        reference = jax.jit(jax.value_and_grad(
            lambda p: (lambda parts: (parts["loss"], parts))(REF.loss_parts(p, batch, fields_of(cfg))),
            has_aux=True))(params)
    return cfg, params, batch, program, reference


# ------------------------------------------------- the config, the pattern
def test_the_config_is_the_published_one():
    cfg = L.laguna_config()
    assert PUB["source"] == L.LAGUNA_XS2_SOURCE and get_family("laguna").meta_configs is L.PUBLISHED
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.window_num_heads, cfg.num_kv_heads,
            cfg.head_dim) == (40, 2048, 48, 64, 8, 128)
    assert (cfg.sliding_window, cfg.position_type, cfg.rope_theta, cfg.window_rope_theta, cfg.partial_rotary_factor,
            cfg.window_partial_rotary_factor, cfg.rotary_dim) == (512, "rope", 5e5, 1e4, 0.5, 1, 64)
    assert cfg.rope_scaling == {"rope_type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
                                "beta_fast": 64, "beta_slow": 1, "attention_factor": 1.4158883083359672}
    assert cfg.rope_scaling["attention_factor"] == pytest.approx(0.1 * math.log(64) + 1)  # yarn's own mscale
    assert (cfg.num_experts, cfg.experts_per_token, cfg.ffn_hidden, cfg.dense_ffn_hidden, cfg.num_shared_experts,
            cfg.first_dense_layers) == (256, 8, 512, 8192, 1, 1)
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor) == ("softmax", True, 2.5)
    assert (cfg.vocab_size, cfg.layernorm_eps, cfg.max_seq_len) == (100352, 1e-6, 262144)
    assert cfg.attn_head_gate and not cfg.attn_output_gate and not cfg.tie_embeddings
    assert not (cfg.qkv_bias or cfg.out_bias or cfg.mlp_bias)
    # what the published file has no key for is absent
    assert (cfg.qk_norm, cfg.shared_expert_gate, cfg.router_bias, cfg.router_aux_loss_coef) == (False, False, False, 0.0)
    assert cfg.routed and cfg.layer_aux and not cfg.latent_attention and cfg.mtp_layers == 0


def test_the_published_40_are_ten_periods_of_one_full_and_three_window_layers():
    cfg = L.laguna_config()
    assert PUB["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + PUB["layer_types"][4:]
    assert PUB["layer_types"].count("full_attention") == 10 and PUB["layer_types"].count("sliding_attention") == 30
    assert [n for n, t in zip(PUB["num_attention_heads_per_layer"], PUB["layer_types"]) if t == "full_attention"] == [48] * 10
    kinds = cfg.layer_kinds()
    assert kinds[:5] == PATTERN and (kinds.count("dense"), kinds.count("window.routed"), kinds.count("routed")) == (1, 30, 9)
    cut = L.laguna_config(num_layers=5)
    assert cut.layer_kinds() == PATTERN and len(cut.layer_types) == 40  # the list stays whole


@pytest.mark.parametrize("key,value,words", [
    ("attention_bias", True, "attention_bias=True is not modelled"),
    ("moe_apply_router_weight_on_input", True, "moe_apply_router_weight_on_input=True is not modelled"),
    ("layer_types", ["full_attention"] * 39 + ["conv"], "layer_types names"),
    ("layer_types", ["full_attention"] * 39, "for each of the 40 layers"),
    ("mlp_layer_types", ["sparse", "dense"] + ["sparse"] * 38, "leading \"dense\" layers and then \"sparse\""),
    ("num_attention_heads_per_layer", [48, 64, 64, 32] * 10, "ONE head count for the 'sliding_attention' layers"),
    ("num_attention_heads", 32, "num_attention_heads=32 is the full layers' head count"),
    ("shared_expert_intermediate_size", 768, "a shared expert of width 768"),
    ("rope_parameters", {**PUB["rope_parameters"], "sliding_attention": {"rope_type": "yarn", "rope_theta": 1e4}},
     "the sliding layers' rope_type='yarn' is not modelled"),
    ("rope_parameters", {**PUB["rope_parameters"], "full_attention": {"rope_type": "llama3", "rope_theta": 5e5}},
     "rope_type='llama3' has no form"),
])
def test_what_is_not_modelled_is_refused_not_dropped(key, value, words):
    with pytest.raises(ValueError, match=words):
        L.laguna_config_from_hf(SimpleNamespace(**{**PUB, key: value}))


def test_a_pattern_of_full_and_window_layers_gives_three_runs():
    cfg = tiny()
    assert cfg.layer_kinds() == PATTERN and model_layer_kinds(cfg) == PATTERN
    hp = HybridParallelConfig.uniform(1, 5, global_bsz=BATCH, checkpoint=1)
    runs = layer_runs(hp, model_layer_kinds(cfg))
    assert [(r.start, r.stop) for r in runs] == [(0, 1), (1, 4), (4, 5)]  # the middle one a scan of three
    first, window, full = (cfg.layer_config(k) for k in ("dense", "window.routed", "routed"))
    assert (first.mixer, window.mixer, full.mixer) == ("attention", "window", "attention")
    assert (first.num_heads, window.num_heads, full.num_heads) == (4, 6, 4)
    assert not first.routed and first.ffn_hidden == 96 and window.routed and full.routed
    assert window.layer_types is None and not first.layer_aux and window.layer_aux and full.layer_aux


def test_the_published_cut_counts_691_623_936_parameters():
    """The benchmark's configuration counted leaf by leaf, ISSUE 49's table."""
    cfg = L.laguna_config(num_layers=5, vocab_size=12544, experts_held=32)
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))  # noqa: E731
    first, window, full = shapes["layers"][0], shapes["layers"][1], shapes["layers"][4]
    mixer = lambda lp: count({k: lp[k] for k in ("wq", "wkv", "wg", "wo")})  # noqa: E731
    assert first["wq"]["kernel"].shape == (2048, 48, 128) and first["wg"]["kernel"].shape == (2048, 48)
    assert window["wq"]["kernel"].shape == (2048, 64, 128) and window["wg"]["kernel"].shape == (2048, 64)
    assert window["wkv"]["kernel"].shape == full["wkv"]["kernel"].shape == (2048, 2, 8, 128)
    assert window["wo"]["kernel"].shape == (8192, 2048) and full["wo"]["kernel"].shape == (6144, 2048)
    assert (mixer(first), mixer(window), mixer(full)) == (29_458_432, 37_879_808, 29_458_432)
    assert "q_norm" not in window and "q_norm" not in full
    assert count(first["wi"]) + count(first["wo_mlp"]) == 50_331_648
    routed = {k: window[k] for k in ("router", "wi", "wo_mlp", "shared")}
    assert window["wi"]["kernel"].shape == (32, 2048, 1024) and window["router"]["kernel"].shape == (2048, 256)
    assert count(window["shared"]) == 3_145_728 and "gate" not in window["shared"] and count(routed) == 104_333_312
    assert (count(first), count(window), count(full)) == (79_794_176, 142_217_216, 133_795_840)
    assert count(shapes["embed"]) == count(shapes["lm_head"]) == 25_690_112 and count(shapes["final_norm"]) == 2048
    assert count(shapes) == 691_623_936
    # and the whole model's, the published 33.4 B (the evidence for a gate a HEAD: an elementwise one reads 34.1 B)
    whole = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), L.laguna_config()))
    assert round(count(whole) / 1e9, 2) == 33.44
    gated = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), L.laguna_config(
        attn_head_gate=False, attn_output_gate=True)))
    assert round(count(gated) / 1e9, 1) == 34.1


# ------------------------------------------------- the whole model, float32
def test_logits_loss_and_parts_are_the_references(case):
    cfg, params, batch, ((loss, parts), _), ((ref_loss, ref_parts), _) = case
    assert float(loss) == pytest.approx(float(ref_loss), abs=F32_TOL)
    with jax.default_matmul_precision("highest"):
        logits = M.model_forward(params, batch["tokens"], batch["positions"], cfg)
        want = REF.logits(params, batch, fields_of(cfg))
    assert float(jnp.std(want)) > 1.0
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), atol=5e-5)
    assert {"loss_ce", M.EXPERT_LOAD} <= set(parts) and "router_bias_abs_max" not in parts
    assert float(parts["loss_ce"]) == float(loss)  # no auxiliary term in the objective
    assert not set(telemetry.LINEAR_STEP_FIELDS) & set(parts)  # a window mixer hands back no counter
    if cfg.experts_held:
        picks = np.asarray(ref_parts["picks"])  # (batch, routed blocks, seq, k) over all 8
        assert picks.shape == (BATCH, 4, SEQ, 2) and picks.max() >= 6 and picks.min() < 4
        held = np.sum((picks >= 4) & (picks < 6))
        assert float(parts["expert_rows_held"]) == held
        assert float(parts["expert_rows_held_over_even"]) == pytest.approx(held / (4 * BATCH * SEQ * 2 * 2 / 8))


def test_every_leafs_gradient_is_the_references(case):
    _, _, _, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    assert {"['layers'][0]['wq']['kernel']", "['layers'][0]['wg']['kernel']", "['layers'][1]['wq']['kernel']",
            "['layers'][1]['wkv']['kernel']", "['layers'][2]['wg']['kernel']", "['layers'][3]['wo']['kernel']",
            "['layers'][4]['wg']['kernel']", "['layers'][2]['router']['kernel']",
            "['layers'][2]['shared']['wi']['kernel']", "['layers'][0]['wi']['kernel']",
            "['embed']['wte']", "['lm_head']['kernel']", "['final_norm']['scale']"} <= set(errors)
    assert max(errors.values()) < F32_TOL, max(errors, key=errors.get)


@pytest.mark.parametrize("off", ["head_gate", "yarn_scale", "yarn", "window_rope", "window", "gqa", "shared"])
def test_the_whole_model_fails_with_a_mechanism_switched_off(case, off):
    """The comparison above is one that each piece of the mathematics moves:
    the reference without it is 25 tolerances and more from the program in
    the LOSS alone (the least over the two cases: the gate 49, the window
    layers' rope 93, yarn's frequencies at 37 positions 185; the others 1000
    and more), where a leaf's gradient is held to one."""
    cfg, params, batch, ((loss, _), _), _ = case
    with jax.default_matmul_precision("highest"):
        without = float(REF.loss(params, batch, fields_of(cfg), switch_off=(off,)))
    assert abs(without - float(loss)) > 25 * F32_TOL, off


@pytest.mark.parametrize("window", [WINDOW - 1, WINDOW + 1])
def test_the_window_is_not_off_by_one(case, window):
    cfg, params, batch, ((loss, _), _), _ = case
    with jax.default_matmul_precision("highest"):
        other = float(REF.loss(params, batch, {**fields_of(cfg), "sliding_window": window}))
    assert abs(other - float(loss)) > 200 * F32_TOL


def test_bf16_compute_stays_within_the_benchmarks_limit():
    """At the model's own init_std 0.02 (at the fixture's 0.2 the router's near
    ties flip under a bf16 residual stream). The limit is 5e-4, a quarter of
    the benchmark's 2e-3, and two unrelated forwards differ by more than 2e-3
    here."""
    cfg, ref_cfg = tiny(jnp.bfloat16, init_std=0.02), tiny(init_std=0.02)
    params, batch = params_of(ref_cfg), batch_of()
    loss = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss = jax.jit(lambda p: REF.loss(p, batch, fields_of(ref_cfg)))(params)
        other = jax.jit(lambda p: REF.loss(p, batch_of(seed=5), fields_of(ref_cfg)))(params)
    assert float(loss) == pytest.approx(float(ref_loss), abs=5e-4)
    assert float(loss) != float(ref_loss) and abs(float(other) - float(ref_loss)) > 2e-3


def test_the_scanned_stack_is_the_unrolled_one():
    cfg, batch = tiny(), batch_of()
    params = params_of(cfg)
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    with forms.recording() as took, jax.default_matmul_precision("highest"):
        scanned = jax.jit(jax.value_and_grad(model.loss_fn))(params, model.shard_batch(batch))
        plain = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg)))(params)
    assert float(scanned[0]) == pytest.approx(float(plain[0]), abs=1e-6)
    assert max(leaf_errors(scanned[1], plain[1]).values()) < 1e-5
    assert set(took[forms.WINDOW_ATTENTION]) == {"xla"}  # off a TPU: the band mask


# --------------------------------------------------------- yarn, the mixers
def hf_yarn_inv_freq(dim, base, factor, original, beta_fast, beta_slow):
    """HF transformers' `_compute_yarn_parameters` (modeling_rope_utils.py),
    its arithmetic line for line in numpy, `truncate` true."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(original / (num_rotations * 2 * math.pi))) / (2 * math.log(base))

    low, high = math.floor(find_correction_dim(beta_fast)), math.ceil(find_correction_dim(beta_slow))
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    inv_freq_extrapolation, inv_freq_interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    inv_freq_extrapolation_factor = 1 - ramp
    return (inv_freq_interpolation * (1 - inv_freq_extrapolation_factor)
            + inv_freq_extrapolation * inv_freq_extrapolation_factor), (low, high)


@pytest.mark.parametrize("dim,theta,scaling", [
    (64, 5e5, L.laguna_config().rope_scaling), (8, 5e5, YARN), (128, 1e4, {**YARN, "factor": 4, "beta_fast": 32}),
], ids=["published", "tiny", "other"])
def test_yarns_frequencies_are_hfs(dim, theta, scaling):
    want, (low, high) = hf_yarn_inv_freq(dim, theta, scaling["factor"], scaling["original_max_position_embeddings"],
                                         scaling["beta_fast"], scaling["beta_slow"])
    got = np.asarray(rope_frequencies(dim, theta, scaling))
    np.testing.assert_allclose(got, want, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(REF.yarn_inv_freq(dim, theta, scaling)), want, rtol=2e-6)
    plain = np.asarray(rope_frequencies(dim, theta))
    if dim == 64:  # the published: floor 5, ceil 16; below the ramp as they were, above it divided by 64
        assert (low, high) == (5, 16)
        np.testing.assert_allclose(got[:6], plain[:6], rtol=1e-6)
        np.testing.assert_allclose(got[16:], plain[16:] / 64, rtol=1e-6)
        assert np.all(got[6:16] < plain[6:16]) and np.all(got[6:16] > plain[6:16] / 64)


def test_yarns_scale_is_on_the_turned_dims_alone_and_an_unknown_type_is_refused_by_name():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 9, 2, 16))
    positions = jnp.arange(9)[None]
    turned = apply_rotary(x, positions, 5e5, rotary_dim=8, scaling=YARN)
    np.testing.assert_array_equal(np.asarray(turned[..., 8:]), np.asarray(x[..., 8:]))  # the rest pass as they are
    norms = np.linalg.norm(np.asarray(turned[..., :8]), axis=-1) / np.linalg.norm(np.asarray(x[..., :8]), axis=-1)
    np.testing.assert_allclose(norms, 1.2, rtol=1e-5)  # a rotation x the attention factor
    unscaled = apply_rotary(x, positions, 5e5, rotary_dim=8)
    assert np.abs(np.asarray(unscaled) - np.asarray(x))[0, 0].max() < 1e-6  # position 0 turns nothing
    with pytest.raises(ValueError, match="rope_type='linear' has no form here"):
        apply_rotary(x, positions, 5e5, scaling={"rope_type": "linear", "factor": 2.0})
    with pytest.raises(ValueError, match="missing \\['attention_factor'\\]"):
        rope_frequencies(8, 5e5, {k: v for k, v in YARN.items() if k != "attention_factor"})


def test_a_window_mixer_is_the_references_on_its_own_heads_and_rope_under_its_own_scope():
    cfg = tiny()
    lcfg = cfg.layer_config("window.routed")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    positions = jnp.arange(SEQ)[None]
    forward = lambda lp, y: M.MIXERS["window"].forward(  # noqa: E731
        lp, y, positions, lcfg, mesh=None, axes=None, attn_bias=None, attn_sharding=None, return_kv=False)
    with jax.default_matmul_precision("highest"):
        out, kv, counters = forward(lp, y)
        want = REF.attention(lp, y[0], positions[0], fields_of(cfg), True)
        full_rope = REF.attention(lp, y[0], positions[0], fields_of(cfg), True, frozenset(("window_rope",)))
    assert kv is None and counters is None and out.shape == (1, SEQ, 64)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), atol=2e-5)
    assert np.abs(np.asarray(full_rope) - np.asarray(want)).max() > 1e-2
    names = jax.jit(forward).lower(lp, y).as_text(debug_info=True)
    assert tracing.ATTN_WINDOW in names and tracing.ATTN_WINDOW_BAND in names and tracing.ATTN_PROJ not in names
    full = cfg.layer_config("routed")
    names = jax.jit(lambda lp, y: M.MIXERS["attention"].forward(
        lp, y, positions, full, mesh=None, axes=None, attn_bias=None, attn_sharding=None, return_kv=False)).lower(
            M.init_layer_params(jax.random.PRNGKey(0), full), y).as_text(debug_info=True)
    assert tracing.ATTN_PROJ in names and tracing.ATTN_WINDOW not in names and tracing.ATTN_WINDOW_BAND not in names


def test_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: for one routed half, the parts that the 8
    shares give (one expert of 8 each, as 32 of 256 a chip over 8 chips) plus
    what every chip computes alike, the shared expert, COUNTED ONCE, add up to
    the uncut reference's output for the whole layer, under the softmax router
    renormalised over its pick x 2.5."""
    cfg = tiny()
    lcfg = cfg.layer_config("window.routed")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    assert "shared" in lp and lcfg.routed_scaling_factor == 2.5
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        lp32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        whole, picks = REF.routed(lp32, y[0], fields_of(lcfg))
        shared = REF._swiglu(lp32["shared"], y[0])
        total, rows = 0.0, 0.0
        for rank in range(8):
            held = dataclasses.replace(lcfg, experts_held=1, experts_held_start=rank)
            mine = {**lp, "wi": {"kernel": lp["wi"]["kernel"][rank:rank + 1]},
                    "wo_mlp": {"kernel": lp["wo_mlp"]["kernel"][rank:rank + 1]}}
            out, _, aux = M.MLP_HALVES["routed"].forward(mine, y, None, held)  # the routed part + the shared expert
            alone = REF.routed(jax.tree.map(lambda a: a.astype(jnp.float32), mine), y[0], fields_of(held))[0]
            np.testing.assert_allclose(np.asarray(out[0]), np.asarray(alone), atol=5e-6)  # a share is the reference's
            total, rows = total + (out[0] - shared), rows + float(aux["rows_held"])
    assert rows == SEQ * 2  # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(whole), atol=3e-5)  # eight sums at values of 3
    assert float(jnp.abs(shared).max()) > 0.1 and set(np.unique(np.asarray(picks))) == set(range(8))
    # the weights of a token's pick sum to the scaling factor (renormalised over the pick)
    out, aux = moe_ffn(y, lp["router"]["kernel"], lp["wi"]["kernel"], lp["wo_mlp"]["kernel"], experts_per_token=2,
                       norm_topk_prob=True, dtype=jnp.float32, score="softmax", scale=2.5)
    np.testing.assert_allclose(np.asarray(out[0] + shared), np.asarray(whole), atol=1e-5)


# ----------------------------- the window kernels on q as projected (PR 50)
@pytest.fixture(scope="module")
def two_forms(window_kernels_as_on_a_tpu):
    """A tiny Laguna whose window layers the kernels have a form of (heads of
    128, 256 tokens, a window of 160: two key blocks a step at 128-token
    blocks), float32: loss and every leaf's gradient with the window calls as
    the CPU runs them (rope, XLA's band, the gate's product) and as a TPU does
    (`window_takes_kernels` answered as on a TPU, the kernels interpreted: q
    read where the flat projection wrote it, turned in the kernel, the head's
    gate in its epilogue) -> {form: ((loss, parts), grads)}, what `obs/forms` heard."""
    import unittest.mock as mock

    import jax.experimental.pallas.tpu as pltpu

    from galvatron_tpu.ops import window_attention

    cfg = tiny(head_dim=128, max_seq_len=256, sliding_window=160, attn_impl="auto")
    params, batch = params_of(cfg), batch_of(seq=256)

    def run():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)

    out = {"xla": run()}
    with forms.recording() as took, window_kernels_as_on_a_tpu(), mock.patch.object(window_attention, "BLOCK", 128), \
         pltpu.force_tpu_interpret_mode():
        out["kernels"] = run()
    return out, took


def test_the_loss_is_the_same_with_the_window_kernels_on_q_as_projected(two_forms):
    out, took = two_forms
    assert took[forms.WINDOW_ATTENTION]["pallas"] == took[forms.WINDOW_OPERANDS]["as_projected"] > 0
    assert "xla" not in took[forms.WINDOW_ATTENTION]
    (loss, parts), (want, want_parts) = out["kernels"][0], out["xla"][0]
    assert abs(float(loss) - float(want)) < F32_TOL
    for name in want_parts:
        np.testing.assert_allclose(np.asarray(parts[name]), np.asarray(want_parts[name]), atol=F32_TOL, err_msg=name)


def test_every_leafs_gradient_is_the_same_with_the_window_kernels_on_q_as_projected(two_forms):
    out, _ = two_forms
    errors = leaf_errors(out["kernels"][1], out["xla"][1])
    assert len(errors) > 20 and max(errors.values()) < F32_TOL, sorted(errors.items(), key=lambda kv: -kv[1])[:5]
    moved = leaf_errors(out["kernels"][1], jax.tree.map(jnp.zeros_like, out["xla"][1]))
    assert min(moved.values()) > 0  # no leaf's gradient is lost on the way: the gate's, the window's wq among them


# ------------------------------------------------ the table, FLOPs, the counters
def test_one_table_maps_the_window_mixer_to_what_it_brings():
    # (and `gt.attn.diff`, which the differential form alone names: Phi-4-mini-flash's window layers)
    assert M.MIXERS["window"].scopes[:2] == (tracing.ATTN_WINDOW, tracing.ATTN_WINDOW_BAND) == (
        "gt.attn.window", "gt.attn.band")
    scopes = [s for part in M.MIXERS.values() for s in part.scopes]
    assert not any(a != b and (a.startswith(b) or b.startswith(a)) for a in M.MIXERS["window"].scopes for b in scopes)
    assert not M.MIXERS["window"].counters and M.MIXERS["window"].decode is None
    assert M.MIXERS["window"].init is M.MIXERS["attention"].init  # the attention part's leaves, not a copy
    assert set(obs_flops.MIXER_FWD_FLOPS) == set(M.MIXERS)  # a FLOPs row a key, and no other
    cfg = tiny()
    kinds = obs_flops.layer_kind_fwd_flops(cfg, 1.0)
    proj = lambda heads: 2 * 64 * heads * 16 * 2 + 2 * 64 * (2 * 2 * 16) + 2 * 64 * heads  # noqa: E731 (q, o; k, v; the gate)
    keys = (WINDOW * SEQ - WINDOW * (WINDOW - 1) / 2) / SEQ  # the exact band
    full = proj(4) + 2 * (2 * SEQ * 4 * 16) * 0.5
    window = proj(6) + 2 * 2 * keys * 6 * 16
    moe = (2 + 1) * (3 * 2 * 64 * 32) + 2 * 64 * 8  # two experts a token, the shared one, the router
    assert kinds == pytest.approx({"dense": full + 3 * 2 * 64 * 96, "window.routed": window + moe, "routed": full + moe})
    head = 2 * 64 * VOCAB
    assert obs_flops.train_step_flops(cfg, 1) == pytest.approx(3 * SEQ * (
        kinds["dense"] + 3 * kinds["window.routed"] + kinds["routed"] + head))
    # a window that reaches the whole sequence is the causal triangle (+ the diagonal's half)
    wide = obs_flops.window_fwd_flops_a_token(hidden=64, num_heads=6, head_dim=16, num_kv_heads=2, window=SEQ + 9,
                                              head_gate=True, seq_len=SEQ)[1]
    assert wide == pytest.approx(2 * 2 * (SEQ + 1) / 2 * 6 * 16)
    assert "forms" in telemetry.EVENT_SCHEMAS["compile"][1]


# ------------------------------------------------------------ GLS018, by name
def _layers(n, **kw):
    return [LayerStrategy(**kw) for _ in range(n)]


REFUSED = {
    "tp2": (dict(world_size=2, layers=_layers(5, tp=2)), "window attention layers"),
    "sp": (dict(world_size=2, layers=_layers(5, tp=2, sp=1)), "the window kernels' heads have not been split"),
    "cp2": (dict(world_size=2, layers=_layers(5, cp=2)), "the ring has no band"),
    "pp5": (dict(world_size=5, pp=5, layers=_layers(5), chunks=5),
            "not window attention layers among full attention"),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_form_of_the_window_layers_is_refused_by_name(layout):
    cfg = tiny()
    kw, named = REFUSED[layout]
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": 10, **kw})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert any(d.code == "GLS018" and named in d.message for d in report.errors)
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    assert "GLS018" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("kwargs,named", [
    (dict(mode="serve"), "no cache of a window attention layer's last keys"),
    (dict(mode="serve"), "no per-head output gate on a decoded token's attention"),
    (dict(mode="serve"), "no expert form"),
    (dict(mode="train", autotune="observe"), "a window attention layer as full attention")],
    ids=["serve_window", "serve_gate", "serve_experts", "autotune"])
def test_serve_and_the_autotuner_refuse_it_and_name_the_window_layers(kwargs, named):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    errors = strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors
    assert any(d.code == "GLS018" and named in d.message for d in errors)
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok


def test_the_window_part_names_itself_to_every_asker():
    said = M.MIXERS["window"].unsupported(tiny())
    assert set(said) == set(ASKERS) and all("window attention layer" in words for words in said.values())
    for asker in ("search", "profile"):
        assert "window attention layers" in unsupported_reason(tiny(), asker=asker)
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
    args = initialize_galvatron(mode=mode, argv=["--model_type", "laguna"])
    with pytest.raises(DiagnosticError) as e:
        run(args)
    assert "GLS018" in str(e.value) and "window attention layers" in str(e.value)
