"""Nemotron-H's blocks and objective (models/base.py, models/parts/absent.py, ops/ssd.py with groups,
models/nemotron_h.py) against the plain reference (benchmarks/references/nemotron_h_lm.py) on seeded random
weights at a small size: hidden 64, the published pattern's first nine blocks MEMEM*EME (4 Mamba-2 heads of 8
in 2 groups with states of 16, 4 taps and a bias; 4 query heads on 2 KV heads of 16, no positions; 8 experts
of 32, 2 a token, 4 held from the 2nd on, beside a shared expert of 48, all `down(relu(up x)^2)`), untied
128-row tables.

Tolerances, and why. In float32 compute program and reference do the same arithmetic in another order (the
chunked scan against the recurrence token by token, a block of queries at a time, sorted rows against every
expert on every token): every leaf's gradient agrees to 5e-5 relative, the Granite test's limit for the
scan's scalars a head (measured 3.3e-6 here). The router's bias is moved off 0 first, so that the pick is
by score PLUS bias and the weights by score alone."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells
from galvatron_tpu import HybridParallelConfig
from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.models import base as M
from galvatron_tpu.models import nemotron_h as N
from galvatron_tpu.models.parts import MIXERS, MLP_HALVES, unsupported_reason
from galvatron_tpu.models.parts.mlp import _routed_forward
from galvatron_tpu.models.parts.ssm import ssm_mixer
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import forms
from galvatron_tpu.ops import ssd

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/nemotron_h_lm.py")
PATTERN = N.PUBLISHED["nemotron-3-nano-30b-a3b"]["hybrid_override_pattern"]
F32_TOL = 5e-5
BATCH, SEQ, VOCAB = 2, 64, 128
TINY = dict(hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, ssm_num_heads=4, ssm_head_dim=8,
            ssm_state_dim=16, ssm_groups=2, ffn_hidden=32, shared_expert_ffn=48, num_experts=8,
            experts_per_token=2, vocab_size=VOCAB, num_layers=9, max_seq_len=SEQ, compute_dtype=jnp.float32)


def tiny(**overrides):
    return N.nemotron_h_config(**{**TINY, **overrides})


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (BATCH, SEQ), 0, VOCAB)
    return dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(SEQ), (BATCH, SEQ)), labels=jnp.roll(tokens, -1, 1))


def moved_bias(params, cfg):
    """The routers' biases off 0, as a few steps of unequal load leave them."""
    counts = jax.random.uniform(jax.random.PRNGKey(5), (cfg.routed_layers, cfg.num_experts))
    return M.update_router_bias(params, counts, 0.05)


def rel(a, b):
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------------------ pattern -> stack
def test_the_published_pattern_builds_52_layers_of_one_half_in_published_order():
    cfg = N.nemotron_h_config(hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, ffn_hidden=32,
                              shared_expert_ffn=48, vocab_size=VOCAB)
    assert cfg.num_layers == len(PATTERN) == 52 and (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (23, 23, 6)
    kind_of = {"M": "ssm.none", "E": "none.routed", "*": "none"}
    assert cfg.layer_kinds() == tuple(kind_of[c] for c in PATTERN)
    assert cfg.mixers() == tuple({"M": "ssm", "E": "none", "*": "attention"}[c] for c in PATTERN)
    assert cfg.mlp_halves() == tuple("routed" if c == "E" else "none" for c in PATTERN)
    assert cfg.routed_layers == 23 and cfg.published_indices() == tuple(range(52))
    # every E follows a mixer; two mixers meet (M*) with no MLP between them
    assert all(PATTERN[i - 1] in "M*" for i, c in enumerate(PATTERN) if c == "E") and "M*" in PATTERN
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    assert [REF.block_kind(lp) for lp in shapes["layers"]] == list(PATTERN)


def test_a_cut_runs_the_patterns_first_blocks_and_speaks_published_numbers():
    cfg = tiny()
    assert cfg.layer_kinds() == ("ssm.none", "none.routed", "ssm.none", "none.routed", "ssm.none", "none",
                                 "none.routed", "ssm.none", "none.routed")
    assert len(cfg.layer_types) == len(cfg.mlp_types) == 52  # the lists stay whole
    assert tiny(num_layers=6).layer_kinds()[-1] == "none"  # a cut may end on the attention block
    with pytest.raises(ValueError, match=r"block 2 is '-'"):
        N.pattern_layers("ME-M")
    with pytest.raises(ValueError, match="names 4 blocks, num_hidden_layers 52"):
        N.nemotron_h_config_from_hf(type("C", (), {**N.PUBLISHED["nemotron-3-nano-30b-a3b"], "hybrid_override_pattern": "MEM*"}))
    with pytest.raises(ValueError, match=r"published layers \[1\] have neither"):
        tiny(layer_types=["mamba", "none"] + ["mamba"] * 50, mlp_types=["none"] * 52)
    with pytest.raises(ValueError, match="mlp_types names the MLP half"):
        tiny(mlp_types=["none"] * 9)


def test_an_absent_half_has_no_leaves_no_norm_and_no_specs():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    by_kind = {REF.block_kind(lp): lp for lp in params["layers"]}
    assert sorted(by_kind["M"]) == ["ln1", "ssm"] and sorted(by_kind["*"]) == ["ln1", "wkv", "wo", "wq"]
    assert sorted(by_kind["E"]) == ["ln2", "router", "shared", "wi", "wo_mlp"]
    assert MIXERS["none"] is MLP_HALVES["none"] and MIXERS["none"].absent and MIXERS["none"].scopes == ()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    specs = M.model_param_specs(cfg, hp)
    assert jax.tree.structure(jax.tree.map(lambda _: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda _: 0, specs, is_leaf=lambda t: isinstance(t, jax.sharding.PartitionSpec)))
    with pytest.raises(AssertionError, match="skipped by the stack"):
        MIXERS["none"].forward(None, None, None, cfg)


def test_the_cuts_parameter_count_is_the_formulas():
    """At the published widths (shapes alone): the cell's 666,963,456 = 4 M + 4 E (8 held) + 1 * blocks, the two
    sliced tables and the final norm; and the whole model 31.58 B."""
    cfg = N.nemotron_h_config(num_layers=9, experts_held=8, vocab_size=16384)
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))  # noqa: E731
    a_block = {REF.block_kind(lp): count(lp) for lp in shapes["layers"]}
    mamba = 2688 + 2688 * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * 2688
    attention = 2688 + 2 * 2688 * 4096 + 2 * 2688 * 256
    routed = 2688 + 2688 * 128 + 128 + 2 * 2688 * 3712 + 8 * 2 * 2688 * 1856
    assert a_block == {"M": mamba, "*": attention, "E": routed} == {"M": 38_744_896, "*": 23_399_040, "E": 100_125_440}
    assert count(shapes) == 4 * mamba + attention + 4 * routed + 2 * 16384 * 2688 + 2688 == 666_963_456
    whole = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), N.nemotron_h_config()))
    assert count(whole) / 1e9 == pytest.approx(31.58, abs=0.005)


# ------------------------------------------------------------ program against reference
@pytest.fixture(scope="module")
def both():
    cfg = tiny(experts_held=4, experts_held_start=2)
    params = moved_bias(M.init_model_params(jax.random.PRNGKey(0), cfg), cfg)
    batch = batch_of()
    ours = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg)))(params)
    theirs = jax.jit(jax.value_and_grad(lambda p: REF.loss(p, batch, fields_of(cfg))))(params)
    return cfg, params, batch, ours, theirs


def test_the_loss_is_the_references(both):
    _, _, _, (loss, _), (ref_loss, _) = both
    assert abs(float(loss) - float(ref_loss)) < F32_TOL


@pytest.mark.parametrize("kind", ["M", "*", "E", "tables"])
def test_every_leafs_gradient_is_the_references(both, kind):
    _, params, _, (_, grads), (_, ref_grads) = both
    picked = ([i for i, lp in enumerate(params["layers"]) if REF.block_kind(lp) == kind] if kind != "tables" else [])
    ours = [grads["layers"][i] for i in picked] or {k: v for k, v in grads.items() if k != "layers"}
    theirs = [ref_grads["layers"][i] for i in picked] or {k: v for k, v in ref_grads.items() if k != "layers"}
    seen = 0
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(ours)[0], jax.tree.leaves(theirs)):
        if "e_score_correction_bias" in jax.tree_util.keystr(path):
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(r))  # no gradient moves the bias
            continue
        assert float(jnp.linalg.norm(r)) > 0 and rel(g, r) < F32_TOL, jax.tree_util.keystr(path)
        seen += 1
    assert seen >= {"M": 4 * 9, "*": 4, "E": 4 * 6, "tables": 3}[kind]


def test_bf16_compute_stays_within_the_cells_limit():
    cfg = tiny(compute_dtype=jnp.bfloat16, experts_held=4)
    params = M.init_model_params(jax.random.PRNGKey(3), cfg)
    batch = batch_of(4)
    loss = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    ref = jax.jit(lambda p: REF.loss(p, batch, fields_of(cfg)))(params)
    assert abs(float(loss) - float(ref)) < 2e-3


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 experts each: the routed parts they give, with the shared expert (which every chip
    computes alike) counted ONCE, add up to what the uncut reference gives for the whole E block."""
    whole = tiny()
    lcfg = whole.layer_config("none.routed")
    lp = moved_bias(M.init_model_params(jax.random.PRNGKey(0), whole), whole)["layers"][1]
    y = jax.random.normal(jax.random.PRNGKey(7), (1, SEQ, 64), jnp.float32)
    want, _ = REF._routed(lp, y[0], fields_of(whole))
    shared = REF._relu2_mlp(lp["shared"]["wi"]["kernel"], lp["shared"]["wo_mlp"]["kernel"], y[0])
    total = shared
    for first in range(0, 8, 2):
        share_cfg = dataclasses.replace(lcfg, experts_held=2, experts_held_start=first)
        share = {**lp, "wi": {"kernel": lp["wi"]["kernel"][first:first + 2]},
                 "wo_mlp": {"kernel": lp["wo_mlp"]["kernel"][first:first + 2]}}
        out, _, aux = _routed_forward(share, y, None, share_cfg)
        ref_share, _ = REF._routed(share, y[0], fields_of(share_cfg))
        np.testing.assert_allclose(out[0], ref_share, atol=2e-6)
        assert float(aux["rows_held"]) > 0
        total = total + (out[0] - shared)
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert float(jnp.max(jnp.abs(want - shared))) > 1e-3  # the routed part is no rounding


# ------------------------------------------------------------ the scan with groups
def _scan_operands(groups, seq=128, heads=8, p=8, n=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (2, seq, heads, p), jnp.float32)
    dt = jnp.exp(jax.random.uniform(ks[1], (2, seq, heads), jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
    a = -jax.random.uniform(ks[2], (heads,), jnp.float32, 1.0, 16.0)
    bm, cm = (jax.random.normal(k, (2, seq, groups, n), jnp.float32) for k in ks[3:5])
    return x, dt, a, bm, cm, jax.random.normal(ks[5], (heads,), jnp.float32)


@pytest.mark.parametrize("groups,heads_at_once,chunk", [(2, 16, 32), (4, 1, 32), (8, 16, 40), (2, 2, 128)])
def test_the_scan_with_groups_is_the_recurrence_token_by_token(groups, heads_at_once, chunk):
    x, dt, a, bm, cm, d = _scan_operands(groups)
    with forms.recording() as took:
        y, last, peak = jax.jit(lambda *o: ssd.ssd_scan(*o, chunk=chunk, heads_at_once=heads_at_once))(x, dt, a, bm, cm, d)
    at_once = min(heads_at_once, 8 // groups)  # the heads worked at once lie in ONE group
    assert took[forms.SSD] == {"%d groups x %d heads at once" % (groups, at_once): 1}
    for row in range(2):
        want, state = REF.ssm_scan(x[row], dt[row], a, bm[row], cm[row], d)
        assert rel(y[row], want) < 2e-6 and rel(last[row], state) < 2e-6
    assert float(peak) > 0


def test_groups_that_read_the_same_b_and_c_are_the_one_group_scan_bit_for_bit():
    """`ssm_groups` 1 (Granite's call, B and C of (B, S, d_state)) traces what it did; and the grouped path is
    the same `_group_core` on each batch of heads: with every group's B and C equal, its output IS the
    one-group scan's at the same heads at once."""
    x, dt, a, bm, cm, d = _scan_operands(1)
    one = jax.jit(lambda *o: ssd.ssd_scan(*o, chunk=32, heads_at_once=2))(x, dt, a, bm[:, :, 0], cm[:, :, 0], d)
    many = jax.jit(lambda *o: ssd.ssd_scan(*o, chunk=32, heads_at_once=2))(
        x, dt, a, jnp.repeat(bm, 4, axis=2), jnp.repeat(cm, 4, axis=2), d)
    for got, want in zip(many, one):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with forms.recording() as took:
        jax.eval_shape(lambda *o: ssd.ssd_scan(*o), x, dt, a, bm[:, :, 0], cm[:, :, 0], d)
    assert took[forms.SSD] == {"1 group x 8 heads at once": 1}
    text = str(jax.make_jaxpr(lambda *o: ssd.ssd_scan(*o)[0])(x, dt, a, bm[:, :, 0], cm[:, :, 0], d))
    assert "repeat" not in text and "gather" not in text


def test_the_gated_norm_runs_over_a_groups_channels():
    """The mixer's output against the same leaves through a reshape-and-norm by hand; with the norm over ALL
    channels (one group's rule) the output differs by far more than rounding."""
    cfg = tiny().layer_config("ssm.none")
    lp = M.init_layer_params(jax.random.PRNGKey(2), cfg)
    lp["ssm"]["norm"]["scale"] = 1.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    y = jax.random.normal(jax.random.PRNGKey(4), (1, SEQ, 64), jnp.float32)
    out, _, counters = ssm_mixer(lp, y, None, cfg)
    want = REF._ssm(lp, y[0], fields_of(cfg))
    assert rel(out[0], want) < 1e-5 and float(counters["ssm_state_abs_max"]) > 0
    inner, groups = 32, 2
    p = lp["ssm"]
    captured = {}

    def spy(o, scale, eps):
        captured["in"] = o
        return REF._rms(o, scale, eps)

    from galvatron_tpu.models.parts import ssm as part

    norm, part.rms_norm = part.rms_norm, spy
    try:
        ssm_mixer(lp, y, None, cfg)
    finally:
        part.rms_norm = norm
    assert captured["in"].shape == (1, SEQ, groups, inner // groups)
    flat = captured["in"].reshape(1, SEQ, inner)
    over_all = REF._rms(flat, p["norm"]["scale"], cfg.layernorm_eps) @ p["wout"]["kernel"]
    assert rel(over_all[0], want) > 1e-2


# ------------------------------------------------------------ refusals, by name
def test_a_group_limited_router_and_what_else_is_not_modelled_are_refused_by_name():
    published = N.PUBLISHED["nemotron-3-nano-30b-a3b"]
    for key, value in (("n_group", 2), ("topk_group", 2), ("mlp_hidden_act", "silu"), ("use_bias", True),
                       ("use_conv_bias", False), ("sliding_window", 4096)):
        with pytest.raises(ValueError, match="%s=%r is not modelled" % (key, value)):
            N.nemotron_h_config_from_hf(type("C", (), {**published, key: value}))
    with pytest.raises(ValueError, match="ssm_groups that divide the heads"):
        tiny(ssm_groups=3)


@pytest.mark.parametrize("asker,how,says", [
    ("tp", dict(tp=2), "no tensor-, context- or sequence-parallel form of the experts' kernels"),
    ("pp", dict(pp=2), "the pipeline engines"),
    ("serve", None, "serve: the decode engine has"),
    ("search", None, "search: the cost models have no row for"),
    ("profile", None, "profile: the layer profiler"),
], ids=["tp", "pp", "serve", "search", "profile"])
def test_every_layout_but_dp_with_zero_and_every_tool_is_refused_by_name(asker, how, says):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(2, cfg.num_layers, global_bsz=2, **how) if how else None
    reason = unsupported_reason(cfg, hp, None if how else asker)
    assert reason and says in reason and "state-space layer" in reason  # the routed half's words beside the mixer's
    assert any(word in reason for word in ("expert", "router"))
    assert reason.endswith("such a config runs on one chip and under dp with ZeRO-1/2/3")
    with pytest.raises(DiagnosticError, match="GLS018"):
        M.refuse_unsupported(cfg, hp, None if how else asker)
    assert unsupported_reason(cfg, HybridParallelConfig.uniform(2, cfg.num_layers, global_bsz=2, sdp=1)) is None


# ------------------------------------------------------------ the registry, the FLOPs, the forms
def test_the_family_is_registered_with_the_published_keys():
    fam = get_family("nemotron_h")
    assert fam.default_size == "nemotron-3-nano-30b-a3b" and fam.config_from_hf is N.nemotron_h_config_from_hf
    preset = fam.meta_configs[fam.default_size]
    assert preset["source"] == N.NEMOTRON_3_NANO_SOURCE and preset["hybrid_override_pattern"] == PATTERN
    cfg = fam.config_fn(fam.default_size)
    assert (cfg.hidden_size, cfg.ssm_num_heads * cfg.ssm_head_dim, cfg.ssm_groups, cfg.ffn_hidden, cfg.shared_ffn) == (
        2688, 4096, 8, 1856, 3712)  # d_inner is heads x head_dim, NOT expand x hidden
    assert (cfg.activation, cfg.position_type, cfg.router_score, cfg.routed_scaling_factor, cfg.tie_embeddings) == (
        "relu2", "none", "sigmoid", 2.5, False)
    assert cfg.mlp_fan_in == (1856,)  # an expert's up projection has no gate beside it


def test_the_programs_flops_count_by_blocks_of_one_half():
    cfg = N.nemotron_h_config(num_layers=9, experts_held=8, vocab_size=16384, max_seq_len=8192)
    per_kind = {k: v / 8192 for k, v in obs_flops.layer_kind_fwd_flops(cfg, 8192.0).items()}
    assert per_kind["ssm.none"] == 2 * 2688 * 10304 + 2 * 4096 * 2688 + 4 * 64 * 64 * 128
    assert per_kind["none"] == 2 * 2688 * (4096 + 512) + 2 * 4096 * 2688 + 2 * 2 * 8192 * 4096 * 0.5
    assert per_kind["none.routed"] == 2 * 2688 * 128 + 2 * 2 * 2688 * 3712 + 6 * 8 / 128 * 2 * 2 * 2688 * 1856
    assert obs_flops.train_step_flops(cfg, 1) / 8192 / 1e9 == pytest.approx(2.137, abs=5e-4)


def test_the_stack_says_its_halves_and_an_absent_half_opens_no_scope():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    with forms.recording() as took:
        text = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg)).lower(params).as_text(debug_info=True)
    assert took[forms.HALVES] == {"9 of 18": 1}
    assert took[forms.SSD] == {"2 groups x 2 heads at once": 4}
    assert "gt.mlp" not in text  # no dense half anywhere: the shared expert is under gt.moe.shared
    for scope in ("gt.attn.ssm", "gt.attn.ssd", "gt.attn.proj", "gt.moe.router", "gt.moe.experts", "gt.moe.shared"):
        assert scope in text, scope
