"""Qwen3-Next's layers and objective (models/base.py, ops/linear_attention.py,
ops/moe.py, models/qwen3_next.py) against the plain reference
(benchmarks/references/qwen3_next_lm.py) on seeded random weights at a small
size: hidden 64, three linear layers (2 key heads serving 4 value heads, 16 x
8 states, 4 taps) and one attention layer (4 heads of 16 on 2 KV heads, rope
on 4 of the 16 dims), 16 experts of 32 with 4 a token beside a gated shared
one.

Tolerances, and why. In float32 compute program and reference do the same
arithmetic in another order (the chunked delta rule against the recurrence
token by token, sorted rows through a grouped matmul against every held
expert applied densely and masked, attention whole against a block of queries
at a time): measured worst-leaf relative gradient error 8e-6, loss equal to
the last place; the limit is 5e-5. Each piece of the layer that is new has a
test below that holds it to a three-line formula.
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
from galvatron_tpu.models.parts.attention import attention_mixer, qk_normed
from galvatron_tpu.models.parts.common import _norm
from galvatron_tpu.models.parts.linear import linear_mixer
from galvatron_tpu.models.parts.mlp import dense_mlp
from galvatron_tpu.models import qwen3_next as Q
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import telemetry, tracing
from galvatron_tpu.ops.moe import moe_ffn
from galvatron_tpu.ops.rope import apply_rotary
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/qwen3_next_lm.py")

F32_TOL = 5e-5  # loss, each part, worst-leaf relative gradient error
BATCH, SEQ, VOCAB, EXPERTS = 2, 128, 256, 16


def tiny(dtype=jnp.float32, **kw):
    fields = dict(
        hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16, ffn_hidden=32, num_layers=4,
        vocab_size=VOCAB, max_seq_len=SEQ, linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=8, num_experts=EXPERTS, experts_per_token=4,
        compute_dtype=dtype, attn_impl="xla")
    fields.update(kw)
    return Q.qwen3_next_config("qwen3-next-80b-a3b", **fields)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, VOCAB)
    mask = jnp.ones((batch, SEQ), jnp.float32).at[:, -1].set(0.0)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (batch, SEQ)),
                labels=jnp.roll(tok, -1, 1), loss_mask=mask)


def params_of(cfg, seed=0):
    """Seeded weights with norm scales that are not at their start, so that
    (1 + w) and the gated norm's plain w are told apart."""
    params = M.init_model_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(key, leaf.shape) if "scale" in jax.tree_util.keystr(path)
             else leaf for (path, leaf), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, moved)


def leaf_errors(grads, ref_grads):
    def rel(a, b):
        norm = float(jnp.linalg.norm(b))
        diff = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        return diff / norm if norm else diff

    tree = jax.tree.map(rel, grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


HELD = {"whole": {}, "held_4_of_16": dict(experts_held=4, experts_held_start=8)}


@pytest.fixture(scope="module", params=sorted(HELD))
def case(request):
    cfg = tiny(**HELD[request.param])
    params, batch = params_of(cfg), batch_of()
    with jax.default_matmul_precision("highest"):
        program = jax.jit(jax.value_and_grad(
            lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)

        def loss(p):
            parts = REF.loss_parts(p, batch, fields_of(cfg))
            return parts["loss"], parts

        reference = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return cfg, program, reference


# ------------------------------------------------- the whole model, float32
def test_the_config_is_the_published_one():
    cfg = Q.qwen3_next_config()
    pub = Q.PUBLISHED["qwen3-next-80b-a3b"]
    assert pub["source"] == Q.QWEN3_NEXT_SOURCE and get_family("qwen3_next").meta_configs is Q.PUBLISHED
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (
        48, 2048, 16, 2, 256)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel) == (16, 32, 128, 128, 4)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.ffn_hidden, cfg.num_shared_experts) == (
        512, 10, 512, 1)
    assert cfg.router_score == "softmax" and cfg.norm_topk_prob and cfg.shared_expert_gate
    assert cfg.router_aux_loss_coef == 0.001 and cfg.router_z_loss_coef == 0.0
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.layernorm_eps) == (64, 1e7, 1e-6)
    assert cfg.qk_norm == "head" and cfg.attn_output_gate and cfg.norm_zero_centered
    assert cfg.vocab_size == 151936 and not cfg.tie_embeddings and cfg.mtp_layers == 0
    kinds = cfg.layer_kinds()
    assert kinds[:8] == ("linear.routed",) * 3 + ("routed",) + ("linear.routed",) * 3 + ("routed",)
    assert kinds.count("routed") == 12 and cfg.routed_layers == 48


@pytest.mark.parametrize("key,value", [
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]), ("use_sliding_window", True),
    ("shared_expert_intermediate_size", 1024)])
def test_what_is_not_modelled_is_refused_not_dropped(key, value):
    from types import SimpleNamespace

    with pytest.raises(ValueError, match="not modelled"):
        Q.qwen3_next_config_from_hf(SimpleNamespace(**{**Q.PUBLISHED["qwen3-next-80b-a3b"], key: value}))


def test_the_published_cut_counts_625_7_million_parameters():
    """The benchmark's configuration counted leaf by leaf. ISSUE 35's own sum
    of the attention mixer (27,264,000) is 512 over its parts (16,777,216 +
    2 x 1,048,576 + 8,388,608 + 512 = 27,263,488), and its totals with it."""
    cell = cells.load_cell(REPO, "qwen3next-c1-s8k")
    cfg = Q.qwen3_next_config(**{**cell.fields, "max_seq_len": 8192})
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))  # noqa: E731
    linear, full = shapes["layers"][0], shapes["layers"][3]
    assert count(linear["linear"]) == 33_718_464 and "wq" not in linear
    assert count({k: full[k] for k in ("wq", "wkv", "wo", "q_norm", "k_norm")}) == 27_263_488
    assert count(linear["router"]) + count(linear["shared"]) == 4_196_352
    assert count(linear["wi"]) + count(linear["wo_mlp"]) == 32 * 3_145_728
    assert (count(linear), count(full)) == (138_582_208, 132_127_232)
    assert count(shapes) == 625_667_136


def test_loss_and_parts_are_the_references(case):
    cfg, ((loss, parts), _), ((ref_loss, ref_parts), _) = case
    assert float(loss) == pytest.approx(float(ref_loss), abs=F32_TOL)
    assert float(parts["loss_ce"]) == pytest.approx(float(ref_parts["ce"]), abs=F32_TOL)
    assert float(parts["loss_load_balance"]) == pytest.approx(float(ref_parts["load_balance"]), abs=F32_TOL)
    assert float(loss) == pytest.approx(
        float(parts["loss_ce"]) + 0.001 * float(parts["loss_load_balance"]), abs=1e-6)
    want = set(telemetry.EXPERT_STEP_FIELDS) | set(telemetry.LINEAR_STEP_FIELDS)
    if cfg.experts_held:
        want |= {"expert_rows_held", "expert_rows_held_over_even", "expert_window_fallbacks"}
    assert set(parts) == want
    assert 0.0 < float(parts["linear_decay_mean"]) < 1.0 and float(parts["linear_state_abs_max"]) > 0.0


def test_every_leafs_gradient_is_the_references(case):
    _, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    assert {"['layers'][0]['linear']['A_log']", "['layers'][0]['linear']['conv']",
            "['layers'][3]['q_norm']['scale']", "['layers'][3]['shared']['gate']['kernel']"} <= set(errors)
    assert max(errors.values()) < F32_TOL, max(errors, key=errors.get)


def test_the_router_ranks_all_experts_under_a_share(case):
    cfg, ((_, parts), _), ((_, ref_parts), _) = case
    if not cfg.experts_held:
        pytest.skip("all experts held")
    picks = np.asarray(ref_parts["picks"])  # (batch, layers, seq, k) over all 16
    assert picks.max() >= 12 and picks.min() < 8  # experts outside the held 8..11 are picked too
    held = np.sum((picks >= 8) & (picks < 12))
    assert float(parts["expert_rows_held"]) == held
    assert float(parts["expert_rows_held_over_even"]) == pytest.approx(
        held / (4 * BATCH * SEQ * 4 * 4 / 16))


def test_the_scanned_stack_is_the_unrolled_one():
    cfg, batch = tiny(), batch_of()
    params = params_of(cfg)
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    with jax.default_matmul_precision("highest"):
        scanned = jax.jit(jax.value_and_grad(model.loss_fn))(params, model.shard_batch(batch))
        plain = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg)))(params)
    assert float(scanned[0]) == pytest.approx(float(plain[0]), abs=1e-6)
    assert max(leaf_errors(scanned[1], plain[1]).values()) < 1e-5


# --------------------------------------------- each new piece against a formula
def test_rope_turns_the_leading_dims_and_passes_the_rest():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 6, 2, 16))
    pos = jnp.arange(6)[None]
    out = np.asarray(apply_rotary(x, pos, 1e4, rotary_dim=4))
    angle = np.arange(6)[:, None] * (1.0 / 1e4 ** (np.arange(2) / 2))  # frequencies of a 4-dim head
    x1, x2 = np.asarray(x[0, :, :, :2]), np.asarray(x[0, :, :, 2:4])
    cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
    np.testing.assert_allclose(out[0, :, :, :2], x1 * cos - x2 * sin, atol=1e-6)
    np.testing.assert_allclose(out[0, :, :, 2:4], x2 * cos + x1 * sin, atol=1e-6)
    np.testing.assert_array_equal(out[..., 4:], np.asarray(x[..., 4:]))
    np.testing.assert_array_equal(np.asarray(apply_rotary(x, pos, 1e4, rotary_dim=16)),
                                  np.asarray(apply_rotary(x, pos, 1e4)))


def test_the_norm_of_q_and_k_runs_a_head_and_scales_by_one_plus_w():
    cfg = tiny().layer_config("routed")
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 4, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 2, 16))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    got_q, got_k = qk_normed({"q_norm": {"scale": w}, "k_norm": {"scale": -w}}, q, k, cfg)
    rms0 = lambda x, w: x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + 1e-6) * (1 + w)  # noqa: E731
    np.testing.assert_allclose(np.asarray(got_q), rms0(np.asarray(q), np.asarray(w)), atol=1e-6)
    np.testing.assert_allclose(np.asarray(got_k), rms0(np.asarray(k), -np.asarray(w)), atol=1e-6)
    # OLMoE's form runs over the whole projection, with a plain scale
    whole = qk_normed({"q_norm": {"scale": jnp.ones(64)}, "k_norm": {"scale": jnp.ones(32)}}, q, k,
                        dataclasses.replace(cfg, qk_norm=True, norm_zero_centered=False))[0]
    flat = np.asarray(q).reshape(1, 5, 64)
    np.testing.assert_allclose(np.asarray(whole).reshape(1, 5, 64),
                               flat / np.sqrt(np.mean(flat * flat, -1, keepdims=True) + 1e-6), atol=1e-6)


def _layer(kind, seed=0):
    cfg = tiny()
    lcfg = cfg.layer_config(kind)
    lp = M.init_layer_params(jax.random.PRNGKey(seed), lcfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, SEQ, 64))
    return lcfg, lp, x, jnp.arange(SEQ)[None]


def test_the_attention_gate_multiplies_the_attention_by_its_sigmoid():
    lcfg, lp, x, pos = _layer("routed")
    y = _norm(x, lp["ln1"], lcfg)
    with jax.default_matmul_precision("highest"):
        got, _, _ = attention_mixer(lp, y, pos, lcfg, mesh=None, axes=None, attn_bias=None,
                                      attn_sharding=None, return_kv=False)
        # the same with the gate's columns zeroed is half the ungated attention
        open_gate = dict(lp, wq={"kernel": lp["wq"]["kernel"].at[..., 16:].set(0.0)})
        half, _, _ = attention_mixer(open_gate, y, pos, lcfg, mesh=None, axes=None, attn_bias=None,
                                       attn_sharding=None, return_kv=False)
        gate = jax.nn.sigmoid(jnp.einsum("bsh,hnd->bsnd", y, lp["wq"]["kernel"][..., 16:]))
        attn = 2.0 * jnp.linalg.lstsq(lp["wo"]["kernel"].T, half[0].T)[0].T  # undo Wo: (S, 64)
        want = (attn.reshape(1, SEQ, 4, 16) * gate).reshape(1, SEQ, 64) @ lp["wo"]["kernel"]
    assert lp["wq"]["kernel"].shape == (64, 4, 32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_the_shared_expert_passes_a_sigmoid_gate():
    lcfg, lp, x, pos = _layer("routed", seed=3)
    lp = dict(lp, shared=dict(lp["shared"], gate={"kernel": jax.random.normal(jax.random.PRNGKey(9), (64, 1))}))
    with jax.default_matmul_precision("highest"):
        out, _ = M.layer_forward(lp, x, pos, lcfg)
        shut = dict(lp, shared=dict(lp["shared"], wo_mlp={"kernel": jnp.zeros_like(lp["shared"]["wo_mlp"]["kernel"])}))
        without, _ = M.layer_forward(shut, x, pos, lcfg)
        mid = x + attention_mixer(lp, _norm(x, lp["ln1"], lcfg), pos, lcfg, mesh=None, axes=None,
                                    attn_bias=None, attn_sharding=None, return_kv=False)[0]
        y = _norm(mid, lp["ln2"], lcfg)
        want = jax.nn.sigmoid(y @ lp["shared"]["gate"]["kernel"]) * dense_mlp(lp["shared"], y, lcfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(out - without), np.asarray(want), atol=2e-6)


def test_the_linear_mixers_gate_and_norm():
    """g = -exp(A_log) softplus(a + dt_bias) and sigmoid(b), read back through
    the counter; and the output norm scales by a plain w BEFORE silu(z)."""
    lcfg, lp, x, pos = _layer("linear.routed", seed=5)
    y = _norm(x, lp["ln1"], lcfg)
    p = lp["linear"]
    with jax.default_matmul_precision("highest"):
        out, _, stats = linear_mixer(lp, y, pos, lcfg)
        a = (y @ p["wba"]["kernel"])[..., 4:]
        want = jnp.mean(jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])))
        doubled = dict(lp, linear=dict(p, norm={"scale": 2.0 * p["norm"]["scale"]}))
        twice, _, _ = linear_mixer(doubled, y, pos, lcfg)
    assert float(stats["decay_mean"]) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(np.asarray(twice), 2.0 * np.asarray(out), atol=1e-6)  # a plain scale, not 1 + w
    assert p["wqkvz"]["kernel"].shape == (64, 2 * 32 + 2 * 32) and p["conv"].shape == (96, 4)
    assert float(jnp.max(jnp.abs(p["conv"]))) <= 0.5 and float(jnp.min(jnp.exp(p["A_log"]))) > 0.0
    steps = jax.nn.softplus(p["dt_bias"])
    assert 1e-3 <= float(jnp.min(steps)) and float(jnp.max(steps)) <= 0.1 + 1e-6


# ------------------------------------------------------------ the share test
def test_sixteen_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's test of the cut: for one MoE half, the routed parts that
    the 16 shares give (one expert each here), plus what every chip computes
    alike (the gated shared expert) counted once, are the uncut reference's
    output, under the softmax router renormalised over the pick."""
    cfg = tiny()
    lcfg = cfg.layer_config("routed")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        whole, _, _ = REF._moe(jax.tree.map(lambda a: a.astype(jnp.float32), lp), y[0], fields_of(lcfg))
        shared = (dense_mlp(lp["shared"], y, lcfg, jnp.float32)
                  * jax.nn.sigmoid(y @ lp["shared"]["gate"]["kernel"]))[0]
        total, rows = shared, 0.0
        for rank in range(16):
            out, aux = moe_ffn(
                y, lp["router"]["kernel"], lp["wi"]["kernel"][rank:rank + 1],
                lp["wo_mlp"]["kernel"][rank:rank + 1], experts_per_token=4, norm_topk_prob=True,
                dtype=jnp.float32, score="softmax", held=(rank, 1))
            total, rows = total + out[0], rows + float(aux["rows_held"])
            assert float(aux["load_balance"]) > 0.0  # over all 16 experts, whatever is held
    assert rows == SEQ * 4  # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=2e-6)


# ------------------------------------------------ kinds, runs, the table, layouts
def test_layer_kinds_and_runs():
    cfg = tiny()
    assert cfg.layer_kinds() == ("linear.routed",) * 3 + ("routed",)
    hp = HybridParallelConfig.uniform(1, 4, global_bsz=BATCH, checkpoint=1)
    runs = layer_runs(hp, model_layer_kinds(cfg))
    assert [(r.start, r.stop) for r in runs] == [(0, 3), (3, 4)]  # gt.layers.r0 scanned, r1
    twelve = tiny(num_layers=12)
    hp12 = HybridParallelConfig.uniform(1, 12, global_bsz=BATCH)
    assert len(layer_runs(hp12, model_layer_kinds(twelve))) == 6
    linear, full = cfg.layer_config("linear.routed"), cfg.layer_config("routed")
    assert (linear.mixer, full.mixer) == ("linear", "attention") and linear.routed and full.routed
    assert linear.full_attention_interval == 0 and cfg.layer_aux and linear.layer_aux
    dense = cfg.layer_config("linear.dense")  # a kind no zoo model has: the MLP half dense
    assert dense.mixer == "linear" and not dense.routed and dense.layer_aux


def test_the_other_families_kinds_are_what_they_were():
    glm = glm4_moe_lite_config(num_layers=3, hidden_size=64, num_heads=2, num_kv_heads=2, ffn_hidden=32)
    assert glm.layer_kinds() == ("dense", "routed", "routed")
    assert glm.layer_config("routed") is glm and glm.layer_config("dense").num_experts == 0
    olmoe = olmoe_config(num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2, ffn_hidden=32)
    assert olmoe.layer_kinds() == ("routed", "routed") and olmoe.layer_config("routed") is olmoe
    assert model_layer_kinds(olmoe) is None and olmoe.qk_norm is True and olmoe.mixer == "attention"
    assert not dataclasses.replace(olmoe, num_experts=0).layer_aux


def test_one_table_maps_a_mixer_to_what_it_brings():
    assert set(M.MIXERS) == {"attention", "linear", "ssm", "kda", "conv", "window", "mamba1", "gmu", "cross", "eva", "none"}
    assert set(obs_flops.MIXER_FWD_FLOPS) == set(M.MIXERS)  # a FLOPs row a key, and no other
    assert M.MIXERS["linear"].scopes == (tracing.ATTN_LINEAR, tracing.ATTN_DELTA)
    cfg = tiny()
    linear = obs_flops.layer_kind_fwd_flops(cfg, 1.0)
    proj, core = obs_flops.linear_fwd_flops_a_token(
        hidden=64, num_key_heads=2, num_value_heads=4, key_head_dim=16, value_head_dim=8)
    assert (proj, core) == (2 * 64 * (64 + 64) + 2 * 64 * 8 + 2 * 32 * 64, 6 * 4 * 16 * 8)
    mlp = (4 + 1) * 3 * 2 * 64 * 32 + 2 * 64 * 16 + 2 * 64
    assert linear["linear.routed"] == proj + core + mlp
    attention = 2 * 64 * 128 + 2 * 64 * 64 + 2 * 64 * 64 + 2 * 2 * SEQ * 64 * 0.5
    assert linear["routed"] == attention + mlp


def _layers(n, **kw):
    return [LayerStrategy(**kw) for _ in range(n)]


REFUSED = {
    "tp2": (dict(world_size=2, layers=_layers(4, tp=2)), "linear-attention layers"),
    "sp": (dict(world_size=2, layers=_layers(4, tp=2, sp=1)), "linear-attention layers"),
    "cp2": (dict(world_size=2, layers=_layers(4, cp=2)), "linear-attention layers"),
    "pp2": (dict(world_size=2, pp=2, layers=_layers(4), chunks=2), "one kind of layer a stage"),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_form_of_the_linear_layers_is_refused_by_name(layout):
    cfg = tiny()
    kw, named = REFUSED[layout]
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": 4, **kw})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert any(d.code == "GLS018" and named in d.message for d in report.errors)
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    assert "GLS018" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("kwargs,named", [
    (dict(mode="serve"), "recurrent state"), (dict(mode="train", autotune="observe"), "softmax attention")],
    ids=["serve", "autotune"])
def test_serve_and_the_autotuner_refuse_it_and_name_the_linear_layers(kwargs, named):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    errors = strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors
    assert any(d.code == "GLS018" and named in d.message for d in errors)
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok
    # linear layers over dense MLPs (no experts) are refused the same way
    dense = dataclasses.replace(cfg, num_experts=0, experts_per_token=0, num_shared_experts=0)
    assert unsupported_reason(dense, hp, "serve") is not None
    assert unsupported_reason(dense, hp) is None
    assert "cost models" in unsupported_reason(cfg, asker="search")
    assert "linear-attention" not in unsupported_reason(olmoe_config(), asker="search")
