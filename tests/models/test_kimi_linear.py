"""Kimi-Linear's layers and objective (models/parts/kda.py `kda_mixer` and latent
attention without positions, ops/linear_attention.py `kda_rule`,
models/kimi_linear.py) against the plain reference
(benchmarks/references/kimi_linear_lm.py) on seeded random weights at a small
size: hidden 64, the first five layers of the published pattern (KDA + dense
MLP, KDA + experts twice, MLA + experts, KDA + experts; 4 KDA heads of 16 with
4 taps; 2 MLA heads of 16 + 8 q/k dims and 16 v dims on a compressed k/v of
16), a dense SwiGLU of 96, 16 experts of 32 with 4 a token beside an ungated
shared one under a sigmoid router with its bias, an untied 128-row head.

Tolerances, and why. In float32 compute program and reference do the same
arithmetic in another order (the chunked rule against the recurrence token by
token, attention whole and padded against a block of rows at a time at its
true widths, a sort and grouped matmuls against every expert densely). Every
weight matrix's gradient agrees to 1e-5 relative; the worst leaves are the
gate's own, `A_log` a head and `dt_bias`, whose gradients are sums over all
tokens of differences of the decay's running sums (tests/ops/test_kda.py):
measured 1.3e-5, the limit 5e-5, the Qwen3-Next test's. In bf16 compute the
loss is held to 2e-3 of the float32 reference, the benchmark's own limit for
every cell.

The weights are drawn with a wider `init_std` (0.2) than a model starts with
and the norms' scales and the router's bias moved off their start, so that the
attention's logits, the gates and the bias move the loss by far more than the
tolerance.
"""

import dataclasses
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
from galvatron_tpu.models.parts import unsupported_reason
from galvatron_tpu.models.parts.attention import attention_mixer
from galvatron_tpu.models.parts.kda import kda_mixer
from galvatron_tpu.models.parts.mlp import ROUTER_BIAS, dense_mlp
from galvatron_tpu.models import kimi_linear as K
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.ops.moe import moe_ffn
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import telemetry, tracing
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/kimi_linear_lm.py")
PUB = K.PUBLISHED["kimi-linear-48b-a3b"]

F32_TOL = 5e-5  # loss, worst-leaf relative gradient error (the gate's scalars)
MATRIX_TOL = 1e-5  # every weight matrix's gradient
BATCH, SEQ, VOCAB = 2, 128, 128
PATTERN = ("kda.dense", "kda.routed", "kda.routed", "routed", "kda.routed")


def tiny(dtype=jnp.float32, **kw):
    fields = dict(
        hidden_size=64, num_heads=2, num_kv_heads=2, ffn_hidden=32, dense_ffn_hidden=96, num_layers=5,
        vocab_size=VOCAB, max_seq_len=SEQ, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, head_dim=32, linear_num_key_heads=4, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=16, num_experts=16, experts_per_token=4,
        init_std=0.2, compute_dtype=dtype, attn_impl="xla")
    fields.update(kw)
    return K.kimi_linear_config("kimi-linear-48b-a3b", **fields)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, VOCAB)
    mask = jnp.ones((batch, SEQ), jnp.float32).at[:, -1].set(0.0)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (batch, SEQ)),
                labels=jnp.roll(tok, -1, 1), loss_mask=mask)


def params_of(cfg, seed=0):
    """Seeded weights with norm scales and router biases that are not at their start."""
    params = M.init_model_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [leaf + 0.1 * jax.random.normal(key, leaf.shape)
             if any(n in jax.tree_util.keystr(path) for n in ("scale", ROUTER_BIAS)) else leaf
             for (path, leaf), key in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, moved)


def leaf_errors(grads, ref_grads):
    def rel(a, b):
        norm = float(jnp.linalg.norm(b))
        diff = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        return diff / norm if norm else diff

    tree = jax.tree.map(rel, grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module", params=[0, 4], ids=["all_held", "4_of_16_held"])
def case(request):
    cfg = tiny(experts_held=request.param, experts_held_start=8 if request.param else 0)
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
    cfg = K.kimi_linear_config()
    assert PUB["source"] == K.KIMI_LINEAR_SOURCE and get_family("kimi_linear").meta_configs is K.PUBLISHED
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads) == (27, 2304, 32, 32)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.head_dim) == (0, 512, 128, 64, 128, 256)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
            cfg.linear_value_head_dim, cfg.linear_conv_kernel) == (32, 32, 128, 128, 4)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.ffn_hidden, cfg.dense_ffn_hidden,
            cfg.num_shared_experts, cfg.first_dense_layers) == (256, 8, 1024, 9216, 1, 1)
    assert (cfg.router_score, cfg.norm_topk_prob, cfg.routed_scaling_factor, cfg.router_bias,
            cfg.router_bias_update_rate) == ("sigmoid", True, 2.446, True, 0.001)
    assert (cfg.vocab_size, cfg.layernorm_eps, cfg.max_seq_len) == (163840, 1e-5, 1048576)
    assert cfg.position_type == "none" and not cfg.tie_embeddings and cfg.mtp_layers == 0
    assert cfg.latent_attention and cfg.routed and cfg.layer_aux


def test_the_two_published_lists_are_one_list_of_27():
    """1-indexed: 25 and 26 are KDA and 27 attends, the short last period that
    no interval says; the first five are the benchmark's cut."""
    cfg = K.kimi_linear_config()
    group = PUB["linear_attn_config"]
    assert len(cfg.layer_types) == 27 and cfg.layer_types[24:] == ["kda", "kda", "attention"]
    assert [i + 1 for i, t in enumerate(cfg.layer_types) if t == "attention"] == group["full_attn_layers"]
    assert [i + 1 for i, t in enumerate(cfg.layer_types) if t == "kda"] == group["kda_layers"]
    kinds = cfg.layer_kinds()
    assert kinds[0] == "kda.dense" and kinds.count("kda.routed") == 19 and kinds.count("routed") == 7
    assert K.kimi_linear_config(num_layers=5).layer_kinds() == PATTERN
    assert len(K.kimi_linear_config(num_layers=5).layer_types) == 27  # the list stays whole
    with pytest.raises(ValueError, match="do not name each of the layers 1 to 4 once"):
        K.layer_types_from_lists([1, 2], [2, 4])
    with pytest.raises(ValueError, match="name 27 layers, num_hidden_layers is 26"):
        K.kimi_linear_config_from_hf(SimpleNamespace(**{**PUB, "num_hidden_layers": 26}))


@pytest.mark.parametrize("key,value", [
    ("rope_scaling", {"type": "yarn", "factor": 32}), ("num_expert_group", 8), ("topk_group", 4),
    ("q_lora_rank", 1536), ("mla_use_nope", False), ("num_nextn_predict_layers", 1),
    ("moe_layer_freq", 2), ("moe_router_activation_func", "softmax"), ("hidden_act", "gelu")])
def test_what_is_not_modelled_is_refused_not_dropped(key, value):
    with pytest.raises(ValueError, match="%s=.* is not modelled" % key):
        K.kimi_linear_config_from_hf(SimpleNamespace(**{**PUB, key: value}))


def test_the_config_refuses_what_cannot_run_and_no_more():
    with pytest.raises(ValueError, match="layer_types"):
        tiny(layer_types=["kda"] * 3)
    with pytest.raises(ValueError, match="layer_types"):
        tiny(layer_types=["kda"] * 4 + ["windowed"])
    with pytest.raises(ValueError, match="equal under \"kda\""):
        tiny(linear_num_key_heads=2)
    with pytest.raises(ValueError, match="no multi-token-prediction module"):
        tiny(mtp_layers=1)
    with pytest.raises(ValueError, match="head_dim 16 >= qk_nope 16 \\+ qk_rope 8"):
        tiny(head_dim=16)
    # linear layers beside latent attention are this model: no longer refused
    assert tiny().latent_attention and "kda" in tiny().mixers()


def test_a_pattern_of_kda_and_attention_gives_four_runs():
    cfg = tiny()
    assert cfg.layer_kinds() == PATTERN and model_layer_kinds(cfg) == PATTERN
    hp = HybridParallelConfig.uniform(1, 5, global_bsz=BATCH, checkpoint=1)
    runs = layer_runs(hp, model_layer_kinds(cfg))
    assert [(r.start, r.stop) for r in runs] == [(0, 1), (1, 3), (3, 4), (4, 5)]
    first, kda, full = (cfg.layer_config(k) for k in ("kda.dense", "kda.routed", "routed"))
    assert (first.mixer, kda.mixer, full.mixer) == ("kda", "kda", "attention")
    assert not first.routed and first.ffn_hidden == 96 and kda.routed and full.routed
    assert kda.layer_types is None and first.layer_aux and kda.layer_aux and full.layer_aux


def test_the_published_cut_counts_602_434_432_parameters():
    """The benchmark's configuration counted leaf by leaf, ISSUE 42's table."""
    cfg = K.kimi_linear_config(num_layers=5, vocab_size=20480, experts_held=8)
    shapes = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(tree))  # noqa: E731
    first, kda, mla = shapes["layers"][0], shapes["layers"][1], shapes["layers"][3]
    assert kda["kda"]["wqkv"]["kernel"].shape == (2304, 12288) and kda["kda"]["dt_bias"].shape == (4096,)
    assert kda["kda"]["wf_b"]["kernel"].shape == (128, 4096) and kda["kda"]["conv"].shape == (12288, 4)
    assert count(kda["kda"]) == count(first["kda"]) == 39_514_272 and "wq" not in kda
    assert mla["wq"]["kernel"].shape == (2304, 32, 192) and mla["wo"]["kernel"].shape == (4096, 2304)
    assert mla["wkv_b"]["kernel"].shape == (512, 32, 256) and "wq_a" not in mla and "kda" not in mla
    assert count({k: mla[k] for k in ("wq", "wkv_a", "kv_a_norm", "wkv_b", "wo")}) == 29_114_880
    assert count(first["wi"]) + count(first["wo_mlp"]) == 63_700_992
    routed = {k: kda[k] for k in ("router", "wi", "wo_mlp", "shared")}
    assert kda["wi"]["kernel"].shape == (8, 2304, 2048) and count(routed) == 64_291_072
    assert (count(first), count(kda), count(mla)) == (103_219_872, 103_809_952, 93_410_560)
    assert count(shapes["embed"]) + count(shapes["lm_head"]) + count(shapes["final_norm"]) == 94_374_144
    assert count(shapes) == 602_434_432


# ------------------------------------------------- the whole model, float32
def test_loss_and_parts_are_the_references(case):
    cfg, _, _, ((loss, parts), _), ((ref_loss, ref_parts), _) = case
    assert float(loss) == pytest.approx(float(ref_loss), abs=F32_TOL)
    assert set(telemetry.LINEAR_STEP_FIELDS) | {"loss_ce", M.EXPERT_LOAD, "router_bias_abs_max"} <= set(parts)
    assert "loss_load_balance" not in parts  # a sigmoid router has no auxiliary loss
    assert 0.0 < float(parts["linear_decay_mean"]) < 1.0 and float(parts["linear_state_abs_max"]) > 0.0
    # the bias ranks: with it moved off 0 the picks differ from the scores' own
    assert float(parts["router_bias_abs_max"]) > 0.05
    if cfg.experts_held:
        picks = np.asarray(ref_parts["picks"])  # (batch, routed blocks, seq, k) over all 16
        assert picks.shape == (BATCH, 4, SEQ, 4) and picks.max() >= 12 and picks.min() < 8
        held = np.sum((picks >= 8) & (picks < 12))
        assert float(parts["expert_rows_held"]) == held
        assert float(parts["expert_rows_held_over_even"]) == pytest.approx(
            held / (4 * BATCH * SEQ * 4 * 4 / 16))


def test_every_leafs_gradient_is_the_references(case):
    _, _, _, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    assert {"['layers'][0]['kda']['A_log']", "['layers'][0]['kda']['dt_bias']", "['layers'][1]['kda']['conv']",
            "['layers'][2]['kda']['wf_b']['kernel']", "['layers'][4]['kda']['wg_a']['kernel']",
            "['layers'][4]['kda']['wb']['kernel']", "['layers'][1]['kda']['norm']['scale']",
            "['layers'][3]['wq']['kernel']", "['layers'][3]['kv_a_norm']['scale']",
            "['layers'][3]['router']['kernel']", "['lm_head']['kernel']"} <= set(errors)
    bias = {k: v for k, v in errors.items() if ROUTER_BIAS in k}
    assert len(bias) == 4 and not any(bias.values())  # no gradient moves the bias, in either
    assert max(errors.values()) < F32_TOL, max(errors, key=errors.get)
    matrices = {k: v for k, v in errors.items() if "kernel" in k or "wte" in k}
    assert max(matrices.values()) < MATRIX_TOL, max(matrices, key=matrices.get)


def test_bf16_compute_stays_within_the_benchmarks_limit():
    """At the model's own init_std 0.02 (at the fixture's 0.2 the router's near
    ties flip under a bf16 residual stream and move the loss by 4e-2)."""
    cfg, ref_cfg = tiny(jnp.bfloat16, init_std=0.02), tiny(init_std=0.02)
    params, batch = params_of(ref_cfg), batch_of()
    loss = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss = jax.jit(lambda p: REF.loss(p, batch, fields_of(ref_cfg)))(params)
    assert float(loss) == pytest.approx(float(ref_loss), abs=2e-3)
    assert float(loss) != float(ref_loss)


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


# ------------------------------------------------------------ the two mixers
def test_latent_attention_padded_with_zeros_is_latent_attention_at_its_true_widths():
    """q, k (24 wide) and v (16) padded to the attention call's 32, or to 128,
    give the reference's layer, which computes 24 against 16 unpadded: the
    zeros add nothing to a score and the cut drops what they give."""
    outs = {}
    for width in (24, 32, 128):
        lcfg = tiny(head_dim=width).layer_config("routed")
        lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)  # no leaf's shape knows the width
        y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
        with jax.default_matmul_precision("highest"):
            outs[width], _, counters = attention_mixer(
                lp, y, batch_of()["positions"][:1], lcfg, mesh=None, axes=None, attn_bias=None,
                attn_sharding=None, return_kv=False)
            want = REF._latent_attention(lp, y[0], fields_of(lcfg))
        assert counters is None and lp["wq"]["kernel"].shape == (64, 2, 24)
        np.testing.assert_allclose(np.asarray(outs[width][0]), np.asarray(want), atol=2e-6)
    # positions do not enter: another order of the same tokens' positions changes nothing
    lcfg = tiny().layer_config("routed")
    turned, _, _ = attention_mixer(lp, y, batch_of()["positions"][:1][:, ::-1], lcfg, mesh=None, axes=None,
                                     attn_bias=None, attn_sharding=None, return_kv=False)
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(np.asarray(turned), np.asarray(outs[32]), atol=2e-6)


def test_the_kda_mixer_is_the_references_and_hands_back_the_linear_counters():
    lcfg = tiny().layer_config("kda.routed")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    lp["kda"]["norm"]["scale"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        out, kv, counters = kda_mixer(lp, y, None, lcfg)
        want = REF._kda(lp, y[0], fields_of(lcfg))
        q, k, v, g, beta = REF.kda_inputs(lp["kda"], y[0], fields_of(lcfg))
        _, states = REF.kda_recurrence(q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want), atol=5e-6)
    assert kv is None and set(counters) == {"decay_mean", "state_abs_max"}
    assert g.shape == (SEQ, 4, 16) and float(jnp.max(g)) < 0.0  # a vector a head, <= 0
    assert float(counters["decay_mean"]) == pytest.approx(float(jnp.mean(jnp.exp(g))), rel=1e-5)
    assert float(counters["state_abs_max"]) == pytest.approx(float(jnp.max(jnp.abs(states))), rel=1e-4)


# ------------------------------------------------------------ the share test
def test_four_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """The guide's test of the cut: for one MoE half, the routed parts that
    the 4 shares give (4 of 16 experts each, as 8 of 256 a chip over 32 chips),
    plus what every chip computes alike (the ungated shared expert) counted
    once, are the uncut reference's output, under the sigmoid router with its
    bias, renormalised over the pick and scaled."""
    cfg = tiny()
    lcfg = cfg.layer_config("routed")
    lp = M.init_layer_params(jax.random.PRNGKey(0), lcfg)
    lp["router"][ROUTER_BIAS] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), (16,))
    y = jax.random.normal(jax.random.PRNGKey(1), (1, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        lp32 = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
        routed, _ = REF._routed(lp32, y[0], fields_of(lcfg))
        whole = routed + REF._swiglu(lp32["shared"], y[0])
        total, rows = dense_mlp(lp["shared"], y, lcfg, jnp.float32)[0], 0.0
        for rank in range(4):
            out, aux = moe_ffn(
                y, lp["router"]["kernel"], lp["wi"]["kernel"][4 * rank:4 * rank + 4],
                lp["wo_mlp"]["kernel"][4 * rank:4 * rank + 4], experts_per_token=4, norm_topk_prob=True,
                dtype=jnp.float32, score="sigmoid", bias=lp["router"][ROUTER_BIAS],
                scale=cfg.routed_scaling_factor, held=(4 * rank, 4))
            total, rows = total + out[0], rows + float(aux["rows_held"])
    assert rows == SEQ * 4  # every assignment is some share's
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), atol=5e-6)


# ------------------------------------------------ the table, FLOPs, the counters
def test_one_table_maps_the_kda_mixer_to_what_it_brings():
    assert set(M.MIXERS) == {"attention", "linear", "ssm", "kda", "conv", "window", "mamba1", "gmu", "cross", "eva", "none"}
    assert M.MIXERS["kda"].scopes == (tracing.ATTN_KDA, tracing.ATTN_KDA_RULE) == (
        "gt.attn.kda_mixer", "gt.attn.kda_rule")
    assert not any(a != b and a.startswith(b) for a in M.MIXERS["kda"].scopes for b in M.MIXERS["kda"].scopes)
    assert callable(obs_flops.MIXER_FWD_FLOPS["kda"][0])
    proj, core = obs_flops.kda_fwd_flops_a_token(
        hidden=64, num_key_heads=4, num_value_heads=4, key_head_dim=16, value_head_dim=16)
    assert (proj, core) == (2 * 64 * 192 + 2 * (2 * 64 * 16 + 2 * 16 * 64) + 2 * 64 * 4 + 2 * 64 * 64,
                            6 * 4 * 16 * 16)
    # latent attention without a low-rank q, scores at 24 dims and sums at 16, whatever the call's width
    mla = obs_flops.attention_fwd_flops_a_token(
        hidden=64, num_heads=2, head_dim=32, num_kv_heads=2, seq_len=SEQ, latent=dict(
            q_lora_rank=0, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16))
    assert mla == (2 * 64 * 48 + 2 * 64 * 24 + 2 * 16 * 64 + 2 * 32 * 64, 2 * SEQ * 2 * (24 + 16) * 0.5)
    cfg = tiny()
    kinds = obs_flops.layer_kind_fwd_flops(cfg, 1.0)
    expert = 3 * 2 * 64 * 32
    moe = (4 + 1) * expert + 2 * 64 * 16
    assert kinds == {"kda.dense": proj + core + 3 * 2 * 64 * 96, "kda.routed": proj + core + moe,
                     "routed": sum(mla) + moe}
    head = 2 * 64 * VOCAB
    assert obs_flops.train_step_flops(cfg, 1) == 3 * SEQ * (
        kinds["kda.dense"] + 3 * kinds["kda.routed"] + kinds["routed"] + head)
    assert obs_flops.layer_kind_fwd_flops(tiny(head_dim=128), 1.0) == kinds


def test_the_step_hands_back_the_counters_and_the_event_takes_them():
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH, checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    import optax

    tx = optax.adam(1e-3)
    params = model.init_params(jax.random.PRNGKey(0))
    step = model.make_train_step(tx)
    new, _, metrics = step(params, model.init_opt_state(tx, params), model.shard_batch(batch_of()))
    assert float(metrics["linear_state_abs_max"]) > 0.0 and 0.0 < float(metrics["linear_decay_mean"]) < 1.0
    assert set(telemetry.LINEAR_STEP_FIELDS) <= set(telemetry.EVENT_SCHEMAS["step"][1])
    # the step moves the router's bias by the update rate, and no gradient does
    assert float(jnp.max(jnp.abs(new["layers"][1]["router"][ROUTER_BIAS]))) == pytest.approx(1e-3)


# ------------------------------------------------------------ GLS018, by name
def _layers(n, **kw):
    return [LayerStrategy(**kw) for _ in range(n)]


REFUSED = {
    "tp2": (dict(world_size=2, layers=_layers(5, tp=2)), "Kimi-Delta-Attention layers"),
    "sp": (dict(world_size=2, layers=_layers(5, tp=2, sp=1)), "Kimi-Delta-Attention layers"),
    "cp2": (dict(world_size=2, layers=_layers(5, cp=2)), "per-channel delta rule's state runs along"),
    "pp5": (dict(world_size=5, pp=5, layers=_layers(5), chunks=5),
            "not Kimi-Delta-Attention layers among attention"),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_form_of_the_kda_layers_is_refused_by_name(layout):
    cfg = tiny()
    kw, named = REFUSED[layout]
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": 10, **kw})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert any(d.code == "GLS018" and named in d.message for d in report.errors)
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    assert "GLS018" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("kwargs,named", [
    (dict(mode="serve"), "d_k rows a head that forget separately"),
    (dict(mode="serve"), "no cache of latent attention's compressed k/v"),
    (dict(mode="train", autotune="observe"), "a Kimi-Delta-Attention layer as softmax attention")],
    ids=["serve_state", "serve_latent", "autotune"])
def test_serve_and_the_autotuner_refuse_it_and_name_the_kda_layers(kwargs, named):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    errors = strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors
    assert any(d.code == "GLS018" and named in d.message for d in errors)
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok
    assert "Kimi-Delta-Attention layers" in unsupported_reason(cfg, asker="search")
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
    args = initialize_galvatron(mode=mode, argv=["--model_type", "kimi_linear"])
    with pytest.raises(DiagnosticError) as e:
        run(args)
    assert "GLS018" in str(e.value) and "Kimi-Delta-Attention layers" in str(e.value)
