"""The Xing4.0 family (models/xing4.py: DeepSeek-V3's block inside manifold-constrained hyper-connections,
models/parts/hyper.py) against the plain reference benchmarks/references/xing4_lm.py on seeded random weights at a
small size (hidden 128, 4 streams, 2 + 2 layers, 8 experts with 4 held, 20 Sinkhorn steps, 32 tokens): loss and
every leaf's gradient, scanned and unrolled, with and without recomputation; each switch of the reference; what
one stream leaves as it was; H_res's rows and columns; the share test; the layouts that run and the refusals."""

import dataclasses
import math
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells
from galvatron_tpu import HybridParallelConfig
from galvatron_tpu.analysis.diagnostics import DiagnosticError
from galvatron_tpu.models import base as M
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.parts import MIXERS, MLP_HALVES, hyper, unsupported_reason
from galvatron_tpu.models.parts.common import _norm
from galvatron_tpu.models.xing4 import PUBLISHED, xing4_config, xing4_config_from_hf, yarn_from_deepseek
from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops import rope
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEQ, VOCAB = 32, 128
# float32 compute at `highest` precision on both sides: what separates the two is the ORDER of float32 sums (a
# scanned stack and one (S, n C) array against Python loops over (S, n, C) streams), 1e-6 of a loss of order 5
LOSS_ATOL, LEAF_RTOL = 1e-5, 1e-4


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(REPO, "benchmarks/references/xing4_lm.py")


def tiny(**over):
    """The preset at hidden 128: the latent head (128 | 64 | 128 under yarn), the 20 steps and n = 4 stay its own; a
    clamp that BINDS off the diagonal (the published -30 / 30 never does on fresh weights)."""
    return xing4_config(**{**dict(
        num_layers=4, first_dense_layers=2, hidden_size=128, num_heads=2, num_kv_heads=2, ffn_hidden=32,
        dense_ffn_hidden=96, q_lora_rank=48, kv_lora_rank=32, vocab_size=VOCAB, num_experts=8, experts_per_token=2,
        experts_held=4, experts_held_start=2, hc_res_clamp=(-4.0, 4.0), max_seq_len=SEQ, compute_dtype=jnp.float32,
        attn_impl="xla"), **over})


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def seeded(cfg, rows=2):
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)

    def off_its_start(path, a):  # every leaf off its initial value; the coefficients' leaves far enough to vary a token
        name = jax.tree_util.keystr(path)
        if "e_score_correction_bias" in name:
            return a
        by = 30.0 if "'a'" in name and "hc" in name else 20.0 if "'phi'" in name else 1.0
        return by * a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape)

    params = jax.tree_util.tree_map_with_path(off_its_start, params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (rows, SEQ), 0, VOCAB)
    batch = dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(SEQ), (rows, SEQ)),
                 labels=jnp.roll(tokens, -1, 1), loss_mask=jnp.ones((rows, SEQ)).at[:, -1].set(0.0))
    return params, batch


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return (cfg, *seeded(cfg))


@pytest.fixture(scope="module")
def wanted(model, ref):
    cfg, params, batch = model
    return jax.jit(jax.value_and_grad(lambda p: ref.loss(p, batch, fields_of(cfg))))(params)


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-30))


def worst_leaf(grads, want):
    return max(jax.tree_util.tree_leaves_with_path(jax.tree.map(relative, grads, want)), key=lambda kv: kv[1])


def moved_leaves(grads):  # (the router's bias takes no gradient)
    return jax.tree_util.tree_map_with_path(
        lambda path, g: None if "e_score_correction_bias" in jax.tree_util.keystr(path) else g, grads)


# ------------------------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("scan,checkpoint", [(True, 1), (True, 0), (False, 1)])
def test_the_program_is_the_reference_on_loss_and_every_gradient(model, wanted, scan, checkpoint):
    cfg, params, batch = model
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, checkpoint=checkpoint, global_bsz=2, scan_layers=scan)
    m = construct_hybrid_parallel_model(cfg, hp, devices=jax.devices()[:1])
    with jax.default_matmul_precision("highest"), forms.recording() as took:
        (loss, parts), grads = jax.jit(jax.value_and_grad(m.loss_parts_fn, has_aux=True))(params, batch)
    assert abs(float(loss) - float(wanted[0])) < LOSS_ATOL
    where, error = worst_leaf(moved_leaves(grads), moved_leaves(wanted[1]))
    assert error < LEAF_RTOL, jax.tree_util.keystr(where)
    assert set(grads["layers"][0]) >= {"hc1", "hc2"} and set(grads["layers"][3]["hc2"]) == {"phi", "b", "a"}
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(grads["layers"][3]["hc2"]))
    # the step's counters: the columns' error of the worst half and token, the streams' gain through the stack
    assert set(hyper.COUNTERS) <= set(parts) and 0 <= float(parts[hyper.COL_ERR]) < 0.2  # (seeded far from the identity)
    assert 0.1 < float(parts[hyper.GAIN]) < 10
    assert took[forms.HYPER]["xla"] >= 1


# ------------------------------------------------------------------ (b) each switch matters
@pytest.mark.parametrize("off", ["x_scale", "sinkhorn_order", "clamp", "yarn_mscale", "hyper"])
def test_each_switch_of_the_reference_moves_the_loss_beyond_the_comparisons_limit(model, wanted, ref, off):
    cfg, params, batch = model
    moved = abs(float(ref.loss(params, batch, fields_of(cfg), switch_off=(off,))) - float(wanted[0]))
    assert moved > 2 * LOSS_ATOL, (off, moved)


def test_the_streams_mean_in_place_of_their_sum_is_a_quarter_of_what_the_final_norm_reads(model, ref):
    # (under the final RMSNorm the two candidates differ by its eps alone, so the switch is held on the hidden state)
    cfg, params, batch = model
    fields = fields_of(cfg)
    rows = (batch["tokens"][0], batch["positions"][0])
    summed = ref.sequence_hidden(params, fields, *rows)[0]
    mean = ref.sequence_hidden(params, fields, *rows, frozenset({"sum_out"}))[0]
    np.testing.assert_allclose(np.asarray(mean) * cfg.hc_mult, np.asarray(summed), rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ (c) one stream is what it was
def test_one_stream_is_the_plain_residual_bit_for_bit_and_the_reference_without_hyper(ref):
    cfg = tiny(hc_mult=1, hc_sinkhorn_iters=0, hc_res_clamp=None)
    params, batch = seeded(cfg)
    assert not any("hc" in jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(params))
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    assert abs(float(loss) - float(ref.loss(params, batch, fields_of(cfg), switch_off=("hyper",)))) < LOSS_ATOL
    # layer_forward at hc_mult 1 against the block written out from the same parts: the same ops, the same bits
    lcfg, lp = cfg.layer_config("dense"), params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, cfg.hidden_size))
    how = dict(mesh=None, axes=None, attn_bias=None, attn_sharding=None, return_kv=False)

    def written_out(lp, x):
        x = x + MIXERS[lcfg.mixer].forward(lp, _norm(x, lp["ln1"], lcfg), batch["positions"], lcfg, **how)[0]
        return x + MLP_HALVES[lcfg.mlp_half].forward(lp, _norm(x, lp["ln2"], lcfg), batch["positions"], lcfg, **how)[0]

    got = jax.jit(lambda lp, x: M.layer_forward(lp, x, batch["positions"], lcfg))(lp, x)
    assert bool(jnp.all(got == jax.jit(written_out)(lp, x)))


@pytest.mark.parametrize("family", ["dense", "latent_routed"])
def test_a_config_with_the_defaults_has_no_new_leaf_and_no_new_scope(family):
    small = dict(num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2, vocab_size=VOCAB, max_seq_len=SEQ)
    cfg = (llama_config("llama-7b", ffn_hidden=96, **small) if family == "dense"
           else glm4_moe_lite_config(ffn_hidden=32, dense_ffn_hidden=96, attn_impl="xla", **small))
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_res_clamp) == (1, 0, None)
    assert cfg.layer_aux == (family == "latent_routed") and hyper.unsupported(cfg) == {}
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, VOCAB)
    batch = dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ)), labels=tokens)
    step = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg))).lower(params).as_text(debug_info=True)
    assert tracing.layers_scope(0) in step and tracing.HC not in step
    wide = tiny()
    text = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, seeded(wide)[1], wide))).lower(
        seeded(wide)[0]).as_text(debug_info=True)
    assert all(scope in text for scope in hyper.SCOPES)  # (the same text does name them where they run)


# ------------------------------------------------------------------ (d) the coefficients
@pytest.mark.parametrize("gate,identity", [(0.01, True), (1.0, False)])
def test_where_the_gates_start_is_the_configs_and_the_default_start_is_a_plain_residual_half(gate, identity):
    """`hc_init_gate`: the default is the identity-like start (H_res within 1e-3 of the identity, H_pre 1/n,
    H_post 1: on n equal streams a half is `x + F(norm x)`); at a gate of 1 every token reads and writes its
    streams its own way from the first step."""
    off = -8.0
    cfg = tiny(hc_init_gate=gate)
    n = cfg.hc_mult
    hp = hyper.init_hyper(jax.random.PRNGKey(3), cfg)
    assert np.allclose(np.asarray(hp["a"]), gate) and hp["phi"].shape == (n * cfg.hidden_size, n * n + 2 * n)
    res_b = np.asarray(hp["b"][2 * n:]).reshape(n, n)
    assert np.allclose(np.diag(res_b), 0.0) and np.allclose(res_b[~np.eye(n, dtype=bool)], off)
    assert np.allclose(np.asarray(hp["b"][:n]), -math.log(n - 1)) and np.allclose(np.asarray(hp["b"][n:2 * n]), 0.0)
    hp = {**hp, "phi": hp["phi"] * 6.0}  # (x~ Phi at the published width's spread: 0.02 sqrt(14336) = 2.4, here 0.45)
    x = jnp.tile(jax.random.normal(jax.random.PRNGKey(4), (1, SEQ, cfg.hidden_size)), (1, 1, n))  # n equal streams
    mix, _ = hyper.coefficients(hp, x, cfg)
    res = jnp.stack([jnp.concatenate(row, axis=-1) for row in mix.res], axis=-2)
    pre, post = jnp.concatenate(mix.pre, axis=-1), jnp.concatenate(mix.post, axis=-1)
    far = max(float(jnp.max(jnp.abs(t - at))) for t, at in ((res, jnp.eye(n)), (pre, 1 / n), (post, 1.0)))
    assert (far < 0.1) if identity else (far > 0.5)


def test_h_res_rows_sum_to_one_columns_to_within_the_counter_and_the_reference_agrees(model, ref):
    cfg, params, _ = model
    n, c = cfg.hc_mult, cfg.hidden_size
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, n * c))
    hp = params["layers"][2]["hc1"]
    with jax.default_matmul_precision("highest"):
        mix, col_err = hyper.coefficients(hp, x, cfg)
    res = jnp.stack([jnp.stack(row, axis=-1) for row in mix.res], axis=-2)[..., 0, :, :]  # (2, SEQ, n, n)
    assert res.dtype == jnp.float32 and float(jnp.min(res)) > 0
    np.testing.assert_allclose(np.asarray(jnp.sum(res, axis=-1)), 1.0, atol=1e-5)  # rows: the last step's
    cols = float(jnp.max(jnp.abs(jnp.sum(res, axis=-2) - 1.0)))
    assert cols == pytest.approx(float(col_err), abs=1e-6) and cols < 0.2
    assert float(jnp.max(jnp.abs(res - jnp.eye(n)))) > 0.01  # (no identity: the seeded leaves vary it a token)
    pre = jnp.concatenate(mix.pre, axis=-1)
    post = jnp.concatenate(mix.post, axis=-1)
    assert 0 < float(jnp.min(pre)) and float(jnp.max(pre)) < 1 and 0 < float(jnp.min(post)) and float(jnp.max(post)) < 2
    with jax.default_matmul_precision("highest"):
        want = ref.coefficients(jax.tree.map(lambda a: a.astype(jnp.float32), hp), x[0].reshape(SEQ, n, c), fields_of(cfg))
    for got, ours in zip(want, (pre[0], post[0], res[0])):
        np.testing.assert_allclose(np.asarray(ours), np.asarray(got), rtol=2e-5, atol=1e-6)
    # one Sinkhorn step leaves the columns far off: the 20 are what the counter holds near 0
    one_step = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    assert float(hyper.coefficients(hp, x, one_step)[1]) > 2 * cols
    # read and write are the sums the docstring states
    o = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, c))
    streams = x.reshape(2, SEQ, n, c)
    np.testing.assert_allclose(np.asarray(hyper.read(mix, x)), np.asarray(jnp.einsum("bsj,bsjc->bsc", pre, streams)),
                               rtol=1e-5, atol=1e-5)
    wrote = jnp.einsum("bsij,bsjc->bsic", res, streams) + post[..., None] * o[:, :, None, :]
    np.testing.assert_allclose(np.asarray(hyper.write(mix, x, o)), np.asarray(wrote.reshape(2, SEQ, n * c)),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------------ (e) the share test
def test_the_shares_parts_add_up_through_write_to_the_uncut_layer(ref):
    """A routed layer's MLP half over all 8 experts = the parts that the two shares of 4 give, with what every chip
    computes alike counted once: the shared expert, and `H_res X` (the streams kept), through `write`."""
    whole = tiny(experts_held=0, experts_held_start=0)
    params, batch = seeded(whole)
    lp = params["layers"][2]
    lcfg = whole.layer_config("routed")
    x = jax.random.normal(jax.random.PRNGKey(11), (2, SEQ, whole.hc_mult * whole.hidden_size))
    how = dict(mesh=None, axes=None, attn_bias=None, attn_sharding=None, return_kv=False)
    with jax.default_matmul_precision("highest"):
        mix, _ = hyper.coefficients(lp["hc2"], x, lcfg)
        y = _norm(hyper.read(mix, x), lp["ln2"], lcfg)
        uncut = hyper.write(mix, x, MLP_HALVES["routed"].forward(lp, y, batch["positions"], lcfg, **how)[0])
        shared = MLP_HALVES["dense"].forward(lp["shared"], y, batch["positions"], whole.layer_config("dense"), **how)[0]
        zero = jnp.zeros_like(shared)
        kept = hyper.write(mix, x, zero)  # H_res X: counted once
        added = jnp.zeros_like(kept)
        for start in (0, 4):
            share_cfg = dataclasses.replace(lcfg, experts_held=4, experts_held_start=start)
            share = {**lp, "wi": {"kernel": lp["wi"]["kernel"][start:start + 4]},
                     "wo_mlp": {"kernel": lp["wo_mlp"]["kernel"][start:start + 4]}}
            o = MLP_HALVES["routed"].forward(share, y, batch["positions"], share_cfg, **how)[0]
            added = added + (hyper.write(mix, x, o - shared) - kept)  # the share's routed part alone, H_post applied
        total = kept + added + (hyper.write(mix, x, shared) - kept)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), rtol=1e-4, atol=1e-5)
    # and the uncut layer is the reference's with all experts held
    fields = fields_of(whole)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: M.lm_loss_fn(p, batch, whole))(params)
    assert abs(float(got) - float(ref.loss(params, batch, fields))) < LOSS_ATOL


# ------------------------------------------------------------------ (f) layouts and refusals
@pytest.mark.parametrize("dp_type", ["zero2", "zero3"])
def test_two_devices_under_zero_give_the_one_device_loss(model, wanted, dp_type):
    cfg, params, batch = model
    hp = HybridParallelConfig.uniform(2, cfg.num_layers, checkpoint=1, global_bsz=2, default_dp_type=dp_type)
    m = construct_hybrid_parallel_model(cfg, hp, devices=jax.devices()[:2])
    sharded = jax.device_put(params, m.shardings(m.param_specs))
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(m.loss_parts_fn, has_aux=True))(sharded, m.shard_batch(batch))
    assert abs(float(loss) - float(wanted[0])) < LOSS_ATOL
    where, error = worst_leaf(moved_leaves(grads), moved_leaves(wanted[1]))
    assert error < LEAF_RTOL, jax.tree_util.keystr(where)


def plain_wide(**over):  # hyper-connections around parts that have every form: what is refused is theirs alone
    return llama_config("llama-7b", **{**dict(num_layers=4, hidden_size=64, num_heads=2, num_kv_heads=2, ffn_hidden=96,
                                              vocab_size=VOCAB, hc_mult=4, hc_sinkhorn_iters=20), **over})


@pytest.mark.parametrize("asks,named", [
    (dict(hp=dict(pp=2, chunks=2)), r"pp=2: the pipeline engines exchange ONE hidden a token"),
    (dict(hp=dict(tp=2)), r"tp=2 cp=1 sp=0: no tensor-, context- or sequence-parallel form of the n-stream activation"),
    (dict(hp=dict(cp=2)), r"tp=1 cp=2 sp=0: no tensor-, context- or sequence-parallel form of the n-stream"),
    (dict(hp=dict(tp=2, sp=1)), r"sp=1: no tensor-, context- or sequence-parallel form of the n-stream"),
    (dict(hp=dict(vocab_tp=2)), r"vocab_tp=2: tensor parallelism of any layer is unsupported beside hyper-connections"),
    (dict(hp=dict(tp=2, tp_comm_mode="shard_map")), r"no tensor-, context- or sequence-parallel form of the n-stream"),
    (dict(asker="serve"), r"serve: the decode engine has no streams for a decoded token"),
    (dict(asker="search"), r"search: the cost models have no row for hyper-connections"),
    (dict(asker="profile"), r"profile: the layer profiler times a dense block under softmax attention, not hyper-conn"),
    (dict(autotune="observe"), r"autotune=observe: the re-search would price hyper-connected layers"),
])
def test_what_has_no_form_of_the_streams_is_refused_by_name(asks, named):
    cfg = plain_wide()
    hp = asks.pop("hp", None)
    if hp is not None:
        asks["hp"] = HybridParallelConfig.uniform(4, 4, global_bsz=4, **hp)
    reason = unsupported_reason(cfg, **asks)
    assert reason is not None and __import__("re").search(named, reason), reason
    assert reason.endswith("one chip and under dp with ZeRO-1/2/3")
    with pytest.raises(DiagnosticError, match="GLS018"):
        if "hp" in asks:
            construct_hybrid_parallel_model(cfg, asks["hp"])
        else:
            M.refuse_unsupported(cfg, **asks)


def test_dp_with_zero_is_not_refused_and_one_stream_says_nothing():
    for dp_type in ("ddp", "zero2", "zero3"):
        assert unsupported_reason(plain_wide(), HybridParallelConfig.uniform(4, 4, global_bsz=4, default_dp_type=dp_type)) is None
    assert unsupported_reason(plain_wide(hc_mult=1, hc_sinkhorn_iters=0), asker="serve") is None
    # the family's own parts keep their sentences beside the streams'
    assert "latent attention" in unsupported_reason(tiny(), HybridParallelConfig.uniform(4, 4, global_bsz=4, tp=2))


@pytest.mark.parametrize("over,named", [
    (dict(mtp_layers=1, mtp_loss_weight=0.3), "a multi-token-prediction module .mtp_layers > 0"),
    (dict(post_norm=True), "sandwich norms"),
    (dict(residual_multiplier=0.5), "a residual_multiplier"),
    (dict(pre_norm=False), "a post-norm stack"),
    (dict(hc_sinkhorn_iters=0), "wants hc_sinkhorn_iters >= 1 .got 0."),
    (dict(hc_mult=1), "the Sinkhorn steps and the clamp are those of hc_mult > 1"),
    (dict(hc_mult=0), "hc_mult=0"),
])
def test_a_config_the_streams_have_no_form_of_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        plain_wide(**over)


def test_the_family_is_the_published_one_and_the_reader_refuses_what_is_not_modelled():
    cfg = xing4_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ffn_hidden,
            cfg.dense_ffn_hidden, cfg.vocab_size, cfg.max_seq_len) == (40, 3584, 32, 32, 256, 1024, 9216, 131072, 262144)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        768, 512, 128, 64, 128)
    assert (cfg.first_dense_layers, cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts, cfg.router_score,
            cfg.router_bias, cfg.norm_topk_prob, cfg.routed_scaling_factor) == (2, 64, 4, 1, "sigmoid", True, True, 2.0)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps, cfg.hc_res_clamp) == (4, 20, 1e-6, [-30.0, 30.0])
    assert cfg.mtp_layers == 0 and PUBLISHED["xing4.0-29b-a4b"]["num_nextn_predict_layers"] == 1  # (built without)
    assert (cfg.rope_theta, cfg.layernorm_eps, cfg.init_std) == (1e4, 1e-6, 0.02) and not cfg.tie_embeddings
    assert cfg.layer_kinds() == ("dense",) * 2 + ("routed",) * 38 and cfg.layer_aux
    # DeepSeek's yarn onto ops/rope's: cos and sin x m(1) / m(1), the softmax at 192^-1/2 x m(1)^2
    assert cfg.rope_scaling == {"rope_type": "yarn", "factor": 64, "original_max_position_embeddings": 4096,
                                "beta_fast": 32, "beta_slow": 1, "attention_factor": 1.0}
    assert cfg.attention_multiplier == pytest.approx(0.0721688 * 2.004739, rel=1e-6)
    assert yarn_from_deepseek(None, 192) == (None, None)
    assert yarn_from_deepseek({"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096, "beta_fast": 32,
                               "beta_slow": 1, "mscale": 1.0, "mscale_all_dim": 0}, 192)[1] == pytest.approx(192 ** -0.5)
    published = PUBLISHED["xing4.0-29b-a4b"]
    with pytest.raises(ValueError, match=r"a multi-token-prediction module \(mtp_layers > 0"):
        xing4_config_from_hf(SimpleNamespace(**published))  # the published keys as they are: MTP beside 4 streams
    with pytest.raises(ValueError, match="mtp_layers > 0"):
        xing4_config(mtp_layers=1)
    for key, value in (("n_group", 8), ("topk_group", 4), ("ep_size", 8), ("scoring_func", "softmax"),
                       ("topk_method", "greedy")):
        with pytest.raises(ValueError, match="%s=.* is not modelled" % key):
            xing4_config_from_hf(SimpleNamespace(**{**published, key: value}), mtp_layers=0)
    with pytest.raises(ValueError, match="rope_scaling type='linear' is not modelled"):
        xing4_config_from_hf(SimpleNamespace(**{**published, "rope_scaling": {"type": "linear", "factor": 4}}), mtp_layers=0)


def test_a_raw_deepseek_style_scaling_is_refused_with_where_to_map_it():
    with pytest.raises(ValueError, match=r"DeepSeek's spelling \(type, mscale, mscale_all_dim\): a family file maps it"):
        rope.checked_scaling(PUBLISHED["xing4.0-29b-a4b"]["rope_scaling"])
    with pytest.raises(ValueError, match="rope_type='linear' has no form here"):
        rope.checked_scaling({"rope_type": "linear"})
