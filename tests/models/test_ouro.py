"""The Ouro family (models/ouro.py: a looped stack, models/base.looped_states; sandwich norms; the exit gate and
the expected loss, models/parts/loop.py) against the plain reference benchmarks/references/ouro_lm.py on seeded
random weights at a small size (hidden 64, 2 heads, 3 layers, 3 passes, 32 tokens): loss, every part and the
gradients by the worst leaf, scanned and unrolled, with and without recomputation; the tie (a leaf's gradient is
the SUM of the gradients of the untied passes); the reduction to the plain decoder; each switch of the reference;
the layouts that run and the refusals; and what the defaults leave as it was."""

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
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.ouro import PUBLISHED, ouro_config, ouro_config_from_hf
from galvatron_tpu.models.parts import loop, unsupported_reason
from galvatron_tpu.obs import tracing
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEQ, VOCAB, STEPS = 32, 128, 3
# float32 compute at `highest` precision on both sides: what separates the two is the ORDER of float32 sums (a
# scanned stack against Python loops, logs of p against products), 1e-6 of a loss of order 5. The limits are some
# ten times that, and a hundredth of what the smallest switch of the reference moves the loss by (test below)
LOSS_ATOL, PART_ATOL, LEAF_RTOL = 5e-6, 5e-6, 5e-5


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(REPO, "benchmarks/references/ouro_lm.py")


def tiny(**over):
    return ouro_config(**{**dict(num_layers=3, hidden_size=64, num_heads=2, num_kv_heads=2, head_dim=32, ffn_hidden=96,
                                 vocab_size=VOCAB, loop_steps=STEPS, max_seq_len=SEQ, compute_dtype=jnp.float32), **over})


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def seeded(cfg, rows=2):
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    # every leaf off its initial value: a norm's scale of 1 and the gate's bias of 0 would hide their gradients' paths
    params = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape), params)
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
    (loss, parts), grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_parts(p, batch, fields_of(cfg)), has_aux=True))(params)
    return loss, parts, grads


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def worst_leaf(grads, want):
    return max(jax.tree_util.tree_leaves_with_path(jax.tree.map(relative, grads, want)), key=lambda kv: kv[1])


# ------------------------------------------------------------------ (a) against the reference
@pytest.mark.parametrize("scan,checkpoint", [(True, 1), (True, 0), (False, 1)])
def test_the_program_is_the_reference_on_loss_parts_and_every_gradient(model, wanted, scan, checkpoint):
    cfg, params, batch = model
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, checkpoint=checkpoint, global_bsz=2, scan_layers=scan)
    m = construct_hybrid_parallel_model(cfg, hp, devices=jax.devices()[:1])
    assert m.loss_parts_fn is not None  # the step hands the loop's terms back
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(m.loss_parts_fn, has_aux=True))(params, batch)
    want_loss, want_parts, want_grads = wanted
    assert abs(float(loss) - float(want_loss)) < LOSS_ATOL
    assert set(parts) == {"loss_ce", *loop.PARTS}
    for name, value in parts.items():
        assert abs(float(value) - float(want_parts[name])) < PART_ATOL, name
    where, error = worst_leaf(grads, want_grads)
    assert error < LEAF_RTOL, jax.tree_util.keystr(where)
    assert set(grads["layers"][0]) >= {"ln1_post", "ln2_post"} and set(grads["exit_gate"]) == {"kernel", "bias"}


# ------------------------------------------------------------------ (b) the tie
def test_a_tied_leafs_gradient_is_the_sum_over_the_untied_passes(model, ref):
    cfg, params, batch = model
    grads = jax.jit(jax.grad(lambda p: M.lm_loss_fn(p, batch, cfg)))(params)
    rest = {k: v for k, v in params.items() if k != "layers"}
    untied = jax.jit(jax.grad(lambda passes: ref.loss({**rest, "passes": passes}, batch, fields_of(cfg))))(
        [params["layers"]] * STEPS)
    assert len(untied) == STEPS and len(untied[0]) == cfg.num_layers
    summed = jax.tree.map(lambda *g: sum(g), *untied)
    where, error = worst_leaf(grads["layers"], summed)
    assert error < LEAF_RTOL, jax.tree_util.keystr(where)
    # (no pass's share is negligible: the sum is not one pass's gradient)
    kernel = lambda stack: stack[0]["wi"]["kernel"]
    assert all(relative(kernel(one), kernel(summed)) > 0.05 for one in untied)


# ------------------------------------------------------------------ (c) one pass, no sandwich, no gate
def test_one_pass_without_post_norm_or_gate_is_the_plain_decoder():
    cfg = tiny(loop_steps=1, post_norm=False, exit_gate=False, exit_entropy_coef=0.0)
    plain = llama_config("llama-7b", **{k: getattr(cfg, k) for k in (
        "num_layers", "hidden_size", "num_heads", "num_kv_heads", "head_dim", "ffn_hidden", "vocab_size", "max_seq_len",
        "compute_dtype", "rope_theta", "layernorm_eps")})
    assert dataclasses.asdict(cfg) == dataclasses.asdict(plain)
    params, batch = seeded(cfg)
    decoder = cells.load_module(REPO, "benchmarks/references/decoder_lm.py")
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(lambda p: M.lm_loss_fn(p, batch, cfg))(params)
    assert abs(float(loss) - float(decoder.loss(params, batch, fields_of(cfg)))) < LOSS_ATOL
    assert "exit_gate" not in params and "ln1_post" not in params["layers"][0]


# ------------------------------------------------------------------ (d) each switch matters
@pytest.mark.parametrize("off", ["post_norm", "loop_norm", "gate", "entropy", "qkv_bias"])
def test_each_switch_of_the_reference_moves_the_loss_beyond_the_comparisons_limit(model, wanted, ref, off):
    cfg, params, batch = model
    want = float(wanted[0])
    if off == "qkv_bias":  # the other candidate: Qwen2's bias, which the program's tree then holds and the reference adds
        cfg = tiny(qkv_bias=True)
        params, batch = seeded(cfg)
        assert params["layers"][0]["wqkv"]["bias"].shape == (3, 2, 32)
        want = float(ref.loss(params, batch, fields_of(cfg)))
        with jax.default_matmul_precision("highest"):
            assert abs(float(M.lm_loss_fn(params, batch, cfg)) - want) < LOSS_ATOL
    moved = abs(float(ref.loss(params, batch, fields_of(cfg), switch_off=(off,))) - want)
    assert moved > 100 * LOSS_ATOL, (off, moved)


# ------------------------------------------------------------------ (e) the distribution
def test_the_exit_distribution_sums_to_one_and_the_mean_step_lies_inside(model):
    cfg, params, _ = model
    states = jax.random.normal(jax.random.PRNGKey(5), (STEPS, 2, SEQ, 64))
    for gate in (params["exit_gate"], {"kernel": 40.0 * params["exit_gate"]["kernel"], "bias": jnp.full((1,), -30.0)}):
        p = loop.exit_distribution(gate, states)
        assert p.shape == (STEPS, 2, SEQ) and p.dtype == jnp.float32 and float(jnp.min(p)) >= 0.0
        np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0, atol=1e-6)
        loss, parts = loop.expected_loss(p, jnp.ones_like(p), None, 0.1)
        assert 1.0 <= float(parts["exit_step_mean"]) <= STEPS and 0.0 <= float(parts["exit_entropy"]) <= np.log(STEPS) + 1e-6
        assert np.isfinite(float(loss)) and float(parts["loss_ce"]) == pytest.approx(1.0, abs=1e-6)
        grads = jax.grad(lambda g: loop.expected_loss(loop.exit_distribution(g, states), jnp.ones_like(p), None, 0.1)[0])(gate)
        assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))  # (saturated logits too)
    last = loop.exit_distribution(None, states)  # no gate: all mass on the last pass
    assert float(jnp.min(last[-1])) == 1.0 and float(jnp.max(last[:-1])) == 0.0


# ------------------------------------------------------------------ (f) layouts and refusals
@pytest.mark.parametrize("layout", [dict(default_dp_type="zero2"), dict(tp=2, vocab_tp=2)], ids=["dp2_zero2", "tp2"])
def test_two_devices_give_the_one_device_loss(model, wanted, layout):
    cfg, params, batch = model
    hp = HybridParallelConfig.uniform(2, cfg.num_layers, checkpoint=1, global_bsz=2, **layout)
    m = construct_hybrid_parallel_model(cfg, hp, devices=jax.devices()[:2])
    sharded = jax.device_put(params, m.shardings(m.param_specs))
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(m.loss_parts_fn, has_aux=True))(sharded, m.shard_batch(batch))
    assert abs(float(loss) - float(wanted[0])) < LOSS_ATOL
    assert abs(float(parts["exit_step_mean"]) - float(wanted[1]["exit_step_mean"])) < PART_ATOL
    where, error = worst_leaf(grads, wanted[2])
    assert error < LEAF_RTOL, jax.tree_util.keystr(where)


def test_what_has_no_form_of_the_loop_is_refused_by_name():
    cfg = tiny(num_layers=4)
    with pytest.raises(DiagnosticError, match=r"GLS018.*pp=2: the pipeline engines have no ring.*loop_steps > 1"):
        construct_hybrid_parallel_model(cfg, HybridParallelConfig.uniform(4, 4, pp=2, global_bsz=4, chunks=2))
    with pytest.raises(DiagnosticError, match="GLS018.*serve.*no per-pass caches and no early exit"):
        M.refuse_unsupported(cfg, asker="serve")
    for asker in ("search", "profile"):
        assert "a looped stack (loop_steps > 1" in unsupported_reason(cfg, asker=asker)
    assert unsupported_reason(cfg, autotune="observe").startswith(
        "autotune=observe: the re-search would price a looped stack (loop_steps > 1) as a plain one")
    manual = HybridParallelConfig.uniform(2, 4, tp=2, global_bsz=2, tp_comm_mode="shard_map")
    assert unsupported_reason(cfg, manual).startswith("tp_comm_mode='shard_map': the manual TP path has no form of a looped")
    # what it runs on is said as it is: tp is among it
    assert all(unsupported_reason(cfg, asker=a).endswith(loop.RUNS) for a in ("serve", "search", "profile"))
    assert unsupported_reason(cfg, HybridParallelConfig.uniform(2, 4, tp=2, global_bsz=2)) is None
    sandwich = llama_config("llama-7b", num_layers=2, hidden_size=64, num_heads=2, vocab_size=VOCAB, post_norm=True)
    assert "sandwich norm" in unsupported_reason(sandwich, asker="serve") and unsupported_reason(sandwich, asker="search") is None
    # a routed stack keeps its own sentence's end
    routed = olmoe_config(num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2, ffn_hidden=32, vocab_size=VOCAB)
    assert unsupported_reason(routed, asker="serve").endswith("one chip and under dp with ZeRO-1/2/3")


@pytest.mark.parametrize("over,named", [
    (dict(loop_steps=1), "a gate weighs the passes of a stack run 2 or more times"),
    (dict(loop_steps=0), "loop_steps=0"),
    (dict(exit_gate=False), "the entropy is the gate's distribution's"),
    (dict(pre_norm=False), "the loop re-enters a pre-norm stack through its final norm"),
    (dict(mtp_layers=1, mtp_loss_weight=0.3), "an MTP module"),
    (dict(pred_heads=2), "pred_heads > 1"),
])
def test_a_config_the_loop_has_no_form_of_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        tiny(**over)


def test_the_family_is_the_published_one_and_the_reader_refuses_what_is_not_modelled():
    cfg = ouro_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ffn_hidden,
            cfg.vocab_size, cfg.max_seq_len) == (48, 2048, 16, 16, 128, 5632, 49152, 65536)
    assert (cfg.loop_steps, cfg.post_norm, cfg.exit_gate, cfg.exit_entropy_coef) == (4, True, True, 0.1)
    assert (cfg.rope_theta, cfg.layernorm_eps, cfg.init_std, cfg.norm_type, cfg.activation) == (
        1e6, 1e-6, 0.02, "rmsnorm", "swiglu")
    assert not (cfg.tie_embeddings or cfg.qkv_bias or cfg.out_bias or cfg.mlp_bias) and cfg.fused_qkv
    assert cfg.layer_kinds() == ("dense",) * 48 and not cfg.layer_aux
    from types import SimpleNamespace

    for key, value in (("attention_bias", True), ("use_sliding_window", True), ("hidden_act", "gelu"),
                       ("layer_types", ["full_attention", "sliding_attention"])):
        with pytest.raises(ValueError, match="not modelled"):
            ouro_config_from_hf(SimpleNamespace(**{**PUBLISHED["ouro-2.6b"], key: value}))
    once = ouro_config_from_hf(SimpleNamespace(**{**PUBLISHED["ouro-2.6b"], "total_ut_steps": 1}))
    assert (once.loop_steps, once.exit_gate, once.exit_entropy_coef, once.post_norm) == (1, False, 0.0, True)


# ------------------------------------------------------------------ (g) the defaults leave a step as it was
@pytest.mark.parametrize("family", ["dense", "routed"])
def test_a_config_with_the_defaults_has_no_new_leaf_and_no_new_scope(family):
    small = dict(num_layers=2, hidden_size=64, num_heads=2, num_kv_heads=2, vocab_size=VOCAB, max_seq_len=SEQ)
    cfg = (llama_config("llama-7b", ffn_hidden=96, **small) if family == "dense"
           else olmoe_config(ffn_hidden=32, **small))
    assert (cfg.loop_steps, cfg.post_norm, cfg.exit_gate, cfg.exit_entropy_coef) == (1, False, False, 0.0)
    params, batch = seeded(cfg)
    paths = {jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(params)}
    assert not any(word in path for path in paths for word in ("_post", "exit_gate"))
    step = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, batch, cfg))).lower(params).as_text(debug_info=True)
    assert tracing.layers_scope(0) in step and tracing.HEAD_LOSS in step
    for scope in loop.SCOPES:
        assert scope not in step, scope
    looped = jax.jit(jax.value_and_grad(lambda p: M.lm_loss_fn(p, seeded(tiny())[1], tiny()))).lower(
        seeded(tiny())[0]).as_text(debug_info=True)
    assert all(scope in looped for scope in loop.SCOPES)  # (the same text does name them where they run)
