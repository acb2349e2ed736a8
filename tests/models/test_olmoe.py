"""The routed-experts block (ops/moe.py, models/base.py) against the plain
reference (benchmarks/references/olmoe_lm.py) on seeded random weights at a
small size: hidden 64, 8 experts of 32 with 2 a token, 2 layers; and once 64
experts with 8 a token, OLMoE's own counts.

Tolerances, and why. In float32 compute the program and the reference do the
same arithmetic in another order (sorted rows through a grouped matmul
against every expert applied densely and masked): measured worst-leaf
relative gradient error 6e-7, loss 1e-6; the limit is 1e-5, which a bf16
router (2.5e-3 on the router's own gradient), renormalised top-k weights
(2.4) and a dispatch that drops what exceeds an even capacity (0.39 on the
experts' kernels) all fail. In bf16 compute the residual stream itself is
rounded before the router sees it, so a token whose k-th and (k+1)-th
probabilities are within about 1e-3 of each other can choose another expert
than the float32 reference's: measured worst leaf 0.06 to 0.09 (the norm
scales and the kernels of experts that gain or lose one of their few rows),
loss 6e-5. The limit is 0.15 on the worst leaf and 5e-4 on the loss; drops
(0.39) and renormalisation (2.4) fail it. A bf16 ROUTER does not stand out
from a bf16 stream at this size (both flip a token or two); it is held by the
float32 comparison and by `test_the_router_multiplies_in_float32`.
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
from galvatron_tpu.models import base as M
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.obs import telemetry
from galvatron_tpu.ops import moe
from galvatron_tpu.runtime import construct_hybrid_parallel_model, get_optimizer_and_scheduler
from galvatron_tpu.runtime.optimizer import OptimizerArgs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/olmoe_lm.py")

F32_TOL = 1e-5  # loss, each part, worst-leaf relative gradient error
BF16_GRAD_TOL, BF16_LOSS_TOL = 0.15, 5e-4
BATCH, SEQ, VOCAB = 4, 32, 256
SIZES = {"e8k2": (8, 2), "e64k8": (64, 8)}


def tiny(dtype=jnp.float32, experts=8, k=2, **kw):
    return olmoe_config(
        "olmoe-1b-7b", hidden_size=64, num_heads=4, num_kv_heads=4, head_dim=16,
        ffn_hidden=32, num_layers=2, vocab_size=VOCAB, max_seq_len=SEQ,
        num_experts=experts, experts_per_token=k, compute_dtype=dtype, **kw)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, VOCAB)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (batch, SEQ)),
                labels=jnp.roll(tok, -1, 1), loss_mask=jnp.ones((batch, SEQ), jnp.float32))


def program(cfg, params, batch):
    """((loss, parts), grads) of the program's own loss, no mesh."""
    return jax.jit(jax.value_and_grad(
        lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)


def reference(cfg, params, batch):
    def loss(p):
        parts = REF.loss_parts(p, batch, fields_of(cfg))
        return parts["loss"], parts

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def leaf_errors(grads, ref_grads):
    """{leaf path: |g - g_ref| / |g_ref|}."""
    rel = jax.tree.map(
        lambda a, b: float(jnp.linalg.norm(a.astype(jnp.float32) - b) / jnp.linalg.norm(b)),
        grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(rel)[0]}


_REFERENCE = {}


@pytest.fixture(scope="module", params=[(s, d) for s in SIZES for d in ("float32", "bfloat16")],
                ids=lambda p: "%s-%s" % p)
def case(request):
    size, dtype = request.param
    cfg = tiny(getattr(jnp, dtype), *SIZES[size])
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    if size not in _REFERENCE:  # float32 whatever the program computes in
        _REFERENCE[size] = reference(cfg, params, batch)
    return dtype, program(cfg, params, batch), _REFERENCE[size]


def test_loss_and_its_three_parts_match_the_reference(case):
    dtype, ((loss, parts), _), ((ref_loss, ref_parts), _) = case
    tol = F32_TOL if dtype == "float32" else BF16_LOSS_TOL
    assert abs(float(loss - ref_loss)) <= tol
    assert abs(float(parts["loss_ce"] - ref_parts["ce"])) <= tol
    # the router terms are O(1) and O(10) before their coefficients
    assert float(parts["loss_load_balance"]) == pytest.approx(float(ref_parts["load_balance"]),
                                                             rel=tol * 10)
    assert float(parts["loss_router_z"]) == pytest.approx(float(ref_parts["router_z"]),
                                                         rel=tol * 10)
    assert set(parts) == set(telemetry.EXPERT_STEP_FIELDS)


def test_every_leafs_gradient_matches_the_reference(case):
    dtype, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    worst = max(errors, key=errors.get)
    assert errors[worst] <= (F32_TOL if dtype == "float32" else BF16_GRAD_TOL), (worst, errors)
    assert any("router" in k for k in errors) and any("q_norm" in k for k in errors)


# ------------------------------------------------------------- the controls
def _bf16_router(y, kernel):
    return (y.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16)).astype(jnp.float32)


def _dropping(grouped_matmul):
    """A dispatch with capacity factor 1.0: a row past its expert's even
    share of the rows comes back zero, as a dropped token does."""
    def gmm(rows, kernels, group_sizes, on_tpu=False):
        ends = jnp.cumsum(group_sizes)
        group = jnp.searchsorted(ends, jnp.arange(rows.shape[0]), side="right")
        position = jnp.arange(rows.shape[0]) - (ends - group_sizes)[group]
        kept = position < rows.shape[0] // kernels.shape[0]
        return grouped_matmul(rows, kernels, group_sizes) * kept[:, None].astype(rows.dtype)

    return gmm


@pytest.mark.parametrize("control,dtype", [
    ("bf16_router", "float32"), ("renormalised", "float32"), ("dropped", "float32"),
    ("renormalised", "bfloat16"), ("dropped", "bfloat16")])
def test_a_control_fails_the_tolerance(monkeypatch, control, dtype):
    """What the tolerances are for: each of these is outside them."""
    cfg = tiny(getattr(jnp, dtype))
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    if "e8k2" not in _REFERENCE:
        _REFERENCE["e8k2"] = reference(cfg, params, batch)
    if control == "bf16_router":
        monkeypatch.setattr(moe, "router_logits", _bf16_router)
    elif control == "dropped":
        monkeypatch.setattr(moe, "grouped_matmul", _dropping(moe.grouped_matmul))
    else:
        cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    _, grads = program(cfg, params, batch)
    errors = leaf_errors(grads, _REFERENCE["e8k2"][1])
    # outside the limit, in float32 by a factor of 100 at least
    assert max(errors.values()) > (100 * F32_TOL if dtype == "float32" else BF16_GRAD_TOL)


def test_the_router_multiplies_in_float32():
    """bf16 activations against the float32 router kernel, in float32: equal
    to numpy's float64 product to float32 rounding, where a product of bf16
    operands is 1e-3 off."""
    y = jax.random.normal(jax.random.PRNGKey(0), (128, 64), jnp.bfloat16)
    kernel = jax.random.normal(jax.random.PRNGKey(1), (64, 8), jnp.float32) * 0.02
    exact = np.asarray(y.astype(jnp.float32), np.float64) @ np.asarray(kernel, np.float64)
    ours = np.asarray(moe.router_logits(y, kernel))
    assert ours.dtype == np.float32
    assert np.abs(ours - exact).max() <= 1e-6 * np.abs(exact).max()
    assert np.abs(np.asarray(_bf16_router(y, kernel)) - exact).max() > 1e-4 * np.abs(exact).max()


# ------------------------------------------------------- crafted imbalance
def test_dropless_under_a_router_that_starves_one_expert():
    """Every token is sent to expert 0 and none to expert 7: a constant in
    channel 0 of the embedding survives the norm, and the router's row 0
    turns it into a large logit for expert 0 and a large negative one for
    expert 7. Nothing is dropped or padded: loss and gradients still equal
    the dense-and-masked reference's, the starved expert's gradient is
    exactly zero, and the load reads experts / experts-a-token."""
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(3), cfg)
    params["embed"]["wte"] = params["embed"]["wte"].at[:, 0].set(1.0)
    for lp in params["layers"]:
        lp["router"]["kernel"] = lp["router"]["kernel"].at[0, 0].set(4.0).at[0, 7].set(-4.0)
    batch = batch_of(seed=5)
    ((loss, parts), grads) = program(cfg, params, batch)
    ((ref_loss, _), ref_grads) = reference(cfg, params, batch)
    assert float(parts["expert_load_max_over_mean"]) == cfg.num_experts / cfg.experts_per_token
    assert abs(float(loss - ref_loss)) <= F32_TOL
    for lp in grads["layers"]:
        assert not np.any(np.asarray(lp["wi"]["kernel"][7]))
        assert not np.any(np.asarray(lp["wo_mlp"]["kernel"][7]))
        assert np.any(np.asarray(lp["wi"]["kernel"][0]))
    for layer, ref_layer in zip(grads["layers"], ref_grads["layers"]):
        # the starved expert's reference gradient is zero too: compare the rest
        for name in ("wi", "wo_mlp"):
            layer[name]["kernel"] = layer[name]["kernel"][:7]
            ref_layer[name]["kernel"] = ref_layer[name]["kernel"][:7]
    errors = leaf_errors(grads, ref_grads)
    assert max(errors.values()) <= F32_TOL, errors


# ------------------------------------------- the trainer's paths and layouts
def _loss_and_grads(cfg, hp, params, batch):
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    placed = jax.device_put(params, model.shardings())
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(placed, model.shard_batch(batch))
    return float(loss), jax.device_get(grads)


@pytest.fixture(scope="module")
def one_device():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = batch_of()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    return cfg, params, batch, _loss_and_grads(cfg, hp, params, batch)


@pytest.mark.parametrize("name,hp_kw", [
    ("remat", dict(world=1, checkpoint=1)),
    ("remat_no_scan", dict(world=1, checkpoint=1, scan_layers=False)),
    ("no_scan", dict(world=1, scan_layers=False)),
    ("dp2_zero2", dict(world=2, default_dp_type="zero2")),
    ("dp2_zero3", dict(world=2, sdp=1)),
    ("dp4_zero2_remat", dict(world=4, default_dp_type="zero2", checkpoint=1)),
])
def test_remat_scan_and_dp_layouts_give_one_devices_gradients(one_device, name, hp_kw):
    """Recomputation, the unrolled path and data parallelism with ZeRO-2/3
    change how the step is run, not what it computes (float32: 1e-5)."""
    cfg, params, batch, (loss, grads) = one_device
    hp_kw = dict(hp_kw)
    hp = HybridParallelConfig.uniform(hp_kw.pop("world"), cfg.num_layers, global_bsz=BATCH, **hp_kw)
    got_loss, got = _loss_and_grads(cfg, hp, params, batch)
    assert abs(got_loss - loss) <= F32_TOL
    assert max(leaf_errors(got, grads).values()) <= F32_TOL


@pytest.mark.parametrize("chunks", [1, 2])
def test_the_step_hands_back_the_loss_by_parts(chunks):
    """dp2 ZeRO-2 through make_train_step: the four counters come with the
    loss, and the loss is the cross entropy plus the weighted router terms.
    Microbatches weight the terms as they weight the loss."""
    cfg = tiny()
    hp = HybridParallelConfig.uniform(2, cfg.num_layers, global_bsz=BATCH, chunks=chunks,
                                      default_dp_type="zero2", checkpoint=1)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:2])
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=1, total_steps=4))
    params = model.init_params(jax.random.PRNGKey(0))
    step = model.make_train_step(tx)
    _, _, metrics = step(params, model.init_opt_state(tx, params), model.shard_batch(batch_of()))
    metrics = {k: float(v) for k, v in metrics.items()}
    assert set(telemetry.EXPERT_STEP_FIELDS) <= set(metrics)
    assert metrics["loss"] == pytest.approx(
        metrics["loss_ce"] + cfg.router_aux_loss_coef * metrics["loss_load_balance"]
        + cfg.router_z_loss_coef * metrics["loss_router_z"], abs=1e-6)
    assert 1.0 <= metrics["expert_load_max_over_mean"] <= cfg.num_experts / cfg.experts_per_token
    # near-uniform routing on untrained weights: E x sum f P is about k
    assert metrics["loss_load_balance"] == pytest.approx(cfg.experts_per_token, rel=0.05)


def test_a_dense_configs_step_carries_no_router_terms():
    cfg = dataclasses.replace(tiny(), num_experts=0, experts_per_token=0)
    assert not cfg.routed
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    assert model.loss_parts_fn is None
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=1, total_steps=4))
    params = model.abstract_params()
    out = jax.eval_shape(model.make_train_step(tx), params, jax.eval_shape(tx.init, params),
                         batch_of())
    assert set(out[2]) == {"loss", "grad_norm"}
    assert "router" not in params["layers"][0] and "q_norm" in params["layers"][0]


# ----------------------------------------------------------------- refusals
def _layers(n, **kw):
    return [LayerStrategy(**kw)] * n


REFUSED = {
    "tp2": dict(world_size=2, layers=_layers(2, tp=2)),
    "ulysses": dict(world_size=2, layers=_layers(2, tp=2, sp=1)),
    "cp2": dict(world_size=2, layers=_layers(2, cp=2)),
    "vocab_tp2": dict(world_size=2, layers=_layers(2), vocab_tp=2),
    "pp2_gpipe": dict(world_size=2, pp=2, layers=_layers(2), chunks=2),
    "pp2_1f1b": dict(world_size=2, pp=2, layers=_layers(2), chunks=2,
                     pipeline_type="pipedream_flush"),
    "tp_comm_shard_map": dict(world_size=2, layers=_layers(2), tp_comm_mode="shard_map"),
    "tp_comm_overlap": dict(world_size=2, layers=_layers(2), tp_comm_mode="overlap"),
    "quantized_grads": dict(world_size=2, layers=_layers(2, grad_comm_dtype="int8")),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_expert_form_is_refused_by_code(layout):
    """GLS018 before tracing (lint_hp) and at construction, by name, and the
    same layout runs a dense config of the same sizes."""
    cfg = tiny()
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": BATCH, **REFUSED[layout]})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert "GLS018" in {d.code for d in report.errors}
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:2])
    assert "GLS018" in str(e.value)
    dense = dataclasses.replace(cfg, num_experts=0, experts_per_token=0)
    assert "GLS018" not in {d.code for d in strategy_lint.lint_hp(hp, model_cfg=dense).errors}


@pytest.mark.parametrize("kwargs", [dict(mode="serve"), dict(mode="train", autotune="observe")],
                         ids=["serve", "autotune"])
def test_serve_and_the_autotuner_refuse_an_expert_config(kwargs):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    assert "GLS018" in {d.code for d in strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors}
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok


def test_the_serving_forwards_refuse_at_trace_time():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, SEQ, cfg.hidden_size))
    pos = jnp.arange(SEQ)[None]
    with pytest.raises(DiagnosticError, match="GLS018"):
        M.run_layers(params, x, pos, cfg, collect_kv=True)
    cache = jnp.zeros((1, SEQ, cfg.num_kv_heads, cfg.head_dim))
    with pytest.raises(DiagnosticError, match="GLS018"):
        M.decode_layer_forward(params["layers"][0], x[:, :1], pos[:, :1], cfg, k_cache=cache,
                               v_cache=cache, write_index=jnp.zeros((1,), jnp.int32))


def test_search_and_profile_refuse_an_expert_config():
    from galvatron_tpu.cli import profile, search
    from galvatron_tpu.cli.arguments import initialize_galvatron

    for mode, run in (("search", search.search), ("profile", profile.profile_model)):
        args = initialize_galvatron(mode=mode, argv=["--model_type", "olmoe"])
        with pytest.raises(DiagnosticError, match="GLS018"):
            run(args)


# ------------------------------------------------- the family and its FLOPs
def test_the_preset_carries_the_published_config_and_its_source():
    import types

    from galvatron_tpu.models import olmoe, registry

    fam = registry.get_family("olmoe")
    cfg = fam.config_fn(fam.default_size)
    assert fam.default_size == "olmoe-1b-7b" and "olmoe" in registry.family_names()
    assert fam.meta_configs["olmoe-1b-7b"]["source"] == olmoe.OLMOE_1B_7B_SOURCE
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (2048, 16, 16, 128)
    assert (cfg.num_layers, cfg.ffn_hidden, cfg.vocab_size, cfg.max_seq_len) == (16, 1024, 50304, 4096)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.norm_topk_prob) == (64, 8, False)
    assert (cfg.router_aux_loss_coef, cfg.router_z_loss_coef) == (0.01, 0.001)
    assert cfg.qk_norm and cfg.routed and cfg.fused_qkv and not cfg.tie_embeddings
    assert (cfg.norm_type, cfg.activation, cfg.position_type) == ("rmsnorm", "swiglu", "rope")
    assert (cfg.layernorm_eps, cfg.rope_theta, cfg.init_std) == (1e-5, 10000.0, 0.02)
    assert not (cfg.qkv_bias or cfg.mlp_bias or cfg.out_bias)
    # an HF config object reads the same; what is not modelled is refused
    hf = types.SimpleNamespace(**{k: v for k, v in olmoe.PUBLISHED["olmoe-1b-7b"].items()
                                  if k != "source"})
    assert fam.config_from_hf(hf) == cfg
    hf.clip_qkv = 8.0
    with pytest.raises(ValueError, match="clip_qkv"):
        fam.config_from_hf(hf)
    # one layer with every expert is 420 M parameters, the model 6.9 B
    shapes = jax.eval_shape(lambda k: M.init_layer_params(k, cfg), jax.random.PRNGKey(0))
    layer = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert layer == pytest.approx(420e6, rel=0.01)
    assert 16 * layer + 2 * 50304 * 2048 == pytest.approx(6.92e9, rel=0.01)


def test_step_flops_count_the_experts_a_token_is_sent_to():
    from galvatron_tpu.obs import flops

    cfg = olmoe_config("olmoe-1b-7b", num_layers=1)
    a_token = flops.train_step_flops(cfg, 2) / (2 * 4096)
    layer = (4 * 2 * 2048 * 2048 + 2 * 2 * 4096 * 2048 // 2  # projections, causal scores
             + 8 * 3 * 2 * 2048 * 1024 + 2 * 2048 * 64)  # 8 of 64 experts, the router
    assert a_token == 3 * (layer + 2 * 2048 * 50304)
    # the dense count of the same sizes: one MLP and no router
    dense = dataclasses.replace(cfg, num_experts=0, experts_per_token=0)
    assert flops.train_step_flops(cfg, 2) - flops.train_step_flops(dense, 2) == \
        3 * 8192 * (7 * 3 * 2 * 2048 * 1024 + 2 * 2048 * 64)


def test_a_dense_models_checkpoint_digest_is_what_it_was():
    """The fields newer than the first checkpoints stay out of the elastic digest at their defaults."""
    from galvatron_tpu.models.llama import llama_config
    from galvatron_tpu.runtime import elastic

    cfg = llama_config("llama-0.3b")
    fields = {k: str(v) for k, v in dataclasses.asdict(cfg).items()
              if k not in elastic._DIGEST_EXCLUDE and k in elastic._DIGEST_ALWAYS}
    import hashlib

    assert elastic.model_config_digest(cfg) == hashlib.sha256(
        elastic._stable_json(fields).encode()).hexdigest()
    assert elastic.model_config_digest(dataclasses.replace(cfg, qk_norm=True)) != \
        elastic.model_config_digest(cfg)
