"""GLM-4.7-Flash's block and objective (models/base.py, ops/moe.py,
models/glm4_moe_lite.py) against the plain reference
(benchmarks/references/glm4_moe_lite_lm.py) on seeded random weights at a
small size: hidden 64, 4 heads of 12 + 4 = 16 dims, q rank 24, kv rank 16, one
dense layer of 96 then two routed layers of 8 experts of 32 with 2 a token
beside a shared one, and the multi-token-prediction module.

Tolerances, and why. In float32 compute program and reference do the same
arithmetic in another order (sorted rows through a grouped matmul against
every held expert applied densely and masked; attention whole against
attention a block of queries at a time): measured worst-leaf relative
gradient error 1.1e-6, loss 5e-7; the limit is 1e-5. What it holds apart, each
a test below: a bf16 router (flipped picks), rope on every head's own key
instead of the one shared key, the softmax scale of the nope dims alone, an
MTP label shifted by one instead of two.
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
from galvatron_tpu.models.parts import unsupported_reason
from galvatron_tpu.models.parts.attention import latent_qkv_projection
from galvatron_tpu.models.parts.embed_head import _token_nll
from galvatron_tpu.models.parts.mlp import ROUTER_BIAS, dense_mlp
from galvatron_tpu.models import glm4_moe_lite as G
from galvatron_tpu.models.registry import get_family
from galvatron_tpu.obs import telemetry
from galvatron_tpu.ops import moe
from galvatron_tpu.ops.attention import core_attention
from galvatron_tpu.runtime import construct_hybrid_parallel_model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
REF = cells.load_module(REPO, "benchmarks/references/glm4_moe_lite_lm.py")

F32_TOL = 1e-5  # loss, each part, worst-leaf relative gradient error
BATCH, SEQ, VOCAB, EXPERTS = 4, 32, 256, 8


def tiny(dtype=jnp.float32, **kw):
    fields = dict(
        hidden_size=64, num_heads=4, num_kv_heads=4, ffn_hidden=32, dense_ffn_hidden=96,
        num_layers=3, vocab_size=VOCAB, max_seq_len=SEQ, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, num_experts=EXPERTS,
        experts_per_token=2, compute_dtype=dtype)
    fields.update(kw)
    return G.glm4_moe_lite_config("glm-4.7-flash", **fields)


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


def batch_of(seed=1, batch=BATCH):
    tok = jax.random.randint(jax.random.PRNGKey(seed), (batch, SEQ), 0, VOCAB)
    mask = jnp.ones((batch, SEQ), jnp.float32).at[:, -1].set(0.0)
    return dict(tokens=tok, positions=jnp.broadcast_to(jnp.arange(SEQ), (batch, SEQ)),
                labels=jnp.roll(tok, -1, 1), loss_mask=mask)


def params_of(cfg, seed=0):
    """Seeded weights with a bias that is not zero, so that it is read."""
    params = M.init_model_params(jax.random.PRNGKey(seed), cfg)
    for i, router in enumerate(M.router_bias_leaves(params)):
        router[ROUTER_BIAS] = 0.05 * jax.random.normal(jax.random.PRNGKey(100 + i), (EXPERTS,))
    return params


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
    """{leaf path: |g - g_ref| / |g_ref|}; a leaf whose reference gradient is
    zero (the bias) must be zero too."""
    def rel(a, b):
        norm = float(jnp.linalg.norm(b))
        diff = float(jnp.linalg.norm(a.astype(jnp.float32) - b))
        return diff / norm if norm else diff

    tree = jax.tree.map(rel, grads, ref_grads)
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


HELD = {"whole": {}, "held_2_of_8": dict(experts_held=2, experts_held_start=4)}


@pytest.fixture(scope="module", params=sorted(HELD))
def case(request):
    cfg = tiny(**HELD[request.param])
    params, batch = params_of(cfg), batch_of()
    return cfg, program(cfg, params, batch), reference(cfg, params, batch)


# ------------------------------------------------- the whole model, float32
def test_the_config_is_the_published_one_cut_by_the_tests_sizes():
    cfg = G.glm4_moe_lite_config()
    pub = G.PUBLISHED["glm-4.7-flash"]
    assert pub["source"] == G.GLM_47_FLASH_SOURCE and get_family("glm4_moe_lite").meta_configs is G.PUBLISHED
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.head_dim) == (47, 2048, 20, 256)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (768, 512, 192, 64, 256)
    assert (cfg.dense_ffn_hidden, cfg.ffn_hidden, cfg.first_dense_layers) == (10240, 1536, 1)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.num_shared_experts) == (64, 4, 1)
    assert cfg.router_score == "sigmoid" and cfg.router_bias and cfg.norm_topk_prob
    assert cfg.routed_scaling_factor == 1.8 and cfg.mtp_layers == 1 and cfg.vocab_size == 154880
    assert cfg.layer_kinds() == ("dense",) + ("routed",) * 46 and cfg.routed_layers == 47
    assert cfg.held_experts == (0, 64)
    with pytest.raises(ValueError, match="n_group"):
        G.glm4_moe_lite_config_from_hf(type("C", (), {**pub, "n_group": 8}))
    with pytest.raises(ValueError, match="v_head_dim"):
        tiny(v_head_dim=0)
    with pytest.raises(ValueError, match="head_dim 8 >= qk_nope"):
        tiny(head_dim=8)
    # a v narrower than q/k is padded to the one attention call's width since PR 42 (Kimi-Linear's)
    assert tiny(v_head_dim=8).head_dim == tiny().head_dim


def test_the_tree_has_a_dense_layer_then_routed_ones_and_the_mtp_module(case):
    cfg, _, _ = case
    params = jax.eval_shape(lambda: M.init_model_params(jax.random.PRNGKey(0), cfg))
    dense, routed = params["layers"][0], params["layers"][1]
    held = cfg.held_experts[1]
    assert "router" not in dense and dense["wi"]["kernel"].shape == (64, 2, 96)
    assert routed["router"]["kernel"].shape == (64, EXPERTS)  # the router's width is not cut
    assert routed["router"][ROUTER_BIAS].shape == (EXPERTS,)
    assert routed["wi"]["kernel"].shape == (held, 64, 64)
    assert routed["wo_mlp"]["kernel"].shape == (held, 32, 64)
    assert routed["shared"]["wi"]["kernel"].shape == (64, 2, 32)
    for layer in (dense, routed, params["mtp"]["block"]):
        assert layer["wq_b"]["kernel"].shape == (24, 4, 16) and layer["wkv_a"]["kernel"].shape == (64, 20)
        assert layer["wkv_b"]["kernel"].shape == (16, 4, 28) and "wqkv" not in layer
    assert params["mtp"]["eh_proj"]["kernel"].shape == (128, 64)
    assert set(params["mtp"]) == {"enorm", "hnorm", "eh_proj", "block", "norm"}


def test_loss_and_its_parts_match_the_reference(case):
    cfg, ((loss, parts), _), ((ref_loss, ref_parts), _) = case
    assert abs(float(loss - ref_loss)) <= F32_TOL
    assert abs(float(parts["loss_ce"] - ref_parts["ce"])) <= F32_TOL
    assert abs(float(parts["loss_mtp"] - ref_parts["mtp"])) <= F32_TOL
    # MTP's loss enters with lambda, and nothing else does
    assert float(loss) == pytest.approx(
        float(parts["loss_ce"]) + cfg.mtp_loss_weight * float(parts["loss_mtp"]), abs=1e-6)
    assert cfg.mtp_loss_weight == G.MTP_LOSS_WEIGHT == 0.3
    expected = {"loss_ce", "loss_mtp", "expert_load_max_over_mean", "router_bias_abs_max",
                M.ROUTER_COUNTS}
    if cfg.experts_held:
        expected |= {"expert_rows_held", "expert_rows_held_over_even", "expert_window_fallbacks"}
    assert set(parts) == expected
    assert expected - {M.ROUTER_COUNTS} <= set(
        telemetry.EXPERT_STEP_FIELDS + telemetry.SHARE_STEP_FIELDS)


def test_every_leafs_gradient_matches_the_reference(case):
    _, (_, grads), (_, ref_grads) = case
    errors = leaf_errors(grads, ref_grads)
    worst = max(errors, key=errors.get)
    assert errors[worst] <= F32_TOL, (worst, errors[worst])
    for part in ("wq_a", "wkv_b", "kv_a_norm", "router", "shared", "eh_proj", "hnorm"):
        assert any(part in k for k in errors), part


def test_the_counters_count_what_the_reference_picked(case):
    cfg, ((_, parts), _), ((_, ref_parts), _) = case
    picks = np.asarray(ref_parts["picks"])  # (batch, routed blocks, seq, k)
    blocks = cfg.routed_layers
    assert picks.shape == (BATCH, blocks, SEQ, 2) and blocks == 3
    counts = np.stack([np.bincount(picks[:, b].ravel(), minlength=EXPERTS) for b in range(blocks)])
    np.testing.assert_array_equal(np.asarray(parts[M.ROUTER_COUNTS]), counts)
    assert float(parts["expert_load_max_over_mean"]) == pytest.approx(
        (counts.max(axis=1) / counts.mean(axis=1)).max())
    if cfg.experts_held:
        first, held = cfg.held_experts
        rows = counts[:, first:first + held].sum()
        assert float(parts["expert_rows_held"]) == rows
        even = blocks * BATCH * SEQ * 2 * held / EXPERTS
        assert float(parts["expert_rows_held_over_even"]) == pytest.approx(rows / even)


# ----------------------------------------------------- latent attention alone
def _attention_alone(cfg, lp, y, positions):
    q, k, v = latent_qkv_projection(lp, y, positions, cfg, jnp.float32)
    out = core_attention(q, k, v, causal=True, impl="xla")
    return out.reshape(out.shape[0], out.shape[1], -1) @ lp["wo"]["kernel"]


def test_latent_attention_matches_the_references():
    """One rotated key shared by the heads, softmax scale 1/sqrt(nope + rope),
    the reference a block of queries at a time."""
    cfg = tiny()
    lp = M.init_layer_params(jax.random.PRNGKey(2), cfg.layer_config("dense"))
    lp = jax.tree.map(lambda a: a * 5.0 if a.ndim > 1 else a, lp)  # scores that matter
    y = jax.random.normal(jax.random.PRNGKey(3), (2, SEQ, 64))
    positions = jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ))
    REF.QUERY_BLOCK, whole = 8, REF.QUERY_BLOCK
    try:
        with jax.default_matmul_precision("highest"):
            ours = _attention_alone(cfg, lp, y, positions)
            theirs = jnp.stack([REF._latent_attention(lp, y[b], positions[b], fields_of(cfg))
                                for b in range(2)])
    finally:
        REF.QUERY_BLOCK = whole
    scale = float(jnp.max(jnp.abs(theirs)))
    assert float(jnp.max(jnp.abs(ours - theirs))) <= 1e-5 * scale

    # what it is held against: a key rotated a head at its own dims (not
    # shared), and the scale of the nope dims alone
    q, k, v = latent_qkv_projection(lp, y, positions, cfg, jnp.float32)
    assert float(jnp.max(jnp.abs(k[:, :, 0, 12:] - k[:, :, 3, 12:]))) == 0.0
    assert float(jnp.max(jnp.abs(k[:, :, 0, :12] - k[:, :, 3, :12]))) > 0.0
    wrong_scale = core_attention(q, k, v, causal=True, impl="xla", sm_scale=12 ** -0.5)
    wrong = wrong_scale.reshape(2, SEQ, -1) @ lp["wo"]["kernel"]
    assert float(jnp.max(jnp.abs(wrong - theirs))) > 1e-3 * scale


# ------------------------------------------------------------ the share test
def test_the_eight_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """ROADMAP R4: a chip's share is a configuration. Eight programs, each
    holding ONE of the eight experts and routing over all eight, give routed
    parts that add up, with the shared expert counted once, to what the uncut
    reference gives for the whole layer."""
    whole = tiny()
    params = params_of(whole)
    lp = params["layers"][1]
    y = jax.random.normal(jax.random.PRNGKey(7), (BATCH, SEQ, 64))
    fields = fields_of(whole)
    with jax.default_matmul_precision("highest"):
        uncut = jnp.stack([REF._swiglu(lp["shared"], y[b]) + REF._routed(lp, y[b], fields)[0]
                           for b in range(BATCH)])
        shared = dense_mlp(lp["shared"], y, whole, jnp.float32)
        parts, rows = [], 0.0
        for first in range(EXPERTS):
            out, aux = moe.moe_ffn(
                y, lp["router"]["kernel"], lp["wi"]["kernel"][first:first + 1],
                lp["wo_mlp"]["kernel"][first:first + 1], experts_per_token=2, norm_topk_prob=True,
                dtype=jnp.float32, score="sigmoid", bias=lp["router"][ROUTER_BIAS],
                scale=whole.routed_scaling_factor, held=(first, 1))
            parts.append(out)
            rows += float(aux["rows_held"])
    total = shared + sum(parts)
    assert float(jnp.max(jnp.abs(total - uncut))) <= 1e-5 * float(jnp.max(jnp.abs(uncut)))
    assert rows == BATCH * SEQ * 2  # every assignment is in exactly one share
    # a share alone is not the layer, nor is the layer without the shared expert
    assert float(jnp.max(jnp.abs(shared + parts[0] - uncut))) > 1e-2 * float(jnp.max(jnp.abs(uncut)))
    assert float(jnp.max(jnp.abs(sum(parts) - uncut))) > 1e-2 * float(jnp.max(jnp.abs(uncut)))


def test_a_held_share_sends_no_gradient_to_rows_it_does_not_hold():
    """`grouped_matmul(first_group=)`: the other groups' rows come back zero
    and send none back (off a TPU through zero kernels; on one the megablox
    kernels skip them: tests/ops/test_tpu_compile_share.py)."""
    rows = jax.random.normal(jax.random.PRNGKey(0), (12, 4))
    kernels = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 3))
    sizes = jnp.array([3, 2, 4, 3], jnp.int32)  # groups 1 and 2 are held: rows 3 to 9

    def f(rows, kernels):
        return moe.grouped_matmul(rows, kernels, sizes, first_group=1)

    out = f(rows, kernels)
    np.testing.assert_allclose(out[3:5], rows[3:5] @ kernels[0], rtol=1e-5)
    np.testing.assert_allclose(out[5:9], rows[5:9] @ kernels[1], rtol=1e-5)
    assert not np.any(np.asarray(out[:3])) and not np.any(np.asarray(out[9:]))
    d_rows, d_kernels = jax.grad(lambda r, k: jnp.sum(f(r, k) ** 2), argnums=(0, 1))(rows, kernels)
    assert not np.any(np.asarray(d_rows[:3])) and not np.any(np.asarray(d_rows[9:]))
    assert np.all(np.any(np.asarray(d_rows[3:9]), axis=1)) and d_kernels.shape == kernels.shape


# ------------------------------------------------------------------- MTP
def test_mtps_labels_are_shifted_by_two():
    """Position i of the MTP pass is scored against t_{i+2} = labels[i + 1],
    over the positions that have one; a shift by one (the main labels) or by
    three is another number."""
    cfg = tiny()
    params, batch = params_of(cfg), batch_of()
    _, hidden, _ = M._forward(params, batch["tokens"], batch["positions"], cfg)
    logits2, _ = M.mtp_logits(params, hidden, batch, cfg)
    nll = np.asarray(_token_nll(logits2, jnp.roll(batch["labels"], -1, axis=1)))
    by_two = nll[:, :-2].mean()  # positions 0 .. S-3: labels[i+1] exists and counts
    (_, parts), _ = program(cfg, params, batch)
    assert float(parts["loss_mtp"]) == pytest.approx(by_two, rel=1e-6)
    by_one = np.asarray(_token_nll(logits2, batch["labels"]))[:, :-2].mean()
    assert abs(by_one - by_two) > 1e-3
    # t_{i+2} by the tokens themselves
    np.testing.assert_array_equal(np.asarray(jnp.roll(batch["labels"], -1, axis=1))[:, :-2],
                                  np.asarray(batch["tokens"])[:, 2:])


# --------------------------------------------- the controls in the next precision
def test_a_bf16_router_fails_the_tolerance(monkeypatch):
    cfg = tiny()
    params, batch = params_of(cfg), batch_of()
    (_, _), ref_grads = reference(cfg, params, batch)
    monkeypatch.setattr(moe, "router_logits", lambda y, kernel: (
        y.astype(jnp.bfloat16) @ kernel.astype(jnp.bfloat16)).astype(jnp.float32))
    _, grads = program(cfg, params, batch)
    assert max(leaf_errors(grads, ref_grads).values()) > 100 * F32_TOL


# ------------------------------------------- the trainer's paths and layouts
def _loss_and_grads(cfg, hp, params, batch):
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    placed = jax.device_put(params, model.shardings())
    (loss, parts), grads = jax.jit(jax.value_and_grad(model.loss_parts_fn, has_aux=True))(
        placed, model.shard_batch(batch))
    return float(loss), jax.device_get(parts), jax.device_get(grads)


@pytest.fixture(scope="module")
def one_device():
    cfg = tiny(experts_held=4, experts_held_start=2)
    params, batch = params_of(cfg), batch_of()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    return cfg, params, batch, _loss_and_grads(cfg, hp, params, batch)


@pytest.mark.parametrize("name,hp_kw", [
    ("remat", dict(world=1, checkpoint=1)),
    ("remat_no_scan", dict(world=1, checkpoint=1, scan_layers=False)),
    ("dp2_zero2", dict(world=2, default_dp_type="zero2")),
    ("dp2_zero3", dict(world=2, sdp=1)),
    ("dp4_zero2_remat", dict(world=4, default_dp_type="zero2", checkpoint=1)),
])
def test_remat_scan_and_dp_layouts_give_one_devices_gradients_and_counts(one_device, name, hp_kw):
    """Recomputation, the unrolled path and dp with ZeRO-2/3 change how the
    step is run, not what it computes; the routers' counts are the GLOBAL
    batch's under dp."""
    cfg, params, batch, (loss, parts, grads) = one_device
    hp_kw = dict(hp_kw)
    hp = HybridParallelConfig.uniform(hp_kw.pop("world"), cfg.num_layers, global_bsz=BATCH, **hp_kw)
    got_loss, got_parts, got = _loss_and_grads(cfg, hp, params, batch)
    assert abs(got_loss - loss) <= F32_TOL
    assert max(leaf_errors(got, grads).values()) <= F32_TOL
    np.testing.assert_array_equal(got_parts[M.ROUTER_COUNTS], parts[M.ROUTER_COUNTS])
    assert float(got_parts["expert_rows_held"]) == float(parts["expert_rows_held"])


def test_the_stack_runs_as_a_dense_run_then_a_routed_one():
    """Runs split on the kind of layer as on the layout (ROADMAP R5), and
    keep numbering `gt.layers.r<k>`."""
    from galvatron_tpu.config.strategy import layer_runs, model_layer_kinds
    from galvatron_tpu.obs import flops as F

    cfg = tiny(num_layers=4)
    hp = HybridParallelConfig.uniform(1, 4, global_bsz=BATCH, checkpoint=1)
    runs = layer_runs(hp, model_layer_kinds(cfg))
    assert [(r.start, r.stop) for r in runs] == [(0, 1), (1, 4)]
    assert [(r.start, r.stop) for r in layer_runs(hp)] == [(0, 4)]
    assert model_layer_kinds(dataclasses.replace(cfg, first_dense_layers=0)) is None
    model = construct_hybrid_parallel_model(cfg, hp, jax.devices()[:1])
    text = jax.jit(model.loss_fn).lower(model.abstract_params(), batch_of()).as_text(debug_info=True)
    assert "gt.layers.r0" in text and "gt.layers.r1" in text and "gt.layers.r2" not in text
    for scope in ("gt.attn.latent", "gt.moe.shared", "gt.moe.router", "gt.mtp", "gt.head_loss"):
        assert scope in text, scope
    per_run = F.run_fwd_flops(cfg, hp)
    assert len(per_run) == 3 and sum(per_run) == pytest.approx(F.model_fwd_flops(cfg, BATCH))


# ----------------------------------------------------------------- refusals
def _layers(n, **kw):
    return [LayerStrategy(**kw)] * n


REFUSED = {
    "tp2": (dict(world_size=2, layers=_layers(3, tp=2)), "latent attention"),
    "ulysses": (dict(world_size=2, layers=_layers(3, tp=2, sp=1)), "latent attention"),
    "cp2": (dict(world_size=2, layers=_layers(3, cp=2)), "latent attention"),
    "vocab_tp2": (dict(world_size=2, layers=_layers(3), vocab_tp=2), "vocab_tp"),
    "pp3_gpipe": (dict(world_size=3, pp=3, layers=_layers(3), chunks=3), "multi-token-prediction"),
    "tp_comm_overlap": (dict(world_size=2, layers=_layers(3), tp_comm_mode="overlap"),
                        "latent attention"),
    "quantized_grads": (dict(world_size=2, layers=_layers(3, grad_comm_dtype="int8")), "quantized"),
}


@pytest.mark.parametrize("layout", sorted(REFUSED))
def test_a_layout_with_no_expert_or_latent_form_is_refused_by_name(layout):
    cfg = tiny()
    kw, named = REFUSED[layout]
    hp = HybridParallelConfig(**{"pp": 1, "global_bsz": 6, **kw})
    report = strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train")
    assert "GLS018" in {d.code for d in report.errors}
    with pytest.raises(DiagnosticError) as e:
        construct_hybrid_parallel_model(cfg, hp, jax.devices()[:hp.world_size])
    assert "GLS018" in str(e.value) and named in str(e.value)


@pytest.mark.parametrize("kwargs,named", [
    (dict(mode="serve"), "compressed k/v"), (dict(mode="train", autotune="observe"), "full-rank")],
    ids=["serve", "autotune"])
def test_serve_and_the_autotuner_refuse_it_and_name_latent_attention(kwargs, named):
    cfg = tiny()
    hp = HybridParallelConfig.uniform(1, cfg.num_layers, global_bsz=BATCH)
    errors = strategy_lint.lint_hp(hp, model_cfg=cfg, **kwargs).errors
    assert any(d.code == "GLS018" and named in d.message for d in errors)
    assert strategy_lint.lint_hp(hp, model_cfg=cfg, mode="train").ok
    # latent attention alone (no experts) is refused the same way
    dense = dataclasses.replace(cfg, num_experts=0, experts_per_token=0, mtp_layers=0)
    assert unsupported_reason(dense, hp, "serve") is not None
    assert unsupported_reason(dense, hp) is None
