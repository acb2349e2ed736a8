"""The EvaByte family (models/evabyte.py; the mixer models/parts/eva.py; the head of several predictions in
models/parts/embed_head.py) against the plain reference benchmarks/references/evabyte_lm.py on seeded random
weights at a small size: logits of all eight heads, loss and gradients; the shifted targets and their mask; what
`pred_heads` = 1 leaves as it was; scanned against unrolled layers; the refusals."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import cells
from galvatron_tpu.models import base as M
from galvatron_tpu.models.evabyte import evabyte_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.parts import MIXERS, embed_head, eva as part, unsupported_reason
from galvatron_tpu.ops import eva_attention as eva_ops

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SEQ = 160  # two windows of 64 and a half: 20 chunks of 8
# float32 compute on the CPU at `highest` precision on both sides: what separates the two is the ORDER of float32
# sums (a window at a time against every key at once), 1e-6 of a logit of order 1. The limits are some ten times
# that, and a thousandth of what scores rounded to bfloat16 move (the control below)
LOGITS_ATOL, LOSS_ATOL, LEAF_RTOL = 2e-5, 2e-6, 3e-5


@pytest.fixture(scope="module")
def ref():
    return cells.load_module(REPO, "benchmarks/references/evabyte_lm.py")


def tiny(**over):
    return evabyte_config(**{**dict(num_layers=2, hidden_size=64, num_heads=4, num_kv_heads=4, ffn_hidden=96,
                                    eva_window=64, eva_chunk=8, max_seq_len=SEQ, compute_dtype=jnp.float32), **over})


def fields_of(cfg):
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    # every leaf off its initial value: the norms' w = 0 would hide the unit offset, phi's clamp its gradient
    params = jax.tree.map(lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape), params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, 320)
    batch = dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(SEQ), (2, SEQ)),
                 labels=jnp.roll(tokens, -1, 1), loss_mask=jnp.ones((2, SEQ)).at[:, -1].set(0.0))
    return cfg, params, batch


def program(cfg, params, batch):
    with jax.default_matmul_precision("highest"):
        (loss, parts), grads = jax.jit(jax.value_and_grad(
            lambda p: M.lm_loss_fn(p, batch, cfg, with_parts=True), has_aux=True))(params)
        logits = jax.jit(lambda p: M.model_forward(p, batch["tokens"], batch["positions"], cfg))(params)
    return loss, parts, grads, logits


def relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_the_program_is_the_reference_on_logits_loss_and_every_gradient(model, ref):
    cfg, params, batch = model
    loss, parts, grads, logits = program(cfg, params, batch)
    want_loss, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, batch, fields_of(cfg))))(params)
    want_logits = ref.logits(params, batch["tokens"], fields_of(cfg))  # (B, S, 8, 320)
    assert want_logits.shape == (2, SEQ, 8, 320) and logits.shape == (2, SEQ, 8 * 320)
    np.testing.assert_allclose(np.asarray(logits).reshape(want_logits.shape), np.asarray(want_logits), atol=LOGITS_ATOL)
    assert abs(float(loss) - float(want_loss)) < LOSS_ATOL
    errors = jax.tree.map(relative, grads, want_grads)
    worst = max(jax.tree_util.tree_leaves_with_path(errors), key=lambda kv: kv[1])
    assert worst[1] < LEAF_RTOL, jax.tree_util.keystr(worst[0])
    assert set(errors["layers"][0]["eva"]) == {"phi", "mu"}
    assert 0.0 < float(parts["eva_pooled_mass"]) < 1.0 and float(parts["loss_ce"]) == float(loss)


def test_scores_in_bfloat16_fail_the_limits(model, ref, monkeypatch):
    """The control: the same program with the aggregation's scores rounded to bfloat16 before the softmax."""
    cfg, params, batch = model
    monkeypatch.setattr(eva_ops, "_SCORES", jnp.bfloat16)
    loss, _, grads, logits = program(cfg, params, batch)
    want_logits = ref.logits(params, batch["tokens"], fields_of(cfg))
    _, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, batch, fields_of(cfg))))(params)
    assert float(jnp.max(jnp.abs(logits.reshape(want_logits.shape) - want_logits))) > 20 * LOGITS_ATOL
    assert max(jax.tree.leaves(jax.tree.map(relative, grads, want_grads))) > 20 * LEAF_RTOL


@pytest.mark.parametrize("switch", ["far_context", "mu", "own_chunks", "head_shift"])
def test_each_piece_of_the_mathematics_moves_the_reference(model, ref, switch):
    """A reference with one piece changed (no pooled keys; no mu; the own window's chunks seen too; every head on
    byte t + 1) is NOT what the program computes: each piece is in the program, and the limits tell."""
    cfg, params, batch = model
    loss, _, _, logits = program(cfg, params, batch)
    other = ref.logits(params, batch["tokens"], fields_of(cfg), switch_off=(switch,))
    other_loss = float(ref.loss(params, batch, fields_of(cfg), switch_off=(switch,)))
    if switch == "head_shift":  # the logits are the same; the targets are not
        assert abs(float(loss) - other_loss) > 1e-3
    else:
        assert float(jnp.max(jnp.abs(logits.reshape(other.shape) - other))) > 10 * LOGITS_ATOL
        assert abs(float(loss) - other_loss) > 5 * LOSS_ATOL


def by_hand(logits, labels, mask, heads):
    """Head i at position t on `labels[t + i]`, a target past the end or masked left out; the mean of the heads' means."""
    b, s, _ = logits.shape
    logits = np.asarray(logits, np.float64).reshape(b, s, heads, -1)
    means = []
    for i in range(heads):
        total, count = 0.0, 0.0
        for row in range(b):
            for t in range(s - i):
                if mask is not None and not mask[row, t + i]:
                    continue
                z = logits[row, t, i]
                total += np.log(np.exp(z - z.max()).sum()) + z.max() - z[labels[row, t + i]]
                count += 1
        means.append(total / max(count, 1.0))
    return float(np.mean(means))


@pytest.mark.parametrize("heads,masked", [(8, True), (8, False), (3, True), (1, True), (1, False)])
def test_the_shifted_targets_and_their_mask_at_the_tail(heads, masked):
    logits = jax.random.normal(jax.random.PRNGKey(heads), (2, 12, heads * 7)) * 2.0
    labels = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, 7))
    mask = np.ones((2, 12), np.float32)
    mask[:, -1] = 0.0  # the last position's label is the roll's wrap-around
    mask[1, 4] = 0.0
    got = embed_head.next_tokens_cross_entropy(logits, jnp.asarray(labels), jnp.asarray(mask) if masked else None, heads)
    assert float(got) == pytest.approx(by_hand(logits, labels, mask if masked else None, heads), abs=2e-6)
    if heads == 1:  # one head is the ordinary cross entropy
        plain = embed_head.vocab_parallel_cross_entropy(logits, jnp.asarray(labels), jnp.asarray(mask) if masked else None)
        assert float(got) == pytest.approx(float(plain), abs=1e-6)
    # the tail: head i has no target at the last i positions, so what stands there moves nothing
    spoiled = logits.reshape(2, 12, heads, 7).at[:, -1, 1:].set(50.0).reshape(logits.shape) if heads > 1 else logits
    again = embed_head.next_tokens_cross_entropy(spoiled, jnp.asarray(labels), jnp.asarray(mask) if masked else None, heads)
    assert float(again) == float(got)


def test_one_prediction_a_position_leaves_a_llama_models_loss_bit_identical():
    cfg = llama_config("llama-7b", num_layers=2, hidden_size=64, num_heads=4, ffn_hidden=96, vocab_size=128,
                       max_seq_len=32, compute_dtype=jnp.float32)
    assert cfg.pred_heads == 1
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    head = params["embed"]["wte"] if cfg.tie_embeddings else params["lm_head"]["kernel"]
    assert 128 in head.shape and head.size == 64 * 128  # vocab_size columns, not a multiple of them
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    batch = dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(32), (2, 32)), labels=jnp.roll(tokens, -1, 1),
                 loss_mask=jnp.ones((2, 32)).at[:, -1].set(0.0))
    loss = M.lm_loss_fn(params, batch, cfg)
    logits = M.model_forward(params, tokens, batch["positions"], cfg)
    assert float(loss) == float(embed_head.vocab_parallel_cross_entropy(logits, batch["labels"], batch["loss_mask"]))
    with pytest.raises(ValueError, match="pred_heads=8"):
        llama_config("llama-7b", num_layers=2, hidden_size=64, num_heads=4, vocab_size=128, pred_heads=8,
                     tie_embeddings=True)
    with pytest.raises(ValueError, match="pred_heads=0"):
        tiny(pred_heads=0)


def test_scanned_layers_are_unrolled_layers(model):
    cfg, params, batch = model
    x = M.embed_tokens(params["embed"], batch["tokens"], batch["positions"], cfg)
    with jax.default_matmul_precision("highest"):
        scanned, aux_s = M.run_layers(params, x, batch["positions"], cfg, scan=True)
        unrolled, aux_u = M.run_layers(params, x, batch["positions"], cfg, scan=False)
    np.testing.assert_allclose(np.asarray(scanned), np.asarray(unrolled), atol=1e-5)
    assert float(M._fold_aux(aux_s)["eva_pooled_mass"]) == pytest.approx(float(M._fold_aux(aux_u)["eva_pooled_mass"]),
                                                                       abs=1e-6)
    assert [np.shape(a["eva_pooled_mass"]) for a in aux_s] == [(2,)] and len(aux_u) == 2  # a run's, a layer's


def test_the_family_is_the_published_one_and_starts_as_the_equations_say():
    cfg = evabyte_config()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.ffn_hidden,
            cfg.vocab_size, cfg.max_seq_len) == (32, 4096, 32, 32, 128, 11008, 320, 32768)
    assert (cfg.eva_window, cfg.eva_chunk, cfg.pred_heads, cfg.rope_theta, cfg.init_std) == (2048, 16, 8, 1e5, 0.01275)
    assert cfg.norm_zero_centered and not cfg.tie_embeddings and not (cfg.qkv_bias or cfg.out_bias or cfg.mlp_bias)
    assert cfg.mixers() == ("eva",) * 32 and cfg.layer_kinds() == ("eva.dense",) * 32 and cfg.layer_aux
    small = tiny()
    params = M.init_model_params(jax.random.PRNGKey(3), small)
    for lp in params["layers"]:
        for leaf in (lp["eva"]["phi"], lp["eva"]["mu"]):
            assert leaf.shape == (4, 16) and leaf.dtype == jnp.float32
            assert float(jnp.max(jnp.abs(leaf))) <= 16 ** -0.5 and float(jnp.std(leaf)) > 0.5 * 16 ** -0.5 * 0.8
        assert float(jnp.max(jnp.abs(lp["ln1"]["scale"]))) == 0.0  # 1 + w, w from 0
        assert "bias" not in lp["wqkv"] and "bias" not in lp["wo"]
    assert params["lm_head"]["kernel"].shape == (64, 8 * 320)
    assert float(jnp.std(params["lm_head"]["kernel"])) == pytest.approx(0.01275, rel=0.05)


@pytest.mark.parametrize("over,named", [
    (dict(eva_window=60), "eva_window a multiple of eva_chunk"),
    (dict(max_seq_len=100), "a sequence of whole chunks"),
    (dict(num_kv_heads=2), "as many key heads as query heads"),
    (dict(position_type="none"), "plain rope"),
])
def test_a_config_the_mixer_has_no_form_of_is_refused_by_name(over, named):
    with pytest.raises(ValueError, match=named):
        tiny(**over)


@pytest.mark.parametrize("asker", ["serve", "search", "profile"])
def test_the_tools_refuse_the_family_by_name(asker):
    reason = unsupported_reason(tiny(), asker=asker)
    assert reason is not None and ("EVA attention" in reason)
    assert unsupported_reason(tiny(), autotune="observe").startswith("autotune=observe: the re-search would price an "
                                                                     "EVA attention layer as full attention")
    assert MIXERS["eva"].decode is None  # no fall-through to plain attention's decode
