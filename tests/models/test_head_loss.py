"""The head and its loss have a written backward (models/parts/embed_head._head_matmul,
_token_nll: PR 30). Held here, on the CPU at tiny widths: the rule against
`jax.grad` of the plain form it replaced, which this file keeps as the oracle;
that sharding, accumulation and GPipe reach the same numbers through it; and
that the step keeps one cast of the head kernel while forward-only callers
(`eval_loss`, serve's decode step) lower to the text of the plain form."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.parts import embed_head
from galvatron_tpu.models.parts.embed_head import _times_kernel, vocab_parallel_cross_entropy
from galvatron_tpu.models.bert import bert_config
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler
from galvatron_tpu.serve.engine import make_decode_step
from galvatron_tpu.serve.kv_cache import KVCacheConfig, init_kv_cache

B, S, H, V = 8, 32, 64, 256
F32, BF16 = jnp.float32, jnp.bfloat16


# ---------------------------------------------------------------- the oracle
def plain_token_nll(logits, labels):
    """The cross entropy a token as `jax.grad` differentiates it, the row
    maximum included: what `vocab_parallel_cross_entropy` was before PR 30."""
    logits32 = logits.astype(jnp.float32)
    m = jnp.max(logits32, axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(logits32 - m), axis=-1)) + m[..., 0]
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    label_logit = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits32, 0.0), axis=-1)
    return lse - label_logit


def plain_cross_entropy(logits, labels, loss_mask=None):
    losses = plain_token_nll(logits, labels)
    if loss_mask is None:
        return jnp.mean(losses)
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(losses * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


@pytest.fixture
def plain_form(monkeypatch):
    """Inside: models/parts/embed_head differentiates head and loss operation by operation."""
    def on():
        monkeypatch.setattr(embed_head, "_head_matmul", _times_kernel)
        monkeypatch.setattr(embed_head, "_token_nll", plain_token_nll)
    return on


def assert_same(got, want, dtype):
    """(loss, gradients) of the rule against the oracle's; a leaf by the
    norm of the difference over the norm of the oracle's. float32: 1e-6.
    bf16: the loss equal (the forward is the same operations), a gradient
    within two rounding steps of bf16 (2^-7): the term the rule drops,
    `g (1 - sum p) [l = max]` of order 1e-7, tips a rounding of dlogits here
    and there, and the bf16 layers behind the head carry that on."""
    (loss, grads), (want_loss, want_grads) = got, want
    if dtype == BF16:
        assert float(loss) == float(want_loss)
    else:
        assert abs(float(loss) - float(want_loss)) <= 1e-6 * abs(float(want_loss))
    rel = 2.0 ** -7 if dtype == BF16 else 1e-6
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        off, scale = float(np.linalg.norm(g - w)), float(np.linalg.norm(w))
        assert off <= rel * scale + 1e-12, (jax.tree_util.keystr(path), off, scale)


# ----------------------------------------------------- the cross entropy alone
@pytest.mark.parametrize("vocab", [V, 50257], ids=["V256", "V50257"])
@pytest.mark.parametrize("mask", ["none", "some", "all_zero"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
def test_cross_entropy_rule_against_autodiff(dtype, mask, vocab):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    logits = (3.0 * jax.random.normal(k1, (2, 16, vocab))).astype(dtype)
    # a row whose maximum is shared: where autodiff spreads the maximum's gradient
    logits = logits.at[0, 0, :2].set(jnp.max(logits[0, 0]))
    labels = jax.random.randint(k2, (2, 16), 0, vocab)
    loss_mask = {"none": None, "some": (jax.random.uniform(k3, (2, 16)) > 0.3).astype(F32),
                 "all_zero": jnp.zeros((2, 16), F32)}[mask]
    got = jax.value_and_grad(vocab_parallel_cross_entropy)(logits, labels, loss_mask)
    want = jax.value_and_grad(plain_cross_entropy)(logits, labels, loss_mask)
    assert got[1].dtype == logits.dtype
    assert_same(got, want, dtype)
    if mask == "all_zero":
        assert float(got[0]) == 0.0 and not np.asarray(got[1], np.float32).any()


# ------------------------------------------------ head and loss in the model
def tiny(family, dtype, vocab=V):
    kw = dict(num_layers=2, hidden_size=H, num_heads=4, vocab_size=vocab, max_seq_len=S,
              compute_dtype=dtype)
    if family == "gpt_tied":
        return gpt_config("gpt-0.3b", **kw)
    if family == "llama_untied":
        return llama_config("llama-0.3b", ffn_hidden=128, **kw)
    if family == "bert_mlm":  # tied table, a bias after the matmul
        return bert_config("bert-base", ffn_hidden=128, **kw)
    raise KeyError(family)


def lm_batch(vocab=V, mask=False, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, vocab)
    batch = dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(S), (B, S)),
                 labels=jnp.roll(tokens, -1, 1))
    if mask:
        batch["loss_mask"] = (jax.random.uniform(jax.random.PRNGKey(seed + 1), (B, S)) > 0.25).astype(F32)
    return batch


@pytest.mark.parametrize("mask", [False, True], ids=["no_mask", "loss_mask"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
@pytest.mark.parametrize("family,vocab", [("llama_untied", V), ("gpt_tied", V), ("bert_mlm", V),
                                          ("gpt_tied", 50257)],
                         ids=["llama_untied", "gpt_tied", "bert_mlm_bias", "gpt_tied_V50257"])
def test_every_gradient_of_the_model_against_autodiff(family, vocab, dtype, mask, plain_form):
    """Loss and the gradient of every leaf (the head kernel or the tied
    table, the final norm, the mlm head's bias, the layers behind them)."""
    cfg = tiny(family, dtype, vocab)
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    batch = lm_batch(vocab, mask)
    loss = lambda p: M.lm_loss_fn(p, batch, cfg)  # noqa: E731
    got = jax.jit(jax.value_and_grad(loss))(params)
    plain_form()
    want = jax.jit(jax.value_and_grad(loss))(params)
    assert_same(got, want, dtype)


def adam():
    return get_optimizer_and_scheduler(OptimizerArgs(lr=3e-3, warmup_steps=1, total_steps=20))[0]


def train_losses(cfg, hp, devices, steps=3):
    m = construct_hybrid_parallel_model(cfg, hp, devices)
    params = m.init_params(jax.random.PRNGKey(0))
    tx = adam()
    opt = m.init_opt_state(tx, params)
    step = m.make_train_step(tx)
    batch = m.shard_batch(lm_batch(mask=True))
    losses = []
    for _ in range(steps):
        params, opt, mets = step(params, opt, batch)
        losses.append(float(mets["loss"]))
    return losses


LAYOUTS = {
    "vocab_tp2_dp2": dict(world=4, tp=2, vocab_tp=2),
    "chunks2": dict(world=1, chunks=2),
    "gpipe_pp2_chunks2": dict(world=2, pp=2, chunks=2),
}


@pytest.mark.parametrize("name", list(LAYOUTS))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bf16"])
def test_a_layout_trains_through_the_rule(name, dtype, devices8, plain_form):
    """Three Adam steps through the rule: a vocabulary split over tp (the
    statistics' psums are GSPMD's), gradient accumulation, and GPipe's head
    (parallel/pipeline.py calls the same functions). In float32 against one
    device, one chunk; in both dtypes against the same layout under the plain
    form (in bf16 two layouts differ by more than the two forms do: 1.3e-4
    against 1e-6 here)."""
    kw = dict(LAYOUTS[name])
    world = kw.pop("world")
    mixed = "bf16" if dtype == BF16 else "fp32"
    cfg = tiny("llama_untied", dtype)
    hp = HybridParallelConfig.uniform(world, 2, global_bsz=B, mixed_precision=mixed, **kw)
    losses = train_losses(cfg, hp, devices8[:world])
    assert losses[-1] < losses[0]
    if dtype == F32:
        one = HybridParallelConfig.uniform(1, 2, global_bsz=B, mixed_precision=mixed)
        want = train_losses(cfg, one, devices8[:1])
        assert max(abs(a - b) for a, b in zip(losses, want)) < 5e-6, (losses, want)
    plain_form()
    want = train_losses(cfg, hp, devices8[:world])
    assert max(abs(a - b) for a, b in zip(losses, want)) < 5e-6, (losses, want)


# ------------------------------------------------------------- what it lowers to
W = 320  # a vocabulary no other leaf's shape holds


def step_text(cfg, devices8):
    hp = HybridParallelConfig.uniform(1, 2, global_bsz=B, mixed_precision="bf16")
    m = construct_hybrid_parallel_model(cfg, hp, devices8[:1])
    params = m.abstract_params()
    tx = adam()
    return m.make_train_step(tx).lower(
        params, jax.eval_shape(tx.init, params), lm_batch(cfg.vocab_size)).as_text()


@pytest.mark.parametrize("family", ["llama_untied", "gpt_tied"])
def test_the_step_holds_one_cast_of_the_head_kernel(family, devices8):
    """The differentiated step casts the head kernel once, puts the copy
    behind a barrier, and its three matmuls (forward, input gradient, kernel
    gradient) read the barrier's result."""
    text = step_text(tiny(family, BF16, W), devices8)
    shape = "%dx%d" % ((W, H) if family == "gpt_tied" else (H, W))
    casts = re.findall(r"stablehlo\.convert %%\S+ : \(tensor<%sxf32>\) -> tensor<%sxbf16>" % (shape, shape), text)
    # the tied table is cast once more, by the embedding's lookup
    assert len(casts) == (2 if family == "gpt_tied" else 1), casts
    kept = re.findall(r"(%%\S+) = stablehlo\.optimization_barrier %%\S+ : tensor<%sxbf16>" % shape, text)
    assert len(kept) == 1
    readers = [line for line in text.splitlines()
               if re.search(r"%s\b" % re.escape(kept[0]), line.partition(" = ")[2])]
    via_transpose = [re.match(r"\s*(%\S+) = stablehlo\.transpose", line).group(1)
                     for line in readers if "stablehlo.transpose" in line]
    matmuls = [line for line in text.splitlines() if "stablehlo.dot_general" in line and any(
        re.search(r"%s\b" % re.escape(name), line.partition(" = ")[2]) for name in [kept[0]] + via_transpose)]
    assert len(matmuls) == 2, matmuls  # forward and input gradient; the third makes the kernel's gradient
    assert len(re.findall(r"stablehlo\.dot_general.*-> tensor<%sxbf16>" % shape, text)) == 1


def test_a_kernel_stored_in_the_compute_dtype_is_read_as_it_is(devices8):
    """The same rule with nothing to cast (float32 compute here, ZeRO-2's
    compute copy on a mesh): the barrier holds the stored kernel and no
    copy of it is made."""
    text = step_text(tiny("llama_untied", F32, W), devices8)
    assert len(re.findall(r"stablehlo\.optimization_barrier %%\S+ : tensor<%dx%dxf32>" % (H, W), text)) == 1
    assert not re.findall(r"stablehlo\.convert %%\S+ : \(tensor<%dx%dx" % (H, W), text)


@pytest.mark.parametrize("family", ["llama_untied", "gpt_tied"])
def test_forward_only_callers_lower_to_the_plain_forms_text(family, devices8, plain_form):
    """`eval_loss` and serve's decode step (one token a sequence: a kernel
    copy would double the bytes of a bandwidth-bound step) are not
    differentiated, and lower to the text of the plain form: no barrier, no
    copy."""
    cfg = tiny(family, BF16)
    hp = HybridParallelConfig.uniform(1, 2, global_bsz=B, mixed_precision="bf16")
    m = construct_hybrid_parallel_model(cfg, hp, devices8[:1])
    params = m.abstract_params()
    kv = KVCacheConfig(max_slots=4, page_size=16, max_pages=2)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, kv))
    slots = jax.ShapeDtypeStruct((4,), jnp.int32)

    def texts():
        decode = make_decode_step(cfg, None, None, kv, pages=2)
        return (jax.jit(m.eval_loss).lower(params, lm_batch(mask=True)).as_text(),
                decode.lower(params, cache, slots, jax.ShapeDtypeStruct((4,), jnp.bool_),
                             jax.random.PRNGKey(0)).as_text())

    got = texts()
    plain_form()
    assert got == texts()
    # (a GELU's MLP holds a barrier of its own in every caller, models/parts/mlp._matmul_of_written_out:
    # on the activation, (.., ffn), never on a kernel)
    barriers = [line for t in got for line in t.splitlines() if "optimization_barrier" in line]
    assert all(re.search(r": tensor<(\d+x)+%dxbf16>$" % cfg.ffn_hidden, b) for b in barriers), barriers
    assert bool(barriers) == (family == "gpt_tied")
