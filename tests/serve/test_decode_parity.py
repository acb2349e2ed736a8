"""Incremental decode == full-forward recompute, at every step, on the
strategy-sharded cache; and the train-checkpoint -> serve-layout restore.

Tier-1 carries one fast layout (tp=2) plus the restore acceptance; the full
tp/dp/zero3 cross-product is `slow`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.embed_head import embed_tokens, lm_logits
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.serve.engine import ServeEngine
from galvatron_tpu.serve.kv_cache import KVCacheConfig, bucket_pages

pytestmark = [pytest.mark.serve]

_ATOL = 2e-5  # fp32 XLA:CPU scan-vs-unrolled reassociation slack


def tiny_cfg():
    return TransformerConfig(
        hidden_size=32, num_heads=4, num_layers=2, vocab_size=64,
        max_seq_len=32, compute_dtype=jnp.float32)


def layout_hp(cfg, kind):
    mk = lambda **kw: HybridParallelConfig.uniform(
        8, cfg.num_layers, global_bsz=8, **kw)
    return {
        "tp2": mk(tp=2),
        "tp4": mk(tp=4),
        "dp8": mk(),
        "zero3": mk(sdp=1),
        "tp2_zero3": mk(tp=2, sdp=1),
        # the table split over the vocabulary: prefill (1, ctx) and decode
        # (slots, 1) go through models/parts/embed_head.vocab_parallel_lookup
        "tp2_vtp2": mk(tp=2, vocab_tp=2),
    }[kind]


def full_logits(params, cfg, tokens):
    """Reference: the training forward over the whole sequence so far."""
    x = jnp.asarray(tokens, jnp.int32)[None]
    pos = jnp.arange(len(tokens), dtype=jnp.int32)[None]
    h = embed_tokens(params["embed"], x, pos, cfg)
    h = M.run_layers(params, h, pos, cfg)
    return np.asarray(jax.device_get(lm_logits(params, h, cfg)))[0]


def greedy_reference(params, cfg, prompt, n_new):
    toks = list(prompt)
    logits = []
    for _ in range(n_new):
        row = full_logits(params, cfg, toks)[-1]
        logits.append(row)
        toks.append(int(np.argmax(row)))
    return toks[len(prompt):], logits


def run_parity(devices8, kind, prompts, n_new=4):
    cfg = tiny_cfg()
    hp = layout_hp(cfg, kind)
    model = construct_hybrid_parallel_model(cfg, hp, devices8)
    params = model.init_params(jax.random.PRNGKey(0))
    host_params = jax.device_get(params)
    kv_cfg = KVCacheConfig(max_slots=2, page_size=8, max_pages=4)
    engine = ServeEngine(cfg, params, kv_cfg, hp=hp, mesh=model.mesh)

    refs = [greedy_reference(host_params, cfg, p, n_new) for p in prompts]
    cur = np.zeros((kv_cfg.max_slots,), np.int32)
    lens = np.zeros((kv_cfg.max_slots,), np.int64)
    for slot, (prompt, (ref_toks, ref_logits)) in enumerate(zip(prompts, refs)):
        tok, row = engine.prefill(prompt, slot)
        np.testing.assert_allclose(row, ref_logits[0], atol=_ATOL)
        assert tok == ref_toks[0], kind
        cur[slot], lens[slot] = tok, len(prompt)
    active = np.array([s < len(prompts) for s in range(kv_cfg.max_slots)])
    for step in range(1, n_new):
        pages = bucket_pages(int(lens[active].max()), kv_cfg.page_size,
                             kv_cfg.max_pages)
        nxt, rows = engine.decode_step(cur, active, pages)
        for slot, (_, (ref_toks, ref_logits)) in enumerate(zip(prompts, refs)):
            np.testing.assert_allclose(rows[slot], ref_logits[step],
                                       atol=_ATOL, err_msg="%s step %d" % (kind, step))
            assert int(nxt[slot]) == ref_toks[step], (kind, step)
        cur[active] = nxt[active]
        lens[active] += 1


def test_decode_matches_full_forward_tp2(devices8):
    """Two concurrent slots under tp=2 (the searched-layout archetype):
    every decode step's logits match the full-sequence recompute."""
    run_parity(devices8, "tp2", [[5, 9, 2], [17, 3, 44, 8, 1]])


def test_decode_matches_full_forward_vocab_split(devices8):
    """The same under `vocab_tp 2` (tp2 x dp4): one prompt row and two slots
    do not divide over dp, and stay whole on every replica."""
    run_parity(devices8, "tp2_vtp2", [[5, 9, 2], [17, 3, 44, 8, 1]])


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["tp4", "dp8", "zero3", "tp2_zero3"])
def test_decode_matches_full_forward_cross_layouts(devices8, kind):
    run_parity(devices8, kind, [[5, 9, 2], [17, 3, 44, 8, 1]])


def test_train_checkpoint_restores_into_serve_layout(devices8, tmp_path):
    """Acceptance: a pp=2 TRAIN-layout checkpoint restores into a pp=1 tp=2
    serve layout (params-only, tx=None) with bitwise-equal global params,
    and the engine built on the restored params decodes greedily to the
    same tokens as the full-forward reference."""
    from galvatron_tpu.runtime import checkpoint as ck
    from galvatron_tpu.runtime import elastic as els
    from galvatron_tpu.runtime.optimizer import (
        OptimizerArgs, get_optimizer_and_scheduler)

    cfg = tiny_cfg()
    hp_train = HybridParallelConfig.uniform(
        8, cfg.num_layers, pp=2, global_bsz=8, chunks=2)
    m_train = construct_hybrid_parallel_model(cfg, hp_train, devices8)
    tx, _ = get_optimizer_and_scheduler(
        OptimizerArgs(lr=1e-3, warmup_steps=0, total_steps=2))
    p_train = m_train.init_params(jax.random.PRNGKey(7))
    st = m_train.init_opt_state(tx, p_train)
    d = str(tmp_path / "ck")
    prov = els.build_provenance(hp_train, cfg, OptimizerArgs(),
                                mesh=m_train.mesh, memory_budget_gb=16.0)
    ck.save_checkpoint(d, 1, p_train, st, hp_train, provenance=prov)

    hp_serve = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2,
                                            global_bsz=8)
    m_serve = construct_hybrid_parallel_model(cfg, hp_serve, devices8)
    # params-only strategy-portable restore — exactly cli/serve's call
    p_got, st_got, meta = ck.load_checkpoint(d, target=m_serve, tx=None)
    assert st_got is None and meta["iteration"] == 1

    # global values survive the pp2 -> pp1 de-stack + tp relayout bitwise
    from galvatron_tpu.parallel.pipeline import unstack_params
    ref = dict(jax.device_get(p_train))
    ref["layers"] = unstack_params(ref.pop("stages"), hp_train)
    got = jax.device_get(p_got)
    for (ka, va), (_, vb) in zip(
            jax.tree_util.tree_flatten_with_path(got)[0],
            jax.tree_util.tree_flatten_with_path(ref)[0]):
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                      err_msg=jax.tree_util.keystr(ka))
    # and the arrays live in the SERVE layout's shardings
    for w, g in zip(jax.tree.leaves(m_serve.shardings()),
                    jax.tree.leaves(jax.tree.map(lambda x: x.sharding, p_got))):
        assert w.spec == g.spec

    kv_cfg = KVCacheConfig(max_slots=2, page_size=8, max_pages=4)
    engine = ServeEngine(cfg, p_got, kv_cfg, hp=hp_serve, mesh=m_serve.mesh)
    prompt = [11, 3, 29, 6]
    ref_toks, _ = greedy_reference(ref, cfg, prompt, 3)
    tok, _ = engine.prefill(prompt, 0)
    out = [tok]
    cur, ln = np.array([tok, 0], np.int32), len(prompt)
    for _ in range(2):
        pages = bucket_pages(ln, kv_cfg.page_size, kv_cfg.max_pages)
        nxt, _ = engine.decode_step(cur, np.array([True, False]), pages)
        out.append(int(nxt[0]))
        cur[0] = nxt[0]
        ln += 1
    assert out == ref_toks
