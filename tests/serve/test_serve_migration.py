"""Degraded-mesh serve migration correctness on the REAL engine: a request
interrupted by a live world-shrink migration produces the SAME greedy
continuation as an uninterrupted run — journal replay (re-prefill
prompt + output[:-1], restore the last sampled token) is token-faithful.

Tier-1 carries the cheap tp2 8->4 shrink (same param layout, device_put
only); the cross-layout relayout matrix is `slow`. Also the GLS015
refusal when the surviving world cannot serve at all."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from galvatron_tpu.analysis import diagnostics as D
from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.runtime import elastic as els
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.serve.engine import ContinuousBatcher, Request, ServeEngine
from galvatron_tpu.serve.kv_cache import KVCacheConfig

pytestmark = [pytest.mark.serve]


class FakeClock:
    def __init__(self, dt=0.001):
        self.t, self.dt = 0.0, dt

    def __call__(self):
        self.t += self.dt
        return self.t


def tiny_cfg():
    return TransformerConfig(
        hidden_size=32, num_heads=4, num_layers=2, vocab_size=64,
        max_seq_len=32, compute_dtype=jnp.float32)


def requests():
    # fresh objects each call: the batcher mutates Request in place
    return [
        Request(rid=0, arrival_s=0.0, prompt=[5, 9, 2], max_new_tokens=6),
        Request(rid=1, arrival_s=0.0, prompt=[17, 3, 44, 8], max_new_tokens=6),
    ]


def run_shrink(devices8, live_n, target_kw):
    cfg = tiny_cfg()
    hp_a = HybridParallelConfig.uniform(8, cfg.num_layers, tp=2, global_bsz=8)
    model_a = construct_hybrid_parallel_model(cfg, hp_a, devices8)
    params_a = model_a.init_params(jax.random.PRNGKey(0))
    kv = KVCacheConfig(max_slots=2, page_size=8, max_pages=4)
    eng_a = ServeEngine(cfg, params_a, kv, hp=hp_a, mesh=model_a.mesh)

    # reference: the same engine serving the same load, uninterrupted
    ref = ContinuousBatcher(eng_a, kv, clock=FakeClock())
    ref_out = {r.rid: list(r.output) for r in ref.run(requests())}
    assert all(len(o) == 6 for o in ref_out.values())
    prompt_to_rid = {tuple(r.prompt): r.rid for r in requests()}

    hp_b = HybridParallelConfig.uniform(
        live_n, cfg.num_layers, global_bsz=live_n, **target_kw)
    live = list(devices8)[:live_n]
    ticks = {"n": 0}
    res = {}
    replays = []  # (replay_prompt, resampled_tok) seen by the NEW engine

    def control(b):
        ticks["n"] += 1
        if ticks["n"] != 3:
            return None
        new_model, new_params, _ = els.migrate_serve_params(
            model_a, params_a, hp_b, devices=live)
        eng_b = ServeEngine(cfg, new_params, kv, hp=hp_b, mesh=new_model.mesh)
        real_prefill = eng_b.prefill

        def recording_prefill(prompt, slot):
            tok, row = real_prefill(prompt, slot)
            replays.append((list(prompt), int(tok)))
            return tok, row

        eng_b.prefill = recording_prefill
        res.update(b.migrate_to(eng_b, kv))
        # restore semantics: cache holds prompt+output[:-1], next-token
        # state is the already-emitted output[-1]
        for slot, req in enumerate(b.slot_req):
            if req is None:
                continue
            assert int(b.slot_len[slot]) == len(req.journal) - 1
            assert int(b.slot_tok[slot]) == req.output[-1]
        return None

    b = ContinuousBatcher(eng_a, kv, clock=FakeClock(), control=control)
    done = {r.rid: list(r.output) for r in b.run(requests())}

    assert res == {"replayed": 2, "shed": 0}
    assert b.migrations == 1 and not b.shed
    assert done == ref_out, "continuation diverged across the migration"
    # replay faithfulness: re-prefilling prompt+output[:-1] on the NEW
    # layout re-samples exactly the token the OLD layout already emitted
    assert len(replays) == 2
    for replay, tok in replays:
        rid = next(r for p, r in prompt_to_rid.items()
                   if replay[:len(p)] == list(p))
        k = len(replay) - len([p for p in prompt_to_rid if
                               prompt_to_rid[p] == rid][0])
        assert 0 < k < 6  # genuinely mid-flight, not before/after
        assert tok == ref_out[rid][k]


def test_shrink_8_to_4_same_layout_journal_replay(devices8):
    """tp=2 on 8 devices -> tp=2 on the 4 survivors: params relayout is a
    pure device_put; the interrupted requests finish identically."""
    run_shrink(devices8, 4, {"tp": 2})


@pytest.mark.slow
@pytest.mark.parametrize("live_n,target_kw", [
    (4, {"tp": 4}),  # tp widens: cross-layout relayout
    (4, {}),         # pure dp4 (tp=1): shards fold back together
    (2, {"tp": 2}),  # deeper shrink
])
def test_shrink_cross_layout_journal_replay(devices8, live_n, target_kw):
    run_shrink(devices8, live_n, target_kw)


def test_surviving_world_search_refuses_with_gls015():
    """An impossible memory budget on the surviving world must surface as
    the structured GLS015 refusal, not a bare search failure."""
    cfg = tiny_cfg()
    with pytest.raises(D.DiagnosticError) as ei:
        els.search_surviving_serve_strategy(
            cfg, live_world=2, memory_budget_gb=1e-9,
            serve_max_concurrency=8, serve_page_size=8)
    codes = [d.code for d in ei.value.diagnostics]
    assert codes == ["GLS015"]
    assert "surviving" in ei.value.diagnostics[0].message
