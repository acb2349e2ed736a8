"""FLOPs accounting: the analytic counts agree with XLA's own cost analysis
on a tiny model, and MFU plumbs into the profiler summary."""

import jax
import jax.numpy as jnp
import pytest

from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.embed_head import embed_tokens, lm_logits
from galvatron_tpu.obs import flops as F
from galvatron_tpu.profiler.runtime import RuntimeProfiler

TINY = dict(hidden_size=64, num_heads=4, num_layers=2, vocab_size=128,
            max_seq_len=32, compute_dtype=jnp.float32, param_dtype=jnp.float32)


def tiny_cfg(**kw):
    d = dict(TINY)
    d.update(kw)
    return TransformerConfig(**d)


def test_peak_registry_prefix_match():
    assert F.peak_flops_for("TPU v5 lite") == 197e12
    assert F.peak_flops_for("TPU v5p chip") == 459e12  # longest prefix wins
    assert F.peak_flops_for("tpu V5 LITE") == 197e12  # case-insensitive
    assert F.peak_flops_for("quantum-npu-9000") is None
    assert F.peak_flops_for(None) is None


def test_no_peak_off_the_table(monkeypatch, devices8):
    """A device the table does not hold has no peak, whatever the environment
    says, and a run on it reports no MFU: a CPU number is never written under
    the name of a device metric."""
    from tests.cli.test_async_loop import RES_TINY, run

    monkeypatch.setenv("GALVATRON_PEAK_FLOPS", "123e9")
    assert F.peak_flops_for("cpu") is None
    assert F.peak_flops_for("anything") is None
    assert F.peak_flops_for("TPU v5 lite") == 197e12
    summary = run(["--train_iters", "3"], base=RES_TINY)
    assert "mfu" not in summary
    # the throughput that needs no peak is still there
    assert summary["model_flops_per_s"] > 0


def test_layer_flops_scaling_laws():
    base = F.layer_fwd_flops(hidden=64, num_heads=4, seq_len=32)
    # doubling tokens doubles flops; non-causal attention costs more
    assert F.layer_fwd_flops(hidden=64, num_heads=4, seq_len=32, tokens=64) \
        == pytest.approx(2 * base)
    assert F.layer_fwd_flops(hidden=64, num_heads=4, seq_len=32, causal=False) > base
    # swiglu at same ffn costs one extra ffn matmul
    gelu = F.layer_fwd_flops(hidden=64, num_heads=4, seq_len=32, ffn_hidden=256)
    swiglu = F.layer_fwd_flops(hidden=64, num_heads=4, seq_len=32, ffn_hidden=256,
                               swiglu=True)
    assert swiglu == pytest.approx(gelu + 32 * 2 * 64 * 256)


def test_train_step_flops_is_3x_forward():
    cfg = tiny_cfg()
    assert F.train_step_flops(cfg, 8) == pytest.approx(3 * F.model_fwd_flops(cfg, 8))


def test_analytic_forward_flops_match_xla_cost_analysis():
    """The acceptance check behind every MFU number: the analytic forward
    count agrees with what XLA says the lowered forward actually computes
    (XLA:CPU reports flops; it also counts the softmax/norm elementwise work
    the analytic matmul-only model ignores, hence the one-sided band).
    num_layers=1 keeps the stack unrolled: HloCostAnalysis counts a scan
    body ONCE regardless of trip count (see obs.flops.xla_flops), so a
    scanned stack would under-report by the run length."""
    cfg = tiny_cfg(num_layers=1)
    batch = 4
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((batch, cfg.max_seq_len), jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(cfg.max_seq_len), tokens.shape)

    def fwd(p, t):
        return M.model_forward(p, t, positions, cfg)

    compiled = jax.jit(fwd).lower(params, tokens).compile()
    reported = F.xla_flops(compiled)
    if reported is None:
        pytest.skip("backend reports no flops in cost_analysis")
    analytic = F.model_fwd_flops(cfg, batch)
    # analytic counts the matmuls only: it must cover >=60% of XLA's count
    # and never exceed it by more than 25% (constant-folding slack)
    assert 0.6 * reported <= analytic <= 1.25 * reported, (analytic, reported)


def test_mfu_plumbs_into_profiler_summary():
    prof = RuntimeProfiler(warmup=0, model_flops=1e9, peak_flops=1e12)
    prof.start(0)
    prof._t0s[0] -= 0.1  # fake a 100ms step without sleeping
    prof.end(0, n_samples=8)
    s = prof.summary()
    assert s["model_flops_per_step"] == 1e9
    assert s["model_flops_per_s"] == pytest.approx(1e10, rel=0.2)
    assert s["mfu"] == pytest.approx(0.01, rel=0.2)


def test_summary_omits_mfu_without_flops():
    prof = RuntimeProfiler(warmup=0)
    prof.start(0)
    prof.end(0, n_samples=8)
    s = prof.summary()
    assert "mfu" not in s and "model_flops_per_s" not in s


def test_run_fwd_flops_shares_sum_to_one():
    from galvatron_tpu.config.strategy import HybridParallelConfig

    cfg = tiny_cfg()
    hp = HybridParallelConfig.uniform(world_size=8, num_layers=2, tp=2, global_bsz=8)
    runs = F.run_fwd_flops(cfg, hp)
    assert runs is not None and len(runs) == 2  # one scanned run + head
    total = sum(runs)
    assert total == pytest.approx(F.model_fwd_flops(cfg, 8))


def test_decode_step_flops_kv_aware_no_train_multiplier():
    cfg = tiny_cfg()
    one = F.decode_step_flops(cfg, batch_size=1, context_len=16)
    assert one is not None and one > 0
    # forward-only: far below even one-eighth of a train step per token
    assert one < F.train_step_flops(cfg, 1) / 3
    # matmul flops scale linearly in batch; attention linearly in context
    assert F.decode_step_flops(cfg, batch_size=4, context_len=16) \
        == pytest.approx(4 * one)
    grown = F.decode_step_flops(cfg, batch_size=1, context_len=32)
    assert one < grown < 2 * one  # only the attention term grows with ctx
    # the context term prices the FULL cache (no causal 0.5 discount):
    # +16 ctx adds 2*(2*16*q_dim) score+weighted-sum flops per layer
    q_dim = cfg.num_heads * cfg.head_dim
    assert grown - one == pytest.approx(cfg.num_layers * 2 * (2 * 16 * q_dim))
    assert F.decode_step_flops(object()) is None


def test_model_bytes_per_decode_token_roofline_terms():
    cfg = tiny_cfg()
    b1 = F.model_bytes_per_decode_token(cfg, context_len=16, dtype_bytes=2)
    b4 = F.model_bytes_per_decode_token(cfg, context_len=16, dtype_bytes=2,
                                        batch_size=4)
    kv = 2.0 * cfg.num_layers * 16 * cfg.num_kv_heads * cfg.head_dim * 2
    # weights amortise over the batch; the KV read never does
    assert b1 > b4 > kv
    assert b4 - kv == pytest.approx((b1 - kv) / 4)
    # fp32 wire doubles every term
    assert F.model_bytes_per_decode_token(cfg, context_len=16, dtype_bytes=4) \
        == pytest.approx(2 * b1)
    assert F.model_bytes_per_decode_token(object()) is None


def test_decode_step_flops_matches_xla_cost_analysis():
    """Same acceptance band as the training forward: the analytic decode
    count must agree with XLA's own count of the lowered single-token step
    (batch of slots vs a full cache)."""
    cfg = tiny_cfg(num_layers=1)
    slots, ctx = 4, 32
    params = M.init_model_params(jax.random.PRNGKey(0), cfg)
    k = jnp.zeros((slots, ctx, cfg.num_kv_heads, cfg.head_dim), jnp.float32)
    tokens = jnp.zeros((slots,), jnp.int32)
    lengths = jnp.full((slots,), ctx - 1, jnp.int32)

    def decode(p, t, kc, vc, ln):
        x = embed_tokens(p["embed"], t[:, None], ln[:, None], cfg)
        x, _, _ = M.decode_layer_forward(
            p["layers"][0], x, ln[:, None], cfg, k_cache=kc, v_cache=vc,
            write_index=ln)
        return lm_logits(p, x, cfg)

    compiled = jax.jit(decode).lower(params, tokens, k, k, lengths).compile()
    reported = F.xla_flops(compiled)
    if reported is None:
        pytest.skip("backend reports no flops in cost_analysis")
    analytic = F.decode_step_flops(cfg, batch_size=slots, context_len=ctx)
    assert 0.5 * reported <= analytic <= 1.25 * reported, (analytic, reported)


def test_xla_flops_handles_unreportable_objects():
    class NoAnalysis:
        def cost_analysis(self):
            raise RuntimeError("nope")

    class WeirdShape:
        def cost_analysis(self):
            return [{"flops": -1.0}]

    assert F.xla_flops(NoAnalysis()) is None
    assert F.xla_flops(WeirdShape()) is None
