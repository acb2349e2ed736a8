"""What the TPU compiler makes of a scanned run's weight gradients over dp
(PR 55), compiled for a DESCRIBED v5e 2x2 with no chip attached, as
tests/ops/test_tpu_compile_steps.py does: `obs/compiled.dp_grad_sums_mb` reads the
compiled step's text here the way the trainer's `compile` event reads it
(`dp_grad_all_reduce_mb`, `dp_grad_reduce_scatter_mb`)."""

import jax.numpy as jnp
import pytest

from galvatron_tpu.obs import compiled as C
from galvatron_tpu.obs import forms
from galvatron_tpu.parallel.mesh import vocab_axes
from tests.ops.tpu_compile import _model_and_compiled_step, v5e_2x2  # noqa: F401  (the fixture)

# name -> (layout flags, whether the scanned cotangent is asked for in ZeRO's
# layout, the kinds of sum the backward body may hold over dp)
CASES = {
    "zero2": (dict(default_dp_type="zero2", sequence_parallel=False), True, {"reduce-scatter"}),
    "zero2_chunks2": (dict(default_dp_type="zero2", chunks=2), True, {"reduce-scatter"}),
    "zero2_megatron_sp": (dict(default_dp_type="zero2", sequence_parallel=True), True, {"reduce-scatter"}),
    # the control that was already so: ZeRO-3's leaves are split over dp as `param_specs` has them
    "zero3": (dict(default_dp_type="zero3", sequence_parallel=False), False, {"reduce-scatter"}),
    # and the control that keeps its all-reduce: ddp stores every gradient whole
    "ddp": (dict(default_dp_type="ddp", sequence_parallel=False), False, {"all-reduce"}),
}


@pytest.mark.parametrize("name", list(CASES))
def test_the_scanned_layers_gradients_are_summed_into_zeros_shards_on_v5e(v5e_2x2, name):  # noqa: F811
    """tp 2 x dp 2 on the described 2x2, two scanned LLaMA layers wide
    enough that every kernel's gradient a chip is over 1 MB: under ZeRO-2 no
    all-reduce over the dp groups is left under
    `transpose(jvp(gt.layers.r0))/while/body` with such an operand (what is
    left there is the norms' scales'), and the kernels' gradients go through
    reduce-scatters (fusions that call `%all-reduce-scatter`), whose operand
    bytes are the kernels' a chip; ZeRO-3 was so already; ddp keeps the
    all-reduces, the same bytes."""
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.llama import llama_config

    flags, asked, kinds = CASES[name]
    cfg = llama_config("llama-0.3b", num_layers=2, hidden_size=1024, num_heads=8, ffn_hidden=2048,
                       vocab_size=32000, max_seq_len=256, compute_dtype=jnp.bfloat16)
    hp = HybridParallelConfig.uniform(4, 2, tp=2, vocab_tp=2, global_bsz=8, mixed_precision="bf16", **flags)
    with forms.recording() as took:
        model, step = _model_and_compiled_step(cfg, hp, v5e_2x2, batch_rows=8)
    # two norm scales and four kernels a layer (the same leaves in every traced microbatch)
    assert took[forms.SCAN_GRADS]["zero_layout"] == (6 if asked else 0)
    dp_groups = C.axis_groups(model.mesh, vocab_axes(hp).dp)
    assert dp_groups == {frozenset({0, 2}), frozenset({1, 3})}
    sums = C.scan_grad_sums(step.as_text(), [dp_groups])
    assert {kind for kind, _ in sums} == kinds, sums
    # a chip's half (tp 2) of wqkv, wi and wo_mlp in bf16, once a microbatch (wo's is 1 MB, and not over it)
    h, f = cfg.hidden_size, cfg.ffn_hidden
    kernels = [2 * n // 2 for n in (3 * h * h, h * h, 2 * h * f, f * h)]
    assert sorted(n for _, n in sums) == sorted([n for n in kernels if n > C.LARGE_OPERAND_BYTES] * hp.chunks), sums
    mb = C.dp_grad_sums_mb(step.as_text(), [dp_groups])
    assert (mb["dp_grad_all_reduce_mb"] > 0) == (name == "ddp")
    assert (mb["dp_grad_reduce_scatter_mb"] > 0) == (name != "ddp")
