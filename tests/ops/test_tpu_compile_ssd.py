"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
a Granite-4.0-H Mamba-2 layer and a Nemotron-H `M` block at their cells' widths with the scan in each form."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import _calls, v5e_2x2  # noqa: F401  (the fixture)


def _mixers(v5e_2x2, cfg, tokens):  # noqa: F811
    """One Mamba-2 mixer of `cfg` at `tokens` tokens in bf16 under the cell's
    recomputation, forward and backward, compiled for one described chip with
    the scan in each form: -> {form: (the optimised module's text, its
    temporaries in bytes, what `obs/forms` heard)}."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts import ssm

    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("ssm")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"ssm": shapes["ssm"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def compiled(where):
        def loss(p, y):
            mixer = jax.checkpoint(lambda p, y: ssm.ssm_mixer(p, y, None, lcfg, attn_sharding=where))
            out, _, counters = mixer(p, y)
            return jnp.sum(out.astype(jnp.float32)) + counters["ssm_state_abs_max"]

        with forms.recording() as took:
            step = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile()
        return step.as_text(), step.memory_analysis().temp_size_in_bytes, took

    # with no sharding the call reads the default backend, the CPU's here: the XLA form for the same chip
    return {"pallas": compiled(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))),
            "xla": compiled(None)}


@pytest.fixture(scope="module")
def granite_layer(v5e_2x2):  # noqa: F811
    from galvatron_tpu.models.granite_hybrid import granite_hybrid_config

    return _mixers(v5e_2x2, granite_hybrid_config(num_layers=10, max_seq_len=4096, compute_dtype=jnp.bfloat16), 4096)


@pytest.fixture(scope="module")
def nemotron_block(v5e_2x2):  # noqa: F811
    from galvatron_tpu.models.nemotron_h import nemotron_h_config

    return _mixers(v5e_2x2, nemotron_h_config(num_layers=9, max_seq_len=8192, compute_dtype=jnp.bfloat16), 8192)


@pytest.mark.parametrize("layer,said,xla_said,masks", [
    ("granite_layer", "pallas: 1 group x 32 heads a block", "1 group x 16 heads at once", r"f32\[32,16,128,128\]"),
    ("nemotron_block", "pallas: 8 groups x 32 heads a block", "8 groups x 8 heads at once", r"f32\[64,8,128,128\]"),
], ids=["granite_1_group_4096_tokens", "nemotron_8_groups_8192_tokens"])
def test_the_scan_keeps_a_blocks_states_on_the_chip_on_v5e(layer, said, xla_said, masks, request):
    """Mamba-2's scan on a TPU: `ssd_fwd` and `ssd_bwd` under `gt.attn.ssd`,
    once each under the layer's `jax.checkpoint` (the rule keeps its own
    residuals; the first forward and the recomputation are one here, no scan
    between them), no other kernel, none of them under the mixer's own scope
    `gt.attn.ssm`, and no float32 (chunks, heads at once, 128, 128) mask left
    in the module, which the XLA form for the same chip has; the layer's
    temporaries are no more than that form's."""
    from galvatron_tpu.obs import tracing

    both = request.getfixturevalue(layer)
    (text, temp, took), (xla_text, xla_temp, xla_took) = both["pallas"], both["xla"]
    assert took == {forms.SSD: {said: 1}} and xla_took == {forms.SSD: {xla_said: 1}}
    for kernel in ("ssd_fwd", "ssd_bwd"):
        assert _calls(text, kernel, tracing.ATTN_SSD) == 1, kernel
        assert not _calls(text, kernel, tracing.ATTN_SSM), kernel
    assert text.count("tpu_custom_call") == 2 and "tpu_custom_call" not in xla_text
    assert re.search(masks, xla_text)  # the form this PR takes off the chip's path
    assert not re.search(r"f32\[\d+,\d+,128,128\]", text)
    assert temp <= xla_temp, (temp, xla_temp)
