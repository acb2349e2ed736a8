"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached; how and why: tests/ops/tpu_compile.py):
a Phi-4-mini-flash Mamba-1 layer at the cell's widths with the scan in each form."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from galvatron_tpu.obs import forms
from galvatron_tpu.ops import attention as A
from tests.ops.tpu_compile import _calls, v5e_2x2  # noqa: F401  (the fixture)


@pytest.fixture(scope="module")
def phi4_mamba_layer(v5e_2x2):
    """One Mamba-1 mixer at the Phi-4-mini-flash cell's widths (8192 tokens,
    hidden 2560, 5120 channels, states of 16, bf16) under the cell's
    recomputation, forward and backward, compiled for one described chip with
    the scan in each form: -> {form: (the optimised module's text, its
    temporaries in bytes, what `obs/forms` heard)}."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts import mamba
    from galvatron_tpu.models.phi4flash import phi4flash_config

    tokens = 8192
    cfg = phi4flash_config(num_layers=4, max_seq_len=tokens, compute_dtype=jnp.bfloat16)
    lcfg = cfg.layer_config(next(kind for kind in cfg.layer_kinds() if kind.startswith("mamba1")))
    chip = SingleDeviceSharding(v5e_2x2[0])
    shapes = jax.eval_shape(lambda: M.init_layer_params(jax.random.PRNGKey(0), lcfg))
    operands = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                            ({"mamba": shapes["mamba"]},
                             jax.ShapeDtypeStruct((1, tokens, cfg.hidden_size), jnp.bfloat16)))

    def compiled(where):
        def loss(p, y):
            mixer = jax.checkpoint(lambda p, y: mamba.mamba_mixer(p, y, None, lcfg, attn_sharding=where))
            out, _, counters = mixer(p, y)
            return jnp.sum(out.astype(jnp.float32)) + counters["selscan_state_abs_max"]

        with forms.recording() as took:
            step = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*operands).compile()
        return step.as_text(), step.memory_analysis().temp_size_in_bytes, took

    # with no sharding the call reads the default backend, the CPU's here: the XLA form for the same chip
    return {"pallas": compiled(A.KernelSharding(Mesh(np.array(v5e_2x2[:1]), ("dp",)), batch_axes=("dp",))),
            "xla": compiled(None)}


def test_the_mamba_layers_scan_keeps_a_chunks_state_on_the_chip_on_v5e(phi4_mamba_layer):
    """The selective scan on a TPU: `selscan_fwd` and `selscan_bwd` under
    `gt.attn.selscan`, once each under the layer's `jax.checkpoint` (the rule
    keeps its own residuals; the first forward and the recomputation are one
    here, no scan between them), no other kernel, none of them under the
    mixer's own scope `gt.attn.mamba`, and no loop left whose carry is every
    chunk's state, which the XLA form for the same chip has; the layer's
    temporaries are no more than that form's."""
    from galvatron_tpu.obs import tracing

    text, temp, took = phi4_mamba_layer["pallas"]
    xla_text, xla_temp, xla_took = phi4_mamba_layer["xla"]
    assert took == {forms.SELECTIVE_SCAN: {"pallas": 1}} and xla_took == {forms.SELECTIVE_SCAN: {"xla": 1}}
    for kernel in ("selscan_fwd", "selscan_bwd"):
        assert _calls(text, kernel, tracing.ATTN_SELSCAN) == 1, kernel
        assert not _calls(text, kernel, tracing.ATTN_MAMBA), kernel
    assert text.count("tpu_custom_call") == 2 and "tpu_custom_call" not in xla_text
    every_chunks_state = r"f32\[1,64,16,5120\]"
    carried = [line for line in xla_text.splitlines() if re.search(r"\bwhile\(", line) and re.search(every_chunks_state, line)]
    assert carried  # the form this PR takes off the chip's path
    assert not [line for line in text.splitlines() if re.search(r"\bwhile\(", line) and re.search(every_chunks_state, line)]
    assert temp <= xla_temp, (temp, xla_temp)
