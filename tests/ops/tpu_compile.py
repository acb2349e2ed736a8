"""Compile-only checks against a DESCRIBED TPU v5e 2x2 (no chip attached).

libtpu compiles for a topology that is described, not attached
(`jax.experimental.topologies`), so what the chip's compiler would refuse —
a kernel it cannot tile, a Mosaic call GSPMD would have to partition, a step
that does not fit HBM — is refused here, on the CPU box, at no chip time.
Nothing runs: these say nothing about results or speed (chip_smoke.py does).
The persistent compilation cache is off around them: an entry written for a
described device cannot be read back without one, and the next compile would
warn and compile again.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from galvatron_tpu.ops import attention as A


REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


B, S, NH, HD = 2, 2048, 32, 128  # LLaMA-7B attention, batch cut to 2


@pytest.fixture(scope="module")
def v5e_2x2():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this host
        pytest.skip("cannot describe a TPU topology here: %s" % e)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _qkv(sharding):
    return jax.ShapeDtypeStruct((B, S, NH, HD), jnp.bfloat16, sharding=sharding)


def _attn_loss(sharding):
    """Causal flash attention loss; an optional 4th operand is a key-padding
    bias, which rides the kernel as segment ids."""
    def loss(q, k, v, *b):
        out = A.core_attention(q, k, v, causal=True, impl="flash", sharding=sharding,
                               bias=b[0] if b else None, bias_type="key_padding")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    return loss


def _compile_train_step(cfg, hp, devices, batch_rows):
    """The model's train step (Adam) compiled for `devices` from shapes alone."""
    return _model_and_compiled_step(cfg, hp, devices, batch_rows)[1]


def _model_and_compiled_step(cfg, hp, devices, batch_rows):
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

    m = construct_hybrid_parallel_model(cfg, hp, devices)
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-4, warmup_steps=0, total_steps=8))

    def sds(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    params = m.abstract_params()
    opt = jax.eval_shape(tx.init, params)
    tok = jax.ShapeDtypeStruct((batch_rows, cfg.max_seq_len), jnp.int32)
    batch = {k: jax.ShapeDtypeStruct(tok.shape, tok.dtype,
                                     sharding=NamedSharding(m.mesh, m._batch_spec_for(tok)))
             for k in ("tokens", "positions", "labels")}
    return m, m.make_train_step(tx).lower(
        sds(params, m.shardings()), sds(opt, m.opt_state_shardings(tx, params)), batch,
    ).compile()


MEGABLOX_CALL = r"%t?gmm[.\d]* = "  # the grouped matmul and its kernels' gradient


def _calls(text, kernel, scope):
    """Custom calls of `kernel` whose op carries `scope` right above the
    kernel's name (or above the jit its caller is traced once under)."""
    return len(re.findall(r'custom-call\(.*op_name="[^"]*%s/(?:jit\([^)]*\)/)?%s[/"]' % (re.escape(scope), kernel), text))
