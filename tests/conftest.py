"""Test fixtures.

Distributed-without-a-cluster mechanism (TPU-native analogue of the reference's
subprocess+NCCL fixture, tests/conftest.py:32-71): instead of spawning worker
processes, we run JAX on the CPU backend with 8 virtual devices
(`--xla_force_host_platform_device_count=8`) so every sharding/collective path
executes in-process. This must happen before jax initialises its backends."""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

# Session-fresh persistent compile cache: identical HLO recurs across tests
# (same tiny configs under different drivers) and compile time dominates
# suite walltime — cache off, the suite runs ~3x over its budget. Where
# JAX_COMPILATION_CACHE_DIR is set from outside it wins (jax reads it
# itself); otherwise a tmpdir written and read only by THIS session (and the
# children it spawns, which inherit the variable), removed at exit. A cache
# dir shared across machines was tried and reverted — XLA:CPU AOT entries
# embed host machine features, and reloading entries written on a different
# ISA risks SIGILL (cpu_aot_loader.cc).
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import atexit
    import shutil
    import tempfile

    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="jaxcache_")
    atexit.register(shutil.rmtree, os.environ["JAX_COMPILATION_CACHE_DIR"],
                    ignore_errors=True)

import jax  # noqa: E402
import pytest  # noqa: E402

# tests always run on the virtual 8-device CPU backend
jax.config.update("jax_platforms", "cpu")

# Tests are compile-bound on XLA:CPU (tiny shapes, many jitted train steps);
# low optimization effort halves compile time without touching semantics —
# measured 80s -> 43s on the heaviest pipeline-parity test, suite-wide ~2x.
jax.config.update("jax_disable_most_optimizations", True)

jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


@pytest.fixture
def disable_persistent_compile_cache():
    """Keeps a module's compiles out of the session's persistent cache (a
    second identical >1s compile would otherwise execute a DESERIALIZED
    XLA:CPU executable, which an older jaxlib answered with heap
    corruption). Use as `pytest.mark.usefixtures(...)` via an autouse wrapper
    or pytestmark; the knob is restored afterwards."""
    prev = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


@pytest.fixture(scope="session")
def window_kernels_as_on_a_tpu():
    """-> a context under which a window attention call is answered as a TPU
    would answer it (`ops/attention.window_takes_kernels`, asked by the mixer and
    by `core_attention`): the default backend is mocked INSIDE that question
    alone, so nothing else of a CPU run takes a TPU's branch. The kernels
    themselves want `pltpu.force_tpu_interpret_mode()` or a spy beside it."""
    import contextlib
    import unittest.mock as mock

    import jax

    from galvatron_tpu.models.parts import attention as parts
    from galvatron_tpu.ops import attention as ops

    real = ops.window_takes_kernels

    def asked(*args, **kw):
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            return real(*args, **kw)

    @contextlib.contextmanager
    def context():
        with mock.patch.object(ops, "window_takes_kernels", asked), \
             mock.patch.object(parts, "window_takes_kernels", asked):
            yield asked

    return context


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


@pytest.fixture(scope="session")
def tmp_config_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("configs")


# --------------------------------------------------------------- shared GPT
# The pipeline parity tests (gpipe and 1F1B modules) compare against the SAME
# pp=1 baseline trajectories; computing each baseline once per session saves
# several XLA:CPU train-step compiles — the dominant suite cost.
_GPT_B, _GPT_S, _GPT_V = 8, 32, 128


@pytest.fixture(scope="session")
def gpt_cfg():
    import jax.numpy as jnp

    from galvatron_tpu.models.config import TransformerConfig

    return TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=4, vocab_size=_GPT_V,
        max_seq_len=64, compute_dtype=jnp.float32,
    )


@pytest.fixture(scope="session")
def gpt_params(gpt_cfg):
    from galvatron_tpu.models import base as M

    return M.init_model_params(jax.random.PRNGKey(0), gpt_cfg)


def gpt_batch(seed):
    import jax.numpy as jnp

    tokens = jax.random.randint(jax.random.PRNGKey(seed), (_GPT_B, _GPT_S), 0, _GPT_V)
    return dict(
        tokens=tokens,
        positions=jnp.broadcast_to(jnp.arange(_GPT_S), (_GPT_B, _GPT_S)),
        labels=jnp.roll(tokens, -1, 1),
    )


def gpt_traj(cfg, params, hp, devices, steps=3):
    """Train `steps` and return the loss trajectory (shared by the pipeline
    parity tests; pipelined configs stack the canonical layer list)."""
    import jax.numpy as jnp

    from galvatron_tpu.parallel.pipeline import stack_params
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

    m = construct_hybrid_parallel_model(cfg, hp, devices)
    p = jax.tree.map(jnp.copy, params)
    if hp.pp > 1:
        p["stages"] = stack_params(p.pop("layers"), hp)
    p = jax.device_put(p, m.shardings())
    tx, _ = get_optimizer_and_scheduler(
        OptimizerArgs(lr=1e-3, warmup_steps=2, total_steps=10, weight_decay=0.0)
    )
    st = m.init_opt_state(tx, p)
    step = m.make_train_step(tx)
    out = []
    for i in range(steps):
        p, st, mets = step(p, st, m.shard_batch(gpt_batch(i % 2)))
        out.append(float(mets["loss"]))
    return out


@pytest.fixture(scope="session")
def gpt_ref_traj(gpt_cfg, gpt_params, devices8):
    """Memoized pp=1 baseline trajectory per (chunks, steps)."""
    from galvatron_tpu.config.strategy import HybridParallelConfig

    cache = {}

    def get(chunks, steps=3):
        key = (chunks, steps)
        if key not in cache:
            hp = HybridParallelConfig.uniform(
                8, gpt_cfg.num_layers, global_bsz=_GPT_B, chunks=chunks
            )
            cache[key] = gpt_traj(gpt_cfg, gpt_params, hp, devices8, steps)
        return cache[key]

    return get
