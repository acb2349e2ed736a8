"""Persistent XLA compilation cache, on for every entry point.

Scan-over-layer-runs (models/base.py run_layers) makes compile cost
depth-constant; the persistent cache removes it across PROCESS restarts too:
a re-launched train/serve/profile run whose programs are unchanged loads the
compiled executables from disk instead of re-invoking XLA.

Where the cache lives is decided from outside: jax itself reads
``JAX_COMPILATION_CACHE_DIR``, and when that is set nothing here sets another
directory. Otherwise the cache sits at ONE fixed path inside the checkout
(git-ignored) — the path is part of the cache key, so a directory that moves
between runs never hits. ``JAX_ENABLE_COMPILATION_CACHE=0`` is jax's own off
switch.

The cache is per-HOST state: XLA:CPU AOT entries embed the writing host's ISA
features (cpu_aot_loader.cc), so do not point the variable at a directory
shared by different machines.
"""

from __future__ import annotations

import os

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def enable_persistent_cache() -> str:
    """Turn the persistent compilation cache on and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set (jax has already taken it from
    the environment), else DEFAULT_CACHE_DIR. The min-compile-time threshold
    drops to zero so the small per-run programs of a scanned model are cached
    too. Call before the first jit compilation; calling again is a no-op."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
