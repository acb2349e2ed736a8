"""Workaround inventory (utils/jax_compat.py WORKAROUNDS, WA codes).

The inventory lists the GSPMD-hazard workarounds the code still carries, so
it must not rot: every entry needs a registered diagnostic code, a live
probe, and pinning tests that actually exist in the suite — the honesty gate
below collects them with pytest itself. The last case pins the compile-cache
contract that took the place of the retired persistent-cache bypass (WA007).
"""

import os
import subprocess
import sys

import jax

from galvatron_tpu.analysis import diagnostics as D
from galvatron_tpu.utils import jax_compat as JC

REPO = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))


# ------------------------------------------------------------ registry shape
def test_every_entry_has_registered_code_and_probe():
    assert JC.WORKAROUNDS, "inventory is empty"
    codes = [w.code for w in JC.WORKAROUNDS]
    assert len(codes) == len(set(codes)), "duplicate WA codes"
    for w in JC.WORKAROUNDS:
        assert w.code in D.CODES, "%s not in diagnostics.CODES" % w.code
        assert w.code.startswith("WA")
        assert w.title and w.where and w.pinning_tests
        assert callable(w.probe)


def test_inventory_probes_on_installed_jax():
    rows = JC.workaround_inventory()
    assert [r["code"] for r in rows] == [w.code for w in JC.WORKAROUNDS]
    for r in rows:
        assert r["active"] in (True, False, None), r
        assert isinstance(r["detail"], str) and r["detail"], r
        assert r["pinning_tests"], r
    # the GSPMD hazards stay listed, undecided, until reproduced without
    # their workaround; the shims, the probe and the cache bypass are retired
    assert [r["code"] for r in rows] == ["WA004", "WA005", "WA006"]
    assert all(r["active"] is None for r in rows), rows
    for retired in ("WA001", "WA002", "WA003", "WA007", "WA008"):
        assert retired not in D.CODES


def test_render_inventory_lists_every_code():
    out = JC.render_inventory(JC.workaround_inventory())
    for w in JC.WORKAROUNDS:
        assert w.code in out
        assert w.pinning_tests[0].split("::")[-1] in out


# ------------------------------------------------------------- honesty gate
def test_every_pinning_test_exists():
    """Every `file::name` a WA entry names must be collectable by pytest —
    one --collect-only subprocess over the union of referenced files."""
    refs = sorted({t for w in JC.WORKAROUNDS for t in w.pinning_tests})
    files = sorted({t.split("::")[0] for t in refs})
    for f in files:
        assert os.path.exists(os.path.join(REPO, f)), "missing file %s" % f
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "--collect-only", "-q",
         "-p", "no:cacheprovider", *files],
        cwd=REPO, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    collected = proc.stdout
    missing = [t for t in refs if t not in collected]
    assert not missing, "inventory names tests pytest cannot collect: %s\n%s" % (
        missing, proc.stdout[-2000:] + proc.stderr[-2000:])


# ------------------------------------------- compile cache (was: WA007 pin)
def test_step_program_is_written_to_the_persistent_cache(tmp_path, devices8):
    """The train step — the largest program — goes through the persistent
    compilation cache like everything else: the first run writes it, and a
    re-launch (the in-process executable memo emptied) is answered from the
    cache and trains to the same losses on the deserialized executable."""
    from galvatron_tpu.cli import train as T
    from galvatron_tpu.cli.arguments import initialize_galvatron

    argv = [
        "--model_type", "gpt", "--set_model_config_manually", "1",
        "--hidden_size", "64", "--num_attention_heads", "2", "--num_layers", "2",
        "--vocab_size", "128", "--seq_length", "32", "--mixed_precision", "fp32",
        "--global_train_batch_size", "8", "--world_size", "8",
        "--train_iters", "3", "--log_interval", "1000",
    ]
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    memo = dict(T._STEP_EXECUTABLES)
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        # as jax does at start-up when JAX_COMPILATION_CACHE_DIR is set (the
        # session's own variable, tests/conftest.py, keeps train() from
        # placing the cache itself)
        jax.config.update("jax_compilation_cache_dir", str(cache))
        cc.reset_cache()
        T._STEP_EXECUTABLES.clear()
        first = T.train(initialize_galvatron(mode="train_dist", argv=argv))
        assert first["compile_cache_hit"] is False
        assert any(cache.iterdir()), "nothing was written to the cache"
        T._STEP_EXECUTABLES.clear()
        second = T.train(initialize_galvatron(mode="train_dist", argv=argv))
        assert second["compile_cache_hit"] is True
        assert second["losses"] == first["losses"]
    finally:
        T._STEP_EXECUTABLES.clear()
        T._STEP_EXECUTABLES.update(memo)
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)
        cc.reset_cache()
