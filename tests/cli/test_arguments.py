"""Argument-system tests (reference arg plumbing, core/arguments.py:8-30)."""

import glob
import os
import re

import pytest

from galvatron_tpu.cli.arguments import (
    MODES,
    build_parser,
    hp_config_from_args,
    initialize_galvatron,
    model_config_from_args,
)


def test_modes_parse_defaults():
    for mode in ("train_dist", "search", "profile", "profile_hardware"):
        args = initialize_galvatron(mode=mode, argv=[])
        assert args.galvatron_mode == mode
        assert args.model_type == "llama"


def test_extra_args_provider():
    def extra(p):
        p.add_argument("--my_flag", type=int, default=7)

    args = initialize_galvatron(extra, mode="train_dist", argv=["--my_flag", "3"])
    assert args.my_flag == 3


def test_global_mode_hp_config():
    args = initialize_galvatron(mode="train_dist", argv=[
        "--pp_deg", "2", "--global_tp_deg", "2", "--chunks", "2",
        "--global_train_batch_size", "8", "--default_dp_type", "zero2",
        "--checkpoint", "1",
    ])
    hp = hp_config_from_args(args, num_layers=4, world_size=8)
    assert hp.pp == 2 and hp.layers[0].tp == 2 and hp.layers[0].checkpoint == 1
    assert hp.default_dp_type == "zero2"
    assert hp.dp(0) == 2  # 8/(pp2*tp2)


def test_json_mode_hp_config(tmp_path):
    from galvatron_tpu.config.strategy import HybridParallelConfig

    ref = HybridParallelConfig.uniform(world_size=8, num_layers=4, pp=1, tp=2, global_bsz=8)
    p = tmp_path / "strategy.json"
    ref.save(str(p))
    args = initialize_galvatron(mode="train_dist", argv=[
        "--galvatron_config_path", str(p), "--global_train_batch_size", "8",
    ])
    hp = hp_config_from_args(args, num_layers=4, world_size=8)
    hp.assert_equal(ref)


def test_model_config_resolution():
    args = initialize_galvatron(mode="train_dist", argv=[
        "--model_type", "gpt", "--model_size", "gpt-1.5b",
    ])
    fam, cfg = model_config_from_args(args)
    assert fam.name == "gpt" and cfg.hidden_size == 1600 and cfg.num_layers == 48


def test_manual_model_config_override():
    args = initialize_galvatron(mode="train_dist", argv=[
        "--model_type", "llama", "--set_model_config_manually", "1",
        "--hidden_size", "256", "--num_attention_heads", "4",
        "--num_layers", "2", "--vocab_size", "1024", "--seq_length", "128",
    ])
    _, cfg = model_config_from_args(args)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_layers, cfg.vocab_size, cfg.max_seq_len) == (
        256, 4, 2, 1024, 128)


def test_unknown_family_raises():
    args = initialize_galvatron(mode="train_dist", argv=["--model_type", "nope"])
    with pytest.raises(KeyError):
        model_config_from_args(args)


def test_compilation_flags_default_and_plumbing(tmp_path):
    """--no_scan_layers / --remat_policy reach HybridParallelConfig on both
    the GLOBAL-flags path and the searched-JSON path. scan_layers is a pure
    runtime execution knob (never on-disk); remat_policy is a SERIALIZED
    per-layer strategy field since the remat search dimension — the flag is
    a default-override that FILLS layers when the JSON lacks the key."""
    import dataclasses

    args = initialize_galvatron(mode="train_dist", argv=[])
    assert args.scan_layers is True and args.remat_policy == "full"
    assert not hasattr(args, "compile_cache")  # always on; placed by env
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert hp.scan_layers is True and hp.remat_policy == "full"

    args = initialize_galvatron(mode="train_dist", argv=[
        "--no_scan_layers", "--remat_policy", "dots_saveable",
    ])
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert hp.scan_layers is False and hp.remat_policy == "dots_saveable"
    assert all(s.remat_policy == "dots_saveable" for s in hp.layers)

    from galvatron_tpu.config.strategy import HybridParallelConfig

    ref = HybridParallelConfig.uniform(world_size=8, num_layers=2, tp=2, global_bsz=8)
    p = tmp_path / "strategy.json"
    ref.save(str(p))
    assert "scan_layers" not in ref.to_json_dict()
    assert "remat_policy" not in ref.to_json_dict()  # all-"full": no key
    args = initialize_galvatron(mode="train_dist", argv=[
        "--galvatron_config_path", str(p), "--no_scan_layers",
        "--remat_policy", "nothing_saveable", "--global_train_batch_size", "8",
    ])
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert hp.scan_layers is False and hp.remat_policy == "nothing_saveable"
    # the JSON carries no remat_policy key, so the flag filled every layer
    assert all(s.remat_policy == "nothing_saveable" for s in hp.layers)
    # scan_layers never touches strategy identity; the filled remat policies
    # DO (they serialize) — neutralized, the rest of the identity matches
    neutral = dataclasses.replace(
        hp, remat_policy="full",
        layers=[dataclasses.replace(s, remat_policy="full")
                for s in hp.layers])
    neutral.assert_equal(ref)


def test_remat_policy_serialized_values_win_over_flag(tmp_path):
    """Precedence rule (ISSUE 15): a JSON that carries per-layer remat
    policies keeps them verbatim — the global flag does not overwrite."""
    import dataclasses

    from galvatron_tpu.config.strategy import HybridParallelConfig

    ref = HybridParallelConfig.uniform(
        world_size=8, num_layers=2, tp=2, checkpoint=1, global_bsz=8)
    ref = dataclasses.replace(ref, layers=[
        dataclasses.replace(s, remat_policy=rp)
        for s, rp in zip(ref.layers, ("none", "dots_saveable"))])
    p = tmp_path / "strategy.json"
    ref.save(str(p))
    assert "remat_policy" in ref.to_json_dict()
    args = initialize_galvatron(mode="train_dist", argv=[
        "--galvatron_config_path", str(p),
        "--remat_policy", "nothing_saveable", "--global_train_batch_size", "8",
    ])
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert [s.remat_policy for s in hp.layers] == ["none", "dots_saveable"]


def test_tp_comm_mode_flag_plumbing(tmp_path):
    """--tp_comm_mode reaches HybridParallelConfig on both the GLOBAL-flags
    path and the searched-JSON path, and (like remat_policy) is never
    serialized into the on-disk strategy schema."""
    args = initialize_galvatron(mode="train_dist", argv=[])
    assert args.tp_comm_mode == "gspmd"
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert hp.tp_comm_mode == "gspmd"

    args = initialize_galvatron(mode="train_dist", argv=[
        "--global_tp_deg", "2", "--tp_comm_mode", "overlap",
    ])
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert hp.tp_comm_mode == "overlap"
    assert "tp_comm_mode" not in hp.to_json_dict()

    from galvatron_tpu.config.strategy import HybridParallelConfig

    ref = HybridParallelConfig.uniform(world_size=8, num_layers=2, tp=2, global_bsz=8)
    p = tmp_path / "strategy.json"
    ref.save(str(p))
    args = initialize_galvatron(mode="train_dist", argv=[
        "--galvatron_config_path", str(p), "--tp_comm_mode", "shard_map",
        "--global_train_batch_size", "8",
    ])
    hp = hp_config_from_args(args, num_layers=2, world_size=8)
    assert hp.tp_comm_mode == "shard_map"
    hp.assert_equal(ref)  # the knob doesn't change strategy identity


def test_tp_comm_mode_validated():
    from galvatron_tpu.analysis.diagnostics import DiagnosticError
    from galvatron_tpu.config.strategy import HybridParallelConfig

    with pytest.raises(DiagnosticError, match="GLS005"):
        HybridParallelConfig.uniform(8, 2, tp_comm_mode="bogus")


@pytest.mark.parametrize("from_env", [True, False])
def test_persistent_compile_cache_dir_contract(tmp_path, monkeypatch, from_env):
    """Where the cache lives is decided from outside: with
    JAX_COMPILATION_CACHE_DIR set the program sets no directory of its own;
    unset, it is the one fixed path inside the checkout (never a temporary
    name, never under $HOME). Every touched config knob is restored."""
    import os

    import jax

    from galvatron_tpu.utils import compile_cache as CC

    old_dir = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert CC.DEFAULT_CACHE_DIR == os.path.join(repo, ".jax_compile_cache")
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outside"))
            jax.config.update("jax_compilation_cache_dir", "sentinel-set-by-jax")
            assert CC.enable_persistent_cache() == str(tmp_path / "outside")
            # untouched: jax took the variable itself at start-up
            assert jax.config.jax_compilation_cache_dir == "sentinel-set-by-jax"
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            monkeypatch.setattr(CC, "DEFAULT_CACHE_DIR", str(tmp_path / "fixed"))
            assert CC.enable_persistent_cache() == str(tmp_path / "fixed")
            assert (tmp_path / "fixed").is_dir()
            assert jax.config.jax_compilation_cache_dir == str(tmp_path / "fixed")
    finally:
        jax.config.update("jax_compilation_cache_dir", old_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", old_min)

@pytest.fixture(scope="module")
def package_source():
    """Every line of the package, less what arguments.py spends on declaring
    an option: its ``--flag`` strings (in help texts too) and ``dest=``."""
    from galvatron_tpu.cli import arguments

    root = os.path.dirname(os.path.dirname(arguments.__file__))
    chunks = []
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        with open(path) as f:
            text = f.read()
        if os.path.samefile(path, arguments.__file__):
            text = re.sub(r"--\w+|dest=\"\w+\"", "", text)
        chunks.append(text)
    return "\n".join(chunks)


@pytest.mark.parametrize("mode", MODES)
def test_every_option_is_read(mode, package_source):
    """An option that is accepted and read by nothing is worse than an error:
    the user believes they chose something. Every ``dest`` a mode's parser
    declares is named somewhere in the package beside its declaration."""
    dests = {a.dest for a in build_parser(mode)._actions if a.dest != "help"}
    unread = sorted(d for d in dests
                    if not re.search(r"\b%s\b" % re.escape(d), package_source))
    assert unread == [], "parsed and read by nothing: %s" % unread
