"""Profiling drivers: model computation/memory profiling and hardware
(ICI/DCN collective) profiling.

Analogue of the reference's per-model ``profiler.py`` (models/gpt_hf/profiler.py:8-17)
and ``profile_hardware.py`` (profile_hardware/profile_hardware.py:5-16). The
reference launches subprocess training runs and post-processes logs; here both
profilers run in-process on the JAX backend (layer differencing happens on
device, SURVEY.md §7), so one driver call does the whole sweep.
"""

from __future__ import annotations

from galvatron_tpu.cli.arguments import initialize_galvatron, model_config_from_args
from galvatron_tpu.utils.compile_cache import enable_persistent_cache


def profile_model(args) -> dict:
    from galvatron_tpu.profiler.model import ModelProfileArgs, ModelProfiler

    enable_persistent_cache()
    fam, cfg = model_config_from_args(args)
    from galvatron_tpu.models.base import refuse_unsupported

    refuse_unsupported(cfg, asker="profile")
    pargs = ModelProfileArgs(
        profile_type=args.profile_type,
        profile_mode=args.profile_mode,
        profile_batch_size=args.profile_batch_size,
        profile_min_batch_size=args.profile_min_batch_size,
        profile_max_batch_size=args.profile_max_batch_size,
        batch_size_step=args.batch_size_step,
        profile_seq_length=args.profile_seq_length,
        profile_min_seq_length=args.profile_min_seq_length,
        profile_max_seq_length=args.profile_max_seq_length,
        seq_length_step=args.seq_length_step,
        layernum_min=args.layernum_min,
        layernum_max=args.layernum_max,
        max_tp_deg=args.max_tp_deg,
        mixed_precision=args.mixed_precision,
        config_dir=args.config_dir,
        profile_remat=bool(getattr(args, "profile_remat", False)),
    )
    if fam.make_profiler is not None:
        prof = fam.make_profiler(cfg, args.model_type, pargs)
    else:
        prof = ModelProfiler(cfg, model_name=args.model_type, args=pargs)
    return prof.profile_all(write=True)


def profile_hardware(args) -> dict:
    from galvatron_tpu.profiler.hardware import HardwareProfileArgs, HardwareProfiler

    enable_persistent_cache()
    pargs = HardwareProfileArgs(
        start_mb=args.start_mb,
        end_mb=args.end_mb,
        scale=args.scale,
        avg_or_min_or_first=args.avg_or_min_or_first,
        max_pp_deg=args.max_pp_deg,
        overlap_time_multiply=args.overlap_time_multiply,
        config_dir=args.config_dir,
    )
    prof = HardwareProfiler(pargs)
    return prof.profile_all(write=True)


def main_model(argv=None):
    args = initialize_galvatron(mode="profile", argv=argv)
    return profile_model(args)


def main_hardware(argv=None):
    args = initialize_galvatron(mode="profile_hardware", argv=argv)
    return profile_hardware(args)


if __name__ == "__main__":
    main_model()
