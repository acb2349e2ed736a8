"""Search driver: the analogue of every model's ``search_dist.py``
(reference models/gpt_hf/search_dist.py:8-22). Pure CPU: reads profiled
JSON configs, runs the DP search, writes the optimal strategy JSON.
"""

from __future__ import annotations

import os
from typing import Optional

from galvatron_tpu.cli.arguments import initialize_galvatron, model_config_from_args
from galvatron_tpu.search.engine import GalvatronSearchEngine, SearchArgs
from galvatron_tpu.utils.jsonio import read_json_config


def search_args_from(args) -> SearchArgs:
    return SearchArgs(
        memory_constraint=args.memory_constraint,
        search_space=args.search_space,
        sp_space=args.sp_space,
        disable_dp=bool(args.disable_dp),
        disable_tp=bool(args.disable_tp),
        disable_vtp=bool(args.disable_vtp),
        disable_pp=bool(args.disable_pp),
        disable_sdp=bool(args.disable_sdp),
        disable_ckpt=bool(args.disable_ckpt),
        disable_tp_consec=bool(args.disable_tp_consec),
        disable_cp=not bool(args.enable_cp),
        max_tp_deg=args.search_max_tp_deg,
        max_pp_deg=args.search_max_pp_deg,
        max_cp_deg=args.max_cp_deg,
        min_bsz=args.min_bsz,
        max_bsz=args.max_bsz,
        bsz_scale=args.bsz_scale,
        settle_bsz=args.settle_bsz,
        settle_chunk=args.settle_chunk,
        fine_grained_mode=bool(args.fine_grained_mode),
        use_pipeline_costmodel=bool(args.use_pipeline_costmodel),
        mixed_precision=args.mixed_precision == "bf16",
        default_dp_type=getattr(args, "default_dp_type", "ddp"),
        parallel_search=bool(args.parallel_search),
        log_dir=args.log_dir,
        comm_quant=getattr(args, "comm_quant", "off"),
        comm_quant_block=getattr(args, "comm_quant_block", 64),
        comm_quant_budget=getattr(args, "comm_quant_budget", 1.0),
        remat_search=bool(getattr(args, "remat_search", False)),
        objective=getattr(args, "objective", "train"),
        p99_ttft_ms=getattr(args, "p99_ttft_ms", 0.0),
        p99_tpot_ms=getattr(args, "p99_tpot_ms", 0.0),
        serve_max_concurrency=getattr(args, "serve_max_concurrency", 8),
        serve_page_size=getattr(args, "serve_page_size", 16),
        serve_hbm_gbps=getattr(args, "serve_hbm_gbps", 100.0),
        trace_lint=bool(getattr(args, "trace_lint", 0)),
    )


def _hardware_paths(config_dir: str, ndev: int) -> dict:
    tag = "%dchips" % ndev
    return {
        "allreduce": os.path.join(config_dir, "allreduce_bandwidth_%s.json" % tag),
        "p2p": os.path.join(config_dir, "p2p_bandwidth_%s.json" % tag),
        "sp": os.path.join(config_dir, "sp_time_%s.json" % tag),
        "overlap": os.path.join(config_dir, "overlap_coefficient.json"),
    }


def _model_paths(args, fam, cfg) -> dict:
    """Profiled-table paths — derived by the same profiler code that wrote
    them (pass --profile_seq_length here iff the profile run used it)."""
    from galvatron_tpu.profiler.model import ModelProfileArgs, ModelProfiler

    pargs = ModelProfileArgs(
        mixed_precision=args.mixed_precision, config_dir=args.config_dir,
        profile_seq_length=getattr(args, "profile_seq_length", None),
    )
    if fam.make_profiler is not None:
        prof = fam.make_profiler(cfg, args.model_type, pargs)
    else:
        prof = ModelProfiler(cfg, model_name=args.model_type, args=pargs)
    return prof.config_paths()


def search(args, world_size: Optional[int] = None) -> dict:
    fam, cfg = model_config_from_args(args)
    from galvatron_tpu.models.base import refuse_unsupported

    # the cost models know the dense block alone: refuse, do not price a part as dense
    refuse_unsupported(cfg, asker="search")
    world_size = world_size or int(os.environ.get("GALVATRON_WORLD_SIZE", "8"))
    if fam.layer_configs_fn is not None:
        # multi-layer-type families (t5 enc/dec, swin per stage): the DP
        # searches a strategy per layer across every type
        # (reference dynamic_programming.py:170-189)
        layer_cfgs = fam.layer_configs_fn(cfg)
    else:
        layer_cfgs = [
            {"hidden_size": cfg.hidden_size, "seq_len": cfg.max_seq_len,
             "layer_num": cfg.num_layers}
        ]
    sargs = search_args_from(args)
    if sargs.objective == "serve":
        # GQA shrinks KV bytes by num_kv_heads/num_heads; the search engine
        # itself never sees head counts, so resolve the ratio here
        nkv = getattr(cfg, "num_kv_heads", None)
        nh = getattr(cfg, "num_heads", None)
        if nkv and nh:
            sargs.serve_kv_frac = float(nkv) / float(nh)
    engine = GalvatronSearchEngine(
        sargs,
        world_size,
        model_layer_configs=layer_cfgs,
        config_dir=args.config_dir,
        model_name=args.model_type,
        align_type_boundaries=not fam.mid_stage_type_boundaries,
        allow_sequence_sharding=fam.supports_sequence_sharding,
    )
    mp = _model_paths(args, fam, cfg)
    # explicit measured tables (report --emit_profiles output, or a profile
    # run saved elsewhere) override the per-model config-dir convention
    time_path = getattr(args, "time_profile_path", None) or mp["computation"]
    mem_path = getattr(args, "memory_profile_path", None) or mp["memory"]
    engine.set_model_profiles(
        read_json_config(time_path), read_json_config(mem_path)
    )
    hw = _hardware_paths(args.config_dir, world_size)
    engine.set_hardware_profiles(
        read_json_config(hw["allreduce"]),
        read_json_config(hw["p2p"]) if os.path.exists(hw["p2p"]) else None,
        read_json_config(hw["overlap"]) if os.path.exists(hw["overlap"]) else None,
        read_json_config(hw["sp"]) if os.path.exists(hw["sp"]) else None,
    )
    engine.initialize_search_engine()
    if sargs.objective == "serve":
        # raises DiagnosticError [GLS014] when no candidate satisfies the
        # memory budget and p99 latency bounds
        result = engine.serve_optimization()
        sv = result["serve"]
        print("serve winner: %.1f tok/s/chip, prefill %.1f ms, decode %.2f ms"
              "/token, %.0f MB/device (concurrency=%d, ctx=%d)"
              % (sv["tokens_per_s_per_chip"], sv["prefill_ms"], sv["tpot_ms"],
                 sv["memory_mb"], sv["concurrency"], sv["max_ctx"]))
    else:
        result = engine.parallelism_optimization()
        if result is None:
            raise RuntimeError("no feasible strategy under memory constraint %.1f GB" % args.memory_constraint)
    path = engine.save_results(result, args.output_config_path)
    print("saved searched strategy to %s" % path)
    return result


def main(argv=None):
    args = initialize_galvatron(mode="search", argv=argv)
    return search(args)


if __name__ == "__main__":
    main()
