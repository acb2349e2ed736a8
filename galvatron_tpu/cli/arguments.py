"""Argument system for all execution modes.

Reference: ``initialize_galvatron(model_args, mode)`` with modes
``train_dist | train | profile | search | profile_hardware`` (core/arguments.py:8-30),
runtime flags (core/runtime/arguments.py:1-215), search flags
(core/search_engine/arguments.py:1-146) and profiler flags
(core/profiler/arguments.py:1-180). Flag names match the reference where the
concept survives on TPU; NCCL/MPI/apex-specific knobs are dropped and a few
TPU-only knobs (mesh axis control, pallas toggles) are added.
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Callable, Optional, Sequence

MODES = ("train", "train_dist", "search", "profile", "profile_hardware", "serve")


def _add_model_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("model")
    g.add_argument("--model_type", type=str, default="llama", help="model family (see models/registry.py)")
    g.add_argument("--model_size", type=str, default=None, help="meta-config preset, e.g. llama-7b")
    g.add_argument("--set_model_config_manually", type=int, default=0)
    g.add_argument("--set_layernum_manually", type=int, default=0)
    g.add_argument("--set_seqlen_manually", type=int, default=0)
    g.add_argument("--hidden_size", type=int, default=None)
    g.add_argument("--num_attention_heads", type=int, default=None)
    g.add_argument("--num_kv_heads", type=int, default=None)
    g.add_argument("--ffn_hidden_size", type=int, default=None)
    g.add_argument("--num_layers", type=int, default=None)
    g.add_argument("--seq_length", type=int, default=None)
    g.add_argument("--vocab_size", type=int, default=None)
    g.add_argument("--mixed_precision", type=str, default="bf16", choices=("fp32", "bf16"))


def _add_parallel_args(p: argparse.ArgumentParser):
    """GLOBAL-mode strategy flags (reference runtime/arguments.py)."""
    g = p.add_argument_group("parallel")
    g.add_argument("--pp_deg", type=int, default=1)
    g.add_argument("--global_tp_deg", type=int, default=1)
    g.add_argument("--global_cp_deg", type=int, default=1)
    g.add_argument("--cp_mode", type=str, default="zigzag", choices=("ring", "zigzag"))
    g.add_argument("--sdp", type=int, default=0, help="1 => ZeRO-3 on every layer")
    g.add_argument("--global_train_batch_size", type=int, default=8)
    g.add_argument("--chunks", type=int, default=1, help="number of microbatches")
    g.add_argument("--pipeline_type", type=str, default="gpipe", choices=("gpipe", "pipedream_flush"))
    g.add_argument("--default_dp_type", type=str, default="ddp", choices=("ddp", "zero2", "zero3"),
                   help="how a layer's state is held over dp. ddp: whole on every replica. zero2: "
                        "Adam's moments, the accumulated gradient and the float32 parameters split "
                        "over dp; the step gathers a compute-dtype (bf16) copy of the parameters "
                        "once, and float32 only what the model reads in float32. zero3: parameters "
                        "split over dp and gathered at each use (config/strategy.py DP_TYPES)")
    g.add_argument("--embed_sdp", type=int, default=0)
    g.add_argument("--vocab_tp", type=int, default=1)
    g.add_argument("--vocab_sp", type=int, default=0)
    g.add_argument("--vocab_cp", type=int, default=1)
    g.add_argument("--use-ulysses", dest="use_ulysses", action="store_true",
                   help="repurpose the tp axis as a Ulysses sequence axis")
    g.add_argument("--sequence-parallel", dest="sequence_parallel", action="store_true", default=True)
    g.add_argument("--no-sequence-parallel", dest="sequence_parallel", action="store_false")
    g.add_argument("--checkpoint", type=int, default=0, help="1 => activation remat on every layer")
    g.add_argument("--no_scan_layers", dest="scan_layers", action="store_false", default=True,
                   help="disable stacking same-strategy layer runs into lax.scan "
                        "(falls back to unrolled per-layer tracing; compile "
                        "time grows with depth again)")
    g.add_argument("--remat_policy", type=str, default="full",
                   choices=("none", "full", "dots_saveable", "nothing_saveable"),
                   help="DEFAULT jax.checkpoint policy for layers with "
                        "checkpoint=1: 'full' remats everything (default), "
                        "'dots_saveable' keeps matmul outputs resident, "
                        "'none' neutralizes the checkpoint flags. Precedence: "
                        "remat_policy is a per-layer SERIALIZED strategy "
                        "field; this flag only fills layers whose JSON lacks "
                        "the key (uniform configs stamp it on every layer). "
                        "A non-default flag shadowed by serialized per-layer "
                        "values warns GLS103")
    g.add_argument("--tp_comm_mode", type=str, default="gspmd",
                   choices=("gspmd", "shard_map", "overlap"),
                   help="TP-collective execution path for layer runs: "
                        "'gspmd' lets the compiler infer the collectives "
                        "(they serialize with the matmuls), 'shard_map' "
                        "hand-writes them (visible, undecomposed), 'overlap' "
                        "decomposes them into ppermute-pipelined chunked "
                        "matmuls so communication hides behind compute "
                        "(parallel/tp_shard_map.py; unsupported configs are "
                        "refused with GLS012, never silently approximated)")
    g.add_argument("--grad_comm_dtype", type=str, default="none",
                   choices=("none", "bf16", "int8", "fp8_e4m3"),
                   help="wire precision of the DP/ZeRO gradient sync "
                        "(GLOBAL mode: every layer; a searched JSON carries "
                        "per-layer values). int8/fp8_e4m3 run the explicit "
                        "blockwise-quantized shard_map ring "
                        "(parallel/quant_collectives.py, ZeRO++-style); "
                        "unsupported layouts refuse with GLS013")
    g.add_argument("--param_comm_dtype", type=str, default="none",
                   choices=("none", "bf16", "int8", "fp8_e4m3"),
                   help="wire precision of the ZeRO-3 parameter all-gather "
                        "(inert without zero3 layers; the linter warns)")
    g.add_argument("--comm_quant_block", type=int, default=64,
                   help="elements per absmax scale block for every "
                        "quantized collective payload")
    g.add_argument("--tp_comm_quant", type=str, default="none",
                   choices=("none", "bf16", "int8", "fp8_e4m3"),
                   help="wire precision of the manual TP ring payloads "
                        "(requires --tp_comm_mode shard_map|overlap; "
                        "refused under gspmd with GLS013). Runtime knob "
                        "like --tp_comm_mode: not serialized")
    g.add_argument("--galvatron_config_path", type=str, default=None,
                   help="searched per-layer strategy JSON; overrides the GLOBAL flags above")
    g.add_argument("--world_size", type=int, default=None, help="devices to use (default: all)")


def _add_train_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("training")
    g.add_argument("--train_iters", type=int, default=20)
    g.add_argument("--lr", type=float, default=1e-4)
    g.add_argument("--min_lr", type=float, default=1e-5)
    g.add_argument("--weight_decay", type=float, default=0.01)
    g.add_argument("--adam_beta1", type=float, default=0.9)
    g.add_argument("--adam_beta2", type=float, default=0.999)
    g.add_argument("--adam_eps", type=float, default=1e-8)
    g.add_argument("--clip_grad", type=float, default=1.0)
    g.add_argument("--lr_decay_style", type=str, default="cosine", choices=("cosine", "linear", "constant"))
    g.add_argument("--lr_warmup_iters", type=int, default=0)
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--data_path", type=str, default=None, help="indexed dataset prefix; default: synthetic data")
    g.add_argument("--split", type=str, default="969,30,1",
                   help="train/valid/test document weights over --data_path "
                   "(Megatron --split semantics)")
    g.add_argument("--eval_interval", type=int, default=0,
                   help="run a valid-split eval pass every N iterations (0=off)")
    g.add_argument("--eval_iters", type=int, default=5,
                   help="batches averaged per eval pass (and for the final "
                   "test-split eval)")
    # dispatch-ahead input pipeline / deferred host sync (runtime/prefetch.py
    # + the cli/train.py drain window): see README "Steady-state throughput"
    g.add_argument("--prefetch_batches", type=int, default=2,
                   help="batches the background prefetcher prepares and "
                        "device_puts ahead of the step consuming them "
                        "(0 => prepare batches on the critical path)")
    g.add_argument("--inflight_steps", type=int, default=2,
                   help="dispatched steps whose metrics may stay undrained, "
                        "so the host dispatches ahead of the device; anomaly "
                        "detection and iteration logs lag by at most this "
                        "many steps (forced drain at eval/save/preemption "
                        "boundaries; 0 => drain every step)")
    g.add_argument("--profile", type=int, default=0, help="enable the runtime profiler")
    g.add_argument("--train_log_dir", type=str, default=None,
                   help="tee rank-0 iteration stats to <dir>/train_<model>.log")
    # observability (galvatron_tpu/obs): structured telemetry + XLA tracing
    o = p.add_argument_group("observability")
    o.add_argument("--telemetry", type=str, default=None,
                   help="write a schema-versioned JSONL event stream "
                        "(per-step timing/loss/MFU + lifecycle events) to "
                        "this path; analyze with `python -m galvatron_tpu.cli "
                        "report <path>`")
    o.add_argument("--telemetry_buffer", type=int, default=1024,
                   help="bounded queue depth of the background telemetry "
                        "writer (a stalled filesystem back-pressures instead "
                        "of ballooning memory)")
    o.add_argument("--xla_trace", type=str, default=None,
                   help="capture an XLA profiler trace (Perfetto/TensorBoard) "
                        "into this directory for the --trace_steps window; "
                        "skipped gracefully on backends that cannot trace")
    o.add_argument("--trace_steps", type=str, default="3:5",
                   help="K:N (inclusive) iteration window for --xla_trace; "
                        "keep it a few steps wide — traces are large")
    g.add_argument("--save_profiled_memory", type=int, default=0)
    g.add_argument("--profile_type", type=str, default="computation", choices=("computation", "memory"))
    # checkpointing (reference runtime/arguments.py --load_iteration;
    # llama_hf/LlamaModel_checkpoint.py save/load)
    g.add_argument("--save", type=str, default=None, help="checkpoint output dir")
    g.add_argument("--load", type=str, default=None, help="checkpoint dir to resume from")
    g.add_argument("--load_iteration", type=int, default=None)
    g.add_argument("--save_interval", type=int, default=0, help="0 => only at end")
    g.add_argument("--log_interval", type=int, default=1)
    # resilience (runtime/resilience.py): preemption-safe checkpointing,
    # anomaly guard, retry/retention around checkpoint and dataloader I/O
    r = p.add_argument_group("resilience")
    r.add_argument("--keep_latest_k", type=int, default=0,
                   help="GC all but the newest K checkpoints after each save "
                        "(0 => keep all)")
    r.add_argument("--emergency_save", type=int, default=1,
                   help="on SIGTERM/SIGINT, save a checkpoint at the next "
                        "step boundary (needs --save) and exit cleanly")
    r.add_argument("--trace_lint", type=int, default=0,
                   help="before compiling, abstract-eval the train step and "
                        "run the traced-program linter (analysis/"
                        "trace_lint.py, GLT codes): refuses on jaxpr-level "
                        "hazards (pinned GSPMD miscompile shapes, dangling "
                        "axis_index closures), prints warnings otherwise; "
                        "adds one extra trace, no compile")
    r.add_argument("--anomaly_guard", type=int, default=1,
                   help="skip updates whose loss/grad norm is NaN/Inf (or "
                        "spikes past --loss_spike_factor) instead of "
                        "training through them")
    r.add_argument("--loss_spike_factor", type=float, default=0.0,
                   help="treat loss > factor * EMA(accepted losses) as an "
                        "anomaly (0 => NaN/Inf detection only)")
    r.add_argument("--anomaly_min_history", type=int, default=5,
                   help="accepted losses before the spike cap arms")
    r.add_argument("--anomaly_max_strikes", type=int, default=3,
                   help="consecutive anomalies before rolling back to the "
                        "last checkpoint")
    r.add_argument("--anomaly_max_rollbacks", type=int, default=3,
                   help="rollbacks before giving up with an error")
    r.add_argument("--anomaly_reseed", type=int, default=0,
                   help="offset added to the data-stream step after each "
                        "rollback, to step past a deterministically "
                        "poisoned batch (0 => replay the same stream)")
    r.add_argument("--ckpt_retries", type=int, default=2,
                   help="retry budget (exponential backoff) for checkpoint "
                        "save/restore and dataloader I/O")
    r.add_argument("--ckpt_retry_backoff", type=float, default=0.5,
                   help="base backoff delay in seconds")
    r.add_argument("--verify_checkpoint", type=int, default=1,
                   help="verify the integrity manifest on resume and fall "
                        "back to the latest intact checkpoint")
    # elastic degraded-mesh resume (runtime/elastic.py): checkpoints carry a
    # provenance block, so a run that lost devices can restore under a NEW
    # strategy instead of failing the strategy assert
    r.add_argument("--elastic", type=str, default="off",
                   choices=("off", "resume", "search"),
                   help="on --load with a changed device count: 'resume' "
                        "restores under the --elastic_strategy JSON, "
                        "'search' re-runs the strategy search for the "
                        "surviving world size under the saved memory "
                        "budget; 'off' keeps the strict same-strategy "
                        "assert (refuses mesh changes)")
    r.add_argument("--elastic_strategy", type=str, default=None,
                   help="replacement strategy JSON for the surviving mesh "
                        "(implies cross-strategy restore; used by both "
                        "--elastic modes when given)")
    r.add_argument("--elastic_memory_gb", type=float, default=None,
                   help="HBM budget per chip for the elastic re-search "
                        "(default: the budget recorded in the checkpoint's "
                        "provenance, else %.0f GB); also recorded into new "
                        "checkpoints' provenance" % 16.0)
    # self-healing runs (runtime/health.py + runtime/elastic.migrate): the
    # training watchdog, the periodic mesh-health probe, and live in-memory
    # strategy migration (no checkpoint round-trip)
    r.add_argument("--watchdog", type=float, default=0.0,
                   help="arm the training watchdog with this additive floor "
                        "in seconds (0 = off): a step making no progress for "
                        "watchdog_factor * median(step time) + floor seconds "
                        "first drains-and-retries, then emergency-saves and "
                        "exits with code 3")
    r.add_argument("--watchdog_factor", type=float, default=4.0,
                   help="k in the learned watchdog deadline "
                        "k * median(steady step time) + --watchdog floor")
    r.add_argument("--watchdog_startup_s", type=float, default=600.0,
                   help="watchdog deadline before enough steps have drained "
                        "to learn one (first-step compiles take minutes)")
    r.add_argument("--mesh_probe_interval", type=float, default=0.0,
                   help="seconds between mesh-health probes (device "
                        "enumeration diff + tiny jitted collective under a "
                        "timeout; 0 = off)")
    r.add_argument("--migrate_on_degrade", type=int, default=0,
                   help="when the mesh probe reports a degraded world, "
                        "live-migrate to a strategy for the surviving "
                        "devices in memory (--elastic_strategy JSON if "
                        "given, else a fresh search) instead of exiting; "
                        "SIGUSR1 triggers the same migration manually")
    # silent-corruption sentinel (runtime/sdc.py): in-jit integrity digests,
    # cross-replica voting, strike ladder -> quarantine -> migration
    r.add_argument("--sdc_check", type=str, default="off",
                   choices=("off", "digest", "vote"),
                   help="silent-data-corruption sentinel: 'digest' adds a "
                        "layout-invariant integrity digest of the params as "
                        "a pure step side-output (bitwise-transparent); "
                        "'vote' additionally digests every data-parallel "
                        "replica's input params under shard_map and "
                        "majority-votes at drain time — a lying device is "
                        "localized, the frozen state repaired from a "
                        "healthy replica, the step re-executed, and repeat "
                        "offenders quarantined into --migrate_on_degrade; "
                        "downgrades to 'digest' with a log line when the "
                        "layout has no dp redundancy to vote with")
    r.add_argument("--sdc_interval", type=int, default=None,
                   help="emit the sdc_check telemetry heartbeat every N "
                        "drained steps (default 1; digests are computed "
                        "in-jit regardless so the compiled program does not "
                        "depend on the interval)")
    r.add_argument("--sdc_strikes", type=int, default=2,
                   help="consecutive mismatch observations naming the same "
                        "device before it is quarantined (each observation "
                        "first repairs + re-executes; a tie vote only ever "
                        "re-executes)")
    # online autotuner (runtime/autotune.py): measured-cost re-search with
    # in-memory strategy hot-swap once the step time settles
    r.add_argument("--autotune", type=str, default="off",
                   choices=("off", "observe", "apply"),
                   help="once the steady-state detector settles, fold the "
                        "measured step time/memory back into the profiler "
                        "tables and re-run the strategy search on them: "
                        "'observe' logs the decision it WOULD take (the "
                        "counterfactual), 'apply' hot-swaps to the new "
                        "winner in memory through the live-migration path "
                        "when it clears the hysteresis margin and the "
                        "remaining-steps amortization check")
    r.add_argument("--autotune_margin", type=float, default=None,
                   help="hysteresis: the searched winner must beat the "
                        "incumbent's predicted step time by more than this "
                        "fraction to swap (default 0.05)")


def _add_profile_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("model profiling")
    g.add_argument("--profile_mode", type=str, default="static", choices=("static", "batch", "sequence"))
    g.add_argument("--profile_batch_size", type=int, default=8)
    g.add_argument("--profile_min_batch_size", type=int, default=1)
    g.add_argument("--profile_max_batch_size", type=int, default=8)
    g.add_argument("--batch_size_step", type=int, default=1)
    g.add_argument("--profile_seq_length", type=int, default=None)
    g.add_argument("--profile_min_seq_length", type=int, default=512)
    g.add_argument("--profile_max_seq_length", type=int, default=2048)
    g.add_argument("--seq_length_step", type=int, default=512)
    g.add_argument("--layernum_min", type=int, default=1)
    g.add_argument("--layernum_max", type=int, default=2)
    g.add_argument("--max_tp_deg", type=int, default=8)
    g.add_argument("--profile_remat", action="store_true", default=False,
                   help="also measure the per-remat-policy backward "
                        "recompute fraction (remat_recompute_frac in the "
                        "computation table; TimeCostModel's profiled "
                        "override for the remat search axis)")


def _add_hardware_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("hardware profiling")
    g.add_argument("--start_mb", type=float, default=1.0)
    g.add_argument("--end_mb", type=float, default=64.0)
    g.add_argument("--scale", type=int, default=2)
    g.add_argument("--avg_or_min_or_first", type=str, default="avg", choices=("avg", "min", "first"))
    g.add_argument("--max_pp_deg", type=int, default=8)
    g.add_argument("--overlap_time_multiply", type=int, default=4)


def _add_search_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("search")
    g.add_argument("--profile_seq_length", type=int, default=None,
                   help="seq length the profiling tables were written at "
                        "(must match --profile_seq_length of the profile run)")
    g.add_argument("--memory_constraint", type=float, default=16.0, help="HBM budget per chip, GB")
    g.add_argument("--search_space", type=str, default="full",
                   choices=("full", "dp+tp", "dp+pp", "3d", "dp", "sdp", "tp", "pp"))
    g.add_argument("--sp_space", type=str, default="tp", choices=("tp+sp", "tp", "sp"))
    for name in ("dp", "tp", "vtp", "pp", "sdp", "ckpt", "tp_consec"):
        g.add_argument("--disable_%s" % name, type=int, default=0)
    g.add_argument("--enable_cp", type=int, default=0)
    g.add_argument("--max_tp_deg_search", dest="search_max_tp_deg", type=int, default=8)
    g.add_argument("--max_pp_deg_search", dest="search_max_pp_deg", type=int, default=8)
    g.add_argument("--max_cp_deg", type=int, default=4)
    g.add_argument("--min_bsz", type=int, default=8)
    g.add_argument("--max_bsz", type=int, default=None)
    g.add_argument("--bsz_scale", type=int, default=8)
    g.add_argument("--settle_bsz", type=int, default=None)
    g.add_argument("--settle_chunk", type=int, default=None)
    g.add_argument("--fine_grained_mode", type=int, default=1)
    g.add_argument("--use_pipeline_costmodel", type=int, default=0)
    g.add_argument("--parallel_search", type=int, default=0)
    g.add_argument("--log_dir", type=str, default="logs")
    g.add_argument("--output_config_path", type=str, default=None)
    # measured tables from `report --emit_profiles` (or a real profile run):
    # explicit paths override the conventional config-dir lookup
    g.add_argument("--time_profile_path", type=str, default=None,
                   help="explicit computation-profiling JSON to search on "
                        "(overrides the per-model config-dir convention; "
                        "pairs with --memory_profile_path)")
    g.add_argument("--memory_profile_path", type=str, default=None,
                   help="explicit memory-profiling JSON to search on "
                        "(overrides the per-model config-dir convention; "
                        "pairs with --time_profile_path)")
    # comm-precision search axis (ROADMAP item 2: EQuARX / ZeRO++)
    g.add_argument("--comm_quant", type=str, default="off",
                   choices=("off", "bf16", "int8", "fp8_e4m3"),
                   help="let the search choose per-layer grad/param comm "
                        "precision: each pure-dp strategy gains a variant "
                        "whose gradient sync (and zero3 gather) uses this "
                        "wire dtype; the DP picks per layer under the "
                        "accuracy budget. off (default) keeps the "
                        "full-precision-only space")
    g.add_argument("--comm_quant_block", type=int, default=64,
                   help="blockwise-quantization block size priced by the "
                        "cost models and emitted into the strategy JSON")
    g.add_argument("--comm_quant_budget", type=float, default=1.0,
                   help="accuracy budget: max fraction of layers allowed a "
                        "quantized gradient sync (1.0 = all; 0.0 "
                        "effectively disables). Layers with the smallest "
                        "modeled time saving are de-quantized first")
    # remat search axis (ROADMAP item 1: per-layer-run remat tuning)
    g.add_argument("--remat_search", action="store_true", default=False,
                   help="let the search choose per-layer remat policies: "
                        "each checkpointed strategy gains a 'dots_saveable' "
                        "variant (pin the dot outputs, recompute only the "
                        "cheap tail), so a tight --memory_budget yields a "
                        "MIXED per-layer plan between all-none (most memory) "
                        "and all-full (most recompute); emitted as the "
                        "serialized per-layer remat_policy field")
    # latency-aware serving objective (ROADMAP item 4)
    g.add_argument("--objective", type=str, default="train",
                   choices=("train", "serve"),
                   help="'train' maximises training throughput (classic DP "
                        "search); 'serve' prices prefill (compute-bound) and "
                        "decode (bandwidth-bound) separately and maximises "
                        "decode tokens/s/chip under the p99 latency bounds, "
                        "emitting a config that carries serve_max_concurrency"
                        "/serve_page_size; an unsatisfiable bound refuses "
                        "with GLS014 instead of emitting a config that "
                        "misses it")
    g.add_argument("--p99_ttft_ms", type=float, default=0.0,
                   help="serve objective: p99 time-to-first-token bound, ms "
                        "(0 = unbounded)")
    g.add_argument("--p99_tpot_ms", type=float, default=0.0,
                   help="serve objective: p99 time-per-output-token bound, "
                        "ms (0 = unbounded)")
    g.add_argument("--serve_max_concurrency", type=int, default=8,
                   help="serve objective: decode slots the engine must hold "
                        "KV for (sizes both the KV memory term and the "
                        "decode batch the throughput objective prices)")
    g.add_argument("--serve_page_size", type=int, default=16,
                   help="serve objective: KV page granularity; contexts "
                        "round up to whole pages")
    g.add_argument("--serve_hbm_gbps", type=float, default=100.0,
                   help="per-chip HBM read bandwidth backing the decode "
                        "bandwidth roofline")
    g.add_argument("--trace_lint", type=int, default=0,
                   help="before save_results emits the winner, abstract-"
                        "trace the train step it would jit and refuse on "
                        "GLT errors (analysis/trace_lint.py); needs "
                        "world_size visible devices, skipped otherwise")


def _add_serve_args(p: argparse.ArgumentParser):
    g = p.add_argument_group("serving")
    g.add_argument("--load", type=str, default=None,
                   help="checkpoint dir to restore params from (train-layout "
                        "checkpoints relayout into the serve strategy via "
                        "the strategy-portable restore path; omitted => "
                        "fresh random init, for smoke runs)")
    g.add_argument("--load_iteration", type=int, default=None)
    g.add_argument("--serve_max_concurrency", type=int, default=None,
                   help="decode slots (defaults to the strategy JSON's "
                        "serve_max_concurrency, else 8)")
    g.add_argument("--serve_page_size", type=int, default=None,
                   help="KV page granularity (defaults to the strategy "
                        "JSON's serve_page_size, else 16)")
    g.add_argument("--serve_max_pages", type=int, default=None,
                   help="pages per slot (default: enough for the model's "
                        "max_seq_len)")
    g.add_argument("--num_requests", type=int, default=16,
                   help="synthetic requests to run (ignored with --replay)")
    g.add_argument("--rate_rps", type=float, default=0.0,
                   help="Poisson arrival rate for the synthetic load "
                        "(0 = all requests queued at t=0)")
    g.add_argument("--prompt_len_min", type=int, default=4)
    g.add_argument("--prompt_len_max", type=int, default=16)
    g.add_argument("--max_new_tokens", type=int, default=8,
                   help="output tokens per synthetic request")
    g.add_argument("--replay", type=str, default=None,
                   help="JSONL trace ({arrival_s, prompt_len, "
                        "max_new_tokens} per line) replayed instead of the "
                        "Poisson load")
    g.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy argmax; >0 samples from the tempered "
                        "softmax")
    g.add_argument("--seed", type=int, default=1234)
    g.add_argument("--telemetry", type=str, default=None,
                   help="write serve_request/decode_batch events to this "
                        "JSONL (analyze with `cli report`)")
    g.add_argument("--telemetry_buffer", type=int, default=1024)
    r = p.add_argument_group("serving resilience")
    # admission control + overload shedding (serve/engine.ContinuousBatcher)
    r.add_argument("--p99_ttft_ms", type=float, default=0.0,
                   help="shed (retryable) any pending request whose "
                        "predicted TTFT — waited + queue depth x learned "
                        "median prefill/tick cost — exceeds this bound "
                        "(0 = admit everything; defaults to the strategy "
                        "JSON's serve_p99_ttft_ms when set)")
    r.add_argument("--max_pending", type=int, default=0,
                   help="bound on the arrived-but-unadmitted queue; "
                        "overflow sheds retryable from the newest arrivals "
                        "(0 = unbounded; defaults to the strategy JSON's "
                        "serve_max_pending when set)")
    r.add_argument("--request_timeout_s", type=float, default=0.0,
                   help="per-request TTFT deadline from arrival; a pending "
                        "request past it sheds retryable (0 = none)")
    r.add_argument("--shed_min_samples", type=int, default=3,
                   help="prefills AND decode ticks observed before the "
                        "predicted-TTFT shedder arms (compile warmup never "
                        "sheds)")
    # serve watchdog + degraded-mesh migration: the serving twins of the
    # train-mode flags of the same names (runtime/health.py, runtime/elastic)
    r.add_argument("--watchdog", type=float, default=0.0,
                   help="arm the serve watchdog with this additive floor in "
                        "seconds (0 = off): a prefill/decode tick making no "
                        "progress for watchdog_factor * median(tick time) + "
                        "floor seconds first drains-and-retries, then "
                        "gracefully drains the batcher and exits 3")
    r.add_argument("--watchdog_factor", type=float, default=4.0,
                   help="k in the learned watchdog deadline "
                        "k * median(tick time) + --watchdog floor")
    r.add_argument("--watchdog_startup_s", type=float, default=600.0,
                   help="watchdog deadline before enough ticks have run to "
                        "learn one (first-bucket compiles take minutes)")
    r.add_argument("--mesh_probe_interval", type=float, default=0.0,
                   help="seconds between mesh-health probes between ticks "
                        "(0 = off)")
    r.add_argument("--migrate_on_degrade", type=int, default=0,
                   help="on a degraded mesh verdict, re-search a serve "
                        "strategy for the surviving world, relayout params "
                        "in memory, rebuild the KV cache, and journal-replay "
                        "in-flight requests instead of exiting; infeasible "
                        "worlds refuse with GLS015 (exit 2)")
    r.add_argument("--elastic_strategy", type=str, default=None,
                   help="replacement serve strategy JSON for the surviving "
                        "mesh (skips the degraded-world re-search)")
    r.add_argument("--elastic_memory_gb", type=float, default=None,
                   help="HBM budget per chip for the degraded-world serve "
                        "re-search (default %.0f GB)" % 16.0)


def build_parser(mode: str, extra_args_provider: Optional[Callable] = None) -> argparse.ArgumentParser:
    if mode not in MODES:
        raise ValueError("mode must be one of %s, got %r" % (MODES, mode))
    p = argparse.ArgumentParser("galvatron_tpu-%s" % mode, allow_abbrev=False)
    p.add_argument("--config_dir", type=str, default="configs",
                   help="where profiled/searched JSON configs live")
    g = p.add_argument_group("distributed")
    g.add_argument("--coordinator_address", type=str, default=None,
                   help="multi-host bootstrap: host:port of process 0 "
                        "(TPU pod slices auto-discover; see runtime/distributed.py)")
    g.add_argument("--num_processes", type=int, default=None,
                   help="multi-host bootstrap: total process count")
    g.add_argument("--process_id", type=int, default=None,
                   help="multi-host bootstrap: this process's rank")
    _add_model_args(p)
    if mode in ("train", "train_dist"):
        _add_parallel_args(p)
        _add_train_args(p)
        _add_profile_args(p)  # train runs double as profiling runs (reference model_profiler launches train_dist)
    elif mode == "search":
        _add_search_args(p)
    elif mode == "profile":
        _add_profile_args(p)
        p.add_argument("--profile_type_model", dest="profile_type", type=str,
                       default="computation", choices=("computation", "memory"))
    elif mode == "profile_hardware":
        _add_hardware_args(p)
    elif mode == "serve":
        _add_parallel_args(p)
        _add_serve_args(p)
    if extra_args_provider is not None:
        extra_args_provider(p)
    return p


def initialize_galvatron(extra_args_provider: Optional[Callable] = None,
                         mode: str = "train_dist",
                         argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    """Parse args for `mode`. `extra_args_provider(parser)` may add model-
    specific flags (the reference's per-model model_args hook,
    core/arguments.py:8-30)."""
    args = build_parser(mode, extra_args_provider).parse_args(argv)
    args.galvatron_mode = mode
    if mode in ("train", "train_dist", "profile_hardware", "serve"):
        # multi-host bootstrap before any jax.devices() call (the reference's
        # torch.distributed env:// init point, core/arguments.py:8-30)
        from galvatron_tpu.runtime.distributed import initialize_distributed

        initialize_distributed(
            getattr(args, "coordinator_address", None),
            getattr(args, "num_processes", None),
            getattr(args, "process_id", None),
        )
    return args


# --------------------------------------------------------- args -> structures
def hp_config_from_args(args, num_layers: int, world_size: int):
    """GLOBAL flags or a searched JSON -> HybridParallelConfig (reference
    get_hybrid_parallel_configs_api's two modes, hybrid_parallel_config.py:17-158)."""
    from galvatron_tpu.config.strategy import HybridParallelConfig

    # runtime execution knobs. remat_policy is special: it is ALSO a
    # serialized per-layer field — the flag only fills layers whose JSON
    # lacks the key (from_json default) or stamps uniform configs
    exec_kw = dict(
        scan_layers=getattr(args, "scan_layers", True),
        remat_policy=getattr(args, "remat_policy", "full"),
        tp_comm_mode=getattr(args, "tp_comm_mode", "gspmd"),
        tp_comm_quant=getattr(args, "tp_comm_quant", "none"),
    )
    if getattr(args, "galvatron_config_path", None):
        # grad/param comm dtypes + comm_quant_block are SERIALIZED strategy
        # fields: the searched JSON's per-layer values win over the GLOBAL
        # flags (like every other per-layer field)
        return HybridParallelConfig.from_json(
            args.galvatron_config_path, world_size=world_size,
            global_bsz=args.global_train_batch_size, mixed_precision=args.mixed_precision,
            **exec_kw,
        )
    return HybridParallelConfig.uniform(
        world_size=world_size,
        num_layers=num_layers,
        pp=args.pp_deg,
        tp=args.global_tp_deg,
        cp=args.global_cp_deg,
        sp=1 if args.use_ulysses else 0,
        sdp=args.sdp,
        checkpoint=args.checkpoint,
        grad_comm_dtype=getattr(args, "grad_comm_dtype", "none"),
        param_comm_dtype=getattr(args, "param_comm_dtype", "none"),
        comm_quant_block=getattr(args, "comm_quant_block", 64),
        global_bsz=args.global_train_batch_size,
        chunks=args.chunks,
        pipeline_type=args.pipeline_type,
        default_dp_type=args.default_dp_type,
        vocab_tp=args.vocab_tp,
        vocab_sp=args.vocab_sp,
        vocab_cp=args.vocab_cp,
        embed_sdp=args.embed_sdp,
        mixed_precision=args.mixed_precision,
        sequence_parallel=args.sequence_parallel,
        cp_mode=args.cp_mode,
        **exec_kw,
    )


def model_config_from_args(args):
    """Resolve the model family + TransformerConfig from flags (the reference's
    three-way manual override scheme, models/gpt_hf/meta_configs/config_utils.py:30-56)."""
    from galvatron_tpu.models.registry import get_family

    fam = get_family(args.model_type)
    size = args.model_size or fam.default_size
    overrides = {}
    if args.set_model_config_manually:
        for flag, key in (
            ("hidden_size", "hidden_size"),
            ("num_attention_heads", "num_heads"),
            ("num_kv_heads", "num_kv_heads"),
            ("ffn_hidden_size", "ffn_hidden"),
            ("num_layers", "num_layers"),
            ("vocab_size", "vocab_size"),
            ("seq_length", "max_seq_len"),
        ):
            v = getattr(args, flag, None)
            if v is not None:
                overrides[key] = v
    else:
        if args.set_layernum_manually and args.num_layers is not None:
            overrides["num_layers"] = args.num_layers
        if args.set_seqlen_manually and args.seq_length is not None:
            overrides["max_seq_len"] = args.seq_length
    if args.mixed_precision == "bf16":
        import jax.numpy as jnp

        overrides.setdefault("compute_dtype", jnp.bfloat16)
    try:
        cfg = fam.config_fn(size, **overrides)
    except TypeError as e:
        raise ValueError(
            "model overrides %s not supported by family %r (%s); t5/swin use "
            "their own config fields — pass sizes via --model_size or the "
            "family config_fn" % (sorted(overrides), fam.name, e)
        ) from None
    return fam, cfg
