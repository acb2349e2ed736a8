"""Elastic degraded-mesh resume: re-plan the strategy for the surviving mesh.

Galvatron's premise is that the optimal layer-wise strategy is a function of
the hardware (PAPER.md) — so when the hardware changes mid-run (TPU
preemption shrinking a slice, an ICI link flap dropping a host, a chip
failure), the right response is not "refuse to resume" but "re-optimize for
what survived". This module is the resume-side half of that story; the
save-side half is the provenance block runtime/checkpoint.py embeds in every
integrity manifest (:func:`build_provenance`).

On resume with ``--elastic {resume,search}`` the driver calls
:func:`resolve_resume_strategy`, which

1. reads the newest intact manifest's provenance (strategy JSON, mesh/device
   count, model-config digest, optimizer digest, chunks);
2. refuses with structured GLS2xx diagnostics (exit code 2 at the CLI) when
   the checkpoint cannot be resumed safely: different model-config digest
   (GLS201), no provenance at all (GLS204), a changed mesh with no way to
   pick a new strategy (GLS205), or no strategy that fits the memory budget
   on the surviving devices (GLS203);
3. on a world-size match returns the SAVED strategy — same-strategy resume
   stays bitwise identical to the non-elastic path;
4. on a mismatch either loads the user-supplied ``--elastic_strategy`` JSON
   or re-runs :class:`GalvatronSearchEngine` for the surviving world size
   under the same memory budget — with profiled cost tables when the config
   dir has them, and an analytic Megatron-style fallback (the same tables
   the strategy linter's GLS101 estimate uses) when it does not.

The actual cross-strategy restore (different shardings, different pipeline
layout, opt_state re-sharded leaf-wise with structural checks) is
``load_checkpoint(..., target=)`` in runtime/checkpoint.py.

Live in-memory migration
------------------------
:func:`migrate` is the no-disk sibling of the cross-strategy restore: it
moves the LIVE params + optimizer state from the running model onto a new
strategy's model entirely on-device — the same ``_relayout_tree`` family
re-lays out pipeline-layout changes, a plain sharded ``device_put`` handles
everything else — so a degraded or re-planned run swaps strategies mid-
process and continues from the same step, bitwise-identical to a
checkpoint round-trip under the target strategy (pinned by
tests/cli/test_migration.py). :func:`resolve_migration_strategy` picks the
target (operator-supplied JSON or a fresh search for the surviving world)
and refuses infeasible migrations with GLS207; the driver wires both to
the watchdog / mesh-health probe (runtime/health.py) and to a SIGUSR1
manual trigger.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

from galvatron_tpu.analysis import diagnostics as D
from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.obs import telemetry

DEFAULT_MEMORY_GB = 16.0  # matches the search CLI's --memory_constraint default

# model-config fields excluded from the digest: precision knobs are runtime
# choices (the manifest's spec_digest machinery already handles a dtype
# change), not model identity
_DIGEST_EXCLUDE = ("compute_dtype", "param_dtype", "attn_impl")
# the fields of the config checkpoints in the field were first written from
# (PR 26's): always digested. Any other field of `TransformerConfig` at its
# dataclass default is left out, so that a model's digest is what it was before
# the field existed and a new model's fields need no edit here
_DIGEST_ALWAYS = frozenset((
    "hidden_size", "num_heads", "num_layers", "vocab_size", "max_seq_len", "num_kv_heads", "ffn_hidden",
    "head_dim", "norm_type", "activation", "position_type", "causal", "pre_norm", "tie_embeddings", "qkv_bias",
    "mlp_bias", "out_bias", "layernorm_eps", "rope_theta",
    "init_std", "type_vocab_size", "embed_norm", "head_type", "num_classes", "pool_type", "input_type",
    "image_size", "patch_size", "num_channels", "use_cls_token"))


def _stable_json(obj: Any) -> str:
    return json.dumps(obj, sort_keys=True, default=str)


def model_config_digest(model_cfg: Any) -> str:
    """sha256 over the model's architectural identity. Restoring a checkpoint
    into a model with a different digest is refused (GLS201): same-shaped
    trees with different semantics (e.g. swapped activation) would restore
    cleanly and train garbage."""
    if dataclasses.is_dataclass(model_cfg):
        fields = dataclasses.asdict(model_cfg)
    else:  # duck-typed configs (tests)
        fields = {k: v for k, v in vars(model_cfg).items() if not k.startswith("_")}
    # `TransformerConfig`'s own, whatever the config: T5's and Swin's fields are all digested
    defaults = {f.name: f.default for f in dataclasses.fields(TransformerConfig) if f.name not in _DIGEST_ALWAYS}
    fields = {k: str(v) for k, v in fields.items() if k not in _DIGEST_EXCLUDE
              and not (k in defaults and v == defaults[k])}
    return hashlib.sha256(_stable_json(fields).encode()).hexdigest()


def optimizer_digest(opt_args: Any) -> str:
    """sha256 over the optimizer identity + hyperparams (runtime.optimizer
    .OptimizerArgs). A mismatch on resume is a warning, not a refusal — lr
    schedules legitimately change mid-run; the *structural* guard against a
    different optimizer lives in load_checkpoint (GLS202)."""
    fields = dataclasses.asdict(opt_args) if dataclasses.is_dataclass(opt_args) else dict(opt_args)
    return hashlib.sha256(_stable_json({k: str(v) for k, v in fields.items()}).encode()).hexdigest()


def build_provenance(
    hp: HybridParallelConfig,
    model_cfg: Any,
    opt_args: Any = None,
    mesh: Any = None,
    memory_budget_gb: Optional[float] = None,
) -> Dict[str, Any]:
    """The manifest provenance block: everything a future process on
    DIFFERENT hardware needs to decide how (or whether) to resume."""
    prov: Dict[str, Any] = {
        "format": 1,
        "strategy": hp.to_json_dict(),
        "world_size": hp.world_size,
        "chunks": hp.chunks,
        "global_bsz": hp.global_bsz,
        "mixed_precision": hp.mixed_precision,
        "model_digest": model_config_digest(model_cfg),
    }
    if mesh is not None:
        prov["mesh_shape"] = {str(k): int(v) for k, v in dict(mesh.shape).items()}
        prov["device_count"] = int(mesh.devices.size)
    else:
        prov["device_count"] = hp.world_size
    if opt_args is not None:
        prov["optimizer"] = {
            "kind": type(opt_args).__name__,
            "digest": optimizer_digest(opt_args),
        }
    if memory_budget_gb:
        prov["memory_budget_gb"] = float(memory_budget_gb)
    return prov


# ------------------------------------------------------ analytic cost tables
def analytic_model_profiles(model_cfg: Any, max_tp: int) -> Optional[Tuple[dict, dict]]:
    """(time_config, memory_config) for GalvatronSearchEngine synthesized
    from the model config alone — the no-profiles fallback, built on the
    same analytic parameter/activation tables the strategy linter's GLS101
    estimate uses, so the elastic re-search and the linter agree on what
    fits. Timing is a flops-proportional constant: with no profiled tables
    every strategy's compute scales identically, so relative comparisons
    (what the DP needs) remain meaningful."""
    from galvatron_tpu.analysis.strategy_lint import (
        _analytic_activation_dict,
        _analytic_parameter_mb,
    )

    param_mb = _analytic_parameter_mb(model_cfg)
    act = _analytic_activation_dict(model_cfg, max_tp)
    if param_mb is None or not act:
        return None
    h = getattr(model_cfg, "hidden_size", 1024)
    s = getattr(model_cfg, "max_seq_len", 2048)
    # ~12*s*h^2 flops/token forward; an arbitrary-but-fixed throughput turns
    # it into ms/layer/sample (only ratios matter without profiles)
    fwd_ms = 12.0 * s * h * h / 1e12 * 1e3
    time_config = {"layertype_0": max(fwd_ms, 1e-3), "other_time": max(fwd_ms, 1e-3)}
    states = {}
    t = 1
    while t <= max_tp:
        # embed/head model states (params + grads + adam moments ~ 16 bytes/
        # param fp32-master) sharded over vocab tp
        vocab = getattr(model_cfg, "vocab_size", 0) or 0
        states[t] = vocab * h * 16.0 / 2**20 / t
        t *= 2
    act_other = {k: v for k, v in act.items() if k != "checkpoint"}
    memory_config = {
        "layertype_0": {
            "parameter_size": param_mb,
            "tp_activation_per_bsz_dict": dict(act),
        },
        "other_memory_pp_off": {"model_states": dict(states), "activation": dict(act_other)},
        "other_memory_pp_on": {
            "first_stage": {"model_states": {k: v / 2 for k, v in states.items()},
                            "activation": {k: v / 2 for k, v in act_other.items()}},
            "last_stage": {"model_states": {k: v / 2 for k, v in states.items()},
                           "activation": {k: v / 2 for k, v in act_other.items()}},
        },
    }
    return time_config, memory_config


def analytic_hardware_profiles(world: int) -> Tuple[dict, dict, dict]:
    """(allreduce, p2p, overlap) coefficient JSONs for the no-profiles
    fallback: flat plausible ICI bandwidths — without measurements every
    collective is priced identically per byte, which still ranks strategies
    by communication VOLUME (the dominant analytic signal)."""
    allreduce = {}
    size = 2
    while size <= world:
        allreduce["allreduce_size_%d_consec_1" % size] = 100.0
        allreduce["allreduce_size_%d_consec_0" % size] = 80.0
        size *= 2
    p2p = {}
    size = 2
    while size <= world:
        p2p["pp_size_%d" % size] = 120.0
        size *= 2
    return allreduce, p2p, {"overlap_coe": 1.1}


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def search_surviving_strategy(
    model_cfg: Any,
    live_world: int,
    global_bsz: int,
    memory_budget_gb: float,
    model_type: str = "model",
    config_dir: Optional[str] = None,
    default_dp_type: str = "ddp",
    logger=None,
    time_config: Optional[dict] = None,
    memory_config: Optional[dict] = None,
    remat_search: bool = False,
) -> Optional[HybridParallelConfig]:
    """Re-run the strategy search for the surviving world size under the
    same global batch and memory budget. Profiled tables are used when
    `config_dir` has them for this model; otherwise the analytic fallback.
    Explicit `time_config`/`memory_config` (profiler JSON schema) override
    both — the online autotuner re-searches on MEASURED tables through this
    exact recipe, so settle_bsz stays pinned to the live global batch.
    Returns None when nothing fits (the caller turns that into GLS203)."""
    from galvatron_tpu.search.engine import GalvatronSearchEngine, SearchArgs

    heads = getattr(model_cfg, "num_heads", None) or 1
    num_layers = getattr(model_cfg, "num_layers", 1)
    seq_len = getattr(model_cfg, "max_seq_len", 2048)
    hidden = getattr(model_cfg, "hidden_size", 1024)
    # cap tp at the largest power of two dividing the head count so every
    # emitted strategy passes the model-aware GLS007 check
    max_tp = 1
    while max_tp * 2 <= min(heads, live_world) and heads % (max_tp * 2) == 0:
        max_tp *= 2
    args = SearchArgs(
        memory_constraint=memory_budget_gb,
        settle_bsz=global_bsz,  # the batch is part of the training trajectory
        settle_chunk=None,
        max_tp_deg=max_tp,
        max_pp_deg=min(_pow2_floor(num_layers), live_world),
        default_dp_type=default_dp_type,
        sp_space="tp",
        # remat axis: the re-plan may mix per-layer policies (and, with
        # settle_chunk=None, change chunks) when the budget rewards it
        remat_search=remat_search,
    )
    engine = GalvatronSearchEngine(
        args, live_world,
        [{"hidden_size": hidden, "seq_len": seq_len, "layer_num": num_layers}],
        config_dir=config_dir or "configs", model_name=model_type, logger=logger,
    )
    profiles = None
    if config_dir:
        profiles = _load_profiled_tables(model_cfg, model_type, config_dir, live_world)
    if profiles is None:
        synth = analytic_model_profiles(model_cfg, max_tp=live_world)
        if synth is None:
            return None
        time_cfg, mem_cfg = synth
        allreduce, p2p, overlap = analytic_hardware_profiles(live_world)
    else:
        time_cfg, mem_cfg, allreduce, p2p, overlap = profiles
    if time_config is not None and memory_config is not None:
        time_cfg, mem_cfg = time_config, memory_config  # measured tables win
    engine.set_model_profiles(time_cfg, mem_cfg)
    engine.set_hardware_profiles(allreduce, p2p, overlap)
    engine.initialize_search_engine()
    result = engine.parallelism_optimization()
    if result is None:
        return None
    return engine.result_to_config(result)


def _load_profiled_tables(model_cfg, model_type, config_dir, world):
    """The profiled-table path of the elastic re-search: the same files the
    search CLI reads (cli/search.py). None when any required table is
    missing or unreadable — the analytic fallback takes over."""
    try:
        from galvatron_tpu.profiler.model import ModelProfileArgs, ModelProfiler
        from galvatron_tpu.utils.jsonio import read_json_config

        prof = ModelProfiler(model_cfg, model_name=model_type,
                             args=ModelProfileArgs(config_dir=config_dir))
        mp = prof.config_paths()
        time_cfg = read_json_config(mp["computation"])
        mem_cfg = read_json_config(mp["memory"])
        tag = "%dchips" % world
        allreduce = read_json_config(
            os.path.join(config_dir, "allreduce_bandwidth_%s.json" % tag))
        p2p_path = os.path.join(config_dir, "p2p_bandwidth_%s.json" % tag)
        p2p = read_json_config(p2p_path) if os.path.exists(p2p_path) else None
        ov_path = os.path.join(config_dir, "overlap_coefficient.json")
        overlap = read_json_config(ov_path) if os.path.exists(ov_path) else None
        return time_cfg, mem_cfg, allreduce, p2p, overlap
    except (OSError, ValueError, KeyError, TypeError):
        return None


# ------------------------------------------------------------- resume planning
@dataclass
class ElasticPlan:
    """What resolve_resume_strategy decided: run `hp` now; the checkpoint
    was written under `saved_hp` (load_checkpoint's cross-strategy restore
    needs it)."""

    action: str  # "match" | "strategy_file" | "search"
    hp: HybridParallelConfig
    saved_hp: HybridParallelConfig
    provenance: Dict[str, Any]
    ckpt_iteration: Optional[int] = None

    @property
    def cross_strategy(self) -> bool:
        return self.action != "match"


def _budget_refusal(hp, model_cfg, budget_gb) -> Optional[D.Diagnostic]:
    """GLS203 when the strategy's estimated memory exceeds the budget on the
    surviving mesh — the linter only warns (GLS101); a refusal is right here
    because proceeding would OOM minutes into the resumed run."""
    from galvatron_tpu.analysis.strategy_lint import estimate_stage_memory_mb

    stage_mb = estimate_stage_memory_mb(hp, model_cfg)
    if stage_mb is None or not budget_gb:
        return None
    worst = max(stage_mb)
    if worst > budget_gb * 1024.0:
        return D.make(
            "GLS203", "stage memory estimated at %.2f GB exceeds the %.1f GB "
            "budget on the surviving %d-device mesh; lower the batch/enable "
            "checkpointing via --elastic_strategy, or raise "
            "--elastic_memory_gb" % (worst / 1024.0, budget_gb, hp.world_size),
        )
    return None


def resolve_resume_strategy(
    args: Any,
    model_cfg: Any,
    live_world: int,
    opt_args: Any = None,
) -> ElasticPlan:
    """Decide the strategy for an elastic resume (--elastic resume|search).

    Raises DiagnosticError (GLS2xx) whenever resuming would corrupt or
    silently degrade training; the train CLI maps that to exit code 2."""
    from galvatron_tpu.runtime import checkpoint as ckpt

    mode = getattr(args, "elastic", "off")
    it, prov = ckpt.read_provenance(args.load)
    if prov is None:
        raise D.DiagnosticError([D.make(
            "GLS204", "checkpoint %s has no provenance manifest — it predates "
            "elastic resume; resume it on the original mesh with --elastic "
            "off (one save there upgrades it)" % args.load,
        )])
    live_digest = model_config_digest(model_cfg)
    if prov.get("model_digest") and prov["model_digest"] != live_digest:
        raise D.DiagnosticError([D.make(
            "GLS201", "checkpoint %s was written for a different model "
            "config (digest %s.. != %s..): elastic resume re-plans the "
            "PARALLELISM, never the model" % (
                args.load, prov["model_digest"][:12], live_digest[:12]),
        )])
    if opt_args is not None and prov.get("optimizer", {}).get("digest"):
        if prov["optimizer"]["digest"] != optimizer_digest(opt_args):
            telemetry.runtime_log(
                "elastic: optimizer hyperparams differ from the checkpoint's "
                "(%s); continuing — the structural guard still applies"
                % prov["optimizer"].get("kind", "?")
            )
    saved_world = int(prov.get("world_size", live_world))
    exec_kw = dict(
        scan_layers=getattr(args, "scan_layers", True),
        remat_policy=getattr(args, "remat_policy", "full"),
        tp_comm_mode=getattr(args, "tp_comm_mode", "gspmd"),
        tp_comm_quant=getattr(args, "tp_comm_quant", "none"),
        mixed_precision=getattr(args, "mixed_precision", "bf16"),
    )
    # NB grad/param comm dtypes + comm_quant_block are serialized per-layer
    # strategy fields, so they ride prov["strategy"] through resume,
    # re-search fallback excepted (a re-searched strategy starts at 'none')
    saved_hp = HybridParallelConfig.from_json(
        dict(prov["strategy"]), world_size=saved_world, **exec_kw)
    budget = getattr(args, "elastic_memory_gb", None) or prov.get(
        "memory_budget_gb") or DEFAULT_MEMORY_GB

    strategy_file = getattr(args, "elastic_strategy", None)
    if saved_world == live_world and not strategy_file:
        # nothing changed: resume under the saved strategy, bitwise identical
        # to a plain --load (the checkpoint's strategy wins over GLOBAL flags
        # so a stale launch script cannot silently fork the trajectory). An
        # EXPLICIT --elastic_strategy is different from stale flags: the
        # operator deliberately re-plans (e.g. validating a live-migration
        # target offline), so it is honored below even on a matching world.
        telemetry.emit(
            "elastic", action="match", saved_world=saved_world,
            live_world=live_world)
        return ElasticPlan("match", saved_hp, saved_hp, prov, it)

    if strategy_file:
        hp = HybridParallelConfig.from_json(
            strategy_file, world_size=live_world, **exec_kw)
        if saved_world == live_world and hp.to_json_dict() == saved_hp.to_json_dict():
            # the supplied file IS the saved strategy: the cheaper bitwise
            # same-strategy restore applies
            telemetry.emit(
                "elastic", action="match", saved_world=saved_world,
                live_world=live_world)
            return ElasticPlan("match", saved_hp, saved_hp, prov, it)
        if hp.global_bsz != saved_hp.global_bsz:
            telemetry.runtime_log(
                "elastic: --elastic_strategy changes global_bsz %d -> %d; "
                "the loss trajectory will not be comparable to the original "
                "run" % (saved_hp.global_bsz, hp.global_bsz)
            )
        action = "strategy_file"
    elif mode == "search":
        hp = search_surviving_strategy(
            model_cfg, live_world, saved_hp.global_bsz, budget,
            model_type=getattr(args, "model_type", "model"),
            config_dir=getattr(args, "config_dir", None),
            default_dp_type=saved_hp.default_dp_type,
        )
        if hp is None:
            raise D.DiagnosticError([D.make(
                "GLS203", "no strategy for %d surviving devices fits "
                "global_bsz=%d under the %.1f GB budget; shrink the batch "
                "with --elastic_strategy or raise --elastic_memory_gb"
                % (live_world, saved_hp.global_bsz, budget),
            )])
        for k, v in exec_kw.items():
            setattr(hp, k, v)
        action = "search"
    else:
        raise D.DiagnosticError([D.make(
            "GLS205", "world size changed %d -> %d: pass a replacement "
            "strategy via --elastic_strategy, or let the search engine "
            "re-plan with --elastic search" % (saved_world, live_world),
        )])

    from galvatron_tpu.analysis import strategy_lint as _slint

    report = _slint.lint_hp(hp, model_cfg=model_cfg)
    if not report.ok:
        raise D.DiagnosticError(report.errors)
    if action == "strategy_file":
        # the search engine enforced the budget itself (possibly against
        # profiled tables); a hand-supplied strategy gets the analytic check
        refusal = _budget_refusal(hp, model_cfg, budget)
        if refusal is not None:
            raise D.DiagnosticError([refusal])
    telemetry.emit(
        "elastic", action=action, saved_world=saved_world, live_world=live_world)
    return ElasticPlan(action, hp, saved_hp, prov, it)


# ------------------------------------------------------- in-memory migration
@dataclass
class MigrationResult:
    """What :func:`migrate` produced: run `model` with `params`/`opt_state`
    from here on. `same_layout` records whether the swap was a pure
    on-device reshard (no host round trip, no tree rewrite)."""

    model: Any
    params: Any
    opt_state: Any
    same_layout: bool
    from_hp: HybridParallelConfig
    to_hp: HybridParallelConfig


def resolve_migration_strategy(
    args: Any,
    model_cfg: Any,
    live_world: int,
    current_hp: HybridParallelConfig,
) -> Tuple[HybridParallelConfig, str]:
    """Pick the target strategy for a LIVE migration: the operator-supplied
    ``--elastic_strategy`` JSON when given, otherwise a fresh search for
    `live_world` under the memory budget. Returns (hp, action).

    Raises DiagnosticError: GLS203 when nothing fits the budget, GLS207
    when the candidate would fork the training trajectory (a different
    global batch makes "continue from the same step" meaningless — unlike
    a disk resume, a live migration exists only to preserve the run)."""
    exec_kw = dict(
        scan_layers=current_hp.scan_layers,
        remat_policy=current_hp.remat_policy,
        tp_comm_mode=current_hp.tp_comm_mode,
        tp_comm_quant=current_hp.tp_comm_quant,
        mixed_precision=current_hp.mixed_precision,
    )
    budget = getattr(args, "elastic_memory_gb", None) or DEFAULT_MEMORY_GB
    strategy_file = getattr(args, "elastic_strategy", None)
    if strategy_file:
        hp = HybridParallelConfig.from_json(
            strategy_file, world_size=live_world, **exec_kw)
        action = "strategy_file"
    else:
        hp = search_surviving_strategy(
            model_cfg, live_world, current_hp.global_bsz, budget,
            model_type=getattr(args, "model_type", "model"),
            config_dir=getattr(args, "config_dir", None),
            default_dp_type=current_hp.default_dp_type,
        )
        if hp is None:
            raise D.DiagnosticError([D.make(
                "GLS203", "no strategy for %d surviving devices fits "
                "global_bsz=%d under the %.1f GB budget; supply one with "
                "--elastic_strategy or raise --elastic_memory_gb"
                % (live_world, current_hp.global_bsz, budget),
            )])
        for k, v in exec_kw.items():
            setattr(hp, k, v)
        action = "search"
    if hp.global_bsz != current_hp.global_bsz:
        raise D.DiagnosticError([D.make(
            "GLS207", "live migration cannot change global_bsz (%d -> %d): "
            "the run would fork its own trajectory; stop and resume from a "
            "checkpoint instead" % (current_hp.global_bsz, hp.global_bsz),
        )])
    from galvatron_tpu.analysis import strategy_lint as _slint

    report = _slint.lint_hp(hp, model_cfg=model_cfg)
    if not report.ok:
        raise D.DiagnosticError(report.errors)
    if action == "strategy_file":
        refusal = _budget_refusal(hp, model_cfg, budget)
        if refusal is not None:
            raise D.DiagnosticError([refusal])
    return hp, action


def migrate(
    model: Any,
    params: Any,
    opt_state: Any,
    tx: Any,
    target_hp: HybridParallelConfig,
    devices: Any = None,
    build_model: Any = None,
    reason: str = "manual",
    iteration: Optional[int] = None,
    sdc_check: bool = False,
) -> MigrationResult:
    """Hot-swap the LIVE training state onto `target_hp` without a
    checkpoint round-trip.

    - Same pipeline layout (the common case — dp<->tp<->zero reshards,
      world shrink/grow with unchanged stacking): the params/opt_state
      TREES are already right, so the move is one sharded ``device_put``
      per tree onto the new model's shardings — pure on-device data
      movement, bit-exact.
    - Pipeline-layout change (pp on/off, different division): the stacked
      ``stages`` tree is re-laid-out leaf-exactly through the same
      ``_relayout_tree`` family the cross-layout checkpoint restore uses,
      then placed. Adam moments travel with their params.
    - Refusals (GLS207): custom-param-tree families (t5/swin) across
      layouts — ``_relayout_tree`` only knows the generic transformer tree
      — and an opt_state whose re-laid-out structure does not match the
      target optimizer's (corrupting moments silently would be worse than
      stopping).

    `build_model` overrides model construction for families with their own
    build hook; `devices` selects the surviving device subset on a shrink.
    With `sdc_check` the layout-invariant integrity digest (runtime/sdc.py)
    is recorded before the move and asserted unchanged after relayout +
    placement — GLS016 refusal instead of silently garbling state. The swap
    is logged as an ``elastic`` telemetry event carrying the full
    before/after strategy JSON."""
    import jax

    from galvatron_tpu.runtime import checkpoint as ckpt
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    sdc = None
    params_fold = opt_fold = None
    if sdc_check:
        from galvatron_tpu.runtime import sdc

        params_fold = sdc.host_tree_fold(params)
        if opt_state is not None:
            opt_fold = sdc.host_tree_fold(opt_state)

    old_hp: HybridParallelConfig = model.hp
    same_layout = ckpt._same_param_layout(old_hp, target_hp)
    if not same_layout and model.init_fn is not None:
        raise D.DiagnosticError([D.make(
            "GLS207", "live migration across pipeline layouts (pp %s -> pp "
            "%s) is only supported for the generic transformer tree; this "
            "family builds its own params" % (old_hp.pp, target_hp.pp),
        )])
    if target_hp.global_bsz != old_hp.global_bsz:
        raise D.DiagnosticError([D.make(
            "GLS207", "live migration cannot change global_bsz (%d -> %d)"
            % (old_hp.global_bsz, target_hp.global_bsz),
        )])
    t0 = time.perf_counter()
    if build_model is not None:
        new_model = build_model(model.cfg, target_hp, devices)
    else:
        new_model = construct_hybrid_parallel_model(model.cfg, target_hp, devices)

    if same_layout:
        new_params = jax.device_put(params, new_model.shardings())
    else:
        new_params = jax.device_put(
            ckpt._relayout_tree(params, old_hp, target_hp), new_model.shardings())

    new_opt = opt_state
    if opt_state is not None and tx is not None:
        relaid = opt_state if same_layout else ckpt._relayout_tree(
            opt_state, old_hp, target_hp)
        target_abs_params = new_model.abstract_params()
        target_abs_opt = jax.eval_shape(tx.init, target_abs_params)
        got = [(jax.tree_util.keystr(p), tuple(l.shape)) for p, l in
               jax.tree_util.tree_flatten_with_path(relaid)[0]]
        want = [(jax.tree_util.keystr(p), tuple(l.shape)) for p, l in
                jax.tree_util.tree_flatten_with_path(target_abs_opt)[0]]
        if got != want:
            diffs = [(g, w) for g, w in zip(got, want) if g != w][:3]
            raise D.DiagnosticError([D.make(
                "GLS207", "re-laid-out opt_state does not match the target "
                "optimizer tree (%d vs %d leaves; first diffs: %s)"
                % (len(got), len(want), diffs),
            )])
        new_opt = jax.device_put(
            relaid, new_model.opt_state_shardings(tx, target_abs_params))

    if sdc_check:
        # the whole move — stage restack + sharded device_put — is
        # value-preserving by contract; the layout-invariant fold proves it
        sdc.assert_digest_continuity(
            params_fold, new_params, "migrate(params)", iteration=iteration)
        if opt_fold is not None and new_opt is not None:
            sdc.assert_digest_continuity(
                opt_fold, new_opt, "migrate(opt_state)", iteration=iteration)

    telemetry.emit(
        "elastic", action="migrate", reason=reason, iter=iteration,
        saved_world=old_hp.world_size, live_world=target_hp.world_size,
        from_strategy=old_hp.to_json_dict(), to_strategy=target_hp.to_json_dict(),
        duration_ms=(time.perf_counter() - t0) * 1e3,
        same_layout=same_layout,
    )
    return MigrationResult(
        model=new_model, params=new_params, opt_state=new_opt,
        same_layout=same_layout, from_hp=old_hp, to_hp=target_hp,
    )


# ------------------------------------------------- degraded-mesh serve path
def search_surviving_serve_strategy(
    model_cfg: Any,
    live_world: int,
    memory_budget_gb: float,
    serve_max_concurrency: int,
    serve_page_size: int,
    p99_ttft_ms: float = 0.0,
    p99_tpot_ms: float = 0.0,
    model_type: str = "model",
    config_dir: Optional[str] = None,
    default_dp_type: str = "ddp",
    logger=None,
) -> HybridParallelConfig:
    """Re-run ``search --objective serve`` for the surviving world: the same
    decode-compatible enumeration + ServeTimeCostModel pricing the offline
    serve search uses, fed profiled tables when available and the analytic
    fallback otherwise. Concurrency and page size are pinned to the RUNNING
    engine's values so in-flight journals stay replayable into the new
    cache. Raises GLS015 when no strategy is feasible on what survived."""
    from galvatron_tpu.search.engine import GalvatronSearchEngine, SearchArgs

    heads = getattr(model_cfg, "num_heads", None) or 1
    nkv = getattr(model_cfg, "num_kv_heads", None) or heads
    num_layers = getattr(model_cfg, "num_layers", 1)
    seq_len = getattr(model_cfg, "max_seq_len", 2048)
    hidden = getattr(model_cfg, "hidden_size", 1024)
    max_tp = 1
    while max_tp * 2 <= min(heads, live_world) and heads % (max_tp * 2) == 0:
        max_tp *= 2
    args = SearchArgs(
        memory_constraint=memory_budget_gb,
        max_tp_deg=max_tp,
        max_pp_deg=1,  # serve layouts are pp=1 by contract (GLS014)
        default_dp_type=default_dp_type,
        sp_space="tp",
        objective="serve",
        p99_ttft_ms=p99_ttft_ms,
        p99_tpot_ms=p99_tpot_ms,
        serve_max_concurrency=serve_max_concurrency,
        serve_page_size=serve_page_size,
        serve_kv_frac=nkv / heads,
    )
    engine = GalvatronSearchEngine(
        args, live_world,
        [{"hidden_size": hidden, "seq_len": seq_len, "layer_num": num_layers}],
        config_dir=config_dir or "configs", model_name=model_type, logger=logger,
    )
    profiles = None
    if config_dir:
        profiles = _load_profiled_tables(model_cfg, model_type, config_dir, live_world)
    if profiles is None:
        synth = analytic_model_profiles(model_cfg, max_tp=live_world)
        if synth is None:
            raise D.DiagnosticError([D.make(
                "GLS015", "cannot synthesize analytic cost tables for this "
                "model config — no way to re-plan serving for the %d "
                "surviving devices" % live_world,
            )])
        time_cfg, mem_cfg = synth
        allreduce, p2p, overlap = analytic_hardware_profiles(live_world)
    else:
        time_cfg, mem_cfg, allreduce, p2p, overlap = profiles
    engine.set_model_profiles(time_cfg, mem_cfg)
    engine.set_hardware_profiles(allreduce, p2p, overlap)
    engine.initialize_search_engine()
    try:
        result = engine.serve_optimization()
    except D.DiagnosticError as e:
        # the offline objective refuses with GLS014 ("this config cannot
        # serve"); mid-flight the refusal is about the DEGRADED WORLD
        raise D.DiagnosticError([D.make(
            "GLS015", "serve world infeasible after degradation: no serving "
            "strategy for the %d surviving devices (%s); drain and redeploy "
            "on a healthy slice" % (
                live_world,
                "; ".join(d.message for d in e.diagnostics)[:400]),
        )]) from e
    return engine.result_to_config(result)


def resolve_serve_migration_strategy(
    args: Any,
    model_cfg: Any,
    live_world: int,
    current_hp: HybridParallelConfig,
    kv_cfg: Any = None,
) -> Tuple[HybridParallelConfig, str]:
    """Pick the target strategy for a LIVE degraded-mesh serve migration:
    the operator-supplied ``--elastic_strategy`` JSON when given, otherwise
    a fresh ``--objective serve`` search for `live_world`. Returns
    (hp, action). Raises DiagnosticError (GLS015) when the surviving world
    cannot serve; the serve CLI maps that to exit code 2."""
    exec_kw = dict(
        scan_layers=current_hp.scan_layers,
        remat_policy=current_hp.remat_policy,
        tp_comm_mode=current_hp.tp_comm_mode,
        tp_comm_quant=current_hp.tp_comm_quant,
        mixed_precision=current_hp.mixed_precision,
    )
    budget = getattr(args, "elastic_memory_gb", None) or DEFAULT_MEMORY_GB
    concurrency = (getattr(kv_cfg, "max_slots", 0)
                   or current_hp.serve_max_concurrency or 8)
    page = (getattr(kv_cfg, "page_size", 0)
            or current_hp.serve_page_size or 16)
    strategy_file = getattr(args, "elastic_strategy", None)
    if strategy_file:
        hp = HybridParallelConfig.from_json(
            strategy_file, world_size=live_world, **exec_kw)
        action = "strategy_file"
    else:
        hp = search_surviving_serve_strategy(
            model_cfg, live_world, budget,
            serve_max_concurrency=concurrency, serve_page_size=page,
            p99_ttft_ms=getattr(args, "p99_ttft_ms", 0.0) or 0.0,
            p99_tpot_ms=getattr(args, "p99_tpot_ms", 0.0) or 0.0,
            model_type=getattr(args, "model_type", "model"),
            config_dir=getattr(args, "config_dir", None),
            default_dp_type=current_hp.default_dp_type,
        )
        for k, v in exec_kw.items():
            setattr(hp, k, v)
        action = "search"
    from galvatron_tpu.analysis import strategy_lint as _slint

    report = _slint.lint_hp(hp, model_cfg=model_cfg, mode="serve")
    if not report.ok:
        raise D.DiagnosticError([D.make(
            "GLS015", "serve world infeasible after degradation: the %s "
            "strategy for %d devices fails the serve lint (%s)" % (
                action, live_world,
                "; ".join("%s: %s" % (d.code, d.message)
                          for d in report.errors)[:400]),
        )])
    return hp, action


def migrate_serve_params(
    model: Any,
    params: Any,
    target_hp: HybridParallelConfig,
    devices: Any = None,
    build_model: Any = None,
    sdc_check: bool = False,
) -> Tuple[Any, Any, bool]:
    """Params-only live relayout for a serve migration: the inference twin
    of :func:`migrate` with no optimizer state and no trajectory checks
    (serving has no training trajectory to fork — global_bsz is inert).
    With `sdc_check` the layout-invariant digest is asserted unchanged
    across the move (GLS016 on mismatch), like :func:`migrate`.
    Returns (new_model, new_params, same_layout); the caller rebuilds the
    ServeEngine (fresh KV cache in the new layout) and journal-replays the
    in-flight requests (serve/engine.ContinuousBatcher.migrate_to)."""
    import jax

    from galvatron_tpu.runtime import checkpoint as ckpt
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    params_fold = None
    if sdc_check:
        from galvatron_tpu.runtime import sdc

        params_fold = sdc.host_tree_fold(params)

    old_hp: HybridParallelConfig = model.hp
    same_layout = ckpt._same_param_layout(old_hp, target_hp)
    if not same_layout and model.init_fn is not None:
        raise D.DiagnosticError([D.make(
            "GLS015", "serve migration across pipeline layouts (pp %s -> pp "
            "%s) is only supported for the generic transformer tree; this "
            "family builds its own params" % (old_hp.pp, target_hp.pp),
        )])
    if build_model is not None:
        new_model = build_model(model.cfg, target_hp, devices)
    else:
        new_model = construct_hybrid_parallel_model(model.cfg, target_hp, devices)
    if same_layout:
        new_params = jax.device_put(params, new_model.shardings())
    else:
        new_params = jax.device_put(
            ckpt._relayout_tree(params, old_hp, target_hp), new_model.shardings())
    if params_fold is not None:
        from galvatron_tpu.runtime import sdc

        sdc.assert_digest_continuity(
            params_fold, new_params, "migrate_serve_params")
    return new_model, new_params, same_layout
