"""Training driver: the analogue of every model's ``train_dist.py::train()``
(reference models/gpt_hf/train_dist.py:19-77; llama adds checkpoint/scheduler,
models/llama_hf/train_dist.py:30-95). One driver serves all families via the
registry; the per-layer strategy comes from GLOBAL flags or a searched JSON
(``--galvatron_config_path``).
"""

from __future__ import annotations

import hashlib
import os
import signal
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from galvatron_tpu.cli.arguments import (
    hp_config_from_args,
    initialize_galvatron,
    model_config_from_args,
)
from galvatron_tpu.config.strategy import model_layer_kinds
from galvatron_tpu.obs import compiled as obs_compiled
from galvatron_tpu.obs import flops as obs_flops
from galvatron_tpu.obs import forms, launch, telemetry, tracing
from galvatron_tpu.parallel.mesh import layer_axes
from galvatron_tpu.profiler.runtime import (
    RuntimeProfiler,
    compiled_step_memory_mb,
    device_memory_stats,
)
from galvatron_tpu.runtime import health as hlth
from galvatron_tpu.runtime import resilience as rsl
from galvatron_tpu.runtime.dataloader import get_train_iterator
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model, scan_stacks_are_tight
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler
from galvatron_tpu.runtime.prefetch import PrefetchIterator, PrefetchStalledError
from galvatron_tpu.utils.compile_cache import enable_persistent_cache

launch.IMPORTS.done()  # the program is imported: the import record closes and gives its monitoring id back

# In-process memo of AOT-compiled train-step executables, keyed by (device
# ids, sha256 of the lowered StableHLO). Repeated train() calls in one
# interpreter (search trials, resume-after-rollback rebuilds, test suites)
# re-trace cheaply and then REUSE the live executable instead of asking XLA
# (or the persistent cache, utils/compile_cache.py, which serves re-launches)
# again. The HLO text embeds input/output shardings and donation aliasing, so
# an exact-text hit on the same devices is semantically the same program.
_STEP_EXECUTABLES: "OrderedDict" = OrderedDict()
_STEP_EXECUTABLES_MAX = 16


def _import_checkpoint():
    """The import itself: `runtime/checkpoint` with `orbax.checkpoint`, `tensorstore`, `grpc` and the
    `google.cloud.logging` orbax pulls beneath it (12 of a warm start's 17 s of import on the chip's
    host: PERF.md section 5). No module-scope import of it may come back into this module or into what
    this module imports (tests/obs/test_checkpoint_import.py); the tests slow and break it here."""
    from galvatron_tpu.runtime import checkpoint

    return checkpoint


class CheckpointModule:
    """`runtime/checkpoint` for one `train()`, imported when the run first needs it; calling it is
    the one way `_train` reaches the module. Which of four ways it went, by what `args` say:

    `never`       neither --load nor --save: nobody calls it, and the process never holds orbax
    `at_load`     --load: the restore calls it inside `gt/launch/restore`; such a start pays the
                  import as every start did before
    `background`  --save: `start()` once the first step is dispatched (the device is busy and the
                  host waits; not before, so the step's trace and lowering have the interpreter to
                  themselves) imports on a daemon thread, and every call joins it first: a save
                  waits for what is LEFT of the import, a preemption's save among them
    `at_use`      a use before any of that (a save before the first step, a second use after a
                  failed import): on the spot

    `import_s` is what the import took where it ran (None while it runs), `waited_s` what the
    first use waited for the thread. An exception of the import is raised at the first use, with
    the traceback it had on its thread."""

    def __init__(self):
        self.how = "never"
        self.import_s: Optional[float] = None
        self.waited_s: Optional[float] = None
        self._module = None
        self._error: Optional[BaseException] = None
        self._thread: Optional[threading.Thread] = None

    def _import(self):
        t = time.perf_counter()
        try:
            self._module = _import_checkpoint()
        except BaseException as e:  # handed to the thread that uses the module
            self._error = e
        self.import_s = time.perf_counter() - t

    def start(self):
        """Import on a helper thread from now on, unless the run has imported it already."""
        if self.how == "never":
            self.how = "background"
            self._thread = threading.Thread(target=self._import, name="gt-checkpoint-import", daemon=True)
            self._thread.start()

    def join(self):
        if self._thread is not None:
            self._thread.join()

    def __call__(self, how: str = "at_use"):
        if self._thread is not None:
            t = time.perf_counter()
            self._thread.join()
            if self.waited_s is None:
                self.waited_s = time.perf_counter() - t
        elif self._module is None:
            self.how, self._error = how, None
            self._import()
        if self._error is not None:
            raise self._error
        return self._module

    def fields(self) -> dict:
        """The summary's and the `launch` event's `checkpoint_import`."""
        return {"how": self.how, "import_s": self.import_s, "waited_s": self.waited_s}


def _step_exec_key(mesh, lowered):
    devs = tuple(int(d.id) for d in mesh.devices.flat)
    return (devs, hashlib.sha256(lowered.as_text().encode()).hexdigest())


def _step_census(model, compiled) -> dict:
    """The compiled step's collectives, from ONE `as_text()` and one walk of it
    (`obs/compiled.py`): `rows` (`step_collectives`: the summary's
    `step_collectives` and the `compile` event's `collectives`), `census_ms` what
    printing and walking the text took, and `dp_grad_mb` (`dp_grad_sums_mb` over
    the dp groups of the model's layers: the event's two `dp_grad_*_mb`; {} in a
    layout without a dp axis). {} on one chip and where the executable gives no
    text."""
    if model.mesh.devices.size == 1:
        return {}
    t = time.perf_counter()
    try:
        text = compiled.as_text()
    except Exception:  # an executable read back without its modules
        return {}
    walked = obs_compiled.walk(text)
    rows = obs_compiled.step_collectives(walked, model.mesh, model.hp, model_layer_kinds(getattr(model, "cfg", None)))
    dp_axes = {layer_axes(model.hp, i).dp for i in range(len(model.hp.layers))} - {()}
    dp_grad_mb = obs_compiled.dp_grad_sums_mb(
        walked, [obs_compiled.axis_groups(model.mesh, dp) for dp in dp_axes]) if dp_axes else {}
    return {"rows": rows, "census_ms": (time.perf_counter() - t) * 1e3, "dp_grad_mb": dp_grad_mb}


def _census_at_compile(model, compiled) -> dict:
    """`_step_census` where a telemetry sink would hear of it as the step
    compiles, {} where none listens: printing the step's text is then not the
    launch's to pay for, and the run's end does it (the summary's
    `step_collectives`)."""
    return _step_census(model, compiled) if telemetry.active_sink() is not None else {}


def _scan_grad_sums_mb(model, compiled) -> dict:
    """The `compile` event's `dp_grad_all_reduce_mb` / `dp_grad_reduce_scatter_mb`
    as `_census_at_compile` has them (tests/ops/test_tpu_compile_steps.py holds
    the four-chip cell to them): {} on one chip, in a layout without a dp axis,
    where the executable gives no text, and where no telemetry sink listens."""
    return _census_at_compile(model, compiled).get("dp_grad_mb", {})


def _compile_step(lowered, counters: launch.JitCounters):
    """Compile the lowered step; returns (executable, persistent_cache_hit).
    The hit is read off jax's own monitoring event, fired when the backend
    compile was answered from the persistent compilation cache: the launch's
    listeners count it (after the launch they are registered for this call)."""
    with counters:
        hits = counters.cache_hits
        return lowered.compile(), counters.cache_hits > hits


def shared_counts(cfg) -> dict:
    """{mamba_layers, shared_readers} of a config whose layers publish and read (models/config.py
    `shared`): the Mamba-1 layers, and the layers that read an earlier layer's tensor; 0, 0 for any other."""
    if not hasattr(cfg, "shared"):
        return {"mamba_layers": 0, "shared_readers": 0}
    return {"mamba_layers": sum(kind.startswith("mamba1") for kind in cfg.layer_kinds()),
            "shared_readers": sum(bool(read) for _, read in cfg.shared())}


def eva_counts(cfg) -> dict:
    """{eva_layers, eva_windows, eva_pooled_keys} of a config with EVA attention layers (models/parts/eva.py):
    how many they are, the windows a sequence of `max_seq_len` is cut into, and a sequence's pooled keys, the
    chunks of all windows but the last (what a query of the last window meets beside its own window's keys);
    0, 0, 0 for any other."""
    kinds = cfg.layer_kinds() if hasattr(cfg, "layer_kinds") else ()
    layers = sum(kind.startswith("eva.") for kind in kinds)
    if not layers:
        return {"eva_layers": 0, "eva_windows": 0, "eva_pooled_keys": 0}
    windows = -(-cfg.max_seq_len // cfg.eva_window)
    return {"eva_layers": layers, "eva_windows": windows,
            "eva_pooled_keys": (windows - 1) * (cfg.eva_window // cfg.eva_chunk)}


def optimizer_args_from(args) -> OptimizerArgs:
    return OptimizerArgs(
        lr=args.lr,
        min_lr=args.min_lr,
        weight_decay=args.weight_decay,
        adam_beta1=args.adam_beta1,
        adam_beta2=args.adam_beta2,
        adam_eps=args.adam_eps,
        clip_grad=args.clip_grad,
        warmup_steps=args.lr_warmup_iters,
        total_steps=args.train_iters,
        lr_decay_style=args.lr_decay_style,
    )


def build_data_iterator(args, fam, cfg, hp, start_step: int = 0,
                        split: str = "train"):
    """Per-family input pipeline (fam.data_kind): indexed dataset when
    --data_path is given, synthetic stream otherwise (the reference models'
    random-data fallback). All streams are pure functions of the step index,
    so `start_step` resumes in O(1). `split` selects the train/valid/test
    document range (real data) or an independent stream (synthetic — the
    reference's random splits are independent streams too)."""
    # synthetic streams have no documents to split: derive a disjoint,
    # deterministic stream per split from the seed
    split_seed = args.seed + {"train": 0, "valid": 7919, "test": 15838}.get(split, 0)
    split_weights = getattr(args, "split", "969,30,1")
    if args.data_path:
        if fam.data_kind == "seq2seq":
            # span corruption over the indexed corpus (reference
            # T5MaskedWordPieceDataset, models/T5/dataloader.py:152-200)
            from galvatron_tpu.data.dataset import t5_data_iterator

            return t5_data_iterator(
                args.data_path, hp, enc_seq_len=cfg.max_seq_len,
                dec_seq_len=cfg.max_seq_len, seed=args.seed,
                start_step=start_step, split=split,
                split_weights=split_weights, vocab_size=cfg.vocab_size,
            )
        if fam.data_kind == "vision":
            from galvatron_tpu.data.dataset import vision_data_iterator

            return vision_data_iterator(
                args.data_path, hp, image_size=cfg.image_size,
                num_channels=cfg.num_channels, seed=args.seed,
                start_step=start_step, split=split,
                split_weights=split_weights,
            )
        from galvatron_tpu.data.dataset import gpt_data_iterator

        return gpt_data_iterator(
            args.data_path, hp, seq_len=cfg.max_seq_len, seed=args.seed,
            start_step=start_step, split=split, split_weights=split_weights,
        )
    if fam.data_kind == "vision":
        from galvatron_tpu.runtime.dataloader import get_vision_train_iterator

        return get_vision_train_iterator(
            hp, cfg.image_size, cfg.num_channels, cfg.num_classes, seed=split_seed,
            start_step=start_step,
        )
    if fam.data_kind == "seq2seq":
        from galvatron_tpu.runtime.dataloader import get_seq2seq_train_iterator

        return get_seq2seq_train_iterator(
            hp, cfg.vocab_size, cfg.max_seq_len, cfg.max_seq_len, seed=split_seed,
            start_step=start_step,
        )
    return get_train_iterator(hp, cfg.vocab_size, cfg.max_seq_len, seed=split_seed,
                              start_step=start_step)


def train(args) -> dict:
    """Returns a summary dict (losses, timing, resilience counters) for
    tests/driver use. With ``--telemetry <path>`` the run additionally
    writes a schema-versioned JSONL event stream (obs/telemetry.py): the
    sink installs process-wide so the checkpoint/elastic/resilience layers'
    lifecycle events land in the same file as the driver's per-step
    records."""
    sink = None
    if getattr(args, "telemetry", None):
        sink = telemetry.JsonlSink(
            args.telemetry, depth=max(int(getattr(args, "telemetry_buffer", 1024) or 1), 1)
        )
        telemetry.install(sink)
    started = launch.Launch()
    try:
        return _train(args, started)
    finally:
        started.close()  # a run that ended before its first drain still holds the listeners
        if sink is not None:
            telemetry.uninstall(sink)
            sink.close()


def _parse_trace_steps(spec) -> tuple:
    """'K:N' -> (K, N) inclusive; a single 'K' traces one step."""
    lo, _, hi = str(spec or "3:5").partition(":")
    lo = int(lo)
    return lo, int(hi) if hi else lo


def _train(args, started: launch.Launch) -> dict:
    # the run's one trace control (obs/tracing.py): whoever holds `args` (an
    # on_step hook, a test, the benchmark) may request a trace of coming
    # steps through it at any time; --xla_trace is one request made here
    control = getattr(args, "trace_control", None)
    if control is None:
        control = args.trace_control = tracing.TraceControl()
    if getattr(args, "xla_trace", None):
        control.request(args.xla_trace,
                        *_parse_trace_steps(getattr(args, "trace_steps", None)))
    # the launch's phases (obs/launch.py), consecutive from here to the first
    # step's drain; what runs between two of them is `launch_unspanned_pct`
    started.begin(control, tracing.LAUNCH_PLAN)
    ckpt = CheckpointModule()  # runtime/checkpoint, imported when this run first needs it
    cache_path = enable_persistent_cache()
    if jax.process_index() == 0:
        print("persistent compilation cache: %s" % cache_path)
    fam, cfg = model_config_from_args(args)
    world = args.world_size or len(jax.devices())
    # elastic degraded-mesh resume: when the device count no longer matches
    # the checkpoint's provenance, re-plan the strategy for the surviving
    # mesh (user-supplied JSON or a fresh search) instead of failing the
    # strategy assert; on a matching mesh the SAVED strategy wins over the
    # GLOBAL flags so a stale launch script cannot fork the trajectory
    elastic_plan = None
    if args.load and getattr(args, "elastic", "off") != "off":
        from galvatron_tpu.runtime import elastic as els

        elastic_plan = els.resolve_resume_strategy(
            args, cfg, world, opt_args=optimizer_args_from(args))
        hp = elastic_plan.hp
        if jax.process_index() == 0 and elastic_plan.cross_strategy:
            print(
                "elastic resume (%s): checkpoint strategy (world %d) -> new "
                "strategy (world %d)" % (
                    elastic_plan.action, elastic_plan.saved_hp.world_size,
                    hp.world_size)
            )
    else:
        hp = hp_config_from_args(args, cfg.num_layers, world)
    # fail fast on a bad strategy BEFORE any tracing/compilation: the linter
    # re-checks engine consistency plus the model-aware divisibility rules
    # (heads/seq/vocab vs tp/cp/sp) that from_json alone cannot see
    from galvatron_tpu.analysis import strategy_lint as _slint
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    _report = _slint.lint_hp(
        hp, model_cfg=cfg, file=getattr(args, "galvatron_config_path", None),
        # driver state the strategy alone cannot see: quantized grad sync
        # composed with the anomaly guard refuses (GLS013) before tracing;
        # mode="train" flags inert serve knobs (GLS103)
        anomaly_guard=bool(getattr(args, "anomaly_guard", 0)),
        mode="train",
        sdc_check=getattr(args, "sdc_check", None),
        sdc_interval=getattr(args, "sdc_interval", None),
        autotune=getattr(args, "autotune", None),
        autotune_margin=getattr(args, "autotune_margin", None),
        elastic_strategy=getattr(args, "elastic_strategy", None),
    )
    if jax.process_index() == 0:
        for _d in _report.warnings:
            print("strategy lint: %s" % _d.format())
    if not _report.ok:
        raise DiagnosticError(_report.errors)
    if jax.process_index() == 0:
        print(hp.describe())

    # --------------------------------------------------------- observability
    # model-FLOPs + peak registry (obs/flops.py): the constants every MFU
    # surface (per-step telemetry, profiler summary) derives from. None for
    # families the analytic model cannot describe — MFU is then omitted.
    step_flops = obs_flops.train_step_flops(cfg, hp.global_bsz)
    device_kind = getattr(jax.devices()[0], "device_kind", None)
    # the registry's row is ONE chip; the step's FLOPs are spread over the mesh
    chip_peak = obs_flops.peak_flops_for(device_kind)
    peak_flops = chip_peak * hp.world_size if chip_peak else None
    autotune_mode = getattr(args, "autotune", "off") or "off"
    predictions = None
    if telemetry.active_sink() is not None or autotune_mode != "off":
        # per-LayerRun cost-model predictions: the search engine's expected
        # time/memory per compiled run, recorded up-front so `cli report`
        # can lay the measured steady state beside them (obs/attribution.py).
        # The online autotuner needs the same rows (the FLOPs-share split the
        # calibrator folds the measured step across), sink or no sink.
        from galvatron_tpu.obs import attribution as obs_attr

        try:
            predictions = obs_attr.predict_layer_runs(cfg, hp)
        except Exception as e:  # analytic tables cannot price this config
            predictions = None
            telemetry.emit("log", message="layer-run prediction skipped: %s" % e)
        for p in predictions or ():
            telemetry.emit("layer_run", **p)
    started.end()

    # ------------------------------------------------------------- resilience
    res = rsl.ResilienceCounters()
    retry_policy = rsl.RetryPolicy(
        retries=max(getattr(args, "ckpt_retries", 2), 0),
        base_delay_s=getattr(args, "ckpt_retry_backoff", 0.5),
    )
    # fault-injection seam (tests/runtime/fault_injection.py); None in prod
    hooks = getattr(args, "fault_hooks", None)
    guard = None
    if getattr(args, "anomaly_guard", 0):
        guard = rsl.AnomalyGuard(rsl.AnomalyGuardConfig(
            spike_factor=getattr(args, "loss_spike_factor", 0.0),
            min_history=getattr(args, "anomaly_min_history", 5),
            max_strikes=getattr(args, "anomaly_max_strikes", 3),
            max_rollbacks=getattr(args, "anomaly_max_rollbacks", 3),
        ))
    verify_ckpt = bool(getattr(args, "verify_checkpoint", 1))

    started.begin(control, tracing.LAUNCH_BUILD)
    # families with their own param tree (t5/swin) supply a build hook
    model = fam.build(cfg, hp) if fam.build else construct_hybrid_parallel_model(cfg, hp)
    tx, _sched = get_optimizer_and_scheduler(optimizer_args_from(args))

    # opt-in pre-trace hook (--trace_lint): walk the jaxpr of the exact step
    # this driver is about to jit and refuse on GLT errors — the traced-
    # program hazards (sharded-dim reshape under scan, stacked init under
    # out_shardings, ...) that the source/strategy linters above cannot see
    if getattr(args, "trace_lint", 0):
        from galvatron_tpu.analysis import trace_lint as _tlint

        _tres = _tlint.lint_hybrid_model(
            model, data_kind=getattr(fam, "data_kind", "lm"), tx=tx)
        if jax.process_index() == 0:
            for _d in _tres.report.warnings:
                print("trace lint: %s" % _d.format())
        if not _tres.report.ok:
            raise DiagnosticError(_tres.report.errors)

    # ------------------------------------------ silent-corruption sentinel
    # runtime/sdc.py: in-jit integrity digests ("digest"), per-replica vote
    # + freeze + drain-time repair/re-execute ("vote"), and the strike
    # ladder that quarantines a persistently-lying device into the
    # degraded-mesh migration path. Digests are computed in-jit whenever
    # the sentinel is on; --sdc_interval only gates heartbeat emission, so
    # the compiled program does not depend on the interval.
    from galvatron_tpu.runtime import sdc as sdc_mod

    sdc_mode = getattr(args, "sdc_check", "off") or "off"
    sdc_interval = max(int(getattr(args, "sdc_interval", 0) or 1), 1)
    sdc_ladder = None
    if sdc_mode == "vote":
        sdc_ladder = sdc_mod.VoteLadder(
            strikes=max(int(getattr(args, "sdc_strikes", 2) or 2), 1))
    sdc_quarantined = set()  # device ids convicted by the strike ladder
    sdc_req = {"pending": False, "votes": None, "tie_rounds": 0}

    def sdc_vote_ids():
        return sdc_mod.vote_device_ids(model.mesh, sdc_mod.dp_axes_of(model))

    # Decomposed-TP overlap accounting: under tp_comm_mode=overlap, measure
    # per TP LayerRun how much communication the chunked ppermute schedule
    # hides (wall-clock of the run overlapped vs serialized —
    # parallel/tp_shard_map.measure_comm_hidden). A one-off profiling pass
    # (a couple of small per-run compiles), so it only runs when the run is
    # being observed (--profile or --telemetry); recorded into the profiler
    # summary (comm_hidden_ms, next to host_blocked_ms) and the telemetry
    # stream (tp_overlap events the report lays beside the predictions).
    comm_hidden_rows = []
    if (hp.tp_comm_mode == "overlap" and hp.pp == 1 and not fam.build
            and (args.profile or telemetry.active_sink() is not None)):
        from galvatron_tpu.parallel import tp_shard_map as tp_sm

        try:
            comm_hidden_rows = tp_sm.measure_comm_hidden(cfg, hp, model.mesh)
        except Exception as e:  # profiling must never kill the run
            telemetry.runtime_log("tp overlap measurement skipped: %s" % e)
            comm_hidden_rows = []
        for row in comm_hidden_rows:
            telemetry.emit("tp_overlap", mode=hp.tp_comm_mode, **row)

    # Quantized-collectives accounting: when the strategy carries a comm-
    # precision axis (grad/param comm dtypes or a quantized TP ring), record
    # the wire dtypes, the measured quantize+dequantize toll, and the
    # bytes-on-wire estimate — one `quant_comm` telemetry event `cli report`
    # joins into the predicted-vs-measured table. Observation-only (same
    # gating as the overlap measurement): never on the training hot path.
    from galvatron_tpu.parallel import quant_collectives as QC

    if ((QC.wants_quant_comm(hp) or hp.tp_comm_quant != "none")
            and (args.profile or telemetry.active_sink() is not None)):
        try:
            overhead_ms = QC.measure_quant_overhead_ms(
                (1 << 16,), dtype="int8", block=hp.comm_quant_block)
        except Exception:
            overhead_ms = None
        wire = None
        try:
            from galvatron_tpu.analysis.strategy_lint import _analytic_parameter_mb

            pmb = _analytic_parameter_mb(cfg)
            wire = QC.bytes_on_wire_mb(hp, pmb) if pmb else None
        except Exception:
            wire = None
        telemetry.emit(
            "quant_comm",
            grad_comm_dtype=",".join(s.grad_comm_dtype for s in hp.layers),
            param_comm_dtype=",".join(s.param_comm_dtype for s in hp.layers),
            comm_quant_block=hp.comm_quant_block,
            tp_comm_quant=hp.tp_comm_quant
            if hp.tp_comm_quant != "none" else None,
            quant_overhead_ms=overhead_ms,
            wire_mb_fp32=(wire or {}).get("fp32"),
            wire_mb_configured=(wire or {}).get("configured"),
        )
    started.end()

    with started.phase(control, tracing.LAUNCH_INIT_STATE):
        params = model.init_params(jax.random.PRNGKey(args.seed))
        opt_state = model.init_opt_state(tx, params)

    def load_from(ckpt_dir, iteration):
        # retries live INSIDE load_checkpoint now (around the manifest reads
        # and the orbax restore), so structural refusals (GLS202) are never
        # re-attempted while transient I/O still backs off
        kwargs = dict(
            params_target=params,
            params_shardings=model.shardings(),
            opt_state_target=opt_state,
            opt_state_shardings=model.opt_state_shardings(tx, params),
            hp=hp,
            verify_integrity=verify_ckpt,
            retry_policy=retry_policy,
            counters=res,
            sdc_check=sdc_mode != "off",
        )
        if elastic_plan is not None and elastic_plan.cross_strategy:
            # strategy-portable restore into THIS model's shardings; the
            # checkpoint's own strategy comes from its provenance
            kwargs.update(
                target=model, tx=tx, saved_strategy=elastic_plan.saved_hp,
                hp=None, params_target=None, params_shardings=None,
                opt_state_target=None, opt_state_shardings=None,
            )
        return ckpt("at_load").load_checkpoint(ckpt_dir, iteration, **kwargs)

    start_iter = 0
    if args.load:
        fresh_opt_state = opt_state
        with started.phase(control, tracing.LAUNCH_RESTORE):
            params, opt_state, meta = load_from(args.load, args.load_iteration)
        if opt_state is None:
            # params-only checkpoint (h2g conversion): optimizer starts fresh
            opt_state = fresh_opt_state
        start_iter = int(meta.get("iteration", 0))
        res.torn_checkpoints_skipped += len(meta.get("torn_iterations", ()))
        if jax.process_index() == 0:
            print("resumed from %s at iteration %d" % (args.load, start_iter))

    telemetry.emit(
        "run_start",
        model="%s_%s" % (args.model_type, args.model_size or fam.default_size),
        world_size=hp.world_size,
        strategy=hp.to_json_dict(),
        train_iters=args.train_iters,
        global_bsz=hp.global_bsz,
        start_iter=start_iter,
        model_flops_per_step=step_flops,
        peak_flops=peak_flops,
        device_kind=device_kind,
        pipeline_type=hp.pipeline_type,
        num_layers=hp.num_layers,
        resumed_from=args.load or None,
        model_type=args.model_type,
        hidden_size=getattr(cfg, "hidden_size", None),
        num_heads=getattr(cfg, "num_heads", None),
        num_kv_heads=getattr(cfg, "num_kv_heads", None),
        ffn_hidden=getattr(cfg, "ffn_hidden", None),
        vocab_size=getattr(cfg, "vocab_size", None),
        seq_len=getattr(cfg, "max_seq_len", None),
        mixed_precision=hp.mixed_precision,
        activation=getattr(cfg, "activation", None),
        loop_steps=getattr(cfg, "loop_steps", 1) if getattr(cfg, "loop_steps", 1) > 1 else None,
    )

    # ------------------------------------------------------- online autotuner
    # runtime/autotune.py: once the step time settles, fold the measured
    # steady state back into the profiler tables, re-search, and (apply mode)
    # hot-swap through the live-migration path below. `observe` logs the
    # decision it would take without acting on it.
    tuner = None
    autotune_comm_hidden = {"ms": sum(
        float(r.get("comm_hidden_ms") or 0.0) for r in comm_hidden_rows)}
    if autotune_mode != "off":
        from galvatron_tpu.runtime import autotune as AT

        tuner = AT.OnlineAutotuner(AT.AutotuneConfig(
            mode=autotune_mode,
            margin=getattr(args, "autotune_margin", None) or 0.05,
            # driver-state seams (no CLI flags): tests shrink the settle
            # window so the e2e fits the suite budget
            window=getattr(args, "autotune_window", None) or 5,
            rel_std=getattr(args, "autotune_rel_std", None) or 0.15,
        ))

    def build_step_fn():
        """The jitted step for the CURRENT model/hp — also the rebuild path
        after a live migration, where the sentinel downgrades vote->digest
        when the new layout has no dp redundancy left to vote with."""
        nonlocal sdc_mode
        if sdc_mode == "vote":
            reason = sdc_mod.vote_reason(hp)
            if reason is not None:
                telemetry.runtime_log(
                    "sdc_check=vote downgraded to digest: %s" % reason)
                sdc_mode = "digest"
        # (decided here, before anything is traced: the `compile` event's `forms` says `scan_grads`: `compute_dtype`)
        model.hp.narrow_scan_grads = scan_stacks_are_tight(model, tx)
        fn = model.make_train_step(
            tx, guard_anomalies=guard is not None, sdc_check=sdc_mode)
        if hooks is not None and hooks.wrap_step_fn:
            fn = hooks.wrap_step_fn(fn)
        return fn

    with started.phase(control, tracing.LAUNCH_BUILD):
        step_fn = build_step_fn()

    # Separate the one-off program-build cost (trace + XLA compile) from the
    # steady-state step time: AOT-lower and compile at the first batch with
    # explicit timing (profiler trace_ms/compile_ms — under scan-over-layer-
    # runs these are depth-constant), then drive the loop with the compiled
    # step. A lowering or compile failure (out of memory, a kernel the
    # compiler refuses) raises here with its cause; so does a later call
    # whose inputs the executable was not compiled for — the step pins its
    # output shardings (model_api.make_train_step), so that is a bug, not a
    # reason to compile again. Only step fns wrapped by fault hooks, which
    # have no jit surface to lower, are called as they are.
    _aot = {"fn": None, "census": {}}

    def compiled_step(*step_args):
        if not hasattr(step_fn, "trace"):
            return step_fn(*step_args)
        if _aot["fn"] is None:
            with control.span(tracing.COMPILE):
                with forms.recording() as took:
                    with started.phase(control, tracing.COMPILE_TRACE) as traced_in:
                        traced = step_fn.trace(*step_args)
                    with started.phase(control, tracing.COMPILE_LOWER) as lowered_in:
                        lowered = traced.lower()
                with started.phase(control, tracing.COMPILE_KEY) as keyed_in:
                    key = _step_exec_key(model.mesh, lowered)
                with started.phase(control, tracing.COMPILE_LOAD) as loaded_in:
                    compiled = _STEP_EXECUTABLES.get(key)
                    memo_hit = compiled is not None
                    cache_hit = False
                    if memo_hit:
                        _STEP_EXECUTABLES.move_to_end(key)
                    else:
                        compiled, cache_hit = _compile_step(lowered, started.jit)
                        _STEP_EXECUTABLES[key] = compiled
                        while len(_STEP_EXECUTABLES) > _STEP_EXECUTABLES_MAX:
                            _STEP_EXECUTABLES.popitem(last=False)
            if started.open:
                started.begin(control, tracing.LAUNCH_FIRST_RUN)
            # trace_ms is jaxpr and MLIR, compile_ms the memo's key and the
            # executable's load. A memo or persistent-cache hit reports
            # compile_ms ~0 — true: this process did not run XLA again for
            # this program
            trace_ms, compile_ms = traced_in.ms + lowered_in.ms, keyed_in.ms + loaded_in.ms
            prof.record_compile(trace_ms=trace_ms, compile_ms=compile_ms, cache_hit=cache_hit)
            prof.compiled_memory_mb = compiled_step_memory_mb(compiled) or None
            _aot["census"] = census = _census_at_compile(model, compiled)
            telemetry.emit(
                "compile",
                trace_ms=trace_ms,
                compile_ms=compile_ms,
                compiled_memory_mb=prof.compiled_memory_mb,
                xla_flops_per_step=obs_flops.xla_flops(compiled),
                cache_hit=(memo_hit or cache_hit) or None,
                # which form each part of the step took as it was traced (obs/forms.py):
                # part -> form -> how often; a part the step has none of is absent
                forms=took,
                # the layers whose token mixer is a Mamba-1 selective scan (models/parts/mamba.py),
                # and the layers that read a tensor an EARLIER layer published beside the residual
                # stream (`TransformerConfig.shared`); absent where the model has none
                **{k: v or None for k, v in shared_counts(cfg).items()},
                # the layers whose token mixer is EVA attention (models/parts/eva.py), the windows a
                # sequence is cut into and a sequence's pooled keys; absent where the model has none
                **{k: v or None for k, v in eva_counts(cfg).items()},
                # what the COMPILER made of `forms`' scan_grads: MB a chip and a layer of
                # the weight gradients over 1 MB that the compiled step sums
                # over dp inside a scanned run's backward, whole onto every
                # chip (`dp_grad_all_reduce_mb`) or into ZeRO's shards
                # (`dp_grad_reduce_scatter_mb`); absent without a dp axis
                **census.get("dp_grad_mb", {}),
                # every collective of the compiled step, a row an instruction the device trace
                # names (obs/compiled.step_collectives); absent on one chip. From the same walk
                collectives=census.get("rows"),
            )
            _aot["fn"] = compiled
        return _aot["fn"](*step_args)

    # deterministic resume: streams are stateless functions of the step index
    # (the reference keeps Megatron dataset cursors in the optimizer checkpoint)
    def make_stream(start_step: int):
        it_ = rsl.with_retry(
            lambda: build_data_iterator(args, fam, cfg, hp, start_step=start_step),
            retry_policy, res, description="dataloader build",
        )
        if hooks is not None and hooks.wrap_data_iter:
            it_ = hooks.wrap_data_iter(it_, start_step)
        return it_

    # --------------------------------------------------- dispatch-ahead knobs
    # A background thread runs batch prep + the sharded device_put for the
    # next `prefetch_batches` batches, and the host keeps up to
    # `inflight_steps` dispatched steps' metrics undrained so it can issue
    # step N+1..N+W while N executes. Both 0 is the fully host-serialized
    # loop: no prefetch thread, every step drained at once.
    prefetch_depth = max(int(getattr(args, "prefetch_batches", 2) or 0), 0)
    inflight_window = max(int(getattr(args, "inflight_steps", 2) or 0), 0)

    # -------------------------------------------------------- self-healing
    # Watchdog (runtime/health.py): a monitor thread armed around every
    # loop body, deadline learned from the steady-state step time. A missed
    # deadline first requests a drain-and-retry; a second miss with no
    # progress requests the emergency-save exit (exit code 3 via main()).
    wd = None
    if getattr(args, "watchdog", 0):
        wd = hlth.Watchdog(hlth.WatchdogConfig(
            floor_s=float(args.watchdog),
            factor=float(getattr(args, "watchdog_factor", 4.0)),
            startup_deadline_s=float(getattr(args, "watchdog_startup_s", 600.0)),
        )).start()
    # Mesh-health probe: enumeration diff + tiny collective every interval,
    # consulted at step boundaries (where a degraded verdict can be acted
    # on). `probe_devices_fn` is a test seam for simulated device loss.
    mesh_monitor = None
    if getattr(args, "mesh_probe_interval", 0):
        mesh_monitor = hlth.MeshHealthMonitor(
            model.mesh,
            interval_s=float(args.mesh_probe_interval),
            devices_fn=getattr(args, "probe_devices_fn", None),
        )
    # Live-migration requests: set by SIGUSR1 (manual re-plan), by a
    # degraded mesh-probe verdict under --migrate_on_degrade, or by tests;
    # consumed at the next step boundary where params/opt_state are
    # consistent.
    migrate_req = {"pending": False, "reason": None, "world": None}
    prev_usr1 = None
    if hasattr(signal, "SIGUSR1") and \
            threading.current_thread() is threading.main_thread():
        def _on_usr1(signum, frame):
            migrate_req.update(pending=True, reason="sigusr1", world=None)

        prev_usr1 = signal.signal(signal.SIGUSR1, _on_usr1)

    def _retrying(it_):
        """Per-batch retry (transient dataloader I/O) as an iterator, so the
        prefetch worker keeps the same backoff the sync path has."""
        while True:
            try:
                b = rsl.with_retry(lambda: next(it_), retry_policy, res,
                                   description="dataloader")
            except StopIteration:
                return
            yield b

    stream = {"prefetch": None, "iter": None}

    def close_stream():
        if stream["prefetch"] is not None:
            stream["prefetch"].close()
        stream["prefetch"] = None
        stream["iter"] = None

    def open_stream(start_step: int):
        """(Re)build the input pipeline at `start_step` — also the rollback
        path, which must discard the old prefetch thread's buffered batches
        along with the abandoned trajectory."""
        close_stream()
        it_ = make_stream(start_step)
        if prefetch_depth > 0:
            stream["prefetch"] = PrefetchIterator(
                _retrying(it_), depth=prefetch_depth, place_fn=model.shard_batch,
                # bound the wait on a live-but-unproductive producer by the
                # watchdog's current deadline so a wedged place_fn surfaces
                # as a diagnosed stall, not an indefinite driver hang
                stall_timeout=wd.deadline_s() if wd is not None else None,
            )
        else:
            stream["iter"] = it_

    def next_batch():
        if stream["prefetch"] is not None:
            try:
                return next(stream["prefetch"])  # sharded by the prefetch worker
            except PrefetchStalledError as e:
                # one recovery attempt: report through the watchdog event
                # stream, rebuild the pipeline at the current step (exact
                # replay — streams are functions of the step index), retry;
                # a second stall propagates and fails the run honestly
                telemetry.emit("watchdog", action="prefetch_stall", iter=it,
                               detail=str(e))
                telemetry.runtime_log(
                    "prefetch stalled at iteration %d: %s — rebuilding the "
                    "input pipeline" % (it, e))
                open_stream(it)
                return next(stream["prefetch"])
        b = rsl.with_retry(lambda: next(stream["iter"]), retry_policy, res,
                           description="dataloader")
        return model.shard_batch(b)

    with started.phase(control, tracing.LAUNCH_DATA):
        open_stream(start_iter)

    eval_interval = getattr(args, "eval_interval", 0) or 0
    eval_iters = max(getattr(args, "eval_iters", 5) or 0, 1)
    # Eval batches are materialised ONCE up front: every eval pass sees the
    # same batches (steps 0..eval_iters of the split stream), the per-pass
    # index rebuild is avoided, and an unusable split (--split weights that
    # leave valid/test empty for this corpus) fails BEFORE training instead
    # of crashing the final test eval. model.eval_loss is the forward-only
    # path where one exists (reference evaluation is forward-only); under the
    # 1F1B engines the grad-bearing loss_fn would pay the backward too.
    eval_fn = None
    eval_batches = {}
    if eval_interval:
        eval_fn = jax.jit(model.eval_loss)
        for split in ("valid", "test"):
            it = build_data_iterator(args, fam, cfg, hp, start_step=0, split=split)
            eval_batches[split] = [
                model.shard_batch(next(it)) for _ in range(eval_iters)
            ]

    def evaluate(params, split):
        """Mean loss over the split's cached batches (reference
        train_dist.py's evaluate-and-log pass). All eval batches are
        dispatched back-to-back and drained ONCE — the old per-batch
        ``float()`` re-serialized host and device for the whole pass."""
        vals = [eval_fn(params, b) for b in eval_batches[split]]
        return float(jnp.sum(jnp.stack(vals))) / eval_iters
    prof = RuntimeProfiler(
        warmup=min(2, max(args.train_iters - 1, 0)),
        rank=jax.process_index(),
        model_name="%s_%s" % (args.model_type, args.model_size or fam.default_size),
        log_dir=getattr(args, "train_log_dir", None),
        model_flops=step_flops,
        peak_flops=peak_flops,
    )
    for row in comm_hidden_rows:
        prof.record_comm_hidden(row["run"], row["comm_hidden_ms"])

    preempt = None
    if getattr(args, "emergency_save", 0):
        preempt = rsl.PreemptionHandler().install()

    # every save — periodic, final, rollback re-save AND the emergency save a
    # preemption triggers — carries provenance, so the NEXT resume can
    # re-plan for whatever hardware survives
    from galvatron_tpu.runtime import elastic as els

    provenance = els.build_provenance(
        hp, cfg, optimizer_args_from(args), mesh=model.mesh,
        memory_budget_gb=getattr(args, "elastic_memory_gb", None) or (
            elastic_plan.provenance.get("memory_budget_gb")
            if elastic_plan is not None else None),
    )

    def save_now(iteration: int, emergency: bool = False):
        meta = {"iteration": iteration}
        if emergency:
            meta["emergency"] = True
            meta["signal"] = preempt.signal_name if preempt else None
        save_checkpoint = ckpt().save_checkpoint  # what is left of the import is no attempt of the save's
        rsl.with_retry(
            lambda: save_checkpoint(
                args.save, iteration, params, opt_state, hp, train_meta=meta,
                keep_latest_k=getattr(args, "keep_latest_k", 0) or None,
                provenance=provenance,
            ),
            retry_policy, res, description="checkpoint save",
        )

    losses = []
    last_shared = {}  # the last accepted step's telemetry.SHARED_STEP_FIELDS, for the summary
    loss_iters = []  # iteration of each accepted loss (rollback truncation)
    valid_losses = []  # (iteration, mean valid loss)
    # (iteration, metrics, dispatch_ms, data_wait_ms) dispatched, not yet drained
    inflight = deque()
    interrupted = None
    last_save = None
    it = start_iter

    def emit_step_event(d_it, metrics, loss, disp_ms, wait_ms):
        """One schema-valid ``step`` event per drained iteration. Costs a
        device memory-stats read plus one enqueue — only paid when a
        telemetry sink is installed (the ≤2%% steps/s overhead budget).
        `disp_ms` and `wait_ms` (the gt/next_batch span) travel with the
        step through the in-flight window — ``prof.dispatch_ms[-1]`` would
        belong to the latest DISPATCHED iteration, several ahead of the one
        draining here."""
        if telemetry.active_sink() is None:
            return
        iter_ms = prof.all_times_ms[-1] if prof.all_times_ms else None
        # host_blocked was appended by prof.end() for THIS iteration iff it
        # is post-warmup; warmup steps omit the field
        blocked = prof.host_blocked_ms[-1] \
            if (d_it >= prof.warmup and prof.host_blocked_ms) else None
        mem = device_memory_stats()
        grad_norm = metrics.get("grad_norm") if isinstance(metrics, dict) else None
        if grad_norm is not None:
            grad_norm = float(grad_norm)
        telemetry.emit(
            "step", iter=d_it,
            loss=loss if np.isfinite(loss) else None,
            iter_ms=iter_ms,
            dispatch_ms=disp_ms,
            data_wait_ms=wait_ms,
            host_blocked_ms=blocked,
            hbm_in_use_mb=mem["bytes_in_use"] / 2**20 or None,
            hbm_peak_mb=mem["peak_bytes_in_use"] / 2**20 or None,
            mfu=obs_flops.mfu(step_flops, iter_ms, peak_flops),
            model_flops_per_s=obs_flops.flops_per_s(step_flops, iter_ms),
            grad_norm=grad_norm if grad_norm is None or np.isfinite(grad_norm) else None,
            # a routed-experts config's step hands these back beside the loss
            **{k: float(metrics[k])
               for k in (telemetry.EXPERT_STEP_FIELDS + telemetry.SHARE_STEP_FIELDS
                         + telemetry.LINEAR_STEP_FIELDS + telemetry.SSM_STEP_FIELDS
                         + telemetry.SHARED_STEP_FIELDS + telemetry.EVA_STEP_FIELDS
                         + telemetry.LOOP_STEP_FIELDS + telemetry.HYPER_STEP_FIELDS)
               if isinstance(metrics, dict) and k in metrics},
        )

    def drain_one():
        """Drain the oldest in-flight step: block on its metrics and run the
        host-side bookkeeping the synchronous loop did inline (iteration
        log, anomaly accounting, telemetry). Returns (iteration,
        rollback_needed)."""
        d_it, metrics, disp_ms, wait_ms = inflight.popleft()
        with control.span(tracing.DRAIN):  # the blocking read
            prof.end(d_it, n_samples=hp.global_bsz, outputs=metrics["loss"])
        if started.open:
            # the first step has drained: the launch is over, its listeners go
            prof.launch = started.finish()
            telemetry.emit("launch", **prof.launch, checkpoint_import=ckpt.fields())
        if wd is not None:
            # a drain is the loop's liveness signal AND the deadline's
            # training data (the learned budget tracks the steady step time)
            wd.observe_step_time(prof.all_times_ms[-1])
            wd.progress(d_it, inflight=len(inflight))
        if tuner is not None:
            tuner.observe_step(
                prof.all_times_ms[-1] if prof.all_times_ms else None,
                iteration=d_it)
        if args.profile or d_it % max(args.log_interval, 1) == 0:
            prof.log_iteration(d_it, metrics)
        loss = float(metrics["loss"])
        emit_step_event(d_it, metrics, loss, disp_ms, wait_ms)
        control.after_drain(d_it)
        if sdc_ladder is not None and isinstance(metrics, dict) \
                and metrics.get("sdc_mismatch") is not None \
                and bool(metrics["sdc_mismatch"]):
            # replica vote disagreed: the jitted step already froze
            # params/opt_state (keep-old select), and this step's loss came
            # from a corrupt replica — record nothing; drain_inflight runs
            # the repair/re-execute/escalate ladder
            sdc_req.update(pending=True, votes=[
                int(v) for v in np.asarray(metrics["sdc_votes"]).ravel()])
            return d_it, False
        if sdc_mode != "off" and isinstance(metrics, dict) \
                and metrics.get("sdc_fold") is not None \
                and d_it % sdc_interval == 0:
            res.sdc_checks += 1
            telemetry.emit(
                "sdc_check", mode=sdc_mode, iter=d_it,
                fold=int(metrics["sdc_fold"]),
                sumsq=float(metrics["sdc_sumsq"]),
            )
        verdict = guard.observe(loss) if guard is not None else "ok"
        if verdict == "ok":
            losses.append(loss)
            loss_iters.append(d_it)
            if isinstance(metrics, dict) and telemetry.SHARED_STEP_FIELDS[0] in metrics:
                last_shared.update({k: float(metrics[k]) for k in telemetry.SHARED_STEP_FIELDS if k in metrics})
            return d_it, False
        # the jitted step already kept the old params/opt_state
        # (guard_anomalies select); only account and maybe roll back
        res.anomalies_skipped += 1
        telemetry.emit(
            "anomaly_skip", iter=d_it, verdict=verdict,
            loss=loss if np.isfinite(loss) else None, strikes=guard.strikes,
        )
        if jax.process_index() == 0:
            print(
                "iteration %d: %s anomaly (loss %r) — update skipped "
                "(strike %d/%d)"
                % (d_it, verdict, loss, guard.strikes, guard.cfg.max_strikes)
            )
        return d_it, guard.should_roll_back

    def sdc_recover(d_it, votes):
        """A drained step's replica vote disagreed. The jitted step froze
        params/opt_state, and every later in-flight step carried the frozen
        (still-corrupt) state forward through the same select, so the whole
        window is abandoned and the driver's newest params ARE the
        mismatching step's input state. Vote on the host, repair the
        convicted replica from a healthy peer, reopen the stream at the
        mismatching step and re-execute — bitwise identical to a clean run
        because the digest fold is exact. Repeat offenders escalate through
        the strike ladder into the degraded-mesh migration path."""
        nonlocal it, params, opt_state
        verdict = sdc_ladder.observe(votes, sdc_vote_ids())
        res.sdc_mismatches += 1
        suspects = verdict["suspects"]
        telemetry.emit(
            "sdc_mismatch", iter=d_it, action=verdict["action"],
            suspects=suspects or None, folds=votes,
            strikes=verdict["strikes"] or None,
        )
        if jax.process_index() == 0:
            print(
                "iteration %d: replica vote mismatch (%s) — %s%s"
                % (d_it, " ".join("0x%08x" % v for v in votes),
                   verdict["action"],
                   " (suspect devices %s)" % suspects if suspects else "")
            )
        inflight.clear()  # descendants of the frozen state
        if suspects:
            sdc_req["tie_rounds"] = 0
            params = sdc_mod.repair_from_replica(params, suspects)
            opt_state = sdc_mod.repair_from_replica(opt_state, suspects)
        else:
            # detected but not localizable (tied vote, e.g. dp=2): the only
            # move is re-executing and hoping the lie was transient — but a
            # persistent tie would re-execute forever, so bound it
            sdc_req["tie_rounds"] += 1
            if sdc_req["tie_rounds"] > sdc_ladder.strikes:
                raise rsl.TrainingAnomalyError(
                    "replica digests keep disagreeing with no majority at "
                    "iteration %d (%d consecutive tied votes); cannot "
                    "localize the lying device"
                    % (d_it, sdc_req["tie_rounds"]))
        res.sdc_reexecutions += 1
        it = d_it
        open_stream(d_it)
        if verdict["quarantine"]:
            sdc_quarantined.update(int(d) for d in verdict["quarantine"])
            res.sdc_quarantines += 1
            avail = [d for d in jax.devices()
                     if int(d.id) not in sdc_quarantined]
            telemetry.emit(
                "sdc_quarantine", iter=d_it,
                device_ids=sorted(int(d) for d in verdict["quarantine"]),
                strikes=verdict["strikes"] or None, reason="replica_vote")
            if jax.process_index() == 0:
                print(
                    "iteration %d: device(s) %s quarantined after %d "
                    "consecutive strikes — %d device(s) survive"
                    % (d_it, sorted(verdict["quarantine"]),
                       sdc_ladder.strikes, len(avail)))
            if mesh_monitor is not None:
                # future probes keep reporting the world degraded until the
                # run migrates off the convicted device
                mesh_monitor.quarantine(verdict["quarantine"])
            if getattr(args, "migrate_on_degrade", 0):
                migrate_req.update(pending=True, reason="sdc_quarantine",
                                   world=len(avail))
            else:
                raise rsl.TrainingAnomalyError(
                    "device(s) %s convicted of silent corruption at "
                    "iteration %d; restart without them or pass "
                    "--migrate_on_degrade 1 to migrate off them in place"
                    % (sorted(verdict["quarantine"]), d_it))

    def drain_inflight(window: int) -> bool:
        """Drain until at most `window` steps remain in flight (window=0 is
        the forced drain at eval/save/preemption boundaries and in the
        synchronous escape-hatch loop). On a guard-demanded rollback the
        rest of the window is discarded undrained — those steps extend the
        abandoned trajectory — and the checkpoint/stream state is swapped
        here. Returns True iff a rollback happened, so the caller re-enters
        the loop at the restored iteration."""
        nonlocal it, params, opt_state
        while len(inflight) > window:
            d_it, need_rollback = drain_one()
            if sdc_req["pending"]:
                sdc_req.update(pending=False)
                sdc_recover(d_it, sdc_req["votes"])
                return True
            if not need_rollback:
                continue
            intact = ckpt().intact_iterations(args.save) if args.save else []
            if res.rollbacks >= guard.cfg.max_rollbacks or not intact:
                raise rsl.TrainingAnomalyError(
                    "persistent training anomalies at iteration %d "
                    "(%d consecutive; %d rollbacks used, %s checkpoints "
                    "to roll back to)"
                    % (d_it, guard.strikes, res.rollbacks,
                       len(intact) if args.save else "no")
                )
            res.rollbacks += 1
            inflight.clear()  # the not-yet-drained steps are abandoned too
            prev_opt_state = opt_state
            params, opt_state, meta = load_from(args.save, None)
            if opt_state is None:  # params-only checkpoint
                opt_state = prev_opt_state
            it = int(meta.get("iteration", 0))
            res.torn_checkpoints_skipped += len(meta.get("torn_iterations", ()))
            while loss_iters and loss_iters[-1] >= it:
                loss_iters.pop()
                losses.pop()
            while valid_losses and valid_losses[-1][0] > it:
                valid_losses.pop()
            # optional stream reseed: shift the deterministic stream
            # so the replay does not hit the same poisoned batch
            offset = res.rollbacks * getattr(args, "anomaly_reseed", 0)
            open_stream(it + offset)
            guard.reset_after_rollback()
            telemetry.emit(
                "rollback", to_iter=it, at_iter=d_it, count=res.rollbacks,
                stream_offset=offset,
            )
            if jax.process_index() == 0:
                print(
                    "rolled back to checkpoint iteration %d "
                    "(rollback %d/%d, stream offset +%d)"
                    % (it, res.rollbacks, guard.cfg.max_rollbacks, offset)
                )
            return True
        return False

    def do_migrate(reason: str, target_world: Optional[int] = None,
                   target_hp=None) -> bool:
        """Live in-memory strategy migration (runtime/elastic.migrate): at a
        step boundary with the in-flight window drained and the prefetch
        thread torn down, resolve a strategy for `target_world` (operator
        JSON or a fresh search), relayout params + adam moments on-device,
        rebuild the model + step function (recompiling through the
        in-process executable memo), and reopen the input pipeline at the
        SAME step — the trajectory continues as if the run had been
        checkpointed and resumed under the target strategy, minus the disk
        round-trip. Returns True when a swap happened; refusals raise the
        GLS2xx DiagnosticError contract (GLS207 for migration-specific
        infeasibility)."""
        nonlocal model, hp, params, opt_state, step_fn, provenance, \
            eval_fn, mesh_monitor
        if wd is not None:
            wd.disarm()
        if drain_inflight(0):
            # the guard demanded a rollback while draining: the restored
            # trajectory wins this boundary; the migration request is dropped
            # (the next probe/SIGUSR1 re-raises it against the restored run)
            return False
        avail = [d for d in jax.devices() if int(d.id) not in sdc_quarantined]
        if target_hp is not None:
            # the caller (the autotuner) already searched and linted its
            # winner; skip the resolve loop and swap straight to it
            new_hp, action, world = target_hp, "autotune", target_hp.world_size
        else:
            world = int(target_world or len(avail))
            new_hp = action = None
            last_err = None
            for w in range(world, 0, -1):
                try:
                    new_hp, action = els.resolve_migration_strategy(args, cfg, w, hp)
                    world = w
                    break
                except DiagnosticError as e:
                    # a quarantined world (e.g. 3 of 4 devices) often has no
                    # feasible strategy at its exact size; shrink until one fits
                    last_err = e
                    if reason != "sdc_quarantine":
                        raise
            if new_hp is None:
                raise last_err
            if world < len(avail) and jax.process_index() == 0:
                print("migration (%s): no feasible strategy for all %d "
                      "surviving device(s); migrating to %d"
                      % (reason, len(avail), world))
        if new_hp.to_json_dict() == hp.to_json_dict() and world == hp.world_size:
            # resolve BEFORE tearing anything down: a no-op request (already
            # on the target strategy — e.g. a repeated trigger) leaves the
            # stream and model untouched
            telemetry.runtime_log(
                "migration (%s): resolved strategy is identical to the "
                "running one; nothing to swap" % reason)
            return False
        close_stream()
        devs = avail[:world] \
            if (world != hp.world_size or sdc_quarantined) else None
        build = None
        if fam.build:
            build = lambda c, h, d=None: fam.build(c, h)  # noqa: E731
        result = els.migrate(
            model, params, opt_state, tx, new_hp, devices=devs,
            build_model=build, reason=reason, iteration=it,
            sdc_check=sdc_mode != "off",
        )
        model, params, opt_state = result.model, result.params, result.opt_state
        hp = new_hp
        provenance = els.build_provenance(
            hp, cfg, optimizer_args_from(args), mesh=model.mesh,
            memory_budget_gb=getattr(args, "elastic_memory_gb", None))
        step_fn = build_step_fn()
        _aot.update(fn=None, census={})  # re-lower; the executable memo absorbs repeats
        if sdc_ladder is not None:
            # the convicted device is out of the new mesh; surviving devices
            # start with a clean slate
            sdc_ladder.reset()
        if eval_fn is not None:
            eval_fn = jax.jit(model.eval_loss)
            for split in eval_batches:
                # device_put onto the new model's batch shardings (committed
                # arrays reshard in place; values are unchanged)
                eval_batches[split] = [
                    model.shard_batch(b) for b in eval_batches[split]]
        if mesh_monitor is not None:
            mesh_monitor = hlth.MeshHealthMonitor(
                model.mesh, interval_s=mesh_monitor.interval_s,
                devices_fn=getattr(args, "probe_devices_fn", None),
                quarantined_ids=set(mesh_monitor.quarantined_ids),
            )
        open_stream(it)
        if jax.process_index() == 0:
            print(
                "live migration (%s/%s) at iteration %d: world %d -> %d, "
                "%s relayout"
                % (reason, action, it, result.from_hp.world_size,
                   hp.world_size,
                   "same-tree" if result.same_layout else "cross-layout")
            )
        return True

    def autotune_plan() -> bool:
        """One planning epoch of the online autotuner (runtime/autotune.py):
        fold the measured steady state into the profiler tables, re-search
        under the original memory budget with settle_bsz pinned to the live
        global batch, and — in apply mode — hot-swap through do_migrate when
        the predicted saving clears the hysteresis margin and amortizes over
        the remaining steps. Returns True iff a swap happened (the loop
        re-enters at the same step under the new strategy)."""
        nonlocal predictions
        from galvatron_tpu.runtime import autotune as AT

        steady_ms = tuner.steady_step_ms()
        remaining = max(args.train_iters - it, 0)
        budget = getattr(args, "elastic_memory_gb", None) or \
            provenance.get("memory_budget_gb") or els.DEFAULT_MEMORY_GB
        from_json = hp.to_json_dict()
        incumbent_ms = winner_ms = None
        new_hp = tables = None
        base = els.analytic_model_profiles(cfg, max_tp=hp.world_size)
        if base is not None and steady_ms is not None:
            tables = AT.calibrate_from_run(
                cfg, hp, base[0], base[1], predictions or [], steady_ms,
                comm_hidden_ms=autotune_comm_hidden["ms"],
                compiled_memory_mb=prof.compiled_memory_mb,
            )
        if tables is not None:
            tcfg, mcfg = tables
            try:
                new_hp = els.search_surviving_strategy(
                    cfg, hp.world_size, hp.global_bsz, budget,
                    model_type=args.model_type,
                    config_dir=getattr(args, "config_dir", None),
                    default_dp_type=hp.default_dp_type,
                    time_config=tcfg, memory_config=mcfg,
                    # the re-plan searches the remat axis too: freed memory
                    # from heavier per-layer remat can convert into fewer
                    # chunks (settle_chunk=None sweeps them) and vice versa
                    remat_search=True,
                )
            except Exception as e:  # a failed re-search must not kill the run
                telemetry.runtime_log("autotune search failed: %s" % e)
                new_hp = None
            if new_hp is not None:
                # the winner inherits the run's execution knobs, exactly as
                # resolve_migration_strategy grafts them onto a searched hp
                for k in ("scan_layers", "remat_policy", "tp_comm_mode",
                          "tp_comm_quant", "mixed_precision"):
                    setattr(new_hp, k, getattr(hp, k))
                incumbent_ms = AT.predicted_step_ms(cfg, hp, tcfg, mcfg)
                winner_ms = AT.predicted_step_ms(cfg, new_hp, tcfg, mcfg)
        decision = tuner.decide(
            incumbent_ms, winner_ms, remaining,
            identical=(new_hp is not None
                       and new_hp.to_json_dict() == from_json),
            target_hp=new_hp)
        swapped = False
        wall_ms = 0.0
        if decision.swap and tuner.config.mode == "apply":
            t0 = time.perf_counter()
            swapped = do_migrate("autotune", target_hp=decision.target_hp)
            wall_ms = (time.perf_counter() - t0) * 1e3
        telemetry.emit(
            "autotune", action="plan", iter=it, mode=tuner.config.mode,
            reason=decision.reason,
            steady_step_ms=steady_ms,
            incumbent_ms=incumbent_ms, winner_ms=winner_ms,
            predicted_saving_ms=decision.predicted_saving_ms,
            margin=tuner.config.margin, remaining_steps=remaining,
            swap_cost_ms=decision.swap_cost_ms,
            swapped=int(swapped),
            from_strategy=from_json,
            to_strategy=new_hp.to_json_dict() if new_hp is not None else None,
        )
        if jax.process_index() == 0:
            print("autotune (%s) at iteration %d: %s (steady %.2f ms, "
                  "incumbent %s ms, winner %s ms)"
                  % (tuner.config.mode, it,
                     "swapping" if swapped else decision.reason,
                     steady_ms or -1.0,
                     "%.2f" % incumbent_ms if incumbent_ms else "-",
                     "%.2f" % winner_ms if winner_ms else "-"))
        if swapped:
            tuner.mark_swapped(it, wall_ms, decision.predicted_saving_ms)
            # the overlap measurement belongs to the old layout; a stale
            # subtraction would mis-calibrate the next epoch
            autotune_comm_hidden["ms"] = 0.0
            try:
                from galvatron_tpu.obs import attribution as obs_attr

                predictions = obs_attr.predict_layer_runs(cfg, hp)
            except Exception:
                predictions = None
            for p in predictions or ():
                telemetry.emit("layer_run", **p)
        return swapped

    try:
        while True:
            if interrupted is None and it < args.train_iters:
                if hooks is not None and hooks.on_step:
                    with control.span(tracing.ON_STEP):
                        hooks.on_step(it)
                if preempt is not None and preempt.triggered:
                    interrupted = preempt.signal_name
                    telemetry.emit("preemption", signal=interrupted, iter=it)
                if wd is not None and interrupted is None:
                    if wd.abort_requested:
                        # second missed deadline with no progress: take the
                        # emergency-save exit path; main() maps the summary
                        # to WATCHDOG_EXIT_CODE
                        interrupted = "watchdog"
                    elif wd.take_retry_request():
                        # first missed deadline: drain whatever the device
                        # will still give us and keep going
                        telemetry.runtime_log(
                            "watchdog: draining %d in-flight step(s) after "
                            "stall at iteration %d" % (len(inflight), it))
                        if drain_inflight(0):
                            continue
                if interrupted is None and mesh_monitor is not None:
                    verdict = mesh_monitor.maybe_probe()
                    if verdict is not None and verdict["status"] != "healthy":
                        telemetry.emit(
                            "watchdog", action="mesh_probe", iter=it,
                            status=verdict["status"],
                            expected=verdict["expected"], live=verdict["live"],
                            missing_ids=verdict["missing_ids"] or None,
                            detail=verdict.get("error"),
                        )
                        telemetry.runtime_log(
                            "mesh probe: %s (expected %d devices, live %d)"
                            % (verdict["status"], verdict["expected"],
                               verdict["live"]))
                        if verdict["status"] == "degraded" and \
                                getattr(args, "migrate_on_degrade", 0):
                            migrate_req.update(
                                pending=True, reason="degraded_mesh",
                                world=verdict["live"])
                if interrupted is None and migrate_req["pending"]:
                    migrate_req.update(pending=False)
                    do_migrate(migrate_req["reason"], migrate_req["world"])
                    continue
                if interrupted is None and tuner is not None \
                        and tuner.plan_pending:
                    if autotune_plan():
                        continue
            if interrupted is not None or it >= args.train_iters:
                # loop exit: forced full drain first. A rollback surfacing in
                # the final drain resumes training at the restored iteration
                # — unless we are exiting on a preemption signal, where the
                # emergency save (of the rolled-back state) takes priority.
                if drain_inflight(0) and interrupted is None:
                    continue
                if wd is not None:
                    wd.disarm()  # the exit saves are not step work
                break
            if wd is not None:
                wd.arm(it, "fetch", inflight=len(inflight))
            # a requested trace starts here, before the fetch, so that the
            # first traced step's gt/next_batch is in it
            control.before_dispatch(it)
            # the wait for the first batch is the launch's; first_run holds the later ones
            first_fetch = started.phase(control, tracing.LAUNCH_DATA) if started.open else tracing.OFF
            with first_fetch, control.span(tracing.NEXT_BATCH) as fetch:
                batch = next_batch()
            prof.start(it)
            with control.span(tracing.DISPATCH, step_num=it):
                if guard is not None:
                    # NB deferred metrics: the spike cap is computed from losses
                    # drained so far, i.e. it lags the dispatched step by at most
                    # `inflight_steps` (NaN/Inf gating is in-jit and exact)
                    params, opt_state, metrics = compiled_step(
                        params, opt_state, batch, np.float32(guard.spike_cap()))
                else:
                    params, opt_state, metrics = compiled_step(params, opt_state, batch)
            disp_ms = prof.dispatched(it)
            inflight.append((it, metrics, disp_ms, fetch.ms))
            if args.save:
                # the first step is on the device: a run that will save imports the
                # checkpoint module behind its first steps (once: CheckpointModule.start)
                ckpt.start()
            if wd is not None:
                wd.arm(it, "inflight", inflight=len(inflight))
            it += 1
            if drain_inflight(inflight_window):
                continue
            if eval_interval and it % eval_interval == 0:
                if drain_inflight(0):  # forced drain before every eval
                    continue
                if wd is not None:
                    wd.disarm()  # eval passes are legitimately slow
                with control.span(tracing.EVAL):
                    vloss = evaluate(params, "valid")
                valid_losses.append((it, vloss))
                telemetry.emit("eval", iter=it, split="valid", loss=vloss)
                if jax.process_index() == 0:
                    print("iteration %d: valid loss %.6f" % (it, vloss))
            if args.save and args.save_interval and it % args.save_interval == 0:
                if drain_inflight(0):  # forced drain before every save
                    continue
                if wd is not None:
                    wd.disarm()  # checkpoint I/O has its own retry containment
                with control.span(tracing.SAVE):
                    save_now(it)
                last_save = it
        if interrupted is not None and args.save and last_save != it:
            # preemption: commit the state reached so far at the step boundary
            save_now(it, emergency=True)
            res.emergency_saves += 1
            last_save = it
            if jax.process_index() == 0:
                print("emergency checkpoint at iteration %d (%s)" % (it, interrupted))
        elif args.save and last_save != it:
            save_now(it)
            last_save = it
        # end-of-run fence: steady-state numbers must not credit device work
        # still in flight behind the last dispatch
        prof.loop_fence((params, opt_state))
    finally:
        ckpt.join()  # the helper thread of a run that ends before its first save
        close_stream()
        control.close()
        prof.close()
        if preempt is not None:
            preempt.uninstall()
        if wd is not None:
            wd.stop()
        if prev_usr1 is not None:
            signal.signal(signal.SIGUSR1, prev_usr1)
    prof.resilience_counters = res.as_dict()
    summary = prof.summary()
    summary["losses"] = losses
    if last_shared:  # a model whose layers publish: its two layer counts and the last step's counters
        summary.update(shared_counts(cfg), **last_shared)
    summary["resilience"] = res.as_dict()
    # how this run came by runtime/checkpoint, as it stands now (the `launch` event: at the first drain)
    summary["checkpoint_import"] = ckpt.fields()
    # the compiled step's collectives (obs/compiled.step_collectives): counted as the step
    # compiled where a sink listened then, else HERE, once the last step has drained and a
    # trace has stopped, so that neither the launch nor a step waits for the text
    census = _aot["census"] or (_step_census(model, _aot["fn"]) if _aot["fn"] is not None else {})
    if census:
        summary["step_collectives"] = {"rows": census["rows"], "census_ms": census["census_ms"]}
    if tuner is not None:
        summary["autotune"] = {"plans": tuner.plans, "swaps": tuner.swaps}
    if wd is not None:
        summary["watchdog"] = wd.summary()
    if interrupted is not None:
        summary["interrupted"] = interrupted
    if eval_interval:
        summary["valid_losses"] = valid_losses
        summary["test_loss"] = evaluate(params, "test")
        telemetry.emit("eval", iter=it, split="test", loss=summary["test_loss"])
        if jax.process_index() == 0:
            print("final test loss %.6f" % summary["test_loss"])
    telemetry.emit("run_end", summary={
        k: v for k, v in summary.items() if k not in ("losses", "valid_losses")
    })
    if args.profile and jax.process_index() == 0:
        print({k: v for k, v in summary.items() if k != "losses"})
    return summary


def main(argv=None):
    args = initialize_galvatron(mode="train_dist", argv=argv)
    try:
        summary = train(args)
    except Exception as e:
        from galvatron_tpu.analysis.diagnostics import DiagnosticError

        if isinstance(e, DiagnosticError) and any(
            d.code.startswith("GLS2") for d in e.diagnostics
        ):
            # the elastic-resume refusal contract: actionable diagnostics on
            # stderr and exit code 2 (distinct from ordinary failures), so
            # supervisors can tell "needs operator input" from "retry me"
            for d in e.diagnostics:
                print(d.format(), file=sys.stderr)
            sys.exit(2)
        raise
    if (summary.get("watchdog") or {}).get("escalated"):
        # the run wedged, evacuated through the emergency save, and exited
        # cleanly: a DISTINCT exit code (3) tells the supervisor "resume me,
        # and look at the watchdog events" rather than "retry blindly"
        print("watchdog escalated: emergency state saved; exiting %d"
              % hlth.WATCHDOG_EXIT_CODE, file=sys.stderr)
        sys.exit(hlth.WATCHDOG_EXIT_CODE)
    return summary


if __name__ == "__main__":
    main()
