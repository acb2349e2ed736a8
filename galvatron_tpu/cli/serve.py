"""``python -m galvatron_tpu.cli serve`` — searched-strategy inference.

Restores a checkpoint (train layout or serve layout — the strategy-portable
restore path relayouts either into THIS run's strategy), builds the
prefill/decode engine over the strategy-sharded KV cache (serve/), drives a
synthetic or replayed request load through the continuous batcher, and
reports TTFT/TPOT percentiles and tokens/s.

    python -m galvatron_tpu.cli serve \
        --galvatron_config_path configs/galvatron_config_serve.json \
        --load /ckpts/run42 --num_requests 64 --rate_rps 4

The strategy is linted in serve mode before any tracing: pp>1, ring-cp and
ulysses layouts refuse with GLS014 (the decode step cannot run them), and
with a --memory_budget the KV+weight budget is checked against the config's
serve_max_concurrency.

Serving resilience (the serve-side mirror of the train loop's stack):

- admission control + shedding: ``--p99_ttft_ms`` / ``--max_pending`` /
  ``--request_timeout_s`` shed requests as structured retryable rejections
  (serve_shed events) instead of admitting them to time out;
- ``--watchdog`` arms runtime/health.Watchdog around prefill/decode ticks
  with learned deadlines; escalation gracefully drains (in-flight decodes
  complete where possible, the rest shed retryable) and exits 3, the same
  drain SIGTERM/SIGINT take via PreemptionHandler (exit 0);
- ``--mesh_probe_interval`` + ``--migrate_on_degrade`` poll the mesh between
  ticks and, on a degraded verdict, re-run the serve-objective search for
  the surviving world, relayout params in memory, rebuild the KV cache in
  the new layout, and journal-replay in-flight requests — no checkpoint
  round-trip. Worlds that cannot serve refuse with GLS015 (exit 2).
"""

from __future__ import annotations

import sys
import time
from typing import Optional

import jax

from galvatron_tpu.cli.arguments import (
    hp_config_from_args,
    initialize_galvatron,
    model_config_from_args,
)
from galvatron_tpu.obs import telemetry
from galvatron_tpu.utils.compile_cache import enable_persistent_cache


def serve(args) -> dict:
    """Returns the load summary dict (tests/driver use); with --telemetry
    the serve_request/decode_batch events stream to JSONL like train's."""
    sink = None
    if getattr(args, "telemetry", None):
        sink = telemetry.JsonlSink(
            args.telemetry,
            depth=max(int(getattr(args, "telemetry_buffer", 1024) or 1), 1),
        )
        telemetry.install(sink)
    try:
        return _serve(args)
    finally:
        if sink is not None:
            telemetry.uninstall(sink)
            sink.close()


def _serve(args) -> dict:
    enable_persistent_cache()
    fam, cfg = model_config_from_args(args)
    world = args.world_size or len(jax.devices())
    hp = hp_config_from_args(args, cfg.num_layers, world)

    # fail fast BEFORE tracing: decode-incompatible layouts (pp>1, ring cp,
    # ulysses) refuse with GLS014; train-only knobs warn
    from galvatron_tpu.analysis import strategy_lint as _slint
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    report = _slint.lint_hp(
        hp, model_cfg=cfg, file=getattr(args, "galvatron_config_path", None),
        mode="serve",
    )
    for d in report.warnings:
        print("strategy lint: %s" % d.format())
    if not report.ok:
        raise DiagnosticError(report.errors)

    if fam.build is not None:
        raise ValueError(
            "serving supports the generic causal-LM families only; %r "
            "builds its own model tree" % fam.name
        )

    from galvatron_tpu.runtime import elastic as els
    from galvatron_tpu.runtime import health as hlth
    from galvatron_tpu.runtime import resilience as rsl
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
    from galvatron_tpu.serve.engine import (
        ContinuousBatcher,
        ServeEngine,
        replay_requests,
        summarize,
        synthetic_requests,
    )
    from galvatron_tpu.serve.kv_cache import KVCacheConfig, kv_bytes_per_slot

    model = construct_hybrid_parallel_model(cfg, hp)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    if args.load:
        from galvatron_tpu.runtime import checkpoint as ckpt

        # strategy-portable restore (tx=None => params only): a TRAIN-layout
        # checkpoint relayouts into this serve strategy via the same
        # machinery elastic resume uses — the saved strategy comes from the
        # checkpoint's provenance, the target layout from `model`
        params, _, meta = ckpt.load_checkpoint(
            args.load, args.load_iteration, target=model, tx=None,
        )
        print("restored %s at iteration %s into the serve layout"
              % (args.load, meta.get("iteration")))

    # a train state may store leaves split over dp (ZeRO-2); decode reads
    # them at every token, so they are placed as the forward lays them out
    params = jax.device_put(params, model.shardings(model.param_specs))

    # cache geometry: CLI flags win, then the strategy JSON's serve knobs,
    # then defaults; pages default to covering the model's max_seq_len
    max_slots = args.serve_max_concurrency or hp.serve_max_concurrency or 8
    page = args.serve_page_size or hp.serve_page_size or 16
    max_pages = args.serve_max_pages or -(-cfg.max_seq_len // page)
    kv_cfg = KVCacheConfig(max_slots=max_slots, page_size=page, max_pages=max_pages)

    engine = ServeEngine(
        cfg, params, kv_cfg, hp=hp, mesh=model.mesh,
        temperature=args.temperature, rng_seed=args.seed,
    )
    # fault-injection seam (absent in production): the harness wraps the
    # decode step (hangs, simulated device errors) and observes each tick
    hooks = getattr(args, "fault_hooks", None)
    if hooks is not None and hooks.wrap_step_fn:
        engine.decode_step = hooks.wrap_step_fn(engine.decode_step)

    if args.replay:
        reqs = replay_requests(args.replay, vocab_size=cfg.vocab_size, seed=args.seed)
    else:
        pmax = max(args.prompt_len_min,
                   min(args.prompt_len_max, kv_cfg.max_ctx - args.max_new_tokens))
        reqs = synthetic_requests(
            args.num_requests, vocab_size=cfg.vocab_size, seed=args.seed,
            rate_rps=args.rate_rps,
            prompt_len_range=(args.prompt_len_min, pmax),
            max_new_tokens=args.max_new_tokens,
        )

    # ------------------------------------------------------ resilience stack
    wd = None
    if getattr(args, "watchdog", 0):
        wd = hlth.Watchdog(hlth.WatchdogConfig(
            floor_s=float(args.watchdog),
            factor=float(getattr(args, "watchdog_factor", 4.0)),
            startup_deadline_s=float(getattr(args, "watchdog_startup_s", 600.0)),
        )).start()
    mesh_monitor = None
    if getattr(args, "mesh_probe_interval", 0):
        mesh_monitor = hlth.MeshHealthMonitor(
            model.mesh,
            interval_s=float(args.mesh_probe_interval),
            devices_fn=getattr(args, "probe_devices_fn", None),
        )
    preempt = rsl.PreemptionHandler().install()

    state = {"interrupted": None, "error": None}

    def do_serve_migrate(reason: str, live_world: int, b: ContinuousBatcher) -> None:
        """Degraded-mesh serve migration: re-plan for the surviving world,
        relayout params in memory, rebuild the KV cache, journal-replay the
        in-flight requests. Raises DiagnosticError (GLS015) when the
        surviving world cannot serve."""
        nonlocal model, params, hp, kv_cfg, mesh_monitor
        t0 = time.perf_counter()
        if wd is not None:
            wd.disarm()
        new_hp, action = els.resolve_serve_migration_strategy(
            args, cfg, live_world, hp, kv_cfg)
        devices_fn = getattr(args, "probe_devices_fn", None) or jax.devices
        live_devs = list(devices_fn())
        devs = live_devs if live_world != hp.world_size else None
        new_model, new_params, same_layout = els.migrate_serve_params(
            model, params, new_hp, devices=devs)
        new_kv = KVCacheConfig(
            max_slots=new_hp.serve_max_concurrency or kv_cfg.max_slots,
            page_size=kv_cfg.page_size, max_pages=kv_cfg.max_pages,
        )
        new_engine = ServeEngine(
            cfg, new_params, new_kv, hp=new_hp, mesh=new_model.mesh,
            temperature=args.temperature, rng_seed=args.seed,
        )
        if hooks is not None and hooks.wrap_step_fn:
            new_engine.decode_step = hooks.wrap_step_fn(new_engine.decode_step)
        res = b.migrate_to(new_engine, new_kv)
        telemetry.emit(
            "serve_migrate", from_world=hp.world_size,
            to_world=new_hp.world_size, replayed=res["replayed"],
            shed=res["shed"], duration_ms=(time.perf_counter() - t0) * 1e3,
            reason=reason, from_strategy=hp.to_json_dict(),
            to_strategy=new_hp.to_json_dict(),
            kv_slots=new_kv.max_slots, kv_pages=new_kv.max_pages,
        )
        print("serve migration (%s/%s): world %d -> %d, %s relayout, "
              "%d in-flight replayed, %d shed"
              % (reason, action, hp.world_size, new_hp.world_size,
                 "same-tree" if same_layout else "cross-layout",
                 res["replayed"], res["shed"]))
        model, params, hp, kv_cfg = new_model, new_params, new_hp, new_kv
        if mesh_monitor is not None:
            mesh_monitor = hlth.MeshHealthMonitor(
                model.mesh, interval_s=mesh_monitor.interval_s,
                devices_fn=getattr(args, "probe_devices_fn", None),
            )

    def control(b: ContinuousBatcher) -> Optional[str]:
        """Polled once per scheduler iteration, mirroring the train loop's
        step-boundary order: hooks -> preemption -> watchdog -> mesh probe.
        Returns a drain reason to wind the batcher down, else None."""
        if hooks is not None and hooks.on_step:
            hooks.on_step(b.decode_steps)
        if preempt.triggered:
            state["interrupted"] = preempt.signal_name
            telemetry.emit("preemption", signal=preempt.signal_name,
                           iter=b.decode_steps)
            return preempt.signal_name
        if wd is not None:
            if wd.abort_requested:
                # second missed deadline with no progress: graceful drain;
                # main() maps the summary to WATCHDOG_EXIT_CODE (3)
                state["interrupted"] = "watchdog"
                return "watchdog"
            if wd.take_retry_request():
                # first missed deadline: the stalled tick has since
                # completed (the batcher is synchronous) — log and continue
                telemetry.runtime_log(
                    "serve watchdog: tick stalled past deadline at step %d; "
                    "retrying" % b.decode_steps)
        if mesh_monitor is not None:
            verdict = mesh_monitor.maybe_probe()
            if verdict is not None and verdict["status"] != "healthy":
                telemetry.emit(
                    "watchdog", action="mesh_probe", iter=b.decode_steps,
                    status=verdict["status"], expected=verdict["expected"],
                    live=verdict["live"],
                    missing_ids=verdict["missing_ids"] or None,
                    detail=verdict.get("error"),
                )
                telemetry.runtime_log(
                    "mesh probe: %s (expected %d devices, live %d)"
                    % (verdict["status"], verdict["expected"],
                       verdict["live"]))
                if verdict["status"] == "degraded" and \
                        getattr(args, "migrate_on_degrade", 0):
                    try:
                        do_serve_migrate("degraded_mesh", verdict["live"], b)
                    except DiagnosticError as e:
                        # GLS015: the surviving world cannot serve — drain
                        # (admitted requests complete or shed retryable),
                        # then _serve re-raises for the exit-2 contract
                        state["error"] = e
                        return "migrate_infeasible"
        return None

    # shedding knobs: CLI flags win, then the strategy JSON's serve_* knobs
    batcher = ContinuousBatcher(
        engine, kv_cfg,
        p99_ttft_ms=getattr(args, "p99_ttft_ms", 0.0) or hp.serve_p99_ttft_ms,
        max_pending=getattr(args, "max_pending", 0) or hp.serve_max_pending,
        request_timeout_s=getattr(args, "request_timeout_s", 0.0) or 0.0,
        min_shed_samples=int(getattr(args, "shed_min_samples", 3) or 3),
        watchdog=wd, control=control,
    )
    t0 = time.monotonic()
    try:
        completed = batcher.run(reqs)
    finally:
        preempt.uninstall()
        if wd is not None:
            wd.stop()
    wall = time.monotonic() - t0
    if state["error"] is not None:
        telemetry.emit("serve_drain", reason="migrate_infeasible",
                       completed=len(batcher.completed),
                       shed=len(batcher.shed), exit_code=2)
        raise state["error"]

    summary = summarize(completed, wall, world_size=hp.world_size,
                        shed=batcher.shed)
    summary["decode_steps"] = batcher.decode_steps
    summary["migrations"] = batcher.migrations
    summary["drain"] = batcher.drain_reason
    if state["interrupted"] is not None:
        summary["interrupted"] = state["interrupted"]
    if wd is not None:
        summary["watchdog"] = wd.summary()
    bytes_per = 2 if args.mixed_precision == "bf16" else 4
    summary["kv_mb_per_slot"] = kv_bytes_per_slot(
        cfg, kv_cfg.max_ctx, dtype_bytes=bytes_per) / 2**20
    print("served %d requests in %.2f s: %.1f tok/s (%.2f tok/s/chip), "
          "%d decode steps" % (
              summary["requests"], wall, summary["tokens_per_s"],
              summary["tokens_per_s_per_chip"], batcher.decode_steps))
    if summary["shed"]:
        print("shed %d request(s) (%d retryable): %s" % (
            summary["shed"], summary["shed_retryable"],
            ", ".join("%s=%d" % kv for kv in
                      sorted(summary["shed_by_reason"].items()))))
    if summary["drain"]:
        print("drained (%s): %d completed, %d shed" % (
            summary["drain"], summary["requests"], summary["shed"]))
    if summary["migrations"]:
        print("live serve migrations: %d (now world %d)"
              % (summary["migrations"], hp.world_size))
    for name in ("ttft_ms", "tpot_ms"):
        p = summary[name]
        print("%s p50/p90/p99: %.1f / %.1f / %.1f"
              % (name, p["p50"], p["p90"], p["p99"]))
    return summary


def main(argv: Optional[list] = None):
    args = initialize_galvatron(mode="serve", argv=argv)
    try:
        summary = serve(args)
    except Exception as e:
        from galvatron_tpu.analysis.diagnostics import DiagnosticError

        if isinstance(e, DiagnosticError) and any(
            d.code.startswith("GLS2") or d.code == "GLS015"
            for d in e.diagnostics
        ):
            # the degraded-world refusal contract (mirrors train): actionable
            # diagnostics on stderr and exit code 2 — "needs operator input",
            # not "retry me"
            for d in e.diagnostics:
                print(d.format(), file=sys.stderr)
            sys.exit(2)
        raise
    if (summary.get("watchdog") or {}).get("escalated"):
        from galvatron_tpu.runtime.health import WATCHDOG_EXIT_CODE

        print("serve watchdog escalated: batcher drained; exiting %d"
              % WATCHDOG_EXIT_CODE, file=sys.stderr)
        sys.exit(WATCHDOG_EXIT_CODE)
    return summary


if __name__ == "__main__":
    main()
