"""``python -m galvatron_tpu.cli lint`` — static strategy + code analysis.

Usage:
    # lint searched/hand-written strategy JSONs (no device work):
    python -m galvatron_tpu.cli lint strategy.json --world_size 8 \
        --model_type llama --model_size llama-7b --memory_budget_gb 16

    # lint Python sources for jax-API drift and jit-safety hazards:
    python -m galvatron_tpu.cli lint --code            # the installed package
    python -m galvatron_tpu.cli lint my_module.py some/dir

    # audit a checkpoint directory offline (manifests, provenance, embedded
    # strategy — no arrays restored):
    python -m galvatron_tpu.cli lint --ckpt /ckpts/run42

    # trace-lint: abstract-eval the train step each strategy would jit and
    # audit the jaxpr (GLT codes; CPU-only, forced host devices, no compile):
    python -m galvatron_tpu.cli lint --trace strategy.json --world_size 8 \
        --model_type gpt --hidden_size 64 --num_heads 4 --seq_length 64 \
        --vocab_size 128

    # jax-workaround inventory: the GSPMD-hazard workarounds still carried
    # in the code, probed against the installed jax:
    python -m galvatron_tpu.cli lint --compat

Exit-code contract: 0 = clean (warnings allowed), 1 = at least one error
diagnostic, 2 = usage/IO failure. ``--json`` prints the machine-readable
report (schema: analysis/diagnostics.py `DiagnosticReport.to_json`; with
--compat/--trace the document gains additive ``compat_inventory`` /
``trace_audit`` keys); ``--strict`` upgrades warnings to the failing exit
code.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from galvatron_tpu.analysis import diagnostics as D


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser("galvatron_tpu-lint", allow_abbrev=False)
    p.add_argument("paths", nargs="*",
                   help="strategy .json files and/or .py files / directories")
    p.add_argument("--code", action="store_true",
                   help="lint the installed galvatron_tpu package sources "
                        "(in addition to any explicit paths)")
    p.add_argument("--ckpt", action="append", default=[], metavar="DIR",
                   help="audit a checkpoint directory offline (repeatable): "
                        "per-iteration manifest integrity, provenance "
                        "presence/consistency, embedded-strategy lint "
                        "(GLS21x; no arrays are restored)")
    p.add_argument("--deep", action="store_true",
                   help="with --ckpt: restore every array item and verify "
                        "its layout-invariant integrity fold against the "
                        "manifest (GLS214) — catches bit rot between save "
                        "and resume at the cost of reading the checkpoint")
    p.add_argument("--json", dest="as_json", action="store_true",
                   help="machine-readable JSON output")
    p.add_argument("--strict", action="store_true",
                   help="exit non-zero on warnings too")
    p.add_argument("--explain", action="store_true",
                   help="print the diagnostic-code table and exit")
    p.add_argument("--world_size", type=int,
                   default=int(os.environ.get("GALVATRON_WORLD_SIZE", "8")),
                   help="device count the strategy must tile (default: "
                        "$GALVATRON_WORLD_SIZE or 8)")
    p.add_argument("--model_type", type=str, default=None,
                   help="model family for model-aware checks (heads/seq/vocab "
                        "divisibility, memory estimate)")
    p.add_argument("--model_size", type=str, default=None)
    p.add_argument("--memory_budget_gb", type=float, default=None,
                   help="HBM budget per chip; enables the GLS101 estimate")
    p.add_argument("--memory_profile", type=str, default=None,
                   help="profiled memory JSON (profiler schema) to back the "
                        "GLS101 estimate instead of the analytic tables")
    p.add_argument("--serve", action="store_true",
                   help="lint strategy JSONs for serve-mode feasibility "
                        "(GLS014: decode-incompatible layouts, KV-cache "
                        "budget when --memory_budget_gb is given)")
    p.add_argument("--rules", type=str, default=None,
                   help="comma-separated code-lint rule subset, e.g. GLC001")
    p.add_argument("--trace", action="store_true",
                   help="trace-lint (GLT codes): abstract-eval the train "
                        "step each strategy JSON would jit (or a uniform "
                        "data-parallel default when no JSONs are given) and "
                        "audit the jaxpr for the pinned GSPMD miscompile "
                        "classes, donation waste, manual-region hazards and "
                        "predicted-vs-traced collective drift. CPU-only: "
                        "devices are forced host devices, nothing compiles")
    p.add_argument("--compat", action="store_true",
                   help="jax-workaround inventory (WA codes): probe every "
                        "carried workaround against the installed jax and "
                        "report ACTIVE/RETIRABLE/UNKNOWN with its pinning "
                        "tests")
    t = p.add_argument_group(
        "model-dim overrides (model-aware GLS checks and --trace)")
    t.add_argument("--num_layers", type=int, default=None,
                   help="layer count for the no-JSON default trace "
                        "(strategy JSONs pin their own layer count)")
    t.add_argument("--hidden_size", type=int, default=None)
    t.add_argument("--num_heads", type=int, default=None)
    t.add_argument("--seq_length", type=int, default=None)
    t.add_argument("--vocab_size", type=int, default=None)
    return p


def _overrides(args, num_layers=None):
    out = {}
    if num_layers is not None:
        out["num_layers"] = num_layers
    for flag, key in (("hidden_size", "hidden_size"),
                      ("num_heads", "num_heads"),
                      ("seq_length", "max_seq_len"),
                      ("vocab_size", "vocab_size")):
        v = getattr(args, flag)
        if v is not None:
            out[key] = v
    return out


def _model_cfg(args):
    if not args.model_type:
        return None
    from galvatron_tpu.models.registry import get_family

    fam = get_family(args.model_type)
    return fam.config_fn(args.model_size or fam.default_size,
                         **_overrides(args))


def _run_trace(args, json_paths, report, trace_audits) -> int:
    """--trace: abstract-eval the train step each strategy would jit and
    walk the jaxpr. Returns a non-zero usage exit code, or 0 to continue.

    Host-device forcing already happened at the top of run() — here we only
    verify it took (it cannot once the jax backend has initialized)."""
    import jax

    if len(jax.devices()) < args.world_size:
        print("cannot trace: %d device(s) visible but --world_size is %d "
              "(the jax backend initialized before host-device forcing "
              "could apply)" % (len(jax.devices()), args.world_size),
              file=sys.stderr)
        return 2
    from dataclasses import replace

    from galvatron_tpu.analysis import trace_lint as T
    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.registry import get_family

    try:
        fam = get_family(args.model_type or "gpt")
        fam.config_fn(args.model_size or fam.default_size)
    except (KeyError, ValueError) as e:
        print("bad --model_type/--model_size: %s" % e, file=sys.stderr)
        return 2
    targets = []
    if json_paths:
        for path in json_paths:
            try:
                targets.append(
                    (path, HybridParallelConfig.from_json(path,
                                                          args.world_size)))
            except (OSError, ValueError) as e:
                # structural GLS errors were already reported by the
                # strategy linter above; record the skip and move on
                report.add(D.make(
                    "GLT102", "trace skipped (strategy rejected): %s" % e,
                    file=path))
    else:
        nl = args.num_layers or 4
        targets.append(
            ("<uniform dp%d>" % args.world_size,
             HybridParallelConfig.uniform(args.world_size, nl)))
    for label, hp in targets:
        try:
            cfg = fam.config_fn(args.model_size or fam.default_size,
                                **_overrides(args, num_layers=hp.num_layers))
            res = T.lint_model(cfg, hp, data_kind=fam.data_kind)
        except Exception as e:
            report.add(D.make(
                "GLT102", "trace skipped: %s" % e, file=label))
            continue
        for d in res.report.diagnostics:
            report.add(d if d.file else replace(d, file=label))
        trace_audits.append((label, res))
    return 0


def run(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.explain:
        print(D.registry_table())
        return 0
    if args.trace:
        # tracing builds a world_size mesh: force host devices BEFORE any
        # pass can initialize the jax backend (the other linters query
        # devices indirectly — importing jax alone does not initialize it,
        # the first device query does)
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=%d"
                % args.world_size).strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    json_paths = [p for p in args.paths if p.endswith(".json")]
    code_paths = [p for p in args.paths if not p.endswith(".json")]
    if args.code:
        import galvatron_tpu

        code_paths.append(os.path.dirname(galvatron_tpu.__file__))
    if (not json_paths and not code_paths and not args.ckpt
            and not args.trace and not args.compat):
        print("nothing to lint: pass strategy .json / .py paths, --ckpt "
              "dirs, --code, --trace, or --compat", file=sys.stderr)
        return 2

    report = D.DiagnosticReport()
    if json_paths:
        from galvatron_tpu.analysis import strategy_lint as S
        from galvatron_tpu.utils.jsonio import read_json_config

        try:
            model_cfg = _model_cfg(args)
        except (KeyError, ValueError) as e:
            print("bad --model_type/--model_size: %s" % e, file=sys.stderr)
            return 2
        memory_profile = None
        if args.memory_profile:
            try:
                memory_profile = read_json_config(args.memory_profile)
            except (OSError, ValueError) as e:
                print("cannot read --memory_profile: %s" % e, file=sys.stderr)
                return 2
        for path in json_paths:
            try:
                report.extend(S.lint_strategy_file(
                    path, args.world_size, model_cfg=model_cfg,
                    memory_budget_gb=args.memory_budget_gb,
                    memory_profile=memory_profile,
                    mode="serve" if args.serve else None,
                ).diagnostics)
            except (OSError, ValueError) as e:
                print("cannot lint %s: %s" % (path, e), file=sys.stderr)
                return 2
    if code_paths:
        from galvatron_tpu.analysis import code_lint as C

        rules = args.rules.split(",") if args.rules else None
        report.extend(C.lint_paths(code_paths, rules=rules).diagnostics)
    for ckpt_dir in args.ckpt:
        from galvatron_tpu.analysis import ckpt_lint as K

        if not os.path.isdir(ckpt_dir):
            print("cannot audit %s: not a directory" % ckpt_dir, file=sys.stderr)
            return 2
        report.extend(
            K.audit_checkpoint_dir(ckpt_dir, deep=args.deep).diagnostics)

    trace_audits = []
    if args.trace:
        rc = _run_trace(args, json_paths, report, trace_audits)
        if rc:
            return rc
    inventory = None
    if args.compat:
        from galvatron_tpu.utils.jax_compat import workaround_inventory

        inventory = workaround_inventory()
        for row in inventory:
            if row["active"] is False:
                report.add(D.make(
                    row["code"],
                    "retirable on the installed jax: %s — %s (pinned by %s)"
                    % (row["title"], row["detail"],
                       ", ".join(row["pinning_tests"])),
                    file="galvatron_tpu/utils/jax_compat.py"))

    if args.as_json:
        import json as _json

        payload = _json.loads(report.to_json())
        if inventory is not None:
            payload["compat_inventory"] = inventory
        if trace_audits:
            payload["trace_audit"] = [
                {"target": label,
                 "collectives": res.collectives,
                 "predicted_comm": res.predicted}
                for label, res in trace_audits]
        print(_json.dumps(payload, indent=2))
    else:
        print(report.render())
        for label, res in trace_audits:
            print("\n== trace audit: %s ==" % label)
            print(res.render_audit())
        if inventory is not None:
            from galvatron_tpu.utils.jax_compat import render_inventory

            print("\n== jax-workaround inventory (installed jax) ==")
            print(render_inventory(inventory))
    if args.strict and report.warnings:
        return 1
    return report.exit_code()


def main(argv: Optional[List[str]] = None) -> None:
    rc = run(argv)
    if rc:
        sys.exit(rc)
