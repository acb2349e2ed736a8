"""jax workaround inventory (the `lint --compat` registry).

The codebase runs on the installed jax's native surface (`jax.shard_map` with
``axis_names=``/``check_vma=``, `jax.sharding.get_abstract_mesh`); nothing is
patched in. What remains here is the list of *GSPMD-hazard workarounds* that
were pinned on an older jax and are still carried in the code: each has a
stable WA*** id, the module that carries it, and the pytest ids of the tests
that pin the behaviour it protects. A workaround leaves this list (and the
code) only after its hazard has been shown gone on the installed jax — the
pinning tests pass WITH the workaround in place, so they cannot show that by
themselves; the GLT detectors (analysis/trace_lint.py) keep the hazard
*classes* flagged either way.

Probes return ``(active, detail)`` where active is True (the installed jax
still needs the workaround), False (retirable) or None (not decided).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax


@dataclass(frozen=True)
class WorkaroundEntry:
    code: str  # diagnostics.CODES id (WA0xx)
    title: str
    where: str  # the module carrying the workaround
    pinning_tests: Tuple[str, ...]  # pytest ids that pin the behaviour
    probe: Callable[[], Tuple[Optional[bool], str]]


def _probe_unverified() -> Tuple[Optional[bool], str]:
    """The GSPMD miscompile classes produce silently wrong values, not
    errors; no cheap in-process probe can prove the installed jax free of
    them, so the answer is 'unverified' rather than a guess."""
    return None, ("unverified on jax %s — reproduce the hazard without the "
                  "workaround before retiring" % jax.__version__)


WORKAROUNDS: Tuple[WorkaroundEntry, ...] = (
    WorkaroundEntry(
        code="WA004",
        title="jnp.stack (never concat+reshape) when stacking layer params "
              "for the scan runs — GSPMD miscompiled a sharded-dim reshape "
              "inside a scan",
        where="models/base.py:stack_layer_run",
        pinning_tests=(
            "tests/models/test_tp_comm_mode.py::test_sharded_paths_match_unsharded_reference",
            "tests/analysis/test_trace_lint.py::test_glt001_sharded_reshape_in_scan_flagged",
        ),
        probe=_probe_unverified,
    ),
    WorkaroundEntry(
        code="WA005",
        title="explicit sharding constraints on the pipeline microbatch "
              "split before the tick scan",
        where="parallel/pipeline.py:make_pipelined_loss",
        pinning_tests=(
            "tests/parallel/test_pipeline.py::test_pipeline_matches_dp",
            "tests/analysis/test_trace_lint.py::test_glt002_unconstrained_microbatch_split_flagged",
        ),
        probe=_probe_unverified,
    ),
    WorkaroundEntry(
        code="WA006",
        title="pp>1 init: per-layer init jitted, stages stacked OUTSIDE jit, "
              "then device_put — never fused under pp out_shardings",
        where="runtime/model_api.py:HybridParallelModel.init_params",
        pinning_tests=(
            "tests/parallel/test_pipeline.py::test_pipelined_bert_mlm_matches_single_stage",
            "tests/analysis/test_trace_lint.py::test_glt003_stacked_init_under_out_shardings_flagged",
        ),
        probe=_probe_unverified,
    ),
)


def workaround_inventory() -> List[dict]:
    """Probe every registered workaround against the installed jax.
    Each row: ``{code, title, where, active, detail, pinning_tests}`` with
    ``active`` True/False/None (see module comment)."""
    rows = []
    for wa in WORKAROUNDS:
        active, detail = wa.probe()
        rows.append({
            "code": wa.code,
            "title": wa.title,
            "where": wa.where,
            "active": active,
            "detail": detail,
            "pinning_tests": list(wa.pinning_tests),
        })
    return rows


def render_inventory(rows: List[dict]) -> str:
    """Fixed-width human rendering of `workaround_inventory` output."""
    lines = ["jax workaround inventory (installed jax %s):" % jax.__version__]
    for r in rows:
        status = {True: "ACTIVE", False: "RETIRABLE", None: "UNKNOWN"}[r["active"]]
        lines.append("  %s  %-9s %s" % (r["code"], status, r["title"]))
        lines.append("         where: %s" % r["where"])
        lines.append("         probe: %s" % r["detail"])
        lines.append("         pinned by: %s" % ", ".join(r["pinning_tests"]))
    return "\n".join(lines)
