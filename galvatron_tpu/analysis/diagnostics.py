"""Shared diagnostics framework for the static analyzers.

Both the strategy linter (analysis/strategy_lint.py, ``GLS***`` codes) and the
code linter (analysis/code_lint.py, ``GLC***`` codes) report through this
module so the CLI, the runtime config validator and CI all speak one format:

- `Diagnostic`: one finding — stable code, severity, message, location
  (file/line for code findings, layer/key for strategy findings), optional
  did-you-mean hint.
- `DiagnosticReport`: a collection with machine-readable JSON output
  (`to_json`), human rendering (`render`) and the exit-code contract
  (`exit_code`: 0 = clean or warnings only, 1 = at least one error).

This module is import-light on purpose (stdlib only, no jax, no other
galvatron modules) so `config/strategy.py` can raise structured
`DiagnosticError`s without creating an import cycle with the linters.
"""

from __future__ import annotations

import difflib
import json
from dataclasses import asdict, dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

ERROR = "error"
WARNING = "warning"

# ------------------------------------------------------------- code registry
# code -> (default severity, short title). The README's diagnostic-code table
# is generated from this registry (see `registry_table`), so it cannot drift.
CODES: Dict[str, Tuple[str, str]] = {
    # ---- strategy linter (GLS0xx structural errors) ----
    "GLS001": (ERROR, "unknown or misspelled strategy-JSON key"),
    "GLS002": (ERROR, "device-grid divisibility violation (world/pp/tp/cp/vocab)"),
    "GLS003": (ERROR, "pipeline division inconsistent with pp/layer count"),
    "GLS004": (ERROR, "batch divisibility violation (global_bsz/chunks/dp)"),
    "GLS005": (ERROR, "invalid field value or flag"),
    "GLS006": (ERROR, "per-layer arrays disagree in length"),
    "GLS007": (ERROR, "attention heads not divisible by tensor-parallel degree"),
    "GLS008": (ERROR, "sequence length not divisible by its shard degree"),
    "GLS009": (ERROR, "vocab size not divisible by vocab-parallel degree"),
    "GLS010": (ERROR, "cross-layer mesh-axis inconsistency within a pipeline stage"),
    "GLS011": (ERROR, "illegal activation-checkpoint placement"),
    "GLS012": (ERROR, "config unsupported by the manual shard_map TP path"),
    "GLS013": (ERROR, "unsupported comm-precision (quantized collectives) configuration"),
    "GLS014": (ERROR, "serve-infeasible configuration (latency bound, KV budget, or layout)"),
    "GLS015": (ERROR, "serve world infeasible after mesh degradation"),
    "GLS016": (ERROR, "state motion changed the layout-invariant integrity digest"),
    "GLS017": (ERROR, "online autotuner fighting a pinned strategy"),
    "GLS018": (ERROR, "a part of the model (models/parts) has no form under this layout, mode or tool"),
    # ---- strategy linter (GLS1xx cost-model-backed warnings) ----
    "GLS101": (WARNING, "estimated per-device memory exceeds the HBM budget"),
    "GLS102": (WARNING, "expensive cross-layer redistribution between adjacent layers"),
    "GLS103": (WARNING, "suspicious but runnable configuration"),
    # ---- elastic resume / checkpoint portability (GLS20x) ----
    "GLS201": (ERROR, "model-config digest mismatch between checkpoint and run"),
    "GLS202": (ERROR, "optimizer state incompatible with the checkpoint's"),
    "GLS203": (ERROR, "no feasible strategy for the surviving mesh under the memory budget"),
    "GLS204": (ERROR, "checkpoint lacks the provenance elastic resume requires"),
    "GLS205": (ERROR, "world size changed but no replacement strategy was resolved"),
    "GLS206": (ERROR, "cross-strategy relayout unsupported for this model family"),
    "GLS207": (ERROR, "live in-memory strategy migration infeasible for this run"),
    # ---- checkpoint auditor (GLS21x) ----
    "GLS210": (ERROR, "checkpoint step without a committed integrity manifest (torn save)"),
    "GLS211": (WARNING, "stray or orphaned entry in the checkpoint directory"),
    "GLS212": (ERROR, "malformed checkpoint manifest or inconsistent provenance"),
    "GLS213": (WARNING, "checkpoint predates provenance (not elastically resumable)"),
    "GLS214": (ERROR, "checkpoint bytes no longer match the manifest's integrity digest"),
    # ---- code linter (GLC0xx) ----
    "GLC001": (ERROR, "jax attribute chain missing from the installed jax"),
    "GLC002": (WARNING, "host-side numpy call inside a jitted function"),
    "GLC003": (WARNING, "Python control flow on a traced value inside jit"),
    "GLC004": (ERROR, "donated buffer used again after the donating jit call"),
    "GLC005": (WARNING, "blocking host sync inside a loop in driver code"),
    "GLC006": (WARNING, "ad-hoc print/append-file logging in runtime library code"),
    "GLC007": (ERROR, "custom_vjp closes over a traced axis_index from an enclosing scope"),
    # ---- traced-program linter (GLT0xx jaxpr-level hazards) ----
    "GLT001": (ERROR, "reshape splits/merges an explicitly sharded dim inside a scan body"),
    "GLT002": (ERROR, "sharded-dim reshape feeds a scan without a sharding constraint"),
    "GLT003": (ERROR, "stacked init under out_shardings that shard the stacked dim"),
    "GLT004": (WARNING, "donated input has no same-shape/dtype output to alias"),
    "GLT005": (ERROR, "custom_vjp in a shard_map body closes over a dangling axis_index"),
    "GLT006": (WARNING, "psum-of-psum over the same axis in a manual region (double count)"),
    # ---- traced-program linter (GLT1xx collective audit) ----
    "GLT101": (WARNING, "traced collectives contradict the cost model's predicted comm"),
    "GLT102": (WARNING, "traced-program audit skipped or limited"),
    # ---- jax-workaround inventory (WA0xx, utils/jax_compat.py registry) ----
    "WA004": (WARNING, "jnp.stack (not concat+reshape) in stack_layer_run scan stacking"),
    "WA005": (WARNING, "explicit sharding constraints on the pipeline microbatch split"),
    "WA006": (WARNING, "host-side per-layer init + stack outside jit under pp shardings"),
}


@dataclass(frozen=True)
class Diagnostic:
    code: str
    severity: str
    message: str
    file: Optional[str] = None
    line: Optional[int] = None
    layer: Optional[int] = None
    key: Optional[str] = None
    hint: Optional[str] = None

    def format(self) -> str:
        loc = self.file or "<strategy>"
        if self.line is not None:
            loc += ":%d" % self.line
        if self.layer is not None:
            loc += " [layer %d]" % self.layer
        msg = "%s: %s %s: %s" % (loc, self.severity, self.code, self.message)
        if self.hint:
            msg += " (%s)" % self.hint
        return msg


def make(code: str, message: str, **loc) -> Diagnostic:
    """Build a Diagnostic for a registered code (severity from the registry;
    pass ``severity=`` to override, e.g. demoting an error to a warning)."""
    if code not in CODES:
        raise KeyError("unregistered diagnostic code %r" % code)
    severity = loc.pop("severity", CODES[code][0])
    return Diagnostic(code=code, severity=severity, message=message, **loc)


def did_you_mean(name: str, candidates: Iterable[str]) -> Optional[str]:
    """Closest-match hint for typo'd keys, or None when nothing is close."""
    matches = difflib.get_close_matches(name, list(candidates), n=1, cutoff=0.6)
    return "did you mean %r?" % matches[0] if matches else None


class DiagnosticError(ValueError):
    """Structured validation failure: carries the diagnostics that caused it
    (all errors), rendering like the legacy ValueErrors so existing
    ``pytest.raises(ValueError, match=...)`` callers keep working."""

    def __init__(self, diagnostics: List[Diagnostic]):
        self.diagnostics = list(diagnostics)
        super().__init__("; ".join("[%s] %s" % (d.code, d.message) for d in self.diagnostics))


@dataclass
class DiagnosticReport:
    diagnostics: List[Diagnostic] = field(default_factory=list)

    def add(self, diag: Diagnostic) -> None:
        self.diagnostics.append(diag)

    def extend(self, diags: Iterable[Diagnostic]) -> None:
        self.diagnostics.extend(diags)

    @property
    def errors(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == ERROR]

    @property
    def warnings(self) -> List[Diagnostic]:
        return [d for d in self.diagnostics if d.severity == WARNING]

    @property
    def ok(self) -> bool:
        return not self.errors

    def exit_code(self) -> int:
        """The CLI contract: 0 = clean (warnings allowed), 1 = errors."""
        return 0 if self.ok else 1

    def codes(self) -> List[str]:
        return sorted({d.code for d in self.diagnostics})

    def to_json(self) -> str:
        return json.dumps(
            {
                "version": 1,
                "summary": {
                    "errors": len(self.errors),
                    "warnings": len(self.warnings),
                    "codes": self.codes(),
                },
                "diagnostics": [asdict(d) for d in self.diagnostics],
            },
            indent=2,
        )

    def render(self) -> str:
        lines = [d.format() for d in self.diagnostics]
        lines.append(
            "%d error(s), %d warning(s)" % (len(self.errors), len(self.warnings))
        )
        return "\n".join(lines)


def registry_table() -> str:
    """Markdown table of every registered code (used by the README section
    and by --explain)."""
    lines = ["| code | severity | meaning |", "|------|----------|---------|"]
    for code in sorted(CODES):
        sev, title = CODES[code]
        lines.append("| %s | %s | %s |" % (code, sev, title))
    return "\n".join(lines)
