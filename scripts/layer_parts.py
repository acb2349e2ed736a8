#!/usr/bin/env python3
"""The layer body by part and phase, from the `run.json` a traced benchmark
run leaves under chiprun_out/benchmarks/<cell>/<run>/ (no chip, no profiler):

    python3 scripts/layer_parts.py <run.json> [<run.json> ...]

A row a part (the flash kernels inside the runs, every scope nested in
`gt.layers.r<k>`, the runs' self time), a column a phase; the last row is
`layers_fwd_ms`, `layers_remat_ms`, `layers_bwd_ms` and says whether the
parts add up to them. The patterns are the benchmark's own
(benchmarks/layer_metrics/layers_rest_ms.parts)."""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import scopes  # noqa: E402
from benchmarks.layer_metrics import layers_rest_ms  # noqa: E402

PHASES = (("fwd", scopes.LAYERS_FWD), ("remat", scopes.LAYERS_REMAT), ("bwd", scopes.LAYERS_BWD))


def table(run) -> str:
    by_phase = [layers_rest_ms.parts(run, rx) for _, rx in PHASES]
    rows = [(part, [p[part] for p in by_phase]) for part in by_phase[0]]
    rows.append(("layers_*_ms", [scopes.ms_a_step(run, rx) for _, rx in PHASES]))
    lines = ["%-16s %9s %9s %9s %9s" % ("part", *(name for name, _ in PHASES), "all")]
    lines += ["%-16s %9.3f %9.3f %9.3f %9.3f" % (part, *ms, sum(ms)) for part, ms in rows]
    off = sum(sum(ms) for _, ms in rows[:-1]) - sum(rows[-1][1])
    lines.append("parts - layers_*_ms = %.6f ms" % off)
    return "\n".join(lines)


if __name__ == "__main__":
    for path in sys.argv[1:]:
        with open(path) as f:
            run = json.load(f)
        print("%s seed %s (%s)" % (run["workload"], run["seed"], path))
        print(table(run))
