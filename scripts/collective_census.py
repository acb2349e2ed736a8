#!/usr/bin/env python3
"""One run of a benchmark cell with the program's census of its step's collectives read beside it:

    chiprun --chips 4 -- python3 scripts/collective_census.py --workload qwen7-c4-tp2dp2 --seed 1 --seconds 10 --trace 2

It is `benchmarks/run.py` (the same arguments, the same two lines on stdout, the same `run.json`) with one seam: where
the harness reads the cell's per-layer metrics off a traced run, the six `collective_*` readers
(benchmarks/layer_metrics/, benchmarks/census.py) read it too, listed in `BENCHMARK.json` or not (`per_layer` held 128
of 128 entries when they were written: PERF.md section 7). Beside `run.json` it writes `census.json` (the summary's
`step_collectives`: with `trace_events.json.gz` what a fixture pair under benchmarks/fixtures/ is made of), and on
stderr one line `collective_census {...}`: the six readings, device 0's ms a step by role, the identity `dp + tp + pp +
the rest = collective_ms + collective_fused_ms`, the rows whose role is `other` or whose axes are empty; before it
`cli report`'s table of the census."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READERS = ("collective_fused_ms", "collective_dp_ms", "collective_tp_ms", "collective_pp_ms",
           "collective_wire_gib", "collective_hidden_pct")


def census_of(cell, run, out_dir):
    """What the line says of a traced run, `census.json` written into `out_dir`
    where the program counted."""
    from benchmarks import cells, census

    counted = (run.get("summary") or {}).get("step_collectives")
    readings = {name: cells.load_module(cell.root, "benchmarks/layer_metrics/%s.py" % name).read(run)
                for name in READERS}
    said = {"workload": cell.name, "readings": readings, "census_ms": (counted or {}).get("census_ms"),
            "rows": len((counted or {}).get("rows", ()))}
    joined = census.timed(run)
    if joined is None:
        return said
    with open(os.path.join(out_dir, "census.json"), "w") as f:
        json.dump(counted, f)
    said["ms_by_role"] = by_role = census.ms_by_role(run)
    said["collective_ms"] = run["trace"]["collective_s_a_step"] * 1e3
    said["identity_gap_ms"] = sum(by_role.values()) - said["collective_ms"] - readings["collective_fused_ms"]
    said["not_in_the_trace"] = [row["instruction"] for row, _, _, calls in joined if not calls]
    said["other_or_no_axes"] = [row for row in counted["rows"] if row["role"] == "other" or not row["axes"]]
    return said


def install(harness, say) -> None:
    """Wrap the harness's reading of a traced run: `say(lines)` gets `cli
    report`'s table of the census and the `collective_census` line."""
    read_trace, per_layer_values = harness.read_trace, harness.per_layer_values
    where = {}

    def traced(trace_dir, hlo, out_dir):
        where["dir"] = out_dir
        return read_trace(trace_dir, hlo, out_dir)

    def with_census(cell, run):
        from galvatron_tpu.obs import report  # (here, not at the start: set-up is the plain run's)

        said = census_of(cell, run, where["dir"])
        counted = (run.get("summary") or {}).get("step_collectives")
        table = report._render_collectives(counted["rows"], counted["census_ms"]) if counted else []
        say(table + ["collective_census " + json.dumps(said)])
        return per_layer_values(cell, run)

    harness.read_trace, harness.per_layer_values = traced, with_census


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location("benchmarks_run", os.path.join(ROOT, "benchmarks", "run.py"))
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)  # (its T0: set-up is counted from here, as a run of its own counts it)
    from benchmarks import harness

    install(harness, lambda lines: print("\n".join(lines), file=sys.stderr, flush=True))
    return run_py.main(argv)


if __name__ == "__main__":
    sys.exit(main())
