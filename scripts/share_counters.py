#!/usr/bin/env python3
"""A share cell through `cli train --telemetry` for a few steps on the chip,
and what its `compile` and `step` events say of the experts' window:

    chiprun -- python3 scripts/share_counters.py <cell> <seed> [<steps>]

One line of JSON: the `compile` event's `forms` of the experts' window and
the row movers and the grouped matmuls' tilings (obs/forms.py: `expert_window`, `moe_rows`, `gmm_tiles`), and over the
steps the sum of `expert_window_fallbacks` and the range of
`expert_rows_held_over_even`. The cell's own flags (benchmarks/cells.train_argv),
so the step is the benchmark's and comes out of its compile cache."""

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(workload: str, seed: int, steps: int = 40) -> None:
    from benchmarks import cells
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train
    from galvatron_tpu.obs import forms, telemetry

    cell = cells.load_cell(ROOT, workload)
    cells.register_family(cell)
    out = os.path.join(ROOT, "chiprun_out", "share_counters")
    os.makedirs(out, exist_ok=True)
    path = os.path.join(out, "%s.%d.jsonl" % (workload, seed))
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, seed) + ["--telemetry", path])

    def on_step(it):  # as the benchmark's window ends a run: the schedule is the cell's, whose warm-up may be longer
        if it + 1 >= steps:
            args.train_iters = it + 1

    args.fault_hooks = types.SimpleNamespace(on_step=on_step, wrap_step_fn=None, wrap_data_iter=None)
    train(args)
    events, errors = telemetry.read_events(path)
    compiles = [e for e in events if e["type"] == "compile"]
    step_events = [e for e in events if e["type"] == "step"]
    over_even = [e["expert_rows_held_over_even"] for e in step_events]
    print(json.dumps({
        "workload": workload, "seed": seed, "steps": len(step_events), "errors": len(errors),
        **{part: [e["forms"].get(part) for e in compiles] for part in (forms.EXPERT_WINDOW, forms.MOE_ROWS, forms.GMM_TILES)},
        "expert_window_fallbacks": sum(e["expert_window_fallbacks"] for e in step_events),
        "steps_that_fell_back": sum(e["expert_window_fallbacks"] > 0 for e in step_events),
        "expert_rows_held_over_even": [min(over_even), max(over_even)],
        "loss": [step_events[0]["loss"], step_events[-1]["loss"]]}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), *(int(a) for a in sys.argv[3:4]))
