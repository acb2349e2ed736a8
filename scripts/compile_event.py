#!/usr/bin/env python3
"""A cell's `compile` telemetry event: `forms` says which form each part of
the step took as it was traced (obs/forms.py: part -> form -> count). The
benchmark installs its telemetry sink after
the step is compiled and so does not keep the event; this runs the cell's own
training command with a sink from the start and ends the run after two steps
(the benchmark's seam: `fault_hooks.on_step` sets `train_iters`),

    chiprun -- python3 scripts/compile_event.py <cell>

and prints the event as one line of JSON, its last line of output (one cell a
process: a second model does not fit beside the first's state). The line
before it is the run's `launch` event (`launch_ms`, `launch_imports`,
`launch_jit`: where the start went, obs/launch.py; `checkpoint_import`: how
the run came by `runtime/checkpoint`, "never" here, where nothing is loaded
or saved)."""

from __future__ import annotations

import json
import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(workload: str) -> int:
    from benchmarks import cells
    from galvatron_tpu.cli.arguments import initialize_galvatron
    from galvatron_tpu.cli.train import train
    from galvatron_tpu.obs import telemetry

    cell = cells.load_cell(ROOT, workload)
    cells.register_family(cell)
    sink = telemetry.install(telemetry.MemorySink())
    args = initialize_galvatron(mode="train_dist", argv=cells.train_argv(cell, 0))
    args.fault_hooks = types.SimpleNamespace(
        on_step=lambda it: setattr(args, "train_iters", 2), wrap_step_fn=None, wrap_data_iter=None)
    try:
        train(args)
    finally:
        telemetry.uninstall(sink)
    for kind in ("launch", "compile"):
        event = next(e for e in sink.events if e["type"] == kind)
        print(json.dumps({"workload": workload, **{k: v for k, v in event.items() if k != "type"}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
