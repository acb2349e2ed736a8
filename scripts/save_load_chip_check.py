#!/usr/bin/env python3
"""A cell's configuration through the CLI's own `main` under --save and then
under --load: how each run came by `runtime/checkpoint` (the summary's
`checkpoint_import`, cli/train.CheckpointModule) and, for the run that saves,
the steps beside the helper thread's import against the later ones.

    chiprun -- python3 scripts/save_load_chip_check.py <cell> [steps [trainer flags ...]]

The run that saves takes `steps` steps (120) and saves once, at its end; the
run that loads goes on for 30 more. One process a run (the second needs the
chip the first held); this process touches no jax. The checkpoint goes to
`_bench/` in the checkout (12 bytes a parameter), the telemetry streams, each
run's stderr and the last line's JSON to `chiprun_out/save_load/`. A restore
holds the fresh state beside the restored one, so a configuration whose state
is over half the chip (`qwen7-c1-s2k`: 2 x 8.3 GiB) saves and cannot load:
further trainer flags cut it (`--set_layernum_manually 1 --num_layers 1`)."""

from __future__ import annotations

import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2147484321
OUT = os.path.join(ROOT, "chiprun_out", "save_load")


def run(cell_name: str, option: str, directory: str, steps: int, stream: str, flags) -> int:
    """In a process of its own, in the CLI's order: the package (the import
    record starts), the trainer, then `main` on the cell's own flags."""
    sys.path.insert(0, ROOT)
    import galvatron_tpu.cli  # noqa: F401
    from benchmarks import cells
    from galvatron_tpu.cli import train

    cell = cells.load_cell(ROOT, cell_name)
    cells.register_family(cell)
    train.main(cells.train_argv(cell, SEED) + [
        "--train_iters", str(steps), "--log_interval", "20", "--telemetry", stream, option, directory, *flags])
    return 0


def read(stream: str) -> dict:
    with open(stream) as f:
        events = [json.loads(line) for line in f if line.strip()]
    launched = next(e for e in events if e["type"] == "launch")
    summary = next(e for e in events if e["type"] == "run_end")["summary"]
    steps = [e for e in events if e["type"] == "step"]
    imported = summary["checkpoint_import"]
    out = {"checkpoint_import": imported, "at_the_first_drain": launched.get("checkpoint_import"),
           "launch_ms": {k: round(v, 1) for k, v in launched["launch_ms"].items()},
           "launch_imports": launched.get("launch_imports"),
           "saves_ms": [e.get("duration_ms") for e in events if e["type"] == "checkpoint_save"],
           "restores_ms": [e.get("duration_ms") for e in events if e["type"] == "checkpoint_restore"]}
    if imported["how"] == "background":
        # the thread starts once step 0 is dispatched, `gt/launch/first_run` before the launch event
        until = launched["t"] + imported["import_s"]
        gaps = [(b["t"] - a["t"], b) for a, b in zip(steps, steps[1:])]
        for name, rows in (("beside_the_import", [g for g in gaps if g[1]["t"] <= until]),
                           ("after_it", [g for g in gaps if g[1]["t"] > until])):
            out[name] = rows and {
                "steps": len(rows), "median_gap_ms": statistics.median(g * 1e3 for g, _ in rows),
                "max_gap_ms": max(g * 1e3 for g, _ in rows),
                "median_dispatch_ms": statistics.median(e["dispatch_ms"] for _, e in rows),
                "max_dispatch_ms": max(e["dispatch_ms"] for _, e in rows)}
    return out


def main(cell_name: str, steps: int = 120, *flags: str) -> int:
    directory = os.path.join(ROOT, "_bench", "save_load_ckpt")
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(OUT, exist_ok=True)
    result = {"cell": cell_name}
    for option, n in (("--save", steps), ("--load", steps + 30)):
        stream = os.path.join(OUT, "%s.%s.jsonl" % (cell_name, option.lstrip("-")))
        if os.path.exists(stream):
            os.remove(stream)
        t = time.perf_counter()
        with open(stream[:-len("jsonl")] + "err", "w") as err:
            done = subprocess.run([sys.executable, os.path.abspath(__file__), "--run", cell_name, option, directory,
                                   str(n), stream, *flags], cwd=ROOT, stderr=err)
        if done.returncode != 0:
            with open(err.name) as f:
                said = [line for line in f.read().splitlines() if "cpu_aot_loader" not in line][-12:]
            print(json.dumps({**result, "failed": option, "returncode": done.returncode, "stderr": said}), flush=True)
            return 1
        result[option.lstrip("-")] = {"process_s": time.perf_counter() - t, **read(stream)}
    shutil.rmtree(directory, ignore_errors=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1] == "--run":
        sys.exit(run(sys.argv[2], sys.argv[3], sys.argv[4], int(sys.argv[5]), sys.argv[6], sys.argv[7:]))
    sys.exit(main(sys.argv[1], *map(int, sys.argv[2:3]), *sys.argv[3:]))
