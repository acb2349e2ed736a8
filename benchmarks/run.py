#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

A new process a run. It refuses to start (non-zero exit, nothing on stdout)
unless jax's devices are TPUs whose device_kind is in benchmarks/peaks.json
and as many as the cell's `chips`. Every stdout line is one JSON object; the
LAST is the result (`correct`, `attempted`, `failed`, `metrics`, `device`,
and `breakdown` in a traced run), the earlier one holds what else is worth
reading, and `run.json` in the run's directory under chiprun_out/benchmarks/
holds that and the per-step intervals and losses. The trainer's own log goes
to stderr. See benchmarks/README.md.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(**obj):
    print(json.dumps(obj), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    opts = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    from benchmarks import cells

    try:
        cell = cells.load_cell(ROOT, opts.workload)
        peaks = cells.load_json(ROOT, "benchmarks/peaks.json")
    except cells.CellError as e:
        print("benchmarks/run.py: %s" % e, file=sys.stderr)
        return 2

    marks = {"start_s": time.perf_counter() - T0}
    import jax  # noqa: F401 -- timed: set-up starts with it

    from benchmarks import harness

    marks["import_jax_s"] = time.perf_counter() - T0 - marks["start_s"]
    # The chip's start (6 to 13 s inside jax.devices(), in libtpu, importing
    # nothing) runs beside the import of the program (13 to 22 s of Python):
    # set-up is the longer of the two and not their sum.
    start = harness.DeviceStart()
    if start.wait(0.5):  # off the chip jax answers at once: refuse before any work
        why = harness.refusal(start.devices(), cell.chips, peaks)
        if why is not None:
            print("benchmarks/run.py: %s" % why, file=sys.stderr)
            return 1
    t = time.perf_counter()
    harness.import_program()
    marks["import_program_s"] = time.perf_counter() - t
    devices = start.devices()
    marks["devices_wait_s"] = time.perf_counter() - t - marks["import_program_s"]
    why = harness.refusal(devices, cell.chips, peaks)
    if why is not None:
        print("benchmarks/run.py: %s" % why, file=sys.stderr)
        return 1
    result = harness.run_cell(
        cell, seed=opts.seed, seconds=opts.seconds, traced=opts.trace, peaks=peaks,
        t0=T0, marks=marks, chip_start_s=start.seconds,
        out_dir=harness.out_dir_for(ROOT, cell.name, opts.seed, opts.trace),
        say=say)
    say(**result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
