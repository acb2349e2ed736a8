#!/usr/bin/env python3
"""One run of the looped cell with the loop over the passes in another FORM than the program's, for the
builder's comparison on the chip (PERF.md section 6, PR 64):

    chiprun -- python3 scripts/ouro_loop_forms.py <form> --workload ouro-c1-s4k --seed N --seconds 10 --trace 0

`scan`: the program's own, one `lax.scan` over the passes around the layers' scan (the same as
`benchmarks/run.py`); `calls`: the layers scanned, the passes as many Python calls (four traced copies of the
scanned run; what `models/base.over_passes` does under `--no_scan_layers` for the loop alone). The rest of
the command line is `benchmarks/run.py`'s, whose `main` this calls after patching `over_passes`."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    form, argv = sys.argv[1], sys.argv[2:]
    if form not in ("scan", "calls"):
        print("ouro_loop_forms: form is scan or calls, got %r" % form, file=sys.stderr)
        return 2
    from benchmarks import run
    from galvatron_tpu.models import base as M

    if form == "calls":
        own = M.over_passes
        M.over_passes = lambda one_pass, x, steps, scan: own(one_pass, x, steps, False)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
