#!/usr/bin/env python3
"""Where tier-1's wall goes, from a run's junit file (the driver's command writes
/tmp/_t1.xml; `--junitxml` anywhere else):

    python3 scripts/tier1_durations.py <junit.xml> [<files to list, 25>] [<workers, 6>]

Sums the cases' `time` by test file, prints the longest files with their share of
a worker's fair share T / workers (keep a file under a quarter of it: `--dist
loadfile` puts a whole file on one worker), and replays the run as xdist
schedules it (files in path order, each to the worker that is free first): the
simulated wall (within 1 % of the measured one, ROADMAP D1) and the files that
finish last, which are the tail."""

from __future__ import annotations

import collections
import heapq
import sys
import xml.etree.ElementTree as ET


def seconds_by_file(junit: str) -> dict:
    by = collections.defaultdict(lambda: [0, 0.0])
    for case in ET.parse(junit).getroot().iter("testcase"):
        parts = case.get("classname").split(".")
        while parts and not parts[-1].startswith("test_"):  # a class inside the module
            parts.pop()
        entry = by["/".join(parts) + ".py"]
        entry[0] += 1
        entry[1] += float(case.get("time"))
    return by


def main(junit: str, top: int = 25, workers: int = 6) -> int:
    by = seconds_by_file(junit)
    total = sum(t for _, t in by.values())
    print("T = %.1f s over %d files and %d cases; T/%d = %.1f, a quarter of it %.1f"
          % (total, len(by), sum(n for n, _ in by.values()), workers, total / workers, total / workers / 4))
    for name, (cases, t) in sorted(by.items(), key=lambda kv: -kv[1][1])[:top]:
        print("%-58s %4d %8.1f s %5.1f %% of T/%d" % (name, cases, t, 100 * t * workers / total, workers))
    free = [(0.0, w) for w in range(workers)]
    ends = []
    for name in sorted(by, key=lambda f: f.split("/")):
        at, w = heapq.heappop(free)
        ends.append((at + by[name][1], at, name))
        heapq.heappush(free, (at + by[name][1], w))
    print("simulated wall %.0f s; the workers end at %s" % (max(ends)[0], sorted(round(t) for t, _ in free)))
    for end, start, name in sorted(ends)[-5:]:
        print("  %6.0f -> %6.0f  %s" % (start, end, name))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], *(int(a) for a in sys.argv[2:4])))
