"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read. Two stages, so that the second can be checked on a small recorded
trace (benchmarks/fixtures/) with no chip and no profiler:

1. `load(path)`: `jax.profiler.ProfileData` -> plain events. For each TPU
   device plane the ops of its "XLA Ops" line and the program runs of its
   "XLA Modules" line; for the host the events of the trainer's main thread
   (Python frames under the Python tracer).
2. `reduce(trace)`: events -> the traced steps, the device's busy time, the
   ops that took most time, the longest idle gaps named by what the host was
   doing, collective time and its exposed part, and each kernel's time.

`python3 benchmarks/trace.py <file.xplane.pb>` describes a trace's planes,
lines and a few events: look at one by hand before changing this file.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import sys
from typing import Any, Dict, List, Mapping, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# the trainer's main thread is the one that started and stopped the trace
MAIN_THREAD_MARK = "stop_trace"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
NAME_CHARS = 64
TOP = 10

Event = Tuple[str, int, int]  # (label, start_ns, duration_ns)


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


_INSTRUCTION = re.compile(r"^\s*(ROOT )?%([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_MATMUL = ("dot_general", "conv_general")


def _whole_instructions(text: str) -> List[str]:
    """The text's lines with an instruction's continuation lines joined to
    its first: a `pallas_call` given `metadata=` prints its backend config
    over several lines, and its `op_name` stands on the last. A line that
    starts an instruction, opens a computation or closes one stands alone;
    anything else after an instruction belongs to it."""
    out: List[str] = []
    inside = False  # the last line of `out` is an instruction that may go on
    for line in text.splitlines():
        instruction = _INSTRUCTION.match(line)
        if instruction or _COMPUTATION.match(line) or not inside or line.strip() in ("", "}"):
            out.append(line)
            inside = bool(instruction)
        else:
            out[-1] += " " + line.strip()
    return out


def origins_from_hlo(text: str) -> Dict[str, str]:
    """{instruction name: the jax op it came from} out of a compiled
    program's text: an instruction's own `op_name` (wherever in the
    instruction it stands: `_whole_instructions`), and for a fusion that
    carries none, that of the computation it calls (its matmul if it has
    one, else its root)."""
    own: Dict[str, str] = {}
    calls: Dict[str, str] = {}
    of_computation: Dict[str, Tuple[int, str]] = {}
    computation = None
    for line in _whole_instructions(text):
        m = _COMPUTATION.match(line)
        if m:
            computation = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        called = _CALLS.search(line)
        if called:
            calls[m.group(2)] = called.group(1)
        if not op:
            continue
        own[m.group(2)] = op.group(1)
        if computation is not None:
            rank = 2 if any(k in op.group(1) for k in _MATMUL) else 1 if m.group(1) else 0
            if rank >= of_computation.get(computation, (-1, ""))[0]:
                of_computation[computation] = (rank, op.group(1))
    out = dict(own)
    for name, computation in calls.items():
        if name not in out:
            # a fusion of layout changes alone carries no jax op anywhere:
            # the computation's name (bitcast_fusion...) says what it is
            out[name] = of_computation.get(computation, (0, computation))[1]
    return out


def _label(event_name: str, origins: Mapping[str, str]) -> str:
    """`<hlo instruction>:<the jax op it came from>`; the trace names an op
    by its whole HLO line, of which the first word is the instruction."""
    name = event_name.lstrip("%").split(" ")[0]
    origin = origins.get(name, "")
    origin = re.sub(r"[^A-Za-z0-9_.,>/\-]+", "_", origin.split(")/", 1)[-1])
    return "%s:%s" % (name, origin) if origin else name


def load(path: str, origins: Optional[Mapping[str, str]] = None) -> Dict[str, Any]:
    """Stage 1. {"devices": {id: {"ops": [Event], "modules": [Event]}},
    "host": [Event]}. `origins` (origins_from_hlo of the compiled step) puts
    the jax op beside each instruction's name."""
    origins = origins or {}
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: line for line in plane.lines}
            devices[int(m.group(1))] = {
                "ops": [(_label(e.name, origins), int(e.start_ns), int(e.duration_ns))
                        for e in (lines[OPS_LINE].events if OPS_LINE in lines else ())],
                "modules": [(e.name, int(e.start_ns), int(e.duration_ns))
                            for e in (lines[MODULES_LINE].events if MODULES_LINE in lines else ())],
            }
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                events = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
                if any(MAIN_THREAD_MARK in name for name, _, _ in events):
                    host = events
                    break
    return {"devices": devices, "host": host}


# ------------------------------------------------------------------ stage 2
def self_times(events: Sequence[Event]) -> List[Tuple[str, int, int, int]]:
    """(label, start, duration, self) for events of one line, where an event
    that encloses others (a `while` around its body's ops) keeps only the time
    its children do not cover."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[label, start, dur, dur] for label, start, dur in order]
    stack: List[int] = []
    for i, (_, start, dur, _) in enumerate(out):
        # an enclosing event holds the whole of this one; a partial overlap
        # (two streams on one line) is not enclosure
        while stack and out[stack[-1]][1] + out[stack[-1]][2] < start + dur:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= dur
        stack.append(i)
    return [(a, b, c, max(d, 0)) for a, b, c, d in out]


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def overlap(xs: Sequence[Tuple[int, int]], ys: Sequence[Tuple[int, int]]) -> int:
    """The length two unions share (each sorted and disjoint, as `union`
    gives them), in one sweep over both."""
    total = i = j = 0
    while i < len(xs) and j < len(ys):
        total += max(0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] <= ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def _clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [e for e in events if e[1] >= lo and e[1] + e[2] <= hi]


def steps_of(modules: Sequence[Event], step_names: Sequence[str]) -> List[Event]:
    """The whole runs of the train step in the trace: the step program's
    module events, without the first and the last (either may be cut by the
    trace's edge) when there are more than two."""
    runs = sorted((e for e in modules if any(n in e[0] for n in step_names)),
                  key=lambda e: e[1])
    return runs[1:-1] if len(runs) > 2 else runs


def _covering(host: Sequence[Event], t: int) -> str:
    """The innermost host event that covers time t."""
    best = None
    for label, start, dur in host:
        if start <= t <= start + dur and (best is None or dur < best[2]):
            best = (label, start, dur)
    return best[0][:NAME_CHARS] if best else "no_host_event"


def reduce_device(ops: Sequence[Event], modules: Sequence[Event], host: Sequence[Event],
                  step_names: Sequence[str]) -> Optional[Dict[str, Any]]:
    """Stage 2 for one device. None if the trace holds no whole step of it."""
    steps = steps_of(modules, step_names)
    if not steps:
        return None
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    inside = self_times(_clip(ops, lo, hi))
    if not inside:
        return None
    n = len(steps)
    leaves = [(label, start, dur) for label, start, dur, self_ns in inside if self_ns == dur]
    busy = union([(start, start + dur) for _, start, dur in leaves])
    busy_ns = sum(b - a for a, b in busy)
    by_op: Dict[str, int] = {}
    for label, _, _, self_ns in inside:
        by_op[label] = by_op.get(label, 0) + self_ns
    gaps = [(b0, a1) for (_, b0), (a1, _) in zip(busy, busy[1:])]
    if busy and busy[0][0] > lo:
        gaps.append((lo, busy[0][0]))
    if busy and busy[-1][1] < hi:
        gaps.append((busy[-1][1], hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    coll = [(label, start, dur) for label, start, dur in leaves if COLLECTIVE.search(label)]
    other = union([(start, start + dur) for label, start, dur in leaves
                   if not COLLECTIVE.search(label)])
    coll_union = union([(start, start + dur) for _, start, dur in coll])
    exposed_ns = sum(b - a for a, b in coll_union) - overlap(coll_union, other)
    calls: Dict[str, int] = {}
    for label, _, _ in leaves:
        calls[label] = calls.get(label, 0) + 1
    return {
        "steps": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9,
        "step_s": [dur / 1e9 for _, _, dur in steps],
        "device_ops": [[label[:NAME_CHARS], ns / 1e9] for label, ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[_covering(host, (a + b) // 2), (b - a) / 1e9] for a, b in gaps[:TOP]],
        "collective_s_a_step": sum(dur for _, _, dur in coll) / 1e9 / n,
        "collective_exposed_s_a_step": exposed_ns / 1e9 / n,
        # every op that encloses no other: [seconds a step, calls a step]
        "ops_a_step": {label: [by_op[label] / 1e9 / n, c / n] for label, c in calls.items()},
    }


def reduce(trace: Mapping[str, Any], step_names: Sequence[str]) -> Optional[Dict[str, Any]]:
    """Stage 2. Device 0's reduction (ops, gaps, collectives, kernels), with
    `busy_s` and `window_s` averaged over all the devices in the trace."""
    per_device = {}
    for dev, lines in sorted(trace["devices"].items(), key=lambda kv: int(kv[0])):
        r = reduce_device([tuple(e) for e in lines["ops"]], [tuple(e) for e in lines["modules"]],
                          [tuple(e) for e in trace["host"]], step_names)
        if r is not None:
            per_device[int(dev)] = r
    if not per_device:
        return None
    first = per_device[min(per_device)]
    out = dict(first)
    out["devices"] = len(per_device)
    out["busy_s"] = sum(r["busy_s"] for r in per_device.values()) / len(per_device)
    out["window_s"] = sum(r["window_s"] for r in per_device.values()) / len(per_device)
    return out


def ops_matching(reduced: Mapping[str, Any], pattern: str) -> Tuple[float, float]:
    """(seconds a step, calls a step) of the ops whose label matches."""
    rx = re.compile(pattern)
    hits = [v for label, v in reduced["ops_a_step"].items() if rx.search(label)]
    return sum(s for s, _ in hits), sum(c for _, c in hits)


def save_events(trace: Mapping[str, Any], path: str, step_names: Sequence[str]) -> None:
    """The traced steps' events of device 0, and the host events beside them,
    as gzipped JSON: what a recorded fixture is made from."""
    dev = min(trace["devices"], key=int, default=None)
    if dev is None:
        return
    lines = trace["devices"][dev]
    steps = steps_of(lines["modules"], step_names)
    if not steps:
        return
    lo, hi = steps[0][1], steps[-1][1] + steps[-1][2]
    keep = {"devices": {str(dev): {
        "ops": [[l, s - lo, d] for l, s, d in _clip(lines["ops"], lo, hi)],
        # every run of a program, so that the same steps are found again
        "modules": [[l, s - lo, d] for l, s, d in lines["modules"]]}},
        "host": [[l, s - lo, d] for l, s, d in trace["host"] if s + d >= lo and s <= hi]}
    with gzip.open(path, "wt") as f:
        json.dump(keep, f)


def load_events(path: str) -> Dict[str, Any]:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def describe(path: str, events_a_line: int = 4) -> None:
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print("plane %r: %d lines" % (plane.name, len(lines)))
        for line in lines:
            events = list(line.events)
            print("  line %r: %d events" % (line.name, len(events)))
            for e in events[:events_a_line]:
                try:
                    stats = {k: (v if not isinstance(v, str) else v[:120]) for k, v in e.stats}
                except Exception as err:
                    stats = {"unreadable": str(err)}
                print("    %r start=%d dur=%d %s" % (e.name[:100], e.start_ns, e.duration_ns, stats))


if __name__ == "__main__":
    describe(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)
