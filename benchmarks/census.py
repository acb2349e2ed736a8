"""The program's own count of its step's collectives, joined to the trace.

The trainer's summary holds `step_collectives` (galvatron_tpu/obs/compiled.py
`step_collectives`: a row an instruction of the compiled step that runs a
collective, with its kind, form, mesh axes, role, wire bytes, scope and phase)
on more than one chip; a sink that listened as the step compiled has the same
rows in the `compile` event's `collectives`. A row's `instruction` is the first
word of a device op's label (`trace._label`: `<instruction>:<origin>`), so the
six `collective_*` readers (layer_metrics/) time a row by the op of its name
in `run["trace"]["ops_a_step"]`: seconds and calls a step of device 0.

`collective_ms` and `collective_exposed_ms` time the same layer from OUTSIDE, by
the names the trace gives its ops (`trace.COLLECTIVE`), which miss what the TPU
compiler fuses (`fusion.N` that calls `%all-reduce-scatter`), what it starts
and ends with fusions (`async-collective-start.N`) and a `shard_map`'s
collectives (`all_to_all.N`, `psum_invariant.N`)."""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

from benchmarks.trace import COLLECTIVE

Row = Mapping[str, Any]
Timed = Tuple[Row, str, float, float]  # (row, the op's label, seconds a step, calls a step)


def rows(run: Mapping[str, Any]) -> Optional[List[Row]]:
    """The census of the run's compiled step, or None where the program made
    none: one chip, or a program from before it."""
    census = (run.get("summary") or {}).get("step_collectives")
    if census is not None:
        return census["rows"]
    for event in run.get("events") or ():
        if event.get("type") == "compile" and event.get("collectives") is not None:
            return event["collectives"]
    return None


def timed(run: Mapping[str, Any]) -> Optional[List[Timed]]:
    """Each row beside the device op of its name: its label, its seconds and
    its calls a step (0 where device 0 ran no such op in the traced steps).
    None without a census or without a trace."""
    counted = rows(run)
    if counted is None or not run.get("trace"):
        return None
    ops: Dict[str, Tuple[str, float, float]] = {}
    for label, (seconds, calls) in run["trace"]["ops_a_step"].items():
        _, had_s, had_calls = ops.get(label.split(":")[0], ("", 0.0, 0.0))
        ops[label.split(":")[0]] = (label, had_s + seconds, had_calls + calls)
    return [(row,) + ops.get(row["instruction"], (row["instruction"], 0.0, 0.0)) for row in counted]


def named(label: str) -> bool:
    """Whether `collective_ms` reads the op: the trace's name says what it is."""
    return bool(COLLECTIVE.search(label))


def ms(run: Mapping[str, Any], keep: Callable[[Row, str], bool]) -> Optional[float]:
    """Device 0's milliseconds a step in the rows `keep(row, label)` takes,
    the `hidden` ones never: a matmul that carries a collective is timed as a
    matmul."""
    joined = timed(run)
    if joined is None:
        return None
    return sum(seconds for row, label, seconds, _ in joined
               if row["form"] != "hidden" and keep(row, label)) * 1e3


def ms_by_role(run: Mapping[str, Any]) -> Optional[Dict[str, float]]:
    """role -> device 0's milliseconds a step in its rows that are not hidden:
    `dp`, `tp`, `pp` and the unions; together `collective_ms` +
    `collective_fused_ms`."""
    joined = timed(run)
    if joined is None:
        return None
    by_role: Dict[str, float] = {}
    for row, _, seconds, _ in joined:
        if row["form"] != "hidden":
            by_role[row["role"]] = by_role.get(row["role"], 0.0) + seconds * 1e3
    return by_role


def role_ms(run: Mapping[str, Any], role: str) -> Optional[float]:
    by_role = ms_by_role(run)
    return None if by_role is None else by_role.get(role, 0.0)


def wire_bytes(run: Mapping[str, Any], keep: Callable[[Row], bool] = lambda row: True) -> Optional[float]:
    """Bytes a chip sends a step in the rows `keep` takes, hidden ones too: a
    row's `wire_bytes` (a ring's count over its group) times the calls a step
    of its instruction in the trace."""
    joined = timed(run)
    if joined is None:
        return None
    return sum(row["wire_bytes"] * calls for row, _, _, calls in joined if keep(row))
