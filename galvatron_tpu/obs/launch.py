"""Where a start went: the program's import by package, the launch's phases,
and what jax traced, lowered and asked its compilation cache for on the way
to the first drained step.

Three records, recorded always (a launch is a dozen `time.perf_counter()`
stamps), with no flag and no environment variable:

**ImportRecord**, the process's one. `galvatron_tpu/cli/__init__.py` installs
it with its first statement and `cli/train.py` takes it out after its last
module-level import (`cli/__main__.main` after importing any other
subcommand), so it covers the import of the program as
`python -m galvatron_tpu.cli train` and the benchmark's `import_program()`
pay it: total seconds, modules loaded, SELF seconds by top-level package
(finding, creating and executing a module less the imports nested in it:
`python -X importtime`'s "self") and the INCLUSIVE seconds of
`CHECKPOINT_MODULE`: 0.0 since PR 60, when the trainer stopped importing that
module with itself (a run imports it when it first loads or saves,
`cli/train.CheckpointModule`, whose `checkpoint_import` stands beside this
record in the summary and the `launch` event), and the guard against its
coming back. It is two `sys.monitoring` events on ONE code object,
importlib's `_find_and_load`, which the interpreter calls for every module it does not
hold yet: nothing stands in `sys.meta_path` or `builtins`, no spec or loader
is touched, it imports nothing itself, only the thread that installed it is
timed, and NO FRAME of it lies on the stack while a module's body runs. That
last is why it is not a finder that wraps the loaders' `exec_module` (PERF.md
section 6, PR 51: one more frame an import level moved google.api_core's
`packages_distributions()` across one of CPython 3.12's 16 KiB frame-stack
chunks and made the import 0.7 s slower). A process that `jax` was imported
in before (the benchmark's) reads `jax` near 0. A plain `import galvatron_tpu`
installs nothing.

**Launch**, one a `cli/train.train()` call: the phases `cli/train._train`
marks from its entry to the first step's drain (names in obs/tracing.py,
`gt/launch/*` and the children of `gt/compile`), each a timed
`TraceControl.span` and so a `TraceAnnotation` where a trace is running, and
`total`, entry to first drain. Phases do not overlap: one asked for while
another is open is timed and not recorded.

**JitCounters**, the launch's ONE pair of `jax.monitoring` listeners: jit
traces and the functions they went to, lowerings, the compilation cache's
requests, hits, misses and read seconds, backend compile seconds. Registered
when the launch starts and unregistered when its first step has drained,
before any measured step.

stdlib-only at module scope: this module exists before jax is imported.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

# ----------------------------------------------------------------- imports
PACKAGES = ("jax", "jaxlib", "google", "orbax", "tensorstore", "grpc", "flax", "optax", "numpy",
            "galvatron_tpu")
OTHER = "other"
# the module whose import is also read inclusively: the one that pulls
# `orbax.checkpoint`, which no import of the program may pay (module docstring)
CHECKPOINT_MODULE = "galvatron_tpu.runtime.checkpoint"


# what the interpreter calls for every module that is not in sys.modules yet,
# and what `python -X importtime` times: finding, creating and executing it
_FIND_AND_LOAD = sys.modules["_frozen_importlib"]._find_and_load.__code__
TOOL_IDS = (4, 3)  # sys.monitoring's ids that no debugger, coverage tool or profiler claims
TOOL_NAME = "galvatron_tpu.obs.launch"


class ImportRecord:
    """What importing the program took (module docstring). `total_s` is None
    until `done()`, and stays None where nothing was recorded (a Python
    before 3.12, or both tool ids taken)."""

    def __init__(self):
        self.total_s: Optional[float] = None
        self.modules = 0
        self.self_s: Dict[str, float] = dict.fromkeys(PACKAGES + (OTHER,), 0.0)
        self.checkpoint_s = 0.0
        self._t0: Optional[float] = None
        self._thread: Optional[int] = None
        self._tool: Optional[int] = None
        # the imports under way: [name, start, seconds of the imports nested in it so far]
        self._stack: List[list] = []

    # ------------------------------------------- sys.monitoring's callbacks
    def _start(self, code, offset):
        if threading.get_ident() == self._thread:
            name = sys._getframe(1).f_locals.get("name", OTHER)
            self._stack.append([name, time.perf_counter(), 0.0])

    def _return(self, code, offset, module):
        if threading.get_ident() == self._thread and self._stack:
            self.modules += 1
            self._leave(time.perf_counter())

    def _unwind(self, code, offset, exception):
        # a module that is not there, or whose body raised
        if code is _FIND_AND_LOAD and threading.get_ident() == self._thread and self._stack:
            self._leave(time.perf_counter())

    def _leave(self, now: float) -> None:
        name, start, nested = self._stack.pop()
        seconds = now - start
        if self._stack:
            self._stack[-1][2] += seconds
        package = name.partition(".")[0]
        self.self_s[package if package in self.self_s else OTHER] += seconds - nested
        if name == CHECKPOINT_MODULE:
            self.checkpoint_s += seconds

    # ------------------------------------------------------- in and out
    def install(self) -> None:
        monitoring = getattr(sys, "monitoring", None)
        if self._t0 is not None or monitoring is None:
            return
        for tool in TOOL_IDS:
            if monitoring.get_tool(tool) is None:
                break
        else:
            return
        monitoring.use_tool_id(tool, TOOL_NAME)
        events = monitoring.events
        monitoring.register_callback(tool, events.PY_START, self._start)
        monitoring.register_callback(tool, events.PY_RETURN, self._return)
        monitoring.register_callback(tool, events.PY_UNWIND, self._unwind)
        # the two events of ONE code object; an unwinding frame can only be asked for of all
        monitoring.set_local_events(tool, _FIND_AND_LOAD, events.PY_START | events.PY_RETURN)
        monitoring.set_events(tool, events.PY_UNWIND)
        self._tool, self._thread, self._t0 = tool, threading.get_ident(), time.perf_counter()

    def done(self) -> None:
        """Stop recording and give the tool id back; the imports still under
        way (the module that calls this from its body) are counted up to
        now. Safe to call again."""
        if self._t0 is None or self.total_s is not None:
            return
        now = time.perf_counter()
        self.modules += len(self._stack)
        while self._stack:
            self._leave(now)
        self.total_s = now - self._t0
        monitoring, tool = sys.monitoring, self._tool
        monitoring.set_local_events(tool, _FIND_AND_LOAD, 0)
        monitoring.set_events(tool, 0)
        for event in (monitoring.events.PY_START, monitoring.events.PY_RETURN, monitoring.events.PY_UNWIND):
            monitoring.register_callback(tool, event, None)
        monitoring.free_tool_id(tool)

    def as_dict(self) -> Optional[Dict[str, Any]]:
        if self.total_s is None:
            return None
        return {"total_s": self.total_s, "modules": self.modules,
                "by_package_s": dict(self.self_s),
                "checkpoint_s": self.checkpoint_s}


IMPORTS = ImportRecord()  # the process's one


# ------------------------------------------------------------ jit counters
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
CACHE_MISS = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
TOP_TRACED = 10


class JitCounters:
    """One pair of `jax.monitoring` listeners and what they counted. A context:
    registered while at least one `with` is open, so `_compile_step` reads
    the step's cache hit off the launch's pair and, after the launch (a
    rebuild behind a migration), off a pair of its own."""

    def __init__(self):
        self.jit_traces = 0
        self.traced: Dict[str, list] = {}  # fun_name -> [count, inclusive seconds]
        self.lowerings = 0
        self.lowering_s = 0.0
        self.cache_requests = self.cache_hits = self.cache_misses = 0
        self.cache_retrieval_s = 0.0
        self.backend_compile_s = 0.0
        self._depth = 0

    def _on_event(self, event, **_):
        if event == CACHE_REQUEST:
            self.cache_requests += 1
        elif event == CACHE_HIT:
            self.cache_hits += 1
        elif event == CACHE_MISS:
            self.cache_misses += 1

    def _on_duration(self, event, duration, fun_name=None, **_):
        if event == JAXPR_TRACE:
            self.jit_traces += 1
            row = self.traced.setdefault(str(fun_name), [0, 0.0])
            row[0] += 1
            row[1] += duration
        elif event == LOWERING:
            self.lowerings += 1
            self.lowering_s += duration
        elif event == BACKEND_COMPILE:
            self.backend_compile_s += duration
        elif event == CACHE_RETRIEVAL:
            self.cache_retrieval_s += duration

    def __enter__(self):
        if self._depth == 0:
            import jax

            jax.monitoring.register_event_listener(self._on_event)
            jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        self._depth += 1
        return self

    def __exit__(self, *exc_info):
        self._depth -= 1
        if self._depth == 0:
            import jax

            jax.monitoring.unregister_event_listener(self._on_event)
            jax.monitoring.unregister_event_duration_listener(self._on_duration)
        return False

    def as_dict(self) -> Dict[str, Any]:
        top = sorted(self.traced.items(), key=lambda kv: -kv[1][1])[:TOP_TRACED]
        return {
            "jit_traces": self.jit_traces,
            "top_traced": [{"fun_name": name, "count": count, "trace_s": seconds}
                           for name, (count, seconds) in top],
            "lowerings": self.lowerings, "lowering_s": self.lowering_s,
            "cache_requests": self.cache_requests, "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses, "cache_retrieval_s": self.cache_retrieval_s,
            "backend_compile_s": self.backend_compile_s,
        }


# ---------------------------------------------------------------- a launch
TOTAL = "total"


class _Phase:
    """A timed span that tells its launch when it opens and closes."""

    def __init__(self, launch: "Launch", name: str, span):
        self._launch, self._name, self._span = launch, name, span
        self._counts = False

    def __enter__(self):
        self._counts = self._launch._opens(self)
        return self._span.__enter__()

    def __exit__(self, *exc_info):
        self._span.__exit__(*exc_info)
        if self._counts:
            self._launch._closes(self._name, self._span)
        return False


class Launch:
    """One start of a training run: its phases, its jit counters and, by
    reference, the process's import (module docstring)."""

    def __init__(self, imports: ImportRecord = IMPORTS):
        self.t0 = time.perf_counter()
        self.imports = imports
        self.jit = JitCounters()
        # (name, start and end in ms since t0), in the order they closed
        self.phases: List[Tuple[str, float, float]] = []
        self.total_ms: Optional[float] = None
        self.open = True
        self._phase: Optional[_Phase] = None
        self._jit: Optional[Dict[str, Any]] = None  # the counters as they stood at close()
        self.jit.__enter__()

    def phase(self, control, name: str) -> _Phase:
        """A context around one phase: `control.span(name, timed=True)`, its
        `.ms` set on exit whether or not the launch still records."""
        return _Phase(self, name, control.span(name, timed=True))

    def begin(self, control, name: str) -> None:
        """Open a phase that another function ends (`end`)."""
        self.phase(control, name).__enter__()

    def end(self) -> None:
        if self._phase is not None:
            self._phase.__exit__(None, None, None)

    def _opens(self, phase: _Phase) -> bool:
        if not self.open or self._phase is not None:
            return False
        self._phase = phase
        return True

    def _closes(self, name: str, span) -> None:
        self._phase = None
        start = (span.t0 - self.t0) * 1e3
        self.phases.append((name, start, start + span.ms))

    def finish(self) -> Dict[str, Any]:
        """The first step has drained: the open phase ends, the listeners
        go, and the record is what `fields()` says from now on."""
        self.end()
        self.total_ms = (time.perf_counter() - self.t0) * 1e3
        self.close()
        return self.fields()

    def close(self) -> None:
        """Stop recording and unregister the listeners; safe to call again
        (a run that raises before its first drain ends here)."""
        if self.open:
            self.open = False
            self.jit.__exit__(None, None, None)
            self._jit = self.jit.as_dict()

    def fields(self) -> Dict[str, Any]:
        """`launch_ms` (phase -> ms, a name met twice added up, and `total`),
        `launch_imports` and `launch_jit`: the `launch` event's fields and
        the summary's keys."""
        ms: Dict[str, float] = {}
        for name, start, end in self.phases:
            ms[name] = ms.get(name, 0.0) + (end - start)
        ms[TOTAL] = self.total_ms
        return {"launch_ms": ms, "launch_imports": self.imports.as_dict(), "launch_jit": self._jit}
