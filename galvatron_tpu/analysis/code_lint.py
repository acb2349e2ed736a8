"""Static code linter for jax-API drift and jit-safety hazards (``GLC***``).

A pure-AST pass (no execution of the linted code) over Python sources:

- **GLC001 — missing jax API**: every dotted attribute chain rooted at a
  jax import alias (``jax.shard_map``, ``jnp.einsum``, ``lax.scan`` ...) and
  every ``from jax.x import y`` is resolved against the jax actually
  *installed in this environment* — introspected, not hard-coded — so an
  upgrade/downgrade that removes an API is caught at lint time instead of at
  import/trace time on a TPU pod (the ``jax.shard_map``/
  ``get_abstract_mesh`` class of breakage: ring attention, both 1F1B
  engines and the hardware profiler all hang off those names).
- **GLC002 — host numpy inside jit**: calls to a ``numpy`` alias inside a
  jit-compiled function. `np.asarray(x)` on a tracer either fails or silently
  constant-folds; dtype/constant accesses (``np.float32``, ``np.pi``) are
  trace-time constants and allowed.
- **GLC003 — Python control flow on traced values**: ``if``/``while`` whose
  condition reads a (non-static) parameter of a jit-compiled function.
  Shape/dtype/None tests are static and exempt.
- **GLC004 — donated buffer reuse**: an argument passed at a donated
  position of a ``donate_argnums`` jit is read again afterwards without
  rebinding — the buffer backing it may already be aliased to the output
  (the PR-1 anomaly-guard lesson: donated step inputs cannot be "kept" on
  the host side).
- **GLC006 — ad-hoc logging in runtime library code**: bare ``print(...)``
  calls and append-mode ``open(..., "a")`` file logging inside
  ``galvatron_tpu/runtime/`` and ``galvatron_tpu/obs/`` (the rule is
  path-scoped; CLI drivers and tests may print). Library-layer output must
  go through the telemetry stream (``obs.telemetry.runtime_log`` / a
  ``TelemetrySink``) or an injectable ``print_fn``/``log_fn`` parameter
  (``RuntimeProfiler.log_iteration(print_fn=)``): bare prints are invisible
  to the structured event stream the report/autotuner layers consume, and
  per-call append-opens cost a filesystem round trip on hot paths (the
  ``log_iteration`` reopen bug this rule pins).
- **GLC005 — blocking host sync in a loop**: driver-side loops that force a
  host<->device round trip every iteration (``float(...)``/``.item()``/
  ``np.asarray(...)`` on values produced by a jitted callable, or any
  ``block_until_ready``) kill JAX's async dispatch: the device idles while
  the host books keep, exactly the serialization the dispatch-ahead train
  loop removes (cli/train.py ISSUE 4). Dispatch all iterations first and
  drain once — or mark a deliberate sync point (profilers measure by
  syncing) with the pragma. The value-producer taint is tracked through
  names assigned from ``jax.jit(...)``-wrapped callables and
  ``jax.device_put``, so plain host-numpy ``float()`` loops don't trip it;
  ``block_until_ready`` is a sync by definition and is flagged untainted.
- **GLC007 — custom_vjp closing over a traced axis_index**: a custom_vjp
  primal or ``defvjp`` rule that reads, as a free variable, a name bound
  from ``jax.lax.axis_index`` in an enclosing scope. Inside a shard_map
  region the index is a per-shard traced value; baked into the rule's
  closure, the legacy shard_map transpose replays it with the wrong
  shard's value (the tp ring cotangent hazard ``parallel/tp_shard_map.py``
  documents) — recompute ``axis_index`` inside the rule instead. The
  traced-program linter's GLT005 catches the same bug at jaxpr level.

Jit contexts are found both as decorators (``@jax.jit``,
``@partial(jax.jit, ...)``) and as wrappings of a locally-defined function
(``step = jax.jit(train_step, donate_argnums=(0, 1))``).

Suppressions: a line comment ``# galv-lint: ignore[GLC002]`` (comma-
separated codes) suppresses findings reported for that line.
"""

from __future__ import annotations

import ast
import importlib
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from galvatron_tpu.analysis import diagnostics as D

_PRAGMA_RE = re.compile(r"#\s*galv-lint:\s*ignore\[([A-Za-z0-9_, ]+)\]")

# numpy attributes that are trace-time constants / types, fine inside jit
_NUMPY_STATIC_OK = {
    "pi", "e", "inf", "nan", "newaxis", "ndarray", "dtype", "generic",
    "integer", "floating", "bool_", "float16", "float32", "float64",
    "int8", "int16", "int32", "int64", "uint8", "uint16", "uint32",
    "uint64", "complex64", "complex128", "iinfo", "finfo",
}

# test-expression contexts that are static even on a traced name
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}
_STATIC_CALLS = {"isinstance", "len", "hasattr", "getattr", "callable", "int", "bool"}


# --------------------------------------------------------------- resolution
class JaxResolver:
    """Resolve dotted chains against the installed jax, importing submodules
    on demand. Memoised per (chain) so a package-wide lint is one getattr
    walk per distinct chain."""

    def __init__(self, roots: Sequence[str] = ("jax",)):
        self.roots = tuple(roots)
        self._cache: Dict[Tuple[str, ...], Optional[str]] = {}

    def missing_prefix(self, parts: Sequence[str]) -> Optional[str]:
        """None if the chain resolves; else the shortest unresolvable
        prefix (e.g. 'jax.shard_mapp')."""
        parts = tuple(parts)
        if parts in self._cache:
            return self._cache[parts]
        result: Optional[str] = None
        try:
            obj = importlib.import_module(parts[0])
        except ImportError:
            result = parts[0]
        else:
            for i, name in enumerate(parts[1:], start=1):
                try:
                    obj = getattr(obj, name)
                except AttributeError:
                    dotted = ".".join(parts[: i + 1])
                    try:
                        obj = importlib.import_module(dotted)
                    except ImportError:
                        result = dotted
                        break
        self._cache[parts] = result
        return result


# ------------------------------------------------------------- file linting
class _Aliases:
    """Import-alias tables for one module."""

    def __init__(self):
        self.jax: Dict[str, Tuple[str, ...]] = {}    # alias -> dotted chain
        self.numpy: Set[str] = set()                 # aliases of host numpy

    def visit_import(self, node: ast.Import):
        for a in node.names:
            parts = tuple(a.name.split("."))
            bound = a.asname or parts[0]
            if parts[0] == "jax":
                self.jax[bound] = parts if a.asname else (parts[0],)
            elif parts[0] == "numpy":
                self.numpy.add(bound)

    def visit_import_from(self, node: ast.ImportFrom) -> List[Tuple[Tuple[str, ...], int]]:
        """Returns jax-rooted (chain, lineno) pairs to resolve (GLC001)."""
        out = []
        if node.level or not node.module:
            return out
        mparts = tuple(node.module.split("."))
        for a in node.names:
            if a.name == "*":
                continue
            bound = a.asname or a.name
            if mparts[0] == "jax":
                chain = mparts + (a.name,)
                self.jax[bound] = chain
                out.append((chain, node.lineno))
            elif mparts[0] == "numpy":
                self.numpy.add(bound)
        return out


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """['jnp', 'linalg', 'norm'] for a pure Name.Attr.Attr chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return parts[::-1]
    return None


def _literal_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    try:
        v = ast.literal_eval(node)
    except (ValueError, SyntaxError):
        return None
    if isinstance(v, int):
        return (v,)
    if isinstance(v, (tuple, list)) and all(isinstance(x, int) for x in v):
        return tuple(v)
    return None


def _is_jax_jit(node: ast.AST, aliases: _Aliases) -> bool:
    chain = _attr_chain(node)
    if chain is None:
        return False
    root = aliases.jax.get(chain[0])
    if root is None:
        return False
    return (root + tuple(chain[1:]))[-1] == "jit"


class _JitInfo:
    def __init__(self, static_names: Set[str], donated: Tuple[int, ...] = ()):
        self.static_names = static_names
        self.donated = donated


def _jit_call_info(call: ast.Call, aliases: _Aliases) -> Optional[Tuple[Optional[str], _JitInfo]]:
    """(wrapped function name | None, info) when `call` is jax.jit(...)."""
    if not _is_jax_jit(call.func, aliases):
        return None
    static: Set[str] = set()
    donated: Tuple[int, ...] = ()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            try:
                lv = ast.literal_eval(v)
                static |= {lv} if isinstance(lv, str) else set(lv)
            except (ValueError, SyntaxError):
                pass
        elif kw.arg == "donate_argnums":
            donated = _literal_int_tuple(kw.value) or ()
    fname = None
    if call.args and isinstance(call.args[0], ast.Name):
        fname = call.args[0].id
    return fname, _JitInfo(static, donated)


class _ModuleLint:
    def __init__(self, src: str, filename: str, resolver: JaxResolver,
                 rules: Set[str]):
        self.filename = filename
        self.resolver = resolver
        self.rules = rules
        self.diags: List[D.Diagnostic] = []
        self.tree = ast.parse(src, filename=filename)
        self.lines = src.splitlines()
        self.aliases = _Aliases()
        # function-def name -> _JitInfo for functions that get jit-wrapped
        self.jit_wrapped: Dict[str, _JitInfo] = {}
        # donated-jit callable name -> donated positions
        self.donated_callables: Dict[str, Tuple[int, ...]] = {}
        # names bound to a jax.jit(...) result (device-value producers)
        self.jit_callables: Set[str] = set()

    # ---- pass 1: imports, jit registry --------------------------------
    def scan_module(self):
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                self.aliases.visit_import(node)
            elif isinstance(node, ast.ImportFrom):
                for chain, lineno in self.aliases.visit_import_from(node):
                    self._check_chain(chain, lineno)
            elif isinstance(node, ast.Call):
                info = _jit_call_info(node, self.aliases)
                if info is not None:
                    fname, ji = info
                    if fname:
                        self.jit_wrapped[fname] = ji
            elif isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                info = _jit_call_info(node.value, self.aliases)
                if info is not None:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            self.jit_callables.add(t.id)
                            if info[1].donated:
                                self.donated_callables[t.id] = info[1].donated

    # ---- GLC001 --------------------------------------------------------
    def _check_chain(self, chain: Sequence[str], lineno: int):
        if "GLC001" not in self.rules:
            return
        missing = self.resolver.missing_prefix(chain)
        if missing is not None:
            self.diags.append(D.make(
                "GLC001", "%r does not exist in the installed jax (%s)"
                % (".".join(chain), missing),
                file=self.filename, line=lineno, key=".".join(chain),
            ))

    def check_attribute_chains(self):
        # flag only maximal chains: collect the set of inner Attribute nodes
        inner: Set[int] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Attribute):
                inner.add(id(node.value))
        seen: Set[Tuple[Tuple[str, ...], int]] = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Attribute) or id(node) in inner:
                continue
            if not isinstance(node.ctx, ast.Load):
                continue  # only reads can hit a missing attribute
            chain = _attr_chain(node)
            if chain is None:
                continue
            rooted = self.aliases.jax.get(chain[0])
            if rooted is None:
                continue
            full = rooted + tuple(chain[1:])
            key = (full, node.lineno)
            if key not in seen:
                seen.add(key)
                self._check_chain(full, node.lineno)

    # ---- jit-body rules ------------------------------------------------
    def _jit_functions(self) -> List[Tuple[ast.AST, _JitInfo]]:
        out = []
        for node in ast.walk(self.tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            info: Optional[_JitInfo] = None
            for dec in node.decorator_list:
                if _is_jax_jit(dec, self.aliases):
                    info = _JitInfo(set())
                elif isinstance(dec, ast.Call):
                    # @partial(jax.jit, ...) / @jax.jit(...) with options
                    if _is_jax_jit(dec.func, self.aliases):
                        info = _jit_call_info(dec, self.aliases)[1]
                    elif (isinstance(dec.func, ast.Name) and dec.func.id == "partial"
                          and dec.args and _is_jax_jit(dec.args[0], self.aliases)):
                        info = _jit_call_info(
                            ast.Call(func=dec.args[0], args=dec.args[1:],
                                     keywords=dec.keywords), self.aliases)[1]
            if info is None and node.name in self.jit_wrapped:
                info = self.jit_wrapped[node.name]
            if info is not None:
                out.append((node, info))
        return out

    @staticmethod
    def _param_names(fn) -> List[str]:
        a = fn.args
        return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]

    def check_jit_bodies(self):
        for fn, info in self._jit_functions():
            params = [p for p in self._param_names(fn) if p not in info.static_names]
            traced = set(params)
            if "GLC002" in self.rules:
                self._check_host_numpy(fn)
            if "GLC003" in self.rules:
                self._check_traced_branches(fn, traced)

    def _check_host_numpy(self, fn):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            chain = _attr_chain(node.func)
            if not chain or chain[0] not in self.aliases.numpy:
                continue
            if len(chain) == 2 and chain[1] in _NUMPY_STATIC_OK:
                continue
            self.diags.append(D.make(
                "GLC002", "host-side numpy call %r inside jit-compiled "
                "%r: numpy cannot consume tracers; use jax.numpy (or move "
                "the computation out of the jitted function)"
                % (".".join(chain), fn.name),
                file=self.filename, line=node.lineno, key=".".join(chain),
            ))

    def _check_traced_branches(self, fn, traced: Set[str]):
        class TestVisitor(ast.NodeVisitor):
            """Finds Names of traced params used non-statically in a
            condition expression."""

            def __init__(self, outer):
                self.outer = outer
                self.offending: List[ast.Name] = []

            def visit_Attribute(self, node):
                if node.attr in _STATIC_ATTRS:
                    return  # x.shape/... and anything under it is static
                self.generic_visit(node)

            def visit_Call(self, node):
                if isinstance(node.func, ast.Name) and node.func.id in _STATIC_CALLS:
                    return
                self.generic_visit(node)

            def visit_Compare(self, node):
                # `x is None` / `x is not None` are static identity tests
                if (len(node.ops) == 1 and isinstance(node.ops[0], (ast.Is, ast.IsNot))
                        and isinstance(node.comparators[0], ast.Constant)):
                    return
                # `"key" in batch`: dict-key membership is pytree structure,
                # static under jit (unlike `x in array`)
                if (len(node.ops) == 1 and isinstance(node.ops[0], (ast.In, ast.NotIn))
                        and isinstance(node.left, ast.Constant)
                        and isinstance(node.left.value, str)):
                    return
                self.generic_visit(node)

            def visit_Name(self, node):
                if node.id in traced:
                    self.offending.append(node)

        for node in ast.walk(fn):
            if not isinstance(node, (ast.If, ast.While)):
                continue
            tv = TestVisitor(self)
            tv.visit(node.test)
            for name in tv.offending:
                self.diags.append(D.make(
                    "GLC003", "Python %s on traced value %r inside "
                    "jit-compiled %r: the branch is taken at trace time, not "
                    "per-step; use jax.lax.cond/jnp.where (or mark the "
                    "argument static)"
                    % ("while" if isinstance(node, ast.While) else "if",
                       name.id, fn.name),
                    file=self.filename, line=node.lineno, key=name.id,
                ))
                break  # one finding per statement

    # ---- GLC004 --------------------------------------------------------
    def check_donated_reuse(self):
        if "GLC004" not in self.rules or not self.donated_callables:
            return
        for fn in ast.walk(self.tree):
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Module)):
                self._check_donated_in_scope(fn)

    @staticmethod
    def _walk_scope(scope) -> Iterable[ast.AST]:
        """All nodes of this scope only — nested function/class bodies are
        their own scope and are not entered."""
        stack = list(scope.body)
        while stack:
            node = stack.pop(0)
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def _check_donated_in_scope(self, scope):
        nodes = list(self._walk_scope(scope))
        # (donated arg name, call lineno) events, in order
        events: List[Tuple[str, int]] = []
        for node in nodes:
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Name):
                donated = self.donated_callables.get(node.func.id)
                if not donated:
                    continue
                for pos in donated:
                    if pos < len(node.args) and isinstance(node.args[pos], ast.Name):
                        events.append((node.args[pos].id, node.lineno))
        if not events:
            return
        # per donated name: flag Loads after the donating call and before the
        # next Store to that name
        stores: Dict[str, List[int]] = {}
        loads: Dict[str, List[Tuple[int, ast.Name]]] = {}
        for node in nodes:
            if isinstance(node, ast.Name):
                if isinstance(node.ctx, ast.Store):
                    stores.setdefault(node.id, []).append(node.lineno)
                elif isinstance(node.ctx, ast.Load):
                    loads.setdefault(node.id, []).append((node.lineno, node))
        for name, call_line in events:
            rebind = min((ln for ln in stores.get(name, []) if ln >= call_line),
                         default=None)
            for ln, node in loads.get(name, []):
                if ln <= call_line:
                    continue
                if rebind is not None and ln >= rebind:
                    continue
                self.diags.append(D.make(
                    "GLC004", "%r was donated to the jit call on line %d "
                    "(donate_argnums) and is read again here: its buffer "
                    "may already alias the output; copy it before the call "
                    "or stop donating it" % (name, call_line),
                    file=self.filename, line=ln, key=name,
                ))
                break  # one finding per (name, call)

    # ---- GLC005 --------------------------------------------------------
    def _device_tainted_names(self) -> Set[str]:
        """Names assigned (incl. tuple-unpacked) from a call to a known
        jit-wrapped callable or from jax.device_put — conservative taint for
        'this is (a tree of) device array(s)'."""
        producers = set(self.jit_callables) | set(self.jit_wrapped)
        tainted: Set[str] = set()
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
                continue
            fn = node.value.func
            is_device = isinstance(fn, ast.Name) and fn.id in producers
            if not is_device:
                chain = _attr_chain(fn)
                is_device = bool(
                    chain and chain[0] in self.aliases.jax
                    and chain[-1] in ("device_put", "device_put_sharded",
                                      "device_put_replicated")
                )
            if is_device:
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            tainted.add(n.id)
        return tainted

    def _device_expr(self, expr: ast.AST, tainted: Set[str]) -> bool:
        """expr references a tainted name or calls a jit callable."""
        producers = set(self.jit_callables) | set(self.jit_wrapped)
        for n in ast.walk(expr):
            if isinstance(n, ast.Name) and n.id in tainted:
                return True
            if (isinstance(n, ast.Call) and isinstance(n.func, ast.Name)
                    and n.func.id in producers):
                return True
        return False

    def _blocking_sync(self, call: ast.Call, tainted: Set[str]) -> Optional[str]:
        """The offending sync's key when `call` is a per-iteration blocking
        host sync, else None."""
        func = call.func
        if isinstance(func, ast.Attribute) and func.attr == "block_until_ready":
            chain = _attr_chain(func)
            return ".".join(chain) if chain else "block_until_ready"
        if (isinstance(func, ast.Attribute) and func.attr == "item"
                and not call.args and self._device_expr(func.value, tainted)):
            return "item"
        if (isinstance(func, ast.Name) and func.id == "float"
                and len(call.args) == 1
                and self._device_expr(call.args[0], tainted)):
            return "float"
        chain = _attr_chain(func)
        if (chain and chain[0] in self.aliases.numpy
                and chain[-1] in ("asarray", "array") and call.args
                and self._device_expr(call.args[0], tainted)):
            return ".".join(chain)
        return None

    def check_host_syncs_in_loops(self):
        if "GLC005" not in self.rules:
            return
        # loops inside jitted functions are traced, not executed per-step:
        # a float() there is a different bug (GLC002/tracer error), not a sync
        jit_nodes: Set[int] = set()
        for fn, _ in self._jit_functions():
            jit_nodes.update(id(n) for n in ast.walk(fn))
        tainted = self._device_tainted_names()
        seen: Set[Tuple[int, str]] = set()
        for loop in ast.walk(self.tree):
            if not isinstance(loop, (ast.For, ast.While)) or id(loop) in jit_nodes:
                continue
            for node in ast.walk(loop):
                if not isinstance(node, ast.Call):
                    continue
                key = self._blocking_sync(node, tainted)
                if key is None or (node.lineno, key) in seen:
                    continue
                seen.add((node.lineno, key))
                self.diags.append(D.make(
                    "GLC005", "blocking host sync %r inside a loop: every "
                    "iteration stalls the host on the device (and the device "
                    "on the host), killing async dispatch; dispatch all "
                    "iterations first and drain once, or mark a deliberate "
                    "sync point with the pragma" % key,
                    file=self.filename, line=node.lineno, key=key,
                ))

    # ---- GLC006 --------------------------------------------------------
    def check_runtime_logging(self):
        """Path-scoped: only library code under galvatron_tpu/runtime/ or
        galvatron_tpu/obs/ is held to the no-ad-hoc-logging contract."""
        if "GLC006" not in self.rules or not _GLC006_PATH_RE.search(self.filename):
            return
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Name):
                continue
            if node.func.id == "print":
                self.diags.append(D.make(
                    "GLC006", "bare print() in runtime library code: route "
                    "output through obs.telemetry (runtime_log / a "
                    "TelemetrySink event) or an injectable print_fn/log_fn "
                    "parameter so it reaches the structured event stream",
                    file=self.filename, line=node.lineno, key="print",
                ))
            elif node.func.id == "open":
                mode = None
                if (len(node.args) >= 2 and isinstance(node.args[1], ast.Constant)
                        and isinstance(node.args[1].value, str)):
                    mode = node.args[1].value
                for kw in node.keywords:
                    if (kw.arg == "mode" and isinstance(kw.value, ast.Constant)
                            and isinstance(kw.value.value, str)):
                        mode = kw.value.value
                if mode and mode.startswith("a"):
                    self.diags.append(D.make(
                        "GLC006", "append-mode open(..., %r) logging in "
                        "runtime library code: emit through the telemetry "
                        "sink (or hold ONE appending handle for the run, "
                        "like RuntimeProfiler.log_iteration)" % mode,
                        file=self.filename, line=node.lineno, key="open",
                    ))

    # ---- GLC007 --------------------------------------------------------
    def _axis_index_names(self, scope) -> Set[str]:
        """Names bound in `scope`'s own body (nested functions excluded)
        from a call to jax.lax.axis_index."""
        out: Set[str] = set()
        for node in self._walk_scope(scope):
            if not (isinstance(node, ast.Assign) and isinstance(node.value, ast.Call)):
                continue
            chain = _attr_chain(node.value.func)
            if not chain:
                continue
            rooted = self.aliases.jax.get(chain[0])
            if rooted is None:
                continue
            if (rooted + tuple(chain[1:]))[-1] == "axis_index":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
        return out

    @staticmethod
    def _locally_bound(fn) -> Set[str]:
        a = fn.args
        bound = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
        if isinstance(fn, ast.Lambda):
            return bound
        for n in ast.walk(fn):
            if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                bound.add(n.id)
        return bound

    def check_custom_vjp_closures(self):
        """GLC007: a custom_vjp primal or vjp rule reads, as a free
        variable, a name its enclosing scope bound from jax.lax.axis_index.
        Inside a shard_map region that index is a per-shard traced value;
        closing over it bakes it into the rule's closure, where the legacy
        shard_map transpose replays it wrong (the PR-8 tp ring hazard).
        Recompute axis_index inside the rule instead."""
        if "GLC007" not in self.rules:
            return
        # vjp-rule surface: f.defvjp(fwd, bwd) args, f = jax.custom_vjp(g)
        # operands, and @jax.custom_vjp-decorated primals
        vjp_names: Set[str] = set()
        vjp_lambdas: Set[int] = set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Attribute) and node.func.attr == "defvjp":
                    for a in node.args:
                        if isinstance(a, ast.Name):
                            vjp_names.add(a.id)
                        elif isinstance(a, ast.Lambda):
                            vjp_lambdas.add(id(a))
                else:
                    chain = _attr_chain(node.func)
                    if (chain and chain[-1] == "custom_vjp"
                            and self.aliases.jax.get(chain[0])
                            and node.args and isinstance(node.args[0], ast.Name)):
                        vjp_names.add(node.args[0].id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    target = dec.func if isinstance(dec, ast.Call) else dec
                    chain = _attr_chain(target)
                    if (chain and chain[-1] == "custom_vjp"
                            and self.aliases.jax.get(chain[0])):
                        vjp_names.add(node.name)
        if not vjp_names and not vjp_lambdas:
            return
        for scope in ast.walk(self.tree):
            if not isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            idx_names = self._axis_index_names(scope)
            if not idx_names:
                continue
            for nested in ast.walk(scope):
                if nested is scope:
                    continue
                is_vjp = (
                    isinstance(nested, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and nested.name in vjp_names
                ) or (isinstance(nested, ast.Lambda) and id(nested) in vjp_lambdas)
                if not is_vjp:
                    continue
                local = self._locally_bound(nested)
                for n in ast.walk(nested):
                    if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                            and n.id in idx_names and n.id not in local):
                        fname = getattr(nested, "name", "<lambda>")
                        self.diags.append(D.make(
                            "GLC007", "custom_vjp rule %r closes over %r, "
                            "bound from jax.lax.axis_index in the enclosing "
                            "scope: inside a shard_map region that index is "
                            "a per-shard traced value and the legacy "
                            "shard_map transpose replays the closure with "
                            "the wrong shard's value; recompute "
                            "jax.lax.axis_index inside the rule"
                            % (fname, n.id),
                            file=self.filename, line=n.lineno, key=n.id,
                        ))
                        break  # one finding per rule function

    # ---- pragmas -------------------------------------------------------
    def apply_pragmas(self) -> List[D.Diagnostic]:
        out = []
        for d in self.diags:
            if d.line is not None and 1 <= d.line <= len(self.lines):
                m = _PRAGMA_RE.search(self.lines[d.line - 1])
                if m and d.code in {c.strip() for c in m.group(1).split(",")}:
                    continue
            out.append(d)
        return out


ALL_RULES = frozenset(
    {"GLC001", "GLC002", "GLC003", "GLC004", "GLC005", "GLC006", "GLC007"})

# GLC006 scope: the runtime/observability library layers (posix or windows
# separators); CLI drivers, analysis tools and tests are exempt by path
_GLC006_PATH_RE = re.compile(r"(^|[/\\])galvatron_tpu[/\\](runtime|obs)[/\\]")


def lint_source(
    src: str,
    filename: str = "<string>",
    resolver: Optional[JaxResolver] = None,
    rules: Optional[Iterable[str]] = None,
) -> List[D.Diagnostic]:
    resolver = resolver or JaxResolver()
    rules = set(rules) if rules is not None else set(ALL_RULES)
    try:
        ml = _ModuleLint(src, filename, resolver, rules)
    except SyntaxError as e:
        return [D.make("GLC001", "file does not parse: %s" % e,
                       file=filename, line=e.lineno, severity=D.ERROR)]
    ml.scan_module()
    ml.check_attribute_chains()
    ml.check_jit_bodies()
    ml.check_donated_reuse()
    ml.check_host_syncs_in_loops()
    ml.check_runtime_logging()
    ml.check_custom_vjp_closures()
    return ml.apply_pragmas()


def iter_python_files(paths: Iterable[str]) -> List[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            for dirpath, dirnames, filenames in os.walk(p):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                out.extend(
                    os.path.join(dirpath, f) for f in filenames if f.endswith(".py")
                )
        elif p.endswith(".py"):
            out.append(p)
    return sorted(out)


def lint_paths(
    paths: Iterable[str],
    rules: Optional[Iterable[str]] = None,
) -> D.DiagnosticReport:
    report = D.DiagnosticReport()
    resolver = JaxResolver()
    for f in iter_python_files(paths):
        with open(f, "r", encoding="utf-8") as fp:
            src = fp.read()
        report.extend(lint_source(src, filename=f, resolver=resolver, rules=rules))
    return report
