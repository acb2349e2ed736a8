"""Traced-program lint: jaxpr-level sharding/collective auditor (``GLT***``).

The strategy linter (GLS, analysis/strategy_lint.py) sees the plan and the
code linter (GLC, analysis/code_lint.py) sees the source AST — but every
miscompile in this repo's history lived in the *traced* program, between the
two: the jax-0.4.37 GSPMD partitioner silently corrupting a reshape of a
sharded dim inside a scan (models/base.stack_layer_run), the unconstrained
microbatch split feeding the pipeline tick scan (parallel/pipeline.
make_pipelined_loss), and the fused stacked init under pp ``out_shardings``
(runtime/model_api.HybridParallelModel.init_params). This module abstract-
evals the SAME train-step the driver jits (no compile, no device transfers —
`jax.make_jaxpr` over ShapeDtypeStructs) and walks the ClosedJaxpr with a
sharding-propagation pass:

- a per-variable partition spec environment is seeded from every
  ``sharding_constraint`` eqn and from pjit ``in_/out_shardings``, and
  propagated through shape-preserving ops, transposes, broadcasts and 1:1
  reshapes;
- **GLT001** fires on a reshape that splits or merges an explicitly sharded
  dim inside a `scan` (or `while`) body — the stack_layer_run miscompile
  class;
- **GLT002** taints the output of any sharded-dim-splitting reshape and
  fires when the tainted value reaches a `scan` without an intervening
  ``sharding_constraint`` — the make_pipelined_loss class (the shipped
  ``split()`` constrains immediately, clearing the taint);
- **GLT003** fires on a pjit whose ``out_shardings`` shard dim *d* of an
  output produced by a stack (concatenate of size-1-along-*d* pieces) along
  that same dim — the init_params class;
- **GLT004** warns when a donated input has no same-shape/dtype output to
  alias (donation cannot buy anything and the caller may still hold the
  buffer);
- **GLT005** fires on the PR-8 hazard shape: a shard_map body containing a
  ``custom_vjp`` whose closure captured a traced ``axis_index`` from the
  enclosing scope — under `jax.grad` the capture surfaces as a *dangling*
  ``axis_index`` eqn (all outputs DropVars) next to the
  ``custom_vjp_call_jaxpr``;
- **GLT006** warns on psum-of-psum over the same axis inside a manual region
  (the cotangent double-count shape — autodiff already psums a cotangent
  over the manual axes its primal is invariant over, see
  parallel/tp_shard_map.py).

The collective audit (GLT101/GLT102) extracts every explicit collective
(psum/ppermute/all_gather/reduce_scatter/all_to_all) with its wire bytes
(from avals, multiplied by enclosing scan trip counts) and cross-checks the
result against ``TimeCostModel``'s per-LayerRun predicted comm
(obs/attribution.predict_layer_runs): a strategy that prices manual TP
collectives whose trace contains none is drift the online autotuner would
otherwise only discover after burning steps. GSPMD-mode collectives are
compiler-inserted *after* partitioning and are invisible at trace level;
the audit says so (GLT102) instead of pretending coverage.

Eqn ``source_info`` is mapped to file:line via the user-frame filter, so
findings point at model code, not jax internals. Everything here is
CPU-only and allocation-free: `jax.make_jaxpr` + `jax.eval_shape` over the
same path ``cli/train.py`` traces.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from galvatron_tpu.analysis import diagnostics as D

# Per-variable sharding knowledge: a tuple with one entry per array dim —
# `()` = known replicated on that dim, `("m0", ...)` = known sharded over
# those mesh axes, `None` = unknown. A variable absent from the environment
# is wholly unknown (treated as safe: the detectors only ever fire on
# *explicitly constrained* shardings, never on guesses).
DimSpec = Optional[Tuple[str, ...]]
Spec = Tuple[DimSpec, ...]

# psum_invariant / all_gather_invariant: what psum / all_gather trace to under
# shard_map's varying-axes typing when the result is invariant over the axes
_COLLECTIVES = ("psum", "psum_invariant", "ppermute", "all_gather",
                "all_gather_invariant", "reduce_scatter", "all_to_all",
                "pmax", "pmin")
_PSUMS = ("psum", "psum_invariant")

# single-output ops through which a value keeps its shape and layout intent
_SHAPE_PRESERVING = frozenset({
    "add", "sub", "mul", "div", "max", "min", "pow", "rem", "atan2",
    "and", "or", "xor", "not", "shift_left", "shift_right_logical",
    "shift_right_arithmetic", "nextafter",
    "exp", "exp2", "log", "log1p", "expm1", "tanh", "logistic", "sin",
    "cos", "tan", "asin", "acos", "atan", "sinh", "cosh", "sqrt", "rsqrt",
    "cbrt", "neg", "sign", "abs", "floor", "ceil", "round", "erf",
    "erfc", "erf_inv", "square", "integer_pow", "is_finite", "real",
    "imag", "conj", "clamp", "select_n", "convert_element_type",
    "stop_gradient", "copy", "reduce_precision", "eq", "ne", "lt", "le",
    "gt", "ge", "pvary",
})


def _is_literal(v) -> bool:
    return hasattr(v, "val")


def _is_dropvar(v) -> bool:
    return type(v).__name__ == "DropVar"


def _is_jaxpr_like(v) -> bool:
    return hasattr(v, "eqns") or hasattr(v, "jaxpr")


def _open(j):
    """Jaxpr from a Jaxpr-or-ClosedJaxpr param value."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _src(eqn) -> Tuple[Optional[str], Optional[int]]:
    """eqn source_info -> (user file, line), skipping jax-internal frames."""
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info.traceback)
        if frame is not None:
            return frame.file_name, frame.start_line
    except Exception:
        pass
    return None, None


def _spec_of_sharding(sh, ndim: int) -> Optional[Spec]:
    """NamedSharding -> Spec; UnspecifiedValue/AUTO/None -> None (unknown).
    A constraint makes EVERY dim known: unmentioned dims are `()`."""
    pspec = getattr(sh, "spec", None)
    if pspec is None:
        return None
    entries = tuple(pspec)
    out: List[DimSpec] = []
    for i in range(ndim):
        e = entries[i] if i < len(entries) else None
        if e is None:
            out.append(())
        elif isinstance(e, (tuple, list)):
            out.append(tuple(str(a) for a in e))
        else:
            out.append((str(e),))
    return tuple(out)


def _sharded_axes(spec: Optional[Spec], dim: int) -> Tuple[str, ...]:
    if spec is None or dim >= len(spec) or spec[dim] is None:
        return ()
    return spec[dim]


def _reshape_blocks(in_shape, out_shape):
    """Greedy minimal equal-product blocks mapping input dims to output dims.
    Returns [(in_dims, out_dims), ...] or None when the shapes contain a zero
    (degenerate; nothing to check)."""
    if 0 in in_shape or 0 in out_shape:
        return None
    blocks = []
    i = j = 0
    while i < len(in_shape) or j < len(out_shape):
        ig, og = [], []
        pi = pj = 1
        if i < len(in_shape):
            pi *= in_shape[i]
            ig.append(i)
            i += 1
        if j < len(out_shape):
            pj *= out_shape[j]
            og.append(j)
            j += 1
        while pi != pj:
            if pi < pj and i < len(in_shape):
                pi *= in_shape[i]
                ig.append(i)
                i += 1
            elif pj < pi and j < len(out_shape):
                pj *= out_shape[j]
                og.append(j)
                j += 1
            else:  # ragged tail (cannot happen for equal-size reshapes)
                return None
        blocks.append((ig, og))
    return blocks


@dataclass
class _Taint:
    """A sharded-dim-splitting reshape whose output has not been re-
    constrained yet (the GLT002 precondition)."""

    file: Optional[str]
    line: Optional[int]
    axes: Tuple[str, ...]


@dataclass
class _Ctx:
    in_loop: int = 0  # scan/while body nesting depth
    trip: int = 1  # product of enclosing known scan lengths
    in_shard_map: bool = False
    manual_axes: Tuple[str, ...] = ()


class _State:
    def __init__(self):
        self.report = D.DiagnosticReport()
        self.collectives: List[Dict[str, Any]] = []
        self._seen = set()

    def emit(self, code: str, message: str, eqn, **kw) -> None:
        f, line = _src(eqn)
        key = (code, f, line)
        if key in self._seen:  # fwd + transposed bwd trace the same site
            return
        self._seen.add(key)
        self.report.add(D.make(code, message, file=f, line=line, **kw))


def _axes_of_collective(eqn) -> Tuple[str, ...]:
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list, frozenset, set)):
        return tuple(str(a) for a in axes)
    return (str(axes),)


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * getattr(dtype, "itemsize", 1)


def _map_env(outer_env, outer_taint, outer_vars, inner_vars):
    env: Dict[Any, Spec] = {}
    tnt: Dict[Any, _Taint] = {}
    for o, iv in zip(outer_vars, inner_vars):
        if _is_literal(o):
            continue
        if o in outer_env:
            env[iv] = outer_env[o]
        if o in outer_taint:
            tnt[iv] = outer_taint[o]
    return env, tnt


def _map_back(env, taint, inner_env, inner_taint, inner_outs, outer_outs):
    for bv, ov in zip(inner_outs, outer_outs):
        if _is_dropvar(ov) or _is_literal(bv):
            continue
        if bv in inner_env:
            env[ov] = inner_env[bv]
        if bv in inner_taint:
            taint[ov] = inner_taint[bv]


# --------------------------------------------------------------- the walker
def _walk(jaxpr, env, taint, ctx: _Ctx, st: _State) -> None:
    produced = {}
    for eqn in jaxpr.eqns:
        for ov in eqn.outvars:
            if not _is_dropvar(ov):
                produced[ov] = eqn

    if ctx.in_shard_map:
        _check_dangling_axis_index(jaxpr, st)

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "sharding_constraint":
            _do_constraint(eqn, env, taint)
        elif prim == "reshape":
            _do_reshape(eqn, env, taint, ctx, st)
        elif prim == "transpose":
            _do_transpose(eqn, env, taint)
        elif prim == "squeeze":
            _do_squeeze(eqn, env, taint)
        elif prim == "broadcast_in_dim":
            _do_broadcast(eqn, env)
        elif prim == "optimization_barrier":
            for iv, ov in zip(eqn.invars, eqn.outvars):
                if _is_literal(iv) or _is_dropvar(ov):
                    continue
                if iv in env:
                    env[ov] = env[iv]
                if iv in taint:
                    taint[ov] = taint[iv]
        elif prim == "jit":
            _do_pjit(eqn, env, taint, ctx, st)
        elif prim == "scan":
            _do_scan(eqn, env, taint, ctx, st)
        elif prim == "while":
            _do_while(eqn, ctx, st)
        elif prim == "cond":
            _do_cond(eqn, env, taint, ctx, st)
        elif prim == "shard_map":
            _do_shard_map(eqn, ctx, st)
        elif prim in ("custom_vjp_call_jaxpr", "custom_vjp_call",
                      "custom_jvp_call", "custom_jvp_call_jaxpr"):
            _do_custom_call(eqn, env, taint, ctx, st)
        elif prim in ("remat", "remat2", "checkpoint", "closed_call",
                      "core_call", "xla_call"):
            _do_inline_call(eqn, env, taint, ctx, st)
        elif prim in _COLLECTIVES:
            _do_collective(eqn, produced, ctx, st)
        elif prim in _SHAPE_PRESERVING:
            _do_elementwise(eqn, env, taint)
        else:
            # unknown container primitives still get walked (collectives and
            # constraint seeds inside must not go dark), with a fresh env
            for val in eqn.params.values():
                for j in (val if isinstance(val, (tuple, list)) else (val,)):
                    if _is_jaxpr_like(j):
                        _walk(_open(j), {}, {}, ctx, st)


def _do_constraint(eqn, env, taint) -> None:
    ov = eqn.outvars[0]
    spec = _spec_of_sharding(eqn.params.get("sharding"), len(ov.aval.shape))
    if spec is not None:
        env[ov] = spec
    # the constrained RESULT is clean; other consumers of the unconstrained
    # input stay tainted
    taint.pop(ov, None)


def _do_reshape(eqn, env, taint, ctx: _Ctx, st: _State) -> None:
    iv, ov = eqn.invars[0], eqn.outvars[0]
    in_shape = tuple(iv.aval.shape)
    out_shape = tuple(ov.aval.shape)
    spec = None if _is_literal(iv) else env.get(iv)
    if eqn.params.get("dimensions") is not None:
        # reshape fused with a permutation: too rare to model — spec unknown
        return
    blocks = _reshape_blocks(in_shape, out_shape)
    if blocks is None:
        return
    out_spec: List[DimSpec] = [None] * len(out_shape)
    hazard_axes: Tuple[str, ...] = ()
    for ig, og in blocks:
        nt_in = [d for d in ig if in_shape[d] != 1]
        nt_out = [d for d in og if out_shape[d] != 1]
        if len(nt_in) <= 1 and len(nt_out) <= 1:
            # 1:1 modulo size-1 dims: carry the spec across
            carried: DimSpec = ()
            if nt_in and spec is not None and nt_in[0] < len(spec):
                carried = spec[nt_in[0]]
            for d in og:
                out_spec[d] = () if out_shape[d] == 1 else carried
        else:
            # genuine split/merge block: hazardous iff an input dim in the
            # block is EXPLICITLY sharded
            for d in nt_in:
                ax = _sharded_axes(spec, d)
                if ax:
                    hazard_axes = hazard_axes + ax
    if hazard_axes:
        f, line = _src(eqn)
        if ctx.in_loop > 0:
            st.emit(
                "GLT001",
                "reshape %s -> %s splits/merges a dim sharded over %s inside "
                "a scan body — the jax-0.4.37 GSPMD partitioner miscompiles "
                "this shape (the stack_layer_run class); stack with jnp.stack "
                "or constrain to a replicated layout first"
                % (in_shape, out_shape, sorted(set(hazard_axes))),
                eqn,
            )
        else:
            taint[ov] = _Taint(file=f, line=line,
                               axes=tuple(sorted(set(hazard_axes))))
        return
    if not _is_literal(iv) and iv in taint:
        taint[ov] = taint[iv]
    if all(e is not None for e in out_spec):
        env[ov] = tuple(out_spec)


def _do_transpose(eqn, env, taint) -> None:
    iv, ov = eqn.invars[0], eqn.outvars[0]
    if _is_literal(iv):
        return
    if iv in taint:
        taint[ov] = taint[iv]
    spec = env.get(iv)
    if spec is None:
        return
    perm = eqn.params.get("permutation")
    if perm is None or len(perm) != len(spec):
        return
    env[ov] = tuple(spec[p] for p in perm)


def _do_squeeze(eqn, env, taint) -> None:
    iv, ov = eqn.invars[0], eqn.outvars[0]
    if _is_literal(iv):
        return
    if iv in taint:
        taint[ov] = taint[iv]
    spec = env.get(iv)
    if spec is None:
        return
    dims = set(eqn.params.get("dimensions") or ())
    env[ov] = tuple(s for d, s in enumerate(spec) if d not in dims)


def _do_broadcast(eqn, env) -> None:
    iv, ov = eqn.invars[0], eqn.outvars[0]
    if _is_literal(iv):
        return
    spec = env.get(iv)
    if spec is None:
        return
    bdims = eqn.params.get("broadcast_dimensions") or ()
    out_spec: List[DimSpec] = [()] * len(ov.aval.shape)
    for pos, bd in enumerate(bdims):
        if pos < len(iv.aval.shape) and iv.aval.shape[pos] == ov.aval.shape[bd]:
            out_spec[bd] = spec[pos] if pos < len(spec) else None
    if all(e is not None for e in out_spec):
        env[ov] = tuple(out_spec)


def _do_elementwise(eqn, env, taint) -> None:
    if len(eqn.outvars) != 1:
        return
    ov = eqn.outvars[0]
    if _is_dropvar(ov):
        return
    shape = tuple(getattr(ov.aval, "shape", ()))
    for iv in eqn.invars:
        if _is_literal(iv) or tuple(getattr(iv.aval, "shape", ())) != shape:
            continue
        if ov not in env and iv in env:
            env[ov] = env[iv]
        if ov not in taint and iv in taint:
            taint[ov] = taint[iv]


def _do_pjit(eqn, env, taint, ctx: _Ctx, st: _State) -> None:
    closed = eqn.params["jaxpr"]
    body = _open(closed)
    _check_stacked_init(eqn, body, st)
    _check_donation(eqn, st)
    env2, tnt2 = _map_env(env, taint, eqn.invars, body.invars)
    for sh, iv in zip(eqn.params.get("in_shardings") or (), body.invars):
        spec = _spec_of_sharding(sh, len(getattr(iv.aval, "shape", ())))
        if spec is not None:
            env2[iv] = spec
    _walk(body, env2, tnt2, ctx, st)
    _map_back(env, taint, env2, tnt2, body.outvars, eqn.outvars)
    for sh, ov in zip(eqn.params.get("out_shardings") or (), eqn.outvars):
        if _is_dropvar(ov):
            continue
        spec = _spec_of_sharding(sh, len(getattr(ov.aval, "shape", ())))
        if spec is not None:
            env[ov] = spec
            taint.pop(ov, None)  # an output constraint IS a constraint


def _check_stacked_init(eqn, body, st: _State) -> None:
    """GLT003: pjit output = stack (concatenate of size-1 pieces) along a dim
    its out_shardings shard — the init_params miscompile class."""
    out_sh = eqn.params.get("out_shardings") or ()
    if not out_sh:
        return
    produced = {}
    for e in body.eqns:
        for ov in e.outvars:
            if not _is_dropvar(ov):
                produced[ov] = e
    for sh, bv in zip(out_sh, body.outvars):
        if _is_literal(bv):
            continue
        spec = _spec_of_sharding(sh, len(getattr(bv.aval, "shape", ())))
        if spec is None:
            continue
        src_eqn = produced.get(bv)
        hops = 0
        while (src_eqn is not None and hops < 8
               and src_eqn.primitive.name in ("convert_element_type", "copy",
                                              "sharding_constraint")):
            nxt = src_eqn.invars[0]
            src_eqn = None if _is_literal(nxt) else produced.get(nxt)
            hops += 1
        if src_eqn is None or src_eqn.primitive.name != "concatenate":
            continue
        d = src_eqn.params.get("dimension", 0)
        if not _sharded_axes(spec, d):
            continue
        piece_sizes = [getattr(iv.aval, "shape", (0,))[d]
                       for iv in src_eqn.invars]
        out_size = bv.aval.shape[d]
        if len(piece_sizes) >= 2 and all(p == 1 for p in piece_sizes) \
                and len(piece_sizes) == out_size:
            st.emit(
                "GLT003",
                "jit output stacks %d pieces along dim %d while out_shardings "
                "shard that dim over %s — the jax-0.4.37 GSPMD partitioner "
                "produces silently wrong stacked entries (the init_params "
                "class); stack outside jit and device_put onto the shardings"
                % (len(piece_sizes), d, sorted(set(spec[d]))),
                src_eqn,
            )


def _check_donation(eqn, st: _State) -> None:
    """GLT004: a donated input whose aval matches no output aval cannot be
    aliased — XLA holds the buffer anyway and the caller loses access."""
    donated = eqn.params.get("donated_invars") or ()
    if not any(donated):
        return
    avail = Counter(
        (tuple(getattr(ov.aval, "shape", ())), str(getattr(ov.aval, "dtype", "")))
        for ov in eqn.outvars if not _is_dropvar(ov)
    )
    for don, iv in zip(donated, eqn.invars):
        if not don:
            continue
        key = (tuple(getattr(iv.aval, "shape", ())),
               str(getattr(iv.aval, "dtype", "")))
        if avail.get(key, 0) > 0:
            avail[key] -= 1
        else:
            st.emit(
                "GLT004",
                "donated input %s%s has no same-shape/dtype output to alias; "
                "the donation buys nothing and the caller's buffer is dead"
                % (key[1], list(key[0])),
                eqn,
            )


def _do_scan(eqn, env, taint, ctx: _Ctx, st: _State) -> None:
    closed = eqn.params["jaxpr"]
    body = _open(closed)
    num_consts = eqn.params.get("num_consts", 0)
    num_carry = eqn.params.get("num_carry", 0)
    length = int(eqn.params.get("length", 1) or 1)
    for iv in eqn.invars:
        if not _is_literal(iv) and iv in taint:
            rec = taint[iv]
            origin = ""
            if rec.file:
                origin = " (reshape at %s:%s)" % (rec.file, rec.line)
            st.emit(
                "GLT002",
                "a reshape that split/merged a dim sharded over %s%s feeds "
                "this scan with no sharding_constraint in between — the "
                "jax-0.4.37 GSPMD partitioner miscompiles the unconstrained "
                "split under the scan (the make_pipelined_loss class); "
                "constrain the reshaped value to an explicit layout first"
                % (list(rec.axes), origin),
                eqn,
            )
    env2: Dict[Any, Spec] = {}
    for k, (o, bv) in enumerate(zip(eqn.invars, body.invars)):
        if _is_literal(o):
            continue
        sp = env.get(o)
        if sp is None:
            continue
        if k >= num_consts + num_carry:
            sp = sp[1:] if len(sp) >= 1 else sp  # xs lose the scan dim
        env2[bv] = sp
    ctx2 = _Ctx(in_loop=ctx.in_loop + 1, trip=ctx.trip * max(length, 1),
                in_shard_map=ctx.in_shard_map, manual_axes=ctx.manual_axes)
    tnt2: Dict[Any, _Taint] = {}
    _walk(body, env2, tnt2, ctx2, st)
    for i in range(min(num_carry, len(eqn.outvars))):
        bv = body.outvars[i]
        ov = eqn.outvars[i]
        if not _is_literal(bv) and not _is_dropvar(ov) and bv in env2:
            env[ov] = env2[bv]


def _do_while(eqn, ctx: _Ctx, st: _State) -> None:
    for key in ("cond_jaxpr", "body_jaxpr"):
        j = eqn.params.get(key)
        if _is_jaxpr_like(j):
            ctx2 = _Ctx(in_loop=ctx.in_loop + 1, trip=ctx.trip,
                        in_shard_map=ctx.in_shard_map,
                        manual_axes=ctx.manual_axes)
            _walk(_open(j), {}, {}, ctx2, st)


def _do_cond(eqn, env, taint, ctx: _Ctx, st: _State) -> None:
    for br in eqn.params.get("branches") or ():
        body = _open(br)
        # operands follow the predicate
        env2, tnt2 = _map_env(env, taint, eqn.invars[1:], body.invars)
        _walk(body, env2, tnt2, ctx, st)


def _do_shard_map(eqn, ctx: _Ctx, st: _State) -> None:
    mesh = eqn.params.get("mesh")
    axis_names = tuple(getattr(mesh, "axis_names", ()) or ())
    manual_set = eqn.params.get("manual_axes") or frozenset()
    manual = tuple(a for a in axis_names if a in manual_set)
    body = _open(eqn.params["jaxpr"])
    ctx2 = _Ctx(in_loop=ctx.in_loop, trip=ctx.trip,
                in_shard_map=True, manual_axes=manual)
    # per-shard block shapes: the outer spec environment does not transfer
    _walk(body, {}, {}, ctx2, st)


def _do_custom_call(eqn, env, taint, ctx: _Ctx, st: _State) -> None:
    body = None
    for key in ("fun_jaxpr", "call_jaxpr", "jaxpr"):
        if _is_jaxpr_like(eqn.params.get(key)):
            body = _open(eqn.params[key])
            break
    if body is None:
        return
    if len(body.invars) == len(eqn.invars):
        env2, tnt2 = _map_env(env, taint, eqn.invars, body.invars)
    else:
        env2, tnt2 = {}, {}
    _walk(body, env2, tnt2, ctx, st)
    _map_back(env, taint, env2, tnt2, body.outvars, eqn.outvars)


def _do_inline_call(eqn, env, taint, ctx: _Ctx, st: _State) -> None:
    body = None
    for key in ("jaxpr", "call_jaxpr"):
        if _is_jaxpr_like(eqn.params.get(key)):
            body = _open(eqn.params[key])
            break
    if body is None:
        return
    if len(body.invars) == len(eqn.invars):
        env2, tnt2 = _map_env(env, taint, eqn.invars, body.invars)
    else:
        env2, tnt2 = {}, {}
    _walk(body, env2, tnt2, ctx, st)
    _map_back(env, taint, env2, tnt2, body.outvars, eqn.outvars)


def _do_collective(eqn, produced, ctx: _Ctx, st: _State) -> None:
    axes = _axes_of_collective(eqn)
    nbytes = sum(_aval_bytes(iv) for iv in eqn.invars)
    f, line = _src(eqn)
    st.collectives.append({
        "prim": eqn.primitive.name,
        "axes": axes,
        "bytes": nbytes * ctx.trip,
        "trip": ctx.trip,
        "manual_axes": ctx.manual_axes,
        "file": f,
        "line": line,
    })
    if eqn.primitive.name in _PSUMS:
        for iv in eqn.invars:
            if _is_literal(iv):
                continue
            src_eqn = produced.get(iv)
            if src_eqn is not None and src_eqn.primitive.name in _PSUMS:
                inner_axes = set(_axes_of_collective(src_eqn))
                if inner_axes & set(axes):
                    st.emit(
                        "GLT006",
                        "psum over %s consumes the result of another psum "
                        "over the same axis in one manual region — with "
                        "autodiff's own cotangent psum over the axes a "
                        "primal is invariant over this is the gradient "
                        "double-count shape (see parallel/tp_shard_map.py "
                        "autodiff note)" % (sorted(inner_axes & set(axes)),),
                        eqn,
                    )


def _check_dangling_axis_index(jaxpr, st: _State) -> None:
    """GLT005: inside a shard_map body, an ``axis_index`` whose every output
    is dropped, next to a custom_vjp call. This is exactly how a custom_vjp
    closure over an enclosing-scope traced axis_index surfaces under grad:
    the captured value rides the closure, the eqn that produced it dangles."""
    has_custom_vjp = any(
        e.primitive.name in ("custom_vjp_call_jaxpr", "custom_vjp_call")
        for e in jaxpr.eqns
    )
    if not has_custom_vjp:
        return
    for e in jaxpr.eqns:
        if e.primitive.name == "axis_index" \
                and e.outvars and all(_is_dropvar(v) for v in e.outvars):
            st.emit(
                "GLT005",
                "custom_vjp in this shard_map body closes over a traced "
                "axis_index computed in the enclosing scope (the dangling "
                "axis_index eqn is the capture); jax 0.4.37 miscompiles the "
                "transposed region — compute axis_index INSIDE the fwd/bwd "
                "functions instead (the tp_shard_map pattern)",
                e,
            )


# ---------------------------------------------------------------- entry API
@dataclass
class TraceLintResult:
    report: D.DiagnosticReport
    collectives: List[Dict[str, Any]] = field(default_factory=list)
    predicted: Optional[List[Dict[str, Any]]] = None

    def render_audit(self) -> str:
        """Human-readable collective-audit table (never printed in --json
        mode: stdout stays one JSON document)."""
        lines = ["traced collectives (bytes include scan trip counts):"]
        if not self.collectives:
            lines.append("  (none — gspmd collectives are compiler-inserted "
                         "after partitioning)")
        grouped: Dict[Tuple, Dict[str, Any]] = {}
        for c in self.collectives:
            key = (c["prim"], c["axes"], c["file"], c["line"])
            g = grouped.setdefault(key, {"count": 0, "bytes": 0})
            g["count"] += 1
            g["bytes"] += c["bytes"]
        for (prim, axes, f, line), g in sorted(
                grouped.items(), key=lambda kv: -kv[1]["bytes"]):
            loc = "%s:%s" % (f, line) if f else "<unknown>"
            lines.append("  %-14s axes=%-12s x%-3d %10d B  %s"
                         % (prim, ",".join(axes) or "-", g["count"],
                            g["bytes"], loc))
        if self.predicted:
            lines.append("cost-model predicted comm per LayerRun:")
            for row in self.predicted:
                if row.get("predicted_comm_ms") is None:
                    continue
                lines.append(
                    "  run %-4s layers %s-%s  %-22s comm %.4g ms"
                    % (row["run"], row.get("start"), row.get("stop"),
                       row.get("strategy"), row["predicted_comm_ms"]))
        return "\n".join(lines)


def abstract_batch(cfg, hp, data_kind: str = "lm") -> Dict[str, Any]:
    """ShapeDtypeStruct batch matching cli/train.py's input pipeline for the
    given family data kind. Only token-stream families are traceable here;
    callers turn the ValueError into a GLT102 skip."""
    import numpy as np

    if data_kind != "lm":
        raise ValueError(
            "trace lint supports token-stream (lm) families only; "
            "data_kind=%r has no abstract batch builder yet" % data_kind)
    bsz = hp.global_bsz
    seq = getattr(cfg, "max_seq_len", 64)
    tok = jax.ShapeDtypeStruct((bsz, seq), np.dtype("int32"))
    return {"tokens": tok, "positions": tok, "labels": tok}


def trace_train_step(model, tx=None, data_kind: str = "lm"):
    """ClosedJaxpr of the exact jitted train step cli/train.py dispatches —
    abstract tracing only: no compile, no buffers."""
    import optax

    tx = tx or optax.adam(1e-3)
    step = model.make_train_step(tx, donate=True)
    params = model.abstract_params()
    opt_state = jax.eval_shape(tx.init, params)
    batch = abstract_batch(model.cfg, model.hp, data_kind)
    return jax.make_jaxpr(step)(params, opt_state, batch)


def trace_init(model):
    """ClosedJaxpr of the init program init_params would run, mirroring its
    branch structure (the pp>1 path stacks OUTSIDE jit — that host-side stack
    is exactly the WA006 workaround, so only the jitted part is traced)."""
    import numpy as np

    rng = jax.ShapeDtypeStruct((2,), np.dtype("uint32"))
    if model.init_fn is None and model.hp.pp > 1:
        from galvatron_tpu.models import base as M

        return jax.make_jaxpr(
            jax.jit(lambda r: M.init_model_params(r, model.cfg)))(rng)
    return jax.make_jaxpr(
        jax.jit(model._init_fn, out_shardings=model.shardings()))(rng)


def _tp_axes(hp) -> set:
    from galvatron_tpu.parallel.mesh import layer_axes

    axes: set = set()
    for i in range(hp.num_layers):
        ax = layer_axes(hp, i)
        if getattr(ax, "tp", None) and not getattr(ax, "ulysses", False):
            axes.update(ax.tp)
    return axes


def _audit(model, result: TraceLintResult, st: _State) -> None:
    """GLT101/GLT102: cross-check traced collectives against the cost
    model's predicted comm. Conservative by design — only clear
    contradictions fire; gspmd-implicit comm is reported as invisible."""
    hp = model.hp
    try:
        from galvatron_tpu.obs.attribution import predict_layer_runs

        result.predicted = predict_layer_runs(model.cfg, hp)
    except Exception as e:  # analytic tables cannot price this family
        result.predicted = None
        st.report.add(D.make(
            "GLT102",
            "collective audit skipped: cost model cannot price this "
            "config (%s)" % e))
        return
    if result.predicted is None:
        st.report.add(D.make(
            "GLT102",
            "collective audit skipped: no analytic/profiled cost tables "
            "for this model family"))
        return
    tp_comm_mode = getattr(hp, "tp_comm_mode", "gspmd")
    tp_axes = _tp_axes(hp)
    traced_tp = [c for c in st.collectives if set(c["axes"]) & tp_axes]
    prices_manual_tp = tp_comm_mode in ("shard_map", "overlap") and any(
        row.get("predicted_comm_ms") for row in result.predicted)
    if prices_manual_tp and not traced_tp:
        st.report.add(D.make(
            "GLT101",
            "cost model prices manual TP collectives (tp_comm_mode=%s, "
            "predicted_comm_ms > 0) but the traced program contains no "
            "collective over the tp mesh axes %s — predicted-vs-traced "
            "drift; the plan and the program disagree"
            % (tp_comm_mode, sorted(tp_axes))))
    wants_quant = any(
        s.grad_comm_dtype != "none" or s.param_comm_dtype != "none"
        for s in hp.layers)
    if wants_quant and model.grad_fn is None and not st.collectives:
        st.report.add(D.make(
            "GLT101",
            "strategy requests quantized grad sync (an explicit shard_map "
            "collective ring) but the traced program contains no "
            "collectives at all — the quantized path was not taken"))
    max_tp = max([s.tp for s in hp.layers] + [1])
    if tp_comm_mode == "gspmd" and max_tp > 1 and not traced_tp:
        st.report.add(D.make(
            "GLT102",
            "tp_comm_mode=gspmd with tp>1: TP collectives are compiler-"
            "inserted after partitioning and invisible at trace level; "
            "the per-run comm audit covers manual regions only"))


def lint_hybrid_model(model, *, data_kind: str = "lm", audit: bool = True,
                      tx=None) -> TraceLintResult:
    """Trace-lint an already-constructed HybridParallelModel: train step +
    init program + (optionally) the collective audit."""
    st = _State()
    result = TraceLintResult(report=st.report)
    try:
        closed = trace_train_step(model, tx=tx, data_kind=data_kind)
    except ValueError as e:
        st.report.add(D.make(
            "GLT102", "train-step trace skipped: %s" % e))
        return result
    _walk(closed.jaxpr, {}, {}, _Ctx(), st)
    try:
        init_closed = trace_init(model)
    except Exception as e:
        st.report.add(D.make(
            "GLT102", "init trace skipped: %s" % e))
    else:
        _walk(init_closed.jaxpr, {}, {}, _Ctx(), st)
    result.collectives = st.collectives
    if audit:
        _audit(model, result, st)
    return result


def lint_model(cfg, hp, devices=None, *, data_kind: str = "lm",
               audit: bool = True, tx=None) -> TraceLintResult:
    """Construct the hybrid-parallel model for (cfg, hp) and trace-lint it —
    the same construction path cli/train.py runs before compiling."""
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    model = construct_hybrid_parallel_model(cfg, hp, devices)
    return lint_hybrid_model(model, data_kind=data_kind, audit=audit, tx=tx)


def lint_closed_jaxpr(closed) -> TraceLintResult:
    """Walk an arbitrary ClosedJaxpr (the golden-repro tests' entry point)."""
    st = _State()
    _walk(closed.jaxpr, {}, {}, _Ctx(), st)
    result = TraceLintResult(report=st.report)
    result.collectives = st.collectives
    return result
