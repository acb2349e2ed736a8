"""What a profiler trace of a training run is named by: the scopes inside the
compiled step, the host loop's spans, and the one control that starts and
stops `jax.profiler` in a running process.

**Scopes** (`jax.named_scope`, device side). Trace-time metadata: a scope
changes the `op_name` of the HLO instructions traced under it and nothing
the chip executes. The transforms wrap the name, so the label alone tells a
scope's forward (`jvp(gt.layers.r0)`), its backward
(`transpose(jvp(gt.layers.r0))`) and its recomputation
(`.../checkpoint/rematted_computation/...`) apart. The names are constants
defined here and nowhere else; `benchmarks/layer_metrics/` reads them by
regex from the trace.

**Spans** (`TraceControl.span`, host side). While a trace runs a span is a
`jax.profiler.TraceAnnotation` on the host plane of the same `.xplane.pb` as
the device ops: one clock, so a gap on the device can be laid against the
phase the host was in. While a telemetry sink is installed a span is timed
(`.ms`), which is how `data_wait_ms` reaches the `step` event. With neither,
`span()` returns the shared do-nothing `OFF` after one attribute read, unless
the caller asks for a `timed` one: the phases of a launch (obs/launch.py),
which are recorded always.

**TraceControl**. One object a training run (`args.trace_control`, made by
`cli/train._train` if absent). Any host code of the process that holds the
run may `request()` a trace of some steps, at any time and more than once,
one trace at a time; the loop tells the control when a step is about to be
dispatched and when one has drained, and the profiler starts when the first
requested step is dispatched and stops when the last has drained.
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

import jax

from galvatron_tpu.obs import telemetry

# ------------------------------------------------------------------ scopes
EMBED = "gt.embed"  # embed_tokens / embed_patches: gather, positions
LAYERS = "gt.layers.r%d"  # run k of config/strategy.layer_runs(hp)
HEAD_LOSS = "gt.head_loss"  # final norm, logits, cross entropy
OPTIMIZER = "gt.optimizer"  # tx.update, apply_updates, global_norm
GUARD = "gt.guard"  # the anomaly guard's and the SDC vote's keep-old selects
GRAD_ACCUM = "gt.grad_accum"  # the microbatch loop's weighting and adds
PARAM_GATHER = "gt.param_gather"  # ZeRO-2's compute-dtype copy of its dp-split parameters: cast, all-gather
# the parts of a routed-experts block (ops/moe.py), inside gt.layers.r<k>
MOE_ROUTER = "gt.moe.router"  # float32 logits, softmax, top-k, the auxiliary terms
MOE_DISPATCH = "gt.moe.dispatch"  # sort the assignments by expert, gather the rows
MOE_EXPERTS = "gt.moe.experts"  # the grouped matmuls and SwiGLU
MOE_GMM_IN = "gmm_in"  # inside MOE_EXPERTS: rows x (hidden, 2 x width), gate and up
MOE_GMM_OUT = "gmm_out"  # inside MOE_EXPERTS: rows x (width, hidden)
# back into token order as k slabs of (tokens, hidden) (k-major: a TPU tiles an
# array's two minor dimensions by 8 x 128, so k stays out of them), their
# weighted sum; backward, the cotangent gathered into expert order and weighted
MOE_COMBINE = "gt.moe.combine"
MOE_SHARED = "gt.moe.shared"  # the shared expert(s): a dense SwiGLU beside the routed ones
# latent attention (models/parts/attention.latent_qkv_projection), inside gt.layers.r<k>:
# the low-rank projections, their norms, rope and the output projection,
# everything of the attention half but the attention call itself
ATTN_LATENT = "gt.attn.latent"
# the same for every other softmax layer (models/parts/attention.attention_mixer without
# latent attention): the q, k, v projection, the split-off gate, the heads'
# norms, rope, the gate's product and the output projection, so that a softmax
# mixer is this scope plus the attention call
ATTN_PROJ = "gt.attn.proj"
# a softmax layer over a window (models/parts/window.window_mixer: the attention
# part's code on the window layers' own heads and rope), inside gt.layers.r<k>,
# in two disjoint scopes that add up to the mixer, as the linear mixer's: the
# band (`ops/attention.core_attention(window=)`: the two band kernels, their
# operands' transposes and the sums of the backward's shares of dk and dv; off a
# TPU the band mask on XLA's logits) and everything else of it (projections,
# rope, the gate, the output projection), so that a model's full and window
# layers read apart. No name begins another
ATTN_WINDOW = "gt.attn.window"
ATTN_WINDOW_BAND = "gt.attn.band"
# the dense MLP half, around its call in models/parts/mlp._dense_forward and NOT
# inside dense_mlp, which the shared expert calls under MOE_SHARED: an op
# carries ONE scope nested in its layer run's, so the parts add up. A run's
# norms, residual adds, layout constraints and what the scan does with the
# stacked parameters carry none: they are the run's self time
MLP = "gt.mlp"
# a gated-DeltaNet linear-attention mixer (models/parts/linear.linear_mixer), inside
# gt.layers.r<k>, in two disjoint scopes that add up to the mixer: the core
# (ops/linear_attention.gated_delta_rule: the chunks' solves, the carried
# state, the outputs; forward, recomputed and backward) and everything else
# of it (projections, the convolution, gates, the gated norm, the output
# projection)
ATTN_DELTA = "gt.attn.delta"
ATTN_LINEAR = "gt.attn.linear"
# a Mamba-2 state-space mixer (models/parts/ssm.ssm_mixer), inside gt.layers.r<k>,
# in two disjoint scopes that add up to the mixer, as the linear mixer's: the
# scan (ops/ssd.ssd_scan: the chunks' masks and products, the carried state;
# forward, recomputed and backward) and everything else of it (the
# projections, the convolution and its bias, dt, the gated norm)
ATTN_SSD = "gt.attn.ssd"
ATTN_SSM = "gt.attn.ssm"
# a Kimi-Delta-Attention mixer (models/parts/kda.kda_mixer), inside gt.layers.r<k>,
# in two disjoint scopes that add up to the mixer, as the linear mixer's: the
# core (ops/linear_attention.kda_rule: the chunks' decayed products and
# solves, the carried state, the outputs; forward, recomputed and backward)
# and everything else of it (the projections, the convolution, the two
# low-rank gates, the gated norm). Neither name begins the other
ATTN_KDA_RULE = "gt.attn.kda_rule"
ATTN_KDA = "gt.attn.kda_mixer"
# a gated short-convolution mixer (models/parts/conv.conv_mixer), inside
# gt.layers.r<k>, in two disjoint scopes that add up to the mixer, as the
# state-space mixer's: the two projections' matmuls (hidden -> [B | C | u],
# channels -> hidden), and the memory-bound pass between them (B * u, the
# taps, C * v; forward, recomputed and backward). Neither name begins the other
ATTN_CONV_PROJ = "gt.attn.shortconv"
ATTN_CONV_GATE = "gt.attn.conv_gate"
# a Mamba-1 mixer (models/parts/mamba.mamba_mixer), inside gt.layers.r<k>, in two
# disjoint scopes that add up to the mixer, as the state-space mixer's: the
# selective scan (ops/selective_scan.selective_scan: the chunks' sums, the carried
# states, the positions' loop; forward, recomputed and backward) and everything
# else of it (the projections, the convolution and its bias, dt, the gate)
ATTN_SELSCAN = "gt.attn.selscan"
ATTN_MAMBA = "gt.attn.mamba"
# a gated memory unit (models/parts/mamba.gmu_mixer): its two matmuls and the gate on
# ANOTHER layer's scan output
ATTN_GMU = "gt.attn.gmu"
# differential attention's own arithmetic around the attention calls (models/parts/
# attention.diff_attention): the heads' pairing and padding, lambda, the subtraction,
# the sub-norm and its factor; the projections stay under gt.attn.proj / gt.attn.window
# / gt.attn.cross and the calls where they were (the flash kernels by name, gt.attn.band)
ATTN_DIFF = "gt.attn.diff"
# a cross layer's q and output projections (models/parts/cross.cross_mixer): it has no
# keys or values of its own
ATTN_CROSS = "gt.attn.cross"
# an EVA attention mixer (models/parts/eva.eva_mixer), inside gt.layers.r<k>, in three disjoint
# scopes that add up to the mixer: the pooling of each chunk's keys and values (ops/eva_attention.pooled,
# forward and backward), the aggregation (ops/eva_attention.aggregate: the two kernels, or XLA's windows;
# forward, recomputed and backward) and everything else of it (the projections, rope, `wo`). The
# first name begins the other two, which go on with `_` and a letter: where no reader's pattern ends
# a name (benchmarks/scopes.END), and one word to the readers that name a run's parts by
# `gt.<word>.<word>` (ISSUE 61 spelled them `gt.attn.eva.prep`, `.agg`: a third dotted word is one
# those readers cut off, so the parts would not add up). NOT gt.attn.core: the benchmark prices what runs under that as whole causal attention
ATTN_EVA = "gt.attn.eva"
ATTN_EVA_PREP = "gt.attn.eva_prep"
ATTN_EVA_AGG = "gt.attn.eva_agg"
# the multi-token-prediction module, top level: its norms, the (2h, h)
# projection and its block; its pass through the head and its cross entropy
# run under HEAD_LOSS, beside the main ones
MTP = "gt.mtp"


def layers_scope(run_index: int) -> str:
    """Scope of the k-th layer run: the same k as the `layer_run` telemetry
    event and `obs/attribution.predict_layer_runs`."""
    return LAYERS % run_index


# ------------------------------------------------------------------- spans
NEXT_BATCH = "gt/next_batch"
DISPATCH = "gt/dispatch"  # a StepTraceAnnotation: carries step_num
DRAIN = "gt/drain"
ON_STEP = "gt/on_step"
EVAL = "gt/eval"
SAVE = "gt/save"
COMPILE = "gt/compile"
# the phases of a launch (obs/launch.Launch; cli/train._train marks them):
# consecutive from _train's entry to the first step's drain, each a key of the
# summary's `launch_ms` and of the `launch` telemetry event
LAUNCH_PLAN = "gt/launch/plan"  # the cache's path, model and strategy, the strategy lint, FLOPs, predictions
# the model, the optimizer, --trace_lint, the tp-overlap and quantised-
# collective probes of an observed run, the step function
LAUNCH_BUILD = "gt/launch/build"
# init_params and init_opt_state: host time, their compilations or cache reads
# in it; what the device still owes falls into the first run
LAUNCH_INIT_STATE = "gt/launch/init_state"
LAUNCH_RESTORE = "gt/launch/restore"  # load_checkpoint, under --load alone
LAUNCH_DATA = "gt/launch/data"  # the iterator, the prefetcher's start, the wait for the first batch
# the four children of gt/compile: trace_ms is the first two, compile_ms the last two
COMPILE_TRACE = "gt/compile/trace"  # step_fn.trace(): the jaxpr
COMPILE_LOWER = "gt/compile/lower"  # .lower(): MLIR, every Pallas body's Mosaic lowering in it
COMPILE_KEY = "gt/compile/key"  # the memo's key: as_text() of the whole module and its sha256
COMPILE_LOAD = "gt/compile/load"  # the persistent cache's read on a hit, XLA on a miss; ~0 on a memo hit
# the end of gt/compile to the first step's drain: the program's load, the
# first execution, the dispatches and on_step calls of the steps sent behind it
LAUNCH_FIRST_RUN = "gt/launch/first_run"


class _Off:
    """The span that does nothing; `ms` is None."""

    ms = None

    def __enter__(self):
        return self

    def __exit__(self, *exc_info):
        return False


OFF = _Off()


class _Span:
    """A timed span, and an annotation on the profiler's host plane while a
    trace runs."""

    def __init__(self, name: str, annotate: bool, step_num: Optional[int]):
        self.ms: Optional[float] = None
        self.t0: Optional[float] = None  # its start on time.perf_counter
        self._annotation = None
        if annotate:
            self._annotation = (
                jax.profiler.TraceAnnotation(name) if step_num is None
                else jax.profiler.StepTraceAnnotation(name, step_num=step_num))

    def __enter__(self):
        if self._annotation is not None:
            self._annotation.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc_info):
        self.ms = (time.perf_counter() - self.t0) * 1e3
        if self._annotation is not None:
            self._annotation.__exit__(*exc_info)
        return False


class TraceControl:
    """Starts and stops `jax.profiler` around requested steps of a running
    training loop."""

    def __init__(self):
        self._pending: Optional[Tuple[str, int, int]] = None
        self._running: Optional[Tuple[str, int, int]] = None
        # whether span() does anything; refreshed once an iteration
        self.spans_on = False

    # ------------------------------------------------------------- callers
    def request(self, directory: str, first_step: int, last_step: int) -> bool:
        """Ask for a trace of steps `first_step`..`last_step` (inclusive)
        into `directory`. Callable from `on_step` or any host code in the
        process, at any time. False, and nothing changes, while another
        request is pending or running: one trace at a time. A request for
        steps already dispatched starts at the next dispatch."""
        if self._pending is not None or self._running is not None:
            return False
        if last_step < first_step:
            raise ValueError("trace request for steps %d:%d" % (first_step, last_step))
        self._pending = (str(directory), int(first_step), int(last_step))
        return True

    def span(self, name: str, step_num: Optional[int] = None, timed: bool = False):
        """A context around one phase of the host loop (module docstring)."""
        if not (self.spans_on or timed):
            return OFF
        return _Span(name, self._running is not None, step_num)

    # ------------------------------------------------------------ the loop
    def before_dispatch(self, iteration: int) -> None:
        """The loop is about to fetch and dispatch step `iteration`."""
        if self._pending is not None and iteration >= self._pending[1]:
            request, self._pending = self._pending, None
            directory, first, last = request
            try:
                jax.profiler.start_trace(directory)
            except Exception as e:  # a backend that cannot trace carries on
                telemetry.emit("trace", action="error", error=str(e))
                telemetry.runtime_log("xla trace skipped (%s): %s" % (type(e).__name__, e))
            else:
                self._running = request
                telemetry.emit("trace", action="start", dir=directory,
                               first_step=first, last_step=last)
        self.spans_on = self._running is not None or telemetry.active_sink() is not None

    def after_drain(self, iteration: int) -> None:
        """Step `iteration` has drained: its device work is in the trace."""
        if self._running is not None and iteration >= self._running[2]:
            self._stop()

    def close(self) -> None:
        """The run ends: a running trace stops, a pending request is dropped."""
        self._pending = None
        self._stop()
        self.spans_on = False

    def _stop(self) -> None:
        if self._running is None:
            return
        directory, self._running = self._running[0], None
        try:
            jax.profiler.stop_trace()
            telemetry.emit("trace", action="stop", dir=directory)
        except Exception as e:
            telemetry.emit("trace", action="error", error=str(e))
            telemetry.runtime_log("xla trace stop failed (%s): %s" % (type(e).__name__, e))
