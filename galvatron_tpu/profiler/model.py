"""Model profiler: per-layer time/memory via layer differencing.

TPU-native replacement for the reference ModelProfiler
(galvatron/core/profiler/model_profiler.py:14-1051). The reference launches
the model's own train_dist as subprocesses with varied layer counts via
`os.system` (:181-299) and post-processes the JSONs those runs write; here the
same layer-differencing methodology (:328-372) runs IN-PROCESS:

    per-layer quantity = (Q(layernum_max) - Q(layernum_min))
                         / (layernum_max - layernum_min) / batch_size

- time: jitted forward over an n-layer stack, walltimed with
  `block_until_ready` (the CUDA-event timing of runtime_profiler.py:189-300
  has no TPU analogue; dispatch overhead cancels in the difference);
- memory: XLA's compiled `memory_analysis()` (argument/output/temp bytes) of
  the forward+backward program — exact compiler-reported HBM, not a runtime
  sample, so it needs no accelerator to be present.

Per-tp activation entries: the tp=1 (and remat) numbers are MEASURED; tp=k
entries are act/k because under Megatron-SP every saved activation is
seq-sharded across the tp group (a measured identity on TPU, where no
unsharded LayerNorm copies exist — the reason the reference must measure
per-tp is its partially-replicated SP activations). The vocab ("other")
tables divide by vtp the same way.

Multi-layer-type models plug in by subclassing: `T5ModelProfiler` overrides
the stack builders so encoder (layertype_0) and decoder (layertype_1) are
differenced separately (reference profiles swin/t5 per layer list,
model_profiler.py:71-75); every profile_mode works for every subclass.

Outputs match search/engine.py:set_model_profiles:
  computation_profiling_*.json {"layertype_%d": ms|[m,c], "other_time": ms}
  memory_profiling_*.json      {"layertype_%d": {"parameter_size": MB,
     "tp_activation_per_bsz_dict": {tp: MB, "checkpoint": MB}},
     "other_memory_pp_off"/"other_memory_pp_on": {...}}
"""

from __future__ import annotations

import dataclasses
import os
import time
from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.utils.jsonio import write_json_config

MB = 2.0**20


@dataclass
class ModelProfileArgs:
    """Reference galvatron_profile_args (core/profiler/arguments.py:1-86)."""

    profile_type: str = "computation"  # computation | memory
    profile_mode: str = "static"  # static | batch | sequence
    profile_batch_size: int = 8
    profile_min_batch_size: int = 1
    profile_max_batch_size: int = 8
    batch_size_step: int = 1
    profile_seq_length: Optional[int] = None  # default: cfg.max_seq_len
    profile_min_seq_length: int = 512
    profile_max_seq_length: int = 2048
    seq_length_step: int = 512
    layernum_min: int = 1
    layernum_max: int = 3
    warmup: int = 2
    iters: int = 5
    max_tp_deg: int = 8
    mixed_precision: str = "bf16"
    config_dir: str = "configs"
    # measure the per-remat-policy backward recompute fraction (strategy
    # field remat_policy; TimeCostModel.remat_frac) — 4 extra grad-program
    # compiles per layer type, so opt-in for quick profile runs
    profile_remat: bool = False


def _tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _walltime(fn, args, warmup, iters) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))  # galv-lint: ignore[GLC005] -- profilers measure BY syncing
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))  # galv-lint: ignore[GLC005] -- profilers measure BY syncing
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts))


def _compiled_peak_bytes(fn, args) -> float:
    """Compiler-reported working set of one jitted call: temps + outputs
    (+ arguments are counted by the caller where relevant)."""
    stats = jax.jit(fn).lower(*args).compile().memory_analysis()
    if stats is None:
        return 0.0
    return float(stats.temp_size_in_bytes + stats.output_size_in_bytes)


class ModelProfiler:
    """Profiles one model family. One instance covers every layer type of the
    family (`layer_types`); subclasses override the `_stack_t` /
    `_layer_param_bytes` / `_full_model` hooks."""

    layer_types = 1

    def __init__(self, cfg, model_name: str = "model",
                 args: Optional[ModelProfileArgs] = None):
        self._check_config(cfg)
        self.cfg = cfg
        self.model_name = model_name
        self.args = args or ModelProfileArgs()

    def _check_config(self, cfg):
        if not isinstance(cfg, TransformerConfig):
            raise TypeError(
                "ModelProfiler profiles TransformerConfig families; t5 uses "
                "T5ModelProfiler (two layer types, reference "
                "model_profiler.py:71-75)"
            )

    @property
    def _dtype(self):
        return jnp.bfloat16 if self.args.mixed_precision == "bf16" else jnp.float32

    @property
    def _target_seq(self) -> int:
        return self.args.profile_seq_length or self.cfg.max_seq_len

    def _file_tag(self) -> str:
        c = self.cfg
        return "%s_hidden%d_head%d_seqlen%d" % (
            self.args.mixed_precision, c.hidden_size, c.num_heads, self._target_seq
        )

    # ------------------------------------------------- overridable primitives
    def _stack_t(self, t: int, n: int, bsz: int, seq: int, remat: bool = False):
        """Jitted forward over an n-layer stack of layer type `t` (no
        embed/head): returns (fwd, layers, extra_args_tuple)."""
        cfg = dataclasses.replace(self.cfg, num_layers=max(n, 1))
        keys = jax.random.split(jax.random.PRNGKey(0), max(n, 1))
        layers = [M.init_layer_params(k, cfg) for k in keys[:n]]
        x = jax.random.normal(jax.random.PRNGKey(1), (bsz, seq, cfg.hidden_size), self._dtype)
        positions = jnp.broadcast_to(jnp.arange(seq), (bsz, seq))

        def fwd(layers, x):
            body = partial(M.layer_forward, cfg=cfg)
            for lp in layers:
                f = jax.checkpoint(body) if remat else body
                x = f(lp, x, positions)
            return jnp.sum(x.astype(jnp.float32))

        return fwd, layers, (x,)

    def _layer_param_bytes(self, t: int) -> int:
        return _tree_bytes(M.init_layer_params(jax.random.PRNGKey(0), self.cfg))

    def _full_model(self, n_layers: int, bsz: int, seq: int):
        """(loss_fn, params, batch) for the whole tiny model — used for the
        'other' (embed/head/loss) time and memory tables."""
        cfg = dataclasses.replace(
            self.cfg, num_layers=max(n_layers, 1), max_seq_len=max(seq, self.cfg.max_seq_len)
        )
        params = M.init_model_params(jax.random.PRNGKey(0), cfg)
        params["layers"] = params["layers"][:n_layers]
        if cfg.input_type == "patches":
            batch = {
                "pixels": jax.random.normal(
                    jax.random.PRNGKey(1), (bsz, cfg.image_size, cfg.image_size, cfg.num_channels)
                ),
                "labels": jax.random.randint(jax.random.PRNGKey(2), (bsz,), 0, max(cfg.num_classes, 1)),
            }
            loss = lambda p, b: M.classification_loss_fn(p, b, cfg)
        else:
            tokens = jax.random.randint(jax.random.PRNGKey(1), (bsz, seq), 0, cfg.vocab_size)
            batch = {
                "tokens": tokens,
                "positions": jnp.broadcast_to(jnp.arange(seq), (bsz, seq)),
                "labels": jnp.roll(tokens, -1, 1),
            }
            loss = lambda p, b: M.lm_loss_fn(p, b, cfg)
        return loss, params, batch

    def _other_model_state_tables(self, bsz: int, seq: int, tps: Sequence[int]):
        """(embed_mb, head_mb, rest_mb, act_total_mb) for the 'other' tables."""
        loss, params, batch = self._full_model(0, bsz, seq)
        embed_mb = _tree_bytes(params["embed"]) / MB
        if getattr(self.cfg, "head_type", "lm") in ("lm", "mlm") and self.cfg.tie_embeddings:
            head_mb = embed_mb + _tree_bytes(params.get("head", {})) / MB
        else:
            head_mb = (_tree_bytes(params.get("lm_head", {})) + _tree_bytes(params.get("head", {}))) / MB
        rest_mb = _tree_bytes(params.get("final_norm", {})) / MB
        act_total = _compiled_peak_bytes(lambda p, b: jax.grad(loss)(p, b), (params, batch))
        act_total = max(act_total - 2 * _tree_bytes(params), 1024.0) / MB
        return embed_mb, head_mb, rest_mb, act_total

    # ----------------------------------------------------- shared differencing
    def _fwd_ms(self, t: int, bsz: int, seq: int) -> float:
        a = self.args
        lo, hi = a.layernum_min, a.layernum_max
        f_lo, l_lo, xs = self._stack_t(t, lo, bsz, seq)
        t_lo = _walltime(jax.jit(f_lo), (l_lo,) + xs, a.warmup, a.iters)
        f_hi, l_hi, xs = self._stack_t(t, hi, bsz, seq)
        t_hi = _walltime(jax.jit(f_hi), (l_hi,) + xs, a.warmup, a.iters)
        return max((t_hi - t_lo) / (hi - lo) / bsz * 1e3, 1e-6)

    def _act_bytes(self, t: int, bsz: int, seq: int, remat: bool) -> float:
        """Layer-differenced fwd+bwd working set per layer per sample."""
        a = self.args
        lo, hi = a.layernum_min, a.layernum_max

        def grad_prog(n):
            fwd, layers, xs = self._stack_t(t, n, bsz, seq, remat=remat)
            return (lambda layers, *xs: jax.grad(fwd)(layers, *xs)), (layers,) + xs

        g_lo, args_lo = grad_prog(lo)
        g_hi, args_hi = grad_prog(hi)
        b_lo = _compiled_peak_bytes(g_lo, args_lo)
        b_hi = _compiled_peak_bytes(g_hi, args_hi)
        # subtract the grad outputs (they equal the extra layers' param bytes
        # and are model-state, not activation, memory)
        extra_params = _tree_bytes(args_hi[0]) - _tree_bytes(args_lo[0])
        per_layer = (b_hi - b_lo - 2 * extra_params) / (hi - lo)
        return max(per_layer / bsz, 1024.0)

    def _act_bytes_tp(self, t: int, bsz: int, seq: int, k: int,
                      kind: str = "tp") -> Optional[float]:
        """MEASURED per-device activation bytes per layer per sample at
        degree k of one strategy `kind` — "tp" (megatron-sp), "ulysses", or
        "cp" (zigzag ring): compile the layer-stack gradient over a k-device
        mesh with the runtime's own shardings and difference the compiled
        per-device peaks. Replaces the act(1)/k derivation — attention under
        megatron-sp gathers full-sequence tensors whose footprint does NOT
        divide by k, ulysses' all-to-all and the ring's blockwise state have
        their own footprints (the reference measures per-strategy for the
        same reason, model_profiler.py:374-559). Returns None when fewer
        than k local devices exist (single-chip profiling falls back to the
        derivation)."""
        if k <= 1 or len(jax.devices()) < k:
            return None

        from galvatron_tpu.config.strategy import HybridParallelConfig
        from galvatron_tpu.parallel.mesh import build_mesh

        a = self.args
        lo, hi = a.layernum_min, a.layernum_max

        degrees = {"tp": dict(tp=k), "ulysses": dict(tp=k, sp=1), "cp": dict(cp=k)}[kind]

        def grad_prog(n):
            hp = HybridParallelConfig.uniform(k, max(n, 1), global_bsz=bsz, **degrees)
            mesh = build_mesh(hp, jax.devices()[:k])
            built = self._sharded_stack_t(t, n, bsz, seq, hp, mesh, kind)
            if built is None:
                return None
            fwd, layers, xs = built
            # per-device bytes of the grad outputs, from the actual shardings
            shard_bytes = sum(
                leaf.nbytes // max(len(leaf.sharding.device_set), 1)
                for lp in layers for leaf in jax.tree.leaves(lp)
            )
            return (lambda ls, *xx: jax.grad(fwd)(ls, *xx)), (layers,) + tuple(xs), shard_bytes

        try:
            built_lo, built_hi = grad_prog(lo), grad_prog(hi)
            if built_lo is None or built_hi is None:
                return None
            g_lo, args_lo, p_lo = built_lo
            g_hi, args_hi, p_hi = built_hi
            b_lo = _compiled_peak_bytes(g_lo, args_lo)
            b_hi = _compiled_peak_bytes(g_hi, args_hi)
        except Exception:
            # strategy not measurable on this model/mesh (e.g. heads not
            # divisible by the ulysses degree): fall back to the derivation
            return None
        per_layer = (b_hi - b_lo - 2 * (p_hi - p_lo)) / (hi - lo)
        return max(per_layer / bsz, 1024.0)

    def _sharded_stack_t(self, t: int, n: int, bsz: int, seq: int, hp, mesh,
                         kind: str):
        """Family hook for the per-strategy measurement: an n-layer stack of
        layer type `t` with params device_put in the runtime's own shardings
        under hp's per-layer axes, and a forward applying the same activation
        constraints. Returns (fwd, layers, xs) or None when this family
        cannot realise the strategy."""
        from jax.sharding import PartitionSpec as P

        from galvatron_tpu.models.base import layer_param_specs
        from galvatron_tpu.parallel import spec as S
        from galvatron_tpu.parallel.mesh import layer_axes

        if not isinstance(self.cfg, TransformerConfig):
            return None
        cfg = dataclasses.replace(self.cfg, num_layers=max(n, 1))
        keys = jax.random.split(jax.random.PRNGKey(0), max(n, 1))
        layers = [M.init_layer_params(kk, cfg) for kk in keys[:n]]
        axes = [layer_axes(hp, j) for j in range(n)]
        layers = [
            jax.device_put(lp, jax.tree.map(
                lambda sp: S.named(mesh, sp), layer_param_specs(cfg, ax),
                is_leaf=lambda v: isinstance(v, P),
            ))
            for lp, ax in zip(layers, axes)
        ]
        x = jax.random.normal(jax.random.PRNGKey(1), (bsz, seq, cfg.hidden_size), self._dtype)
        positions = jnp.broadcast_to(jnp.arange(seq), (bsz, seq))

        def fwd(layers, x):
            for j, lp in enumerate(layers):
                ax = axes[j]
                x = S.constrain(x, mesh, S.act_spec(ax))
                x = M.layer_forward(lp, x, positions, cfg, mesh=mesh, axes=ax)
            return jnp.sum(x.astype(jnp.float32))

        return fwd, layers, (x,)

    def _grad_ms(self, t: int, bsz: int, seq: int, policy: Optional[str]) -> float:
        """Per-layer fwd+bwd walltime (layer-differenced), with the stack
        wrapped in jax.checkpoint under `policy` when given. Whole-stack
        wrapping yields the same per-layer recompute toll as per-layer
        wrapping — every layer's forward replays exactly once either way —
        and reuses the family's _stack_t hook unchanged."""
        from galvatron_tpu.models.base import _remat

        a = self.args
        lo, hi = a.layernum_min, a.layernum_max

        def grad_prog(n):
            fwd, layers, xs = self._stack_t(t, n, bsz, seq)
            f = _remat(fwd, policy) if policy and policy != "none" else fwd
            return (lambda ls, *xx: jax.grad(f)(ls, *xx)), (layers,) + tuple(xs)

        g_lo, args_lo = grad_prog(lo)
        g_hi, args_hi = grad_prog(hi)
        t_lo = _walltime(jax.jit(g_lo), args_lo, a.warmup, a.iters)
        t_hi = _walltime(jax.jit(g_hi), args_hi, a.warmup, a.iters)
        return max((t_hi - t_lo) / (hi - lo) * 1e3, 1e-9)

    def profile_remat(self, t: int = 0) -> Dict[str, float]:
        """Measured backward recompute toll per remat policy, as a fraction
        of the forward (TimeCostModel.remat_frac's profiled override):
        frac(policy) = (grad_ms(policy) - grad_ms(no-remat)) / fwd_ms,
        layer-differenced like every other table. Clamped to [0, 1.5] so
        timer noise can never feed the search a negative (or absurd)
        recompute price."""
        a = self.args
        seq = self._target_seq
        bsz = a.profile_batch_size
        fwd_ms = self._fwd_ms(t, bsz, seq) * bsz  # un-normalise to per-layer ms
        base = self._grad_ms(t, bsz, seq, None)
        out: Dict[str, float] = {"none": 0.0}
        for pol in ("full", "nothing_saveable", "dots_saveable"):
            frac = (self._grad_ms(t, bsz, seq, pol) - base) / max(fwd_ms, 1e-9)
            out[pol] = round(float(min(max(frac, 0.0), 1.5)), 4)
        # a policy that pins MORE tensors can never owe more recompute than
        # full remat; enforce against timer noise on tiny profile models
        out["dots_saveable"] = min(out["dots_saveable"], out["full"])
        return out

    def _other_ms_per_sample(self, bsz: int, seq: int, per_layer_ms_sum: float) -> float:
        """Embedding + head + loss time: full tiny model minus its layers'
        share (reference separates this as 'other_time')."""
        a = self.args
        loss, params, batch = self._full_model(a.layernum_min, bsz, seq)
        t = _walltime(jax.jit(loss), (params, batch), a.warmup, a.iters)
        return max(t / bsz * 1e3 - a.layernum_min * per_layer_ms_sum, 1e-6)

    # ------------------------------------------------------------ computation
    def profile_computation(self) -> Dict:
        """time_config for the search engine, every layer type. profile_mode:
        - static: one scalar at (profile_batch_size, seq);
        - batch: linear fit [m, c] of per-layer total ms vs batch size
          (reference fits with scipy at search time, search_engine.py:119-163
          — here the fit happens at profile time, same curve);
        - sequence: quadratic sweep over seq; stored under "seqlen%d" keys plus
          the fit evaluated at the target seq as the headline scalar."""
        a = self.args
        seq = self._target_seq
        out: Dict = {}
        headline = []  # per-type scalar at the target point, for other_time
        for t in range(self.layer_types):
            key = "layertype_%d" % t
            if a.profile_mode == "batch":
                bszs = list(range(a.profile_min_batch_size, a.profile_max_batch_size + 1, a.batch_size_step))
                totals = [self._fwd_ms(t, b, seq) * b for b in bszs]
                m, c = np.polyfit(np.asarray(bszs, np.float64), np.asarray(totals, np.float64), 1)
                # time is monotone in batch; clamp fit noise so a noisy sweep
                # can never feed the search a negative marginal cost
                out[key] = [float(max(m, 0.0)), float(max(c, 0.0))]
                headline.append(totals[-1] / bszs[-1])
            elif a.profile_mode == "sequence":
                seqs = list(range(a.profile_min_seq_length, a.profile_max_seq_length + 1, a.seq_length_step))
                per_seq = {s: self._fwd_ms(t, a.profile_batch_size, s) for s in seqs}
                for s, v in per_seq.items():
                    out["%s_seqlen%d" % (key, s)] = v
                coef = np.polyfit(np.asarray(seqs, np.float64), np.asarray(list(per_seq.values())), 2)
                out["%s_seq_popt" % key] = [float(v) for v in coef]
                out[key] = float(np.polyval(coef, seq))
                headline.append(out[key])
            else:
                out[key] = self._fwd_ms(t, a.profile_batch_size, seq)
                headline.append(out[key])
        bsz_for_other = a.profile_max_batch_size if a.profile_mode == "batch" else a.profile_batch_size
        out["other_time"] = self._other_ms_per_sample(bsz_for_other, seq, sum(headline))
        if a.profile_remat:
            # per-policy backward recompute fractions, consumed by
            # TimeCostModel via ProfileModelArgs.remat_recompute_frac
            out["remat_recompute_frac"] = self.profile_remat()
        return out

    # ----------------------------------------------------------------- memory
    def profile_memory(self) -> Dict:
        a = self.args
        seq = self._target_seq
        bsz = a.profile_batch_size
        tps = []
        t = 1
        while t <= a.max_tp_deg:
            tps.append(t)
            t *= 2
        out: Dict = {}
        for lt in range(self.layer_types):
            param_mb = self._layer_param_bytes(lt) / MB
            act1 = self._act_bytes(lt, bsz, seq, remat=False) / MB
            act_ckpt = self._act_bytes(lt, bsz, seq, remat=True) / MB
            # tp>1 entries are MEASURED on a k-device mesh when the machine
            # has one (tests, multi-chip); a single-chip profile falls back to
            # the act(1)/k derivation
            tp_act = {}
            for k in tps:
                measured = self._act_bytes_tp(lt, bsz, seq, k) if k > 1 else None
                tp_act[k] = round(measured / MB if measured else act1 / k, 3)
                if k > 1:
                    # per-strategy rows (ulysses all-to-all / ring blockwise
                    # footprints differ from act/k); written only when
                    # measured — the cost model falls back to the derivation
                    m_u = self._act_bytes_tp(lt, bsz, seq, k, kind="ulysses")
                    if m_u:
                        tp_act["ulysses_%d" % k] = round(m_u / MB, 3)
                    m_c = self._act_bytes_tp(lt, bsz, seq, k, kind="cp")
                    if m_c:
                        tp_act["cp_%d" % k] = round(m_c / MB, 3)
            tp_act["checkpoint"] = round(min(act_ckpt, act1), 3)
            out["layertype_%d" % lt] = {
                "parameter_size": round(param_mb, 3),
                "tp_activation_per_bsz_dict": tp_act,
            }
        embed_mb, head_mb, rest_mb, act_total = self._other_model_state_tables(bsz, seq, tps)

        def per_tp(x):
            return {k: round(x / k, 3) for k in tps}

        # model_states = 4x params (param+grad+adam moments, fp32 master), the
        # same convention MemoryCostModel applies to layer parameter_size
        out["other_memory_pp_off"] = {
            "model_states": per_tp(4 * (embed_mb + head_mb + rest_mb)),
            "activation": {k: round(act_total / bsz / k, 3) for k in tps},
        }
        out["other_memory_pp_on"] = {
            "first_stage": {
                "model_states": per_tp(4 * embed_mb),
                "activation": {k: round(0.5 * act_total / bsz / k, 3) for k in tps},
            },
            "last_stage": {
                "model_states": per_tp(4 * (head_mb + rest_mb)),
                "activation": {k: round(0.5 * act_total / bsz / k, 3) for k in tps},
            },
        }
        return out

    # ------------------------------------------------------------------- files
    def config_paths(self) -> Dict[str, str]:
        tag = self._file_tag()
        return {
            "computation": os.path.join(
                self.args.config_dir, "computation_profiling_%s_%s.json" % (tag, self.model_name)
            ),
            "memory": os.path.join(
                self.args.config_dir, "memory_profiling_%s_%s.json" % (tag, self.model_name)
            ),
        }

    def profile_all(self, write: bool = True) -> Dict[str, Dict]:
        results = {
            "computation": self.profile_computation(),
            "memory": self.profile_memory(),
        }
        if write:
            os.makedirs(self.args.config_dir, exist_ok=True)
            paths = self.config_paths()
            for k, v in results.items():
                write_json_config(v, paths[k])
        return results


class T5ModelProfiler(ModelProfiler):
    """Two-layer-type profiler for T5 (layertype_0 = encoder, layertype_1 =
    decoder; search consumes them via the multi-layer-type DP,
    dynamic_programming.py:170-189). The decoder stack is differenced against
    a FIXED encoder output so the cross-attention cost lands in the decoder
    layer type. Every profile_mode of the base class works here."""

    layer_types = 2

    def _check_config(self, cfg):
        from galvatron_tpu.models.t5 import T5Config

        if not isinstance(cfg, T5Config):
            raise TypeError("T5ModelProfiler needs a T5Config")

    def _stack_t(self, t: int, n: int, bsz: int, seq: int, remat: bool = False):
        from galvatron_tpu.models import t5 as T

        cfg = dataclasses.replace(self.cfg, compute_dtype=self._dtype)
        keys = jax.random.split(jax.random.PRNGKey(0), max(n, 1))
        x = jax.random.normal(jax.random.PRNGKey(1), (bsz, seq, cfg.hidden_size), self._dtype)
        table = jax.random.normal(
            jax.random.PRNGKey(2), (cfg.rel_buckets, cfg.num_heads), jnp.float32
        ) * 0.02
        if t == 0:
            layers = [T.init_enc_layer(k, cfg) for k in keys[:n]]
            bias = T.rel_bias(table, seq, seq, cfg, bidirectional=True)
            body = lambda lp, x: T.enc_layer_forward(lp, x, cfg, bias)
            extra = (x,)

            def fwd(layers, x):
                for lp in layers:
                    f = jax.checkpoint(body) if remat else body
                    x = f(lp, x)
                return jnp.sum(x.astype(jnp.float32))

            return fwd, layers, extra
        layers = [T.init_dec_layer(k, cfg) for k in keys[:n]]
        bias = T.rel_bias(table, seq, seq, cfg, bidirectional=False)
        enc_out = jax.random.normal(jax.random.PRNGKey(3), (bsz, seq, cfg.hidden_size), self._dtype)
        body = lambda lp, x: T.dec_layer_forward(lp, x, enc_out, cfg, bias)

        def fwd(layers, x):
            for lp in layers:
                f = jax.checkpoint(body) if remat else body
                x = f(lp, x)
            return jnp.sum(x.astype(jnp.float32))

        return fwd, layers, (x,)

    def _sharded_stack_t(self, t: int, n: int, bsz: int, seq: int, hp, mesh,
                         kind: str):
        """Per-strategy measurement for the enc/dec layer types (the
        decoder's fixed encoder memory replicates across the mesh). Ring cp
        needs a zigzag-permuted bias layout the profiler does not model;
        fall back to the derivation for it."""
        if kind == "cp":
            return None
        from jax.sharding import PartitionSpec as P

        from galvatron_tpu.models import t5 as T
        from galvatron_tpu.parallel import spec as S
        from galvatron_tpu.parallel.mesh import layer_axes

        cfg = dataclasses.replace(self.cfg, compute_dtype=self._dtype)
        keys = jax.random.split(jax.random.PRNGKey(0), max(n, 1))
        x = jax.random.normal(jax.random.PRNGKey(1), (bsz, seq, cfg.hidden_size), self._dtype)
        table = jax.random.normal(
            jax.random.PRNGKey(2), (cfg.rel_buckets, cfg.num_heads), jnp.float32
        ) * 0.02
        axes = [layer_axes(hp, j) for j in range(n)]
        init = T.init_enc_layer if t == 0 else T.init_dec_layer
        specs = T.enc_layer_specs if t == 0 else T.dec_layer_specs
        layers = [
            jax.device_put(init(kk, cfg), jax.tree.map(
                lambda sp: S.named(mesh, sp), specs(cfg, ax),
                is_leaf=lambda v: isinstance(v, P),
            ))
            for kk, ax in zip(keys[:n], axes)
        ]
        bias = T.rel_bias(table, seq, seq, cfg, bidirectional=(t == 0))
        if t == 0:
            def fwd(layers, x):
                for j, lp in enumerate(layers):
                    ax = axes[j]
                    x = S.constrain(x, mesh, S.act_spec(ax))
                    x = T.enc_layer_forward(lp, x, cfg, bias, mesh=mesh, axes=ax)
                return jnp.sum(x.astype(jnp.float32))

            return fwd, layers, (x,)
        enc_out = jax.random.normal(
            jax.random.PRNGKey(3), (bsz, seq, cfg.hidden_size), self._dtype
        )

        def fwd(layers, x):
            for j, lp in enumerate(layers):
                ax = axes[j]
                x = S.constrain(x, mesh, S.act_spec(ax))
                x = T.dec_layer_forward(lp, x, enc_out, cfg, bias, mesh=mesh, axes=ax)
            return jnp.sum(x.astype(jnp.float32))

        return fwd, layers, (x,)

    def _layer_param_bytes(self, t: int) -> int:
        from galvatron_tpu.models import t5 as T

        init = T.init_enc_layer if t == 0 else T.init_dec_layer
        return _tree_bytes(init(jax.random.PRNGKey(0), self.cfg))

    def _full_model(self, n_layers: int, bsz: int, seq: int):
        from galvatron_tpu.models import t5 as T

        cfg = dataclasses.replace(
            self.cfg, num_enc_layers=n_layers, num_dec_layers=n_layers,
            compute_dtype=self._dtype,
        )
        params = T.init_t5_params(jax.random.PRNGKey(0), cfg)
        enc = jax.random.randint(jax.random.PRNGKey(1), (bsz, seq), 0, cfg.vocab_size)
        dec = jax.random.randint(jax.random.PRNGKey(2), (bsz, seq), 0, cfg.vocab_size)
        batch = {"tokens": enc, "dec_tokens": dec, "labels": dec}
        return (lambda p, b: T.t5_loss_fn(p, b, cfg)), params, batch

    def _other_model_state_tables(self, bsz: int, seq: int, tps: Sequence[int]):
        loss, params, batch = self._full_model(0, bsz, seq)
        embed_mb = _tree_bytes(params["embed"]) / MB
        rest_mb = (_tree_bytes(params) - _tree_bytes(params["embed"])) / MB
        head_mb = embed_mb if self.cfg.tie_embeddings else _tree_bytes(params.get("lm_head", {})) / MB
        act_total = _compiled_peak_bytes(lambda p, b: jax.grad(loss)(p, b), (params, batch))
        act_total = max(act_total - 2 * _tree_bytes(params), 1024.0) / MB
        return embed_mb, head_mb, rest_mb, act_total


class SwinModelProfiler(ModelProfiler):
    """Per-stage layer types for swin (reference `layernum_listed` profiling,
    model_profiler.py:71-75, with per-stage seqlens :96-100): layertype_s is
    stage s's block at its own resolution/width. Block differencing runs on
    (B, res, res, C) activations; shifted blocks alternate as in the model."""

    def _check_config(self, cfg):
        from galvatron_tpu.models.swin import SwinConfig

        if not isinstance(cfg, SwinConfig):
            raise TypeError("SwinModelProfiler needs a SwinConfig")

    @property
    def _target_seq(self) -> int:
        # each stage has its own resolution; the headline seq is the stage-0
        # patch-grid token count
        return self.args.profile_seq_length or self.cfg.stage_resolution(0) ** 2

    def _file_tag(self) -> str:
        c = self.cfg
        return "%s_hidden%d_head%d_seqlen%d" % (
            self.args.mixed_precision, c.embed_dim, c.num_heads[0], self._target_seq
        )

    @property
    def layer_types(self):  # type: ignore[override]
        return self.cfg.num_stages

    def _sharded_stack_t(self, t: int, n: int, bsz: int, seq: int, hp, mesh,
                         kind: str):
        """Per-strategy measurement for swin blocks. Only tp applies (window
        attention has no sequence dim to shard: cp/ulysses fall back)."""
        if kind != "tp":
            return None
        from jax.sharding import PartitionSpec as P

        from galvatron_tpu.models import swin as W
        from galvatron_tpu.parallel import spec as S
        from galvatron_tpu.parallel.mesh import layer_axes

        cfg = dataclasses.replace(self.cfg, compute_dtype=self._dtype)
        if cfg.num_heads[t] % max(hp.layers[0].tp, 1) != 0:
            return None
        res = cfg.stage_resolution(t)
        keys = jax.random.split(jax.random.PRNGKey(0), max(n, 1))
        axes = [layer_axes(hp, j) for j in range(n)]
        layers = [
            jax.device_put(W.init_block_params(kk, cfg, t), jax.tree.map(
                lambda sp: S.named(mesh, sp), W.block_param_specs(cfg, t, ax),
                is_leaf=lambda v: isinstance(v, P),
            ))
            for kk, ax in zip(keys[:n], axes)
        ]
        x = jax.random.normal(
            jax.random.PRNGKey(1), (bsz, res, res, cfg.stage_dim(t)), self._dtype
        )

        def fwd(layers, x):
            for j, lp in enumerate(layers):
                x = W.block_forward(
                    lp, x, cfg=cfg, stage=t, shift=(j % 2 == 1),
                    mesh=mesh, axes=axes[j],
                )
            return jnp.sum(x.astype(jnp.float32))

        return fwd, layers, (x,)

    def _stack_t(self, t: int, n: int, bsz: int, seq: int, remat: bool = False):
        # `seq` is ignored: each stage has a fixed resolution from the config
        from galvatron_tpu.models import swin as W

        cfg = dataclasses.replace(self.cfg, compute_dtype=self._dtype)
        res = cfg.stage_resolution(t)
        keys = jax.random.split(jax.random.PRNGKey(0), max(n, 1))
        layers = [W.init_block_params(k, cfg, t) for k in keys[:n]]
        x = jax.random.normal(
            jax.random.PRNGKey(1), (bsz, res, res, cfg.stage_dim(t)), self._dtype
        )

        def fwd(layers, x):
            for j, lp in enumerate(layers):
                body = partial(W.block_forward, cfg=cfg, stage=t, shift=(j % 2 == 1))
                f = jax.checkpoint(body) if remat else body
                x = f(lp, x)
            return jnp.sum(x.astype(jnp.float32))

        return fwd, layers, (x,)

    def _layer_param_bytes(self, t: int) -> int:
        from galvatron_tpu.models import swin as W

        return _tree_bytes(W.init_block_params(jax.random.PRNGKey(0), self.cfg, t))

    def _full_model(self, n_layers: int, bsz: int, seq: int):
        from galvatron_tpu.models import swin as W

        cfg = dataclasses.replace(
            self.cfg,
            depths=tuple(max(n_layers, 1) for _ in self.cfg.depths),
            compute_dtype=self._dtype,
        )
        params = W.init_swin_params(jax.random.PRNGKey(0), cfg)
        if n_layers == 0:
            params["blocks"] = []
            cfg = dataclasses.replace(cfg, depths=tuple(0 for _ in self.cfg.depths))
        batch = {
            "pixels": jax.random.normal(
                jax.random.PRNGKey(1), (bsz, cfg.image_size, cfg.image_size, cfg.num_channels)
            ),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (bsz,), 0, max(cfg.num_classes, 1)),
        }
        return (lambda p, b: W.swin_loss_fn(p, b, cfg)), params, batch

    def _other_model_state_tables(self, bsz: int, seq: int, tps: Sequence[int]):
        loss, params, batch = self._full_model(0, bsz, seq)
        embed_mb = _tree_bytes(params["embed"]) / MB
        head_mb = _tree_bytes(params["head"]) / MB
        rest_mb = (_tree_bytes(params["merges"]) + _tree_bytes(params["final_norm"])) / MB
        act_total = _compiled_peak_bytes(lambda p, b: jax.grad(loss)(p, b), (params, batch))
        act_total = max(act_total - 2 * _tree_bytes(params), 1024.0) / MB
        return embed_mb, head_mb, rest_mb, act_total

