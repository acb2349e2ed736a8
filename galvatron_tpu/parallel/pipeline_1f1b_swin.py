"""1F1B pipeline schedule for hierarchical-resolution models (Swin).

The reference pipelines Swin like any other family — its per-stage layer
lists and per-stage sequence lengths flow through the multi-layer-type DP
(reference model_profiler.py:71-100, dynamic_programming.py:170-189) and the
stage pipeline slices arbitrary `model_ranks` (pipeline.py:110-112). The TPU
schedule (parallel/pipeline_1f1b.py — its divergence-safety invariants all
apply here) requires two things a hierarchical model does not natively give:

- a single static CHANNEL shape between stages: Swin halves the token count
  and doubles the channel dim at each patch merge, so the inter-stage
  activation is carried as a FLAT buffer sized to the largest (stage-0)
  activation, ``(mb, L0 * C0)``; each stage body slices the prefix it needs,
  reshapes to its own (H, W, C), runs its blocks (and any patch merges that
  statically fall inside it), then flattens and zero-pads back. Total
  elements halve at every merge, so the padding never exceeds 2x and the
  buffer is tiny relative to transformer channels;
- uniform per-slot parameter trees for the stacked ``(pp, ...)`` layout:
  block params differ in shape across Swin stages (C, heads, window all
  grow), so each slot holds every leaf padded to the element-wise MAX shape
  over the pipeline stages, and the per-stage body statically slices the
  live region. Sliced-out entries get exactly-zero gradients (the vjp of a
  slice), so any elementwise optimizer leaves the padding at zero. Patch
  merges are slot entries of the block they follow; stages without a merge
  at that slot hold never-referenced zeros.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import PP_AXIS, layer_axes, vocab_axes
from galvatron_tpu.parallel.pipeline_1f1b import build_schedule, use_masked_path

Params = Dict[str, Any]


def validate_swin_config(cfg, hp: HybridParallelConfig) -> None:
    # cp/sp are inapplicable at ANY pp degree (windowed attention has no
    # sequence dimension) — check before the pp early-return
    for s in hp.layers:
        if s.cp > 1 or s.sp:
            raise ValueError(
                "swin windowed attention has no sequence dimension to shard: "
                "cp / ulysses-sp do not apply (strategy %r)" % (s,)
            )
    if hp.pp <= 1:
        return
    div = hp.pp_division
    if len(set(div)) != 1:
        raise ValueError(
            "swin 1F1B requires equal layers per stage, got pp_division=%s" % (div,)
        )


# ------------------------------------------------------------- shape algebra
def _block_dims(cfg, t: int) -> Dict[str, int]:
    c = cfg.stage_dim(t)
    nh = cfg.num_heads[t]
    w = min(cfg.window, cfg.stage_resolution(t))
    return dict(c=c, nh=nh, hd=c // nh, ff=int(c * cfg.mlp_ratio), nb=(2 * w - 1) ** 2)


def _slot_types(cfg, hp: HybridParallelConfig, j: int) -> List[int]:
    """Swin-stage type of slot j's block on each pipeline stage."""
    lps = hp.pp_division[0]
    return [cfg.stage_of_block(s * lps + j) for s in range(hp.pp)]


def _merge_types(cfg, hp: HybridParallelConfig, j: int) -> List[int]:
    """Swin stages whose trailing patch merge falls at slot j (on any stage)."""
    lps = hp.pp_division[0]
    cum = np.cumsum(cfg.depths)
    out = []
    for s in range(hp.pp):
        gi = s * lps + j
        t = cfg.stage_of_block(gi)
        if t < cfg.num_stages - 1 and gi == cum[t] - 1:
            out.append(t)
    return out


def _max_dims(cfg, types) -> Dict[str, int]:
    dims = [_block_dims(cfg, t) for t in types]
    return {k: max(d[k] for d in dims) for k in dims[0]}


def _block_shapes(cfg, d: Dict[str, int]) -> Params:
    c, nh, hd, ff, nb = d["c"], d["nh"], d["hd"], d["ff"], d["nb"]
    shapes: Params = {
        "ln1": {"scale": (c,), "bias": (c,)},
        "ln2": {"scale": (c,), "bias": (c,)},
        "wqkv": {"kernel": (c, 3, nh, hd)},
        "wo": {"kernel": (c, c), "bias": (c,)},
        "wi": {"kernel": (c, ff), "bias": (ff,)},
        "wo_mlp": {"kernel": (ff, c), "bias": (c,)},
        "rel_bias": (nb, nh),
    }
    if cfg.qkv_bias:
        shapes["wqkv"]["bias"] = (3, nh, hd)
    return shapes


def _merge_shapes(cfg, c: int) -> Params:
    return {
        "norm": {"scale": (4 * c,), "bias": (4 * c,)},
        "reduction": {"kernel": (4 * c, 2 * c)},
    }


def _pad_leaf(a: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    return jnp.pad(a, [(0, m - n) for n, m in zip(a.shape, shape)])


def _slice_leaf(a: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    return a[tuple(slice(0, n) for n in shape)]


def _map_shapes(fn, tree: Params, shapes: Params) -> Params:
    return jax.tree.map(fn, tree, shapes, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------- stacking / specs
def stack_swin_layer_specs(cfg, hp: HybridParallelConfig):
    """Per-slot specs for the padded universal trees. Within-stage sharding
    follows slot j's first-stage axes (the stacked-layout convention,
    parallel/pipeline.py stack_layer_specs); padded dims need not divide the
    axis size — GSPMD shards unevenly."""
    from galvatron_tpu.models.swin import block_param_specs

    lps = hp.pp_division[0]
    out = []
    for j in range(lps):
        spec_j = dict(block_param_specs(cfg, 0, layer_axes(hp, j)))
        if _merge_types(cfg, hp, j):
            spec_j["merge"] = {
                "norm": {"scale": P(None), "bias": P(None)},
                "reduction": {"kernel": P(None, None)},
            }
        out.append(jax.tree.map(
            lambda sp: P(PP_AXIS, *sp), spec_j, is_leaf=lambda x: isinstance(x, P)
        ))
    return out


def stack_swin_params(params: Params, cfg, hp: HybridParallelConfig) -> List[Params]:
    """Canonical swin tree (blocks / merges) -> lps padded slot trees with a
    leading pp dim."""
    pp, lps = hp.pp, hp.pp_division[0]
    cum = np.cumsum(cfg.depths)
    stacked = []
    for j in range(lps):
        pad_shapes = _block_shapes(cfg, _max_dims(cfg, _slot_types(cfg, hp, j)))
        mts = _merge_types(cfg, hp, j)
        per_stage = []
        for s in range(pp):
            gi = s * lps + j
            tree = _map_shapes(_pad_leaf, params["blocks"][gi], pad_shapes)
            if mts:
                mshapes = _merge_shapes(cfg, max(cfg.stage_dim(t) for t in mts))
                t = cfg.stage_of_block(gi)
                if t < cfg.num_stages - 1 and gi == cum[t] - 1:
                    tree["merge"] = _map_shapes(_pad_leaf, params["merges"][t], mshapes)
                else:
                    tree["merge"] = jax.tree.map(
                        lambda sh: jnp.zeros(sh, cfg.param_dtype), mshapes,
                        is_leaf=lambda x: isinstance(x, tuple),
                    )
            per_stage.append(tree)
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage))
    return stacked


def unstack_swin_params(stacked: List[Params], cfg, hp: HybridParallelConfig) -> Params:
    """Inverse of stack_swin_params (checkpoint export): recover canonical
    blocks and merges at their true shapes."""
    pp, lps = hp.pp, hp.pp_division[0]
    cum = np.cumsum(cfg.depths)
    blocks: List[Params] = [None] * cfg.num_layers  # type: ignore
    merges: List[Params] = [None] * (cfg.num_stages - 1)  # type: ignore
    for j, tree in enumerate(stacked):
        for s in range(pp):
            gi = s * lps + j
            t = cfg.stage_of_block(gi)
            slot = jax.tree.map(lambda a: a[s], tree)
            merge = slot.pop("merge", None)
            blocks[gi] = _map_shapes(_slice_leaf, slot, _block_shapes(cfg, _block_dims(cfg, t)))
            if merge is not None and t < cfg.num_stages - 1 and gi == cum[t] - 1:
                merges[t] = _map_shapes(_slice_leaf, merge, _merge_shapes(cfg, cfg.stage_dim(t)))
    return {"blocks": blocks, "merges": merges}


# ==================================================================== engine
def make_swin_loss_and_grad(cfg, hp: HybridParallelConfig, mesh):
    """``fn(params, batch) -> (loss, grads)`` running Swin through the 1F1B
    schedule. params: {embed, final_norm, head, stages}; batch: pixels
    (B, H, W, C), labels (B,)."""
    from galvatron_tpu.models import swin as SW
    from galvatron_tpu.models.parts.embed_head import patchify, softmax_nll
    from galvatron_tpu.ops.norms import layer_norm

    validate_swin_config(cfg, hp)
    pp, chunks = hp.pp, hp.chunks
    lps = hp.pp_division[0]
    vax = vocab_axes(hp)
    sched = build_schedule(pp, chunks)
    if hp.global_bsz % chunks != 0:
        raise ValueError("global_bsz must divide into chunks")

    ns = cfg.num_stages
    cum = np.cumsum(cfg.depths)
    L0 = cfg.stage_resolution(0) ** 2
    C0 = cfg.embed_dim
    N = L0 * C0  # flat channel width (largest activation; halves per merge)
    ch_spec = P(S._ax(vax.batch_axes), None)

    mask_not_branch = use_masked_path(mesh)

    # ------------------------------------------------- per-stage forward body
    def stage_body(s: int):
        lo = s * lps
        t_in = cfg.stage_of_block(lo)
        res_in = cfg.stage_resolution(t_in)
        c_in = cfg.stage_dim(t_in)

        def body(slots: List[Params], ch):
            x = ch[:, : res_in * res_in * c_in].reshape(-1, res_in, res_in, c_in)
            for j in range(lps):
                gi = lo + j
                t = cfg.stage_of_block(gi)
                d = gi - (int(cum[t - 1]) if t else 0)
                ax = layer_axes(hp, gi)
                bp = _map_shapes(
                    _slice_leaf,
                    {k: v for k, v in slots[j].items() if k != "merge"},
                    _block_shapes(cfg, _block_dims(cfg, t)),
                )
                fwd = partial(
                    SW.block_forward, cfg=cfg, stage=t, shift=(d % 2 == 1),
                    mesh=mesh, axes=ax,
                )
                if hp.layers[gi].checkpoint:
                    fwd = jax.checkpoint(fwd)
                x = fwd(bp, x)
                if t < ns - 1 and gi == cum[t] - 1:
                    mp = _map_shapes(
                        _slice_leaf, slots[j]["merge"], _merge_shapes(cfg, cfg.stage_dim(t))
                    )
                    x = SW.patch_merge(mp, x, cfg)
            out = x.reshape(x.shape[0], -1)
            out = jnp.pad(out, ((0, 0), (0, N - out.shape[1])))
            return S.constrain(out, mesh, ch_spec)

        return body

    # ------------------------------------------------------- uniform pieces
    def embed_fwd(vparams, pixels):
        dtype = cfg.compute_dtype
        emb = vparams["embed"]
        x = patchify(pixels.astype(dtype), cfg.patch_size)
        x = x @ emb["patch"]["kernel"].astype(dtype) + emb["patch"]["bias"].astype(dtype)
        x = layer_norm(x, emb["norm"]["scale"], emb["norm"]["bias"], cfg.layernorm_eps)
        return S.constrain(x.reshape(x.shape[0], -1), mesh, ch_spec)

    resL = cfg.stage_resolution(ns - 1)
    cL = cfg.stage_dim(ns - 1)

    def head_loss(vparams, y, labels, weight):
        dtype = cfg.compute_dtype
        h = S.constrain(y, mesh, ch_spec)[:, : resL * resL * cL]
        h = h.reshape(-1, resL * resL, cL)
        h = layer_norm(
            h, vparams["final_norm"]["scale"], vparams["final_norm"]["bias"],
            cfg.layernorm_eps,
        )
        pooled = jnp.mean(h, axis=1)
        logits = pooled @ vparams["head"]["kernel"].astype(dtype) + vparams["head"]["bias"].astype(dtype)
        return softmax_nll(logits, labels) * weight

    def loss_and_grad(params, batch):
        vparams = {k: v for k, v in params.items() if k != "stages"}
        stages = params["stages"]

        B = batch["pixels"].shape[0]
        mb = B // chunks

        def split(x):
            return x.reshape((chunks, mb) + x.shape[1:])

        pixels_mb = split(batch["pixels"])
        labels_mb = split(batch["labels"])

        def rep(t):
            return S.constrain(t, mesh, S.replicated_spec(t.ndim))

        pixels_mb, labels_mb = rep(pixels_mb), rep(labels_mb)
        weights = jnp.full((chunks,), 1.0 / chunks, jnp.float32)
        act_dtype = cfg.compute_dtype
        bodies = [stage_body(s) for s in range(pp)]

        xs = {
            "fwd_mb": jnp.asarray(sched.fwd_mb),
            "fwd_v": jnp.asarray(sched.fwd_valid),
            "arr_mb": jnp.asarray(sched.arr_mb),
            "arr_v": jnp.asarray(sched.arr_valid),
            "bwd_mb": jnp.asarray(sched.bwd_mb),
            "bwd_v": jnp.asarray(sched.bwd_valid),
            "head_mb": jnp.asarray(sched.head_mb),
            "head_v": jnp.asarray(sched.head_valid),
            "emb_mb": jnp.asarray(sched.emb_mb),
            "emb_v": jnp.asarray(sched.emb_valid),
            "inject_mb": jnp.asarray(sched.inject_mb),
        }

        # (see pipeline_1f1b.make_loss_and_grad for the divergence-safety
        # rationale: manual over pp, ONE cross-stage all-gather per tick,
        # mask-not-branch on CPU, branch exits pinned to fixed specs)
        def schedule_body(stages_in, vparams, pixels_mb, labels_mb, weights, xs):
            stage = lax.axis_index(PP_AXIS)
            local = [jax.tree.map(lambda a: a[0], t) for t in stages_in]

            def gather_mb(table, idx):
                return lax.dynamic_index_in_dim(
                    table, jnp.clip(idx, 0, chunks - 1), 0, keepdims=False
                )

            def tick(carry, xt):
                y_prev, dx_prev, dy, stash, loss, sgrads, vgrads = carry

                # gated on stage 0's forward validity (stage-uniform scalar;
                # see pipeline_1f1b.py): skip the patch embedding on dead
                # ticks; both branches pin ch_spec (invariant (b))
                x_inj = lax.cond(
                    xt["fwd_v"][0],
                    lambda: S.constrain(
                        embed_fwd(vparams, gather_mb(pixels_mb, xt["inject_mb"])).astype(act_dtype),
                        mesh, ch_spec,
                    ),
                    lambda: S.constrain(jnp.zeros((mb, N), act_dtype), mesh, ch_spec),
                )

                # THE cross-stage collective
                prev_all = lax.all_gather(jnp.stack([y_prev, dx_prev]), PP_AXIS)
                x_arr = lax.dynamic_index_in_dim(
                    prev_all, jnp.clip(stage - 1, 0, pp - 1), 0, keepdims=False
                )[0]
                x_arr = jnp.where(stage == 0, x_inj, x_arr)
                g_arr = lax.dynamic_index_in_dim(
                    prev_all, jnp.clip(stage + 1, 0, pp - 1), 0, keepdims=False
                )[1]
                y_exit = prev_all[pp - 1, 0]
                dx0 = prev_all[0, 1]

                aslot = xt["arr_mb"][stage] % sched.stash
                old = lax.dynamic_index_in_dim(stash, aslot, 0, keepdims=False)
                stash = lax.dynamic_update_index_in_dim(
                    stash, jnp.where(xt["arr_v"][stage], x_arr, old), aslot, 0
                )

                fmb = xt["fwd_mb"][stage]
                x_f = lax.dynamic_index_in_dim(stash, fmb % sched.stash, 0, keepdims=False)

                def run_fwd(x):
                    return lax.switch(stage, bodies, local, x)

                if mask_not_branch:
                    y = run_fwd(x_f) * xt["fwd_v"][stage].astype(act_dtype)
                else:
                    y = lax.cond(xt["fwd_v"][stage], run_fwd, jnp.zeros_like, x_f)

                g_in = jnp.where(stage == pp - 1, dy, g_arr)

                bmb = xt["bwd_mb"][stage]
                x_b = lax.dynamic_index_in_dim(stash, bmb % sched.stash, 0, keepdims=False)

                def run_bwd(g):
                    def fb(ps, xx):
                        return lax.switch(stage, bodies, ps, xx)

                    _, vjp = jax.vjp(fb, local, x_b)
                    dps_, dx_ = vjp(g)
                    # pin the branch exit INSIDE the branch (invariant (b),
                    # pipeline_1f1b.py)
                    dps_ = [
                        jax.tree.map(
                            lambda a: S.constrain(a, mesh, S.replicated_spec(a.ndim)), t
                        )
                        for t in dps_
                    ]
                    return dps_, S.constrain(dx_, mesh, ch_spec)

                def zero_bwd(g):
                    return jax.tree.map(jnp.zeros_like, local), jnp.zeros_like(x_b)

                if mask_not_branch:
                    dps, dx = run_bwd(g_in * xt["bwd_v"][stage].astype(act_dtype))
                else:
                    dps, dx = lax.cond(xt["bwd_v"][stage], run_bwd, zero_bwd, g_in)
                sgrads = jax.tree.map(jnp.add, sgrads, dps)

                # [uniform] head + loss on the exiting activation, gated on
                # head_v (stage-uniform; see pipeline_1f1b.py)
                e = xt["head_mb"]
                labels_e = gather_mb(labels_mb, e)
                w_e = weights[jnp.clip(e, 0, chunks - 1)]

                def _pin_tree(t):
                    return jax.tree.map(
                        lambda a: S.constrain(a, mesh, S.replicated_spec(a.ndim)), t
                    )

                def run_head():
                    l_e, head_vjp = jax.vjp(
                        lambda vp, yy: head_loss(vp, yy, labels_e, w_e), vparams, y_exit
                    )
                    dvp, dy_h = head_vjp(jnp.ones((), jnp.float32))
                    return l_e, _pin_tree(dvp), S.constrain(dy_h, mesh, ch_spec)

                l_e, dvp_head, dy_h = lax.cond(
                    xt["head_v"],
                    run_head,
                    lambda: (
                        jnp.zeros((), jnp.float32),
                        _pin_tree(jax.tree.map(jnp.zeros_like, vparams)),
                        S.constrain(jnp.zeros_like(y_exit), mesh, ch_spec),
                    ),
                )
                loss = loss + l_e
                vgrads = jax.tree.map(jnp.add, vgrads, dvp_head)

                # [uniform] patch-embedding backward (stage 0's bwd, lagged)
                pix_b = gather_mb(pixels_mb, xt["emb_mb"])

                def run_emb():
                    _, evjp = jax.vjp(
                        lambda vp: embed_fwd(vp, pix_b).astype(act_dtype), vparams
                    )
                    (d,) = evjp(dx0)
                    return _pin_tree(d)

                dvp_e = lax.cond(
                    xt["emb_v"], run_emb,
                    lambda: _pin_tree(jax.tree.map(jnp.zeros_like, vparams)),
                )
                vgrads = jax.tree.map(jnp.add, vgrads, dvp_e)

                return (
                    y, dx, dy_h.astype(act_dtype), stash, loss, sgrads, vgrads,
                ), None

            deps = jax.tree.leaves(vparams) + jax.tree.leaves(
                (pixels_mb, labels_mb, weights)
            )
            y0 = lax.optimization_barrier(
                tuple([jnp.zeros((mb, N), act_dtype)] + deps)
            )[0]
            carry0 = (
                y0,
                jnp.zeros((mb, N), act_dtype),
                jnp.zeros((mb, N), act_dtype),
                jnp.zeros((sched.stash, mb, N), act_dtype),
                jnp.zeros((), jnp.float32),
                [jax.tree.map(jnp.zeros_like, t) for t in local],
                jax.tree.map(jnp.zeros_like, vparams),
            )
            final, _ = lax.scan(tick, carry0, xs)
            loss, sgrads, vgrads = final[4], final[5], final[6]
            return (
                loss,
                [jax.tree.map(lambda a: a[None], t) for t in sgrads],
                vgrads,
            )

        pp_specs = [jax.tree.map(lambda _: P(PP_AXIS), t) for t in stages]

        def rep_tree(t):
            return jax.tree.map(lambda _: P(), t)

        smap = jax.shard_map(
            schedule_body,
            mesh=mesh,
            in_specs=(pp_specs, rep_tree(vparams), P(), P(), P(), rep_tree(xs)),
            out_specs=(P(), pp_specs, rep_tree(vparams)),
            axis_names={PP_AXIS},
            check_vma=False,
        )
        # Gather slot params from their tp/z3-sharded STORAGE layout to
        # within-stage replicated HERE, in the uniform pre-loop region: the
        # stage bodies statically SLICE the padded universal trees, and a
        # slice of a within-stage-sharded dim lowers to a GSPMD
        # collective-permute — inside the divergent branches that is the
        # deadlock class the engine forbids (pipeline_1f1b.py invariant).
        # State stays sharded (ZeRO semantics: shard for state, gather for
        # compute); window attention parallelises over batch x windows.
        stages_local = [
            jax.tree.map(
                lambda a: S.constrain(a, mesh, P(PP_AXIS, *([None] * (a.ndim - 1)))), t
            )
            for t in stages
        ]
        loss, sgrads, vgrads = smap(
            stages_local, vparams, pixels_mb, labels_mb, weights, xs
        )
        grads = dict(vgrads)
        grads["stages"] = sgrads
        return loss, grads

    return loss_and_grad
