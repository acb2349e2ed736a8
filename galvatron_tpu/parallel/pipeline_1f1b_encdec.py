"""1F1B pipeline schedule for encoder-decoder models (T5).

The reference runs T5 through its pipeline as a matter of course — decoder
stages receive multi-tensor sends carrying BOTH the decoder hidden state and
the encoder output for cross-attention (reference pipeline.py:1442-1580
send/recv_forward_multi; multi-layer-type DP, dynamic_programming.py:170-189).
This module is the TPU-native equivalent, built on the same schedule tables
and divergence-safety rules as the generic engine (parallel/pipeline_1f1b.py
— read its docstring first; every invariant there applies here):

- the pipeline CHANNEL is a PAIR ``(h, mem)``: encoder stages produce
  ``(enc_h, enc_h)`` (the last encoder stage seeds ``mem`` with the
  final-normed encoder output); decoder stages consume ``mem`` for
  cross-attention and pass it through unchanged, so ``jax.vjp`` of the stage
  body automatically accumulates every decoder stage's cross-attention
  cotangent down the chain into the encoder backward — the hand-rolled
  d(enc_out) bookkeeping of a rank-based runtime falls out of autodiff;
- there are TWO injection points: encoder token embeddings enter at stage 0,
  decoder token embeddings replace the ``h`` component at the first decoder
  stage ``pe`` (the arriving encoder hidden is dropped there, so the
  cotangent flowing from stage ``pe`` down to ``pe - 1`` zeroes its ``h``
  component), and symmetrically TWO embedding backwards run in the uniform
  region;
- every stage slot carries a UNIVERSAL decoder-shaped parameter tree:
  encoder stages hold zero-initialised, never-referenced cross-attention
  entries so the stacked (pp, ...) layout stays uniform — the price is
  ~1/3 extra parameter state on encoder stages, the payoff is that the
  stacking/ZeRO/spec machinery of the generic engine applies unchanged;
- T5's relative-position tables live INSIDE slot 0 of each stage (they feed
  every layer's attention bias, so their gradient must flow through the
  stage-body vjp); same-type stages hold tied copies, and the tick-invariant
  tie is restored after the scan by summing + re-broadcasting the stacked
  gradient rows over the encoder range and the decoder range.

Sequence lengths: the schedule's static channel requires one sequence length,
so encoder and decoder streams are padded to ``max(Se, Sd)`` by the caller
(`models/t5.py` pads and extends attn/loss masks).
"""

from __future__ import annotations

from typing import Any, Dict, List

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


def validate_encdec_config(cfg, hp: HybridParallelConfig) -> int:
    """Returns pe, the number of encoder stages. The enc/dec boundary must
    fall on a stage boundary and every stage must hold the same layer count
    (the universal-slot layout needs equal slots per stage)."""
    if hp.pp <= 1:
        return 0
    div = hp.pp_division
    if len(set(div)) != 1:
        raise ValueError(
            "enc-dec 1F1B requires equal layers per stage, got pp_division=%s" % (div,)
        )
    lps = div[0]
    if cfg.num_enc_layers % lps != 0:
        raise ValueError(
            "the encoder/decoder boundary must align with a stage boundary: "
            "%d encoder layers do not divide into stages of %d layers"
            % (cfg.num_enc_layers, lps)
        )
    for s in hp.layers:
        if s.cp > 1:
            raise ValueError("cp>1 with pp>1 is not yet supported in the 1f1b pipeline")
    return cfg.num_enc_layers // lps


# =========================================================== universal stacking
def stack_t5_layer_specs(cfg, hp: HybridParallelConfig):
    """Per-slot specs for the universal decoder-shaped tree (+ slot-0 extras:
    the rel-bias table and the encoder seed norm)."""
    from galvatron_tpu.models.t5 import dec_layer_specs

    lps = hp.pp_division[0]
    out = []
    for j in range(lps):
        ax = layer_axes(hp, j)
        spec_j = dict(dec_layer_specs(cfg, ax))
        if j == 0:
            spec_j["rel_bias"] = P(None, None)
            spec_j["seed_norm"] = {"scale": P(None)}
        out.append(jax.tree.map(
            lambda sp: P(PP_AXIS, *sp), spec_j, is_leaf=lambda x: isinstance(x, P)
        ))
    return out


def stack_t5_params(params: Params, cfg, hp: HybridParallelConfig) -> List[Params]:
    """Canonical t5 tree (enc_layers / dec_layers / rel tables / norms) ->
    list of lps universal slot trees with a leading pp dim."""
    from galvatron_tpu.models.t5 import init_dec_layer

    pp, lps = hp.pp, hp.pp_division[0]
    pe = cfg.num_enc_layers // lps
    template = jax.tree.map(
        jnp.zeros_like, init_dec_layer(jax.random.PRNGKey(0), cfg)
    )

    def slot_tree(s: int, j: int) -> Params:
        if s < pe:
            src = params["enc_layers"][s * lps + j]
            tree = dict(template)
            tree.update(jax.tree.map(lambda a: a, src))
        else:
            tree = dict(params["dec_layers"][(s - pe) * lps + j])
        if j == 0:
            tree["rel_bias"] = (
                params["enc_rel_bias"] if s < pe else params["dec_rel_bias"]
            )
            tree["seed_norm"] = {
                "scale": params["enc_norm"]["scale"] if s == pe - 1
                else jnp.ones_like(params["enc_norm"]["scale"])
            }
        return tree

    stacked = []
    for j in range(lps):
        per_stage = [slot_tree(s, j) for s in range(pp)]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage))
    return stacked


def unstack_t5_params(stacked: List[Params], cfg, hp: HybridParallelConfig) -> Params:
    """Inverse of stack_t5_params for checkpoint export: recovers the
    canonical tree (encoder slots drop the zero cross-attention entries)."""
    pp, lps = hp.pp, hp.pp_division[0]
    pe = cfg.num_enc_layers // lps
    enc_layers, dec_layers = [], []
    for s in range(pp):
        for j in range(lps):
            tree = jax.tree.map(lambda a: a[s], stacked[j])
            rel = tree.pop("rel_bias", None)
            seed = tree.pop("seed_norm", None)
            if s < pe:
                for k in ("cross", "ln_cross"):
                    tree.pop(k, None)
                enc_layers.append(tree)
            else:
                dec_layers.append(tree)
            if j == 0:
                if s == 0:
                    enc_rel = rel
                if s == pe:
                    dec_rel = rel
                if s == pe - 1:
                    enc_norm = {"scale": seed["scale"]}
    return {
        "enc_layers": enc_layers, "dec_layers": dec_layers,
        "enc_rel_bias": enc_rel, "dec_rel_bias": dec_rel, "enc_norm": enc_norm,
    }


# ==================================================================== engine
def make_encdec_loss_and_grad(cfg, hp: HybridParallelConfig, mesh):
    """``fn(params, batch) -> (loss, grads)`` running T5 through the 1F1B
    schedule. params: {embed, dec_norm, (lm_head), stages}; batch (padded to
    a common seq length by models/t5.py): tokens, dec_tokens, labels,
    loss_mask?, attn_mask?."""
    from galvatron_tpu.models import t5 as T

    pe = validate_encdec_config(cfg, hp)
    pp, chunks = hp.pp, hp.chunks
    lps = hp.pp_division[0]
    vax = vocab_axes(hp)
    sched = build_schedule(pp, chunks)
    if hp.global_bsz % chunks != 0:
        raise ValueError("global_bsz must divide into chunks")

    mb_spec = P(S._ax(vax.batch_axes), S._ax(vax.seq_axes), None)
    # boundary spec of the (h, mem) channel pair
    pair_spec = P(None, S._ax(vax.batch_axes), S._ax(vax.seq_axes), None)

    # encoder and decoder bodies always differ, so the lax.switch can never
    # collapse to a single body the way the generic engine's does
    uniform_stages = False
    mask_not_branch = use_masked_path(mesh)

    # ------------------------------------------------- per-stage forward body
    def stage_body(s: int, Sq: int):
        lo = s * lps
        is_enc = s < pe

        def body(stage_layers: List[Params], ch, self_bias, cross_bias):
            rel = stage_layers[0]["rel_bias"]
            h, mem = ch[0], ch[1]
            bias = T.rel_bias(rel, Sq, Sq, cfg, bidirectional=is_enc)
            if is_enc:
                bias = bias + self_bias
            prev = mb_spec
            for j in range(lps):
                gi = lo + j
                ax = layer_axes(hp, gi)
                cur = S.act_spec(ax)
                h = S.monotone_constrain(h, mesh, prev, cur)
                lp = stage_layers[j]
                if is_enc:
                    fwd = lambda p, x: T.enc_layer_forward(p, x, cfg, bias, mesh=mesh, axes=ax)
                else:
                    # mem stays in the boundary layout (it is never rewritten
                    # by a layer), so each transition starts from mb_spec
                    mem_c = S.monotone_constrain(mem, mesh, mb_spec, cur)
                    fwd = lambda p, x: T.dec_layer_forward(
                        p, x, mem_c, cfg, bias, cross_bias=cross_bias, mesh=mesh, axes=ax
                    )
                if hp.layers[gi].checkpoint:
                    fwd = jax.checkpoint(fwd)
                h = fwd(lp, h)
                prev = cur
            h = S.monotone_constrain(h, mesh, prev, mb_spec)
            if is_enc:
                mem_out = h
                if s == pe - 1:
                    mem_out = T._rms(h, stage_layers[0]["seed_norm"], cfg)
            else:
                mem_out = mem
            return jnp.stack([h, mem_out])

        return body

    # ------------------------------------------------------- vocab fwd pieces
    def embed_fwd(vparams, tokens):
        """One-hot wte lookup (see pipeline_1f1b.embed_fwd for why matmul, not
        gather, and for models/parts/embed_head.vocab_parallel_lookup, which replaces it
        outside the 1F1B schedule)."""
        dtype = cfg.compute_dtype
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=dtype)
        x = jnp.einsum("bsv,vh->bsh", onehot, vparams["embed"]["wte"].astype(dtype))
        return S.constrain(x, mesh, mb_spec)

    def head_loss(vparams, y, labels, loss_mask, weight):
        from galvatron_tpu.models.parts.embed_head import vocab_parallel_cross_entropy

        dtype = cfg.compute_dtype
        y = T._rms(S.constrain(y, mesh, mb_spec), vparams["dec_norm"], cfg)
        if cfg.tie_embeddings:
            y = y * (cfg.hidden_size ** -0.5)
            logits = y @ vparams["embed"]["wte"].astype(dtype).T
        else:
            logits = y @ vparams["lm_head"]["kernel"].astype(dtype)
        logits = S.constrain(logits, mesh, S.logits_spec(vax))
        return vocab_parallel_cross_entropy(logits, labels, loss_mask) * weight

    def loss_and_grad(params, batch):
        vparams_stored = {k: v for k, v in params.items() if k != "stages"}
        stages = params["stages"]

        B = batch["tokens"].shape[0]
        mb = B // chunks
        Sq = batch["tokens"].shape[1]
        assert batch["dec_tokens"].shape[1] == Sq, (
            "enc/dec streams must be padded to a common sequence length"
        )

        def split(x):
            return x.reshape((chunks, mb) + x.shape[1:])

        enc_mb = split(batch["tokens"])
        dec_mb = split(batch["dec_tokens"])
        labels_mb = split(batch["labels"])
        has_mask = batch.get("loss_mask") is not None
        mask_mb = split(batch["loss_mask"]) if has_mask else jnp.zeros((chunks, 1), jnp.float32)
        has_bias = batch.get("attn_mask") is not None
        # padded encoder keys mask encoder self-attn and decoder cross-attn
        key_bias_mb = (
            split((1.0 - batch["attn_mask"].astype(jnp.float32))[:, None, None, :] * -1e9)
            if has_bias else jnp.zeros((chunks, 1), jnp.float32)
        )

        def rep(t):
            return S.constrain(t, mesh, S.replicated_spec(t.ndim))

        enc_mb, dec_mb, labels_mb, mask_mb, key_bias_mb = (
            rep(t) for t in (enc_mb, dec_mb, labels_mb, mask_mb, key_bias_mb)
        )

        if has_mask:
            msums = jnp.sum(mask_mb.astype(jnp.float32), axis=tuple(range(1, mask_mb.ndim)))
            weights = msums / jnp.maximum(jnp.sum(msums), 1.0)
        else:
            weights = jnp.full((chunks,), 1.0 / chunks, jnp.float32)

        H = cfg.hidden_size
        act_dtype = cfg.compute_dtype
        bodies_by_stage = [stage_body(s, Sq) for s in range(pp)]

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
            # decoder-side tables: stage pe's arrival (dec embedding swap-in)
            # and stage pe's backward, lagged one tick for its embedding bwd
            "arr_pe_mb": jnp.asarray(sched.arr_mb[:, pe] if pe < pp else sched.arr_mb[:, 0]),
            "arr_pe_v": jnp.asarray(
                sched.arr_valid[:, pe] if pe < pp else sched.arr_valid[:, 0]
            ),
            "emb2_mb": jnp.asarray(
                np.concatenate([[0], sched.bwd_mb[:-1, pe]]) if pe < pp else sched.emb_mb
            ),
            "emb2_v": jnp.asarray(
                np.concatenate([[False], sched.bwd_valid[:-1, pe]])
                if pe < pp else np.zeros_like(sched.emb_valid)
            ),
            "inject_mb": jnp.asarray(sched.inject_mb),
        }

        # (see pipeline_1f1b.make_loss_and_grad for the full divergence-safety
        # rationale behind this structure: one shard_map manual over pp, one
        # cross-stage all-gather per tick, mask-not-branch on CPU)
        def schedule_body(stages_in, vparams, enc_mb, dec_mb, labels_mb,
                          mask_mb, key_bias_mb, weights, xs):
            stage = lax.axis_index(PP_AXIS)
            local = [jax.tree.map(lambda a: a[0], t) for t in stages_in]

            def gather_mb(table, idx):
                return lax.dynamic_index_in_dim(
                    table, jnp.clip(idx, 0, chunks - 1), 0, keepdims=False
                )

            def tick(carry, xt):
                y_prev, dx_prev, dy, stash, loss, sgrads, vgrads = carry

                # [uniform] both embeddings for this tick's injections, gated
                # on their (stage-uniform) validity scalars so the O(V)
                # matmuls skip dead ticks; both cond branches pin mb_spec
                # (invariant (b), pipeline_1f1b.py)
                def _embed_or_zero(valid, tokens):
                    return lax.cond(
                        valid,
                        lambda: S.constrain(
                            embed_fwd(vparams, tokens).astype(act_dtype), mesh, mb_spec
                        ),
                        lambda: S.constrain(
                            jnp.zeros((mb, Sq, H), act_dtype), mesh, mb_spec
                        ),
                    )

                x_inj_enc = _embed_or_zero(xt["fwd_v"][0], gather_mb(enc_mb, xt["inject_mb"]))
                x_inj_dec = _embed_or_zero(xt["arr_pe_v"], gather_mb(dec_mb, xt["arr_pe_mb"]))

                # THE cross-stage collective (channel pairs double the width)
                prev_all = lax.all_gather(jnp.stack([y_prev, dx_prev]), PP_AXIS)
                x_arr = lax.dynamic_index_in_dim(
                    prev_all, jnp.clip(stage - 1, 0, pp - 1), 0, keepdims=False
                )[0]
                zero_ch = jnp.zeros((mb, Sq, H), act_dtype)
                x_arr = jnp.where(stage == 0, jnp.stack([x_inj_enc, zero_ch]), x_arr)
                # first decoder stage: decoder embedding replaces h; the
                # arriving mem (seeded encoder output) is kept
                x_arr = jnp.where(
                    stage == pe, jnp.stack([x_inj_dec, x_arr[1]]), x_arr
                )
                g_arr = lax.dynamic_index_in_dim(
                    prev_all, jnp.clip(stage + 1, 0, pp - 1), 0, keepdims=False
                )[1]
                # the h arriving at stage pe was dropped (replaced by the
                # decoder embedding), so no h-cotangent flows to stage pe-1
                g_arr = jnp.where(
                    stage == pe - 1, jnp.stack([jnp.zeros_like(g_arr[0]), g_arr[1]]), g_arr
                )
                y_exit = prev_all[pp - 1, 0, 0]
                dx0 = prev_all[0, 1, 0]
                dx_pe = prev_all[pe if pe < pp else 0, 1, 0]

                aslot = xt["arr_mb"][stage] % sched.stash
                old = lax.dynamic_index_in_dim(stash, aslot, 0, keepdims=False)
                stash = lax.dynamic_update_index_in_dim(
                    stash, jnp.where(xt["arr_v"][stage], x_arr, old), aslot, 0
                )

                fmb = xt["fwd_mb"][stage]
                x_f = lax.dynamic_index_in_dim(stash, fmb % sched.stash, 0, keepdims=False)
                self_b_f = gather_mb(key_bias_mb, fmb) if has_bias else 0.0
                cross_b_f = self_b_f if has_bias else None

                def run_fwd(x):
                    if uniform_stages:
                        return bodies_by_stage[0](local, x, self_b_f, cross_b_f)
                    return lax.switch(
                        stage, bodies_by_stage, local, x, self_b_f, cross_b_f
                    )

                if mask_not_branch:
                    y = run_fwd(x_f) * xt["fwd_v"][stage].astype(act_dtype)
                else:
                    y = lax.cond(xt["fwd_v"][stage], run_fwd, jnp.zeros_like, x_f)

                g_in = jnp.where(stage == pp - 1, dy, g_arr)

                bmb = xt["bwd_mb"][stage]
                x_b = lax.dynamic_index_in_dim(stash, bmb % sched.stash, 0, keepdims=False)
                self_b_b = gather_mb(key_bias_mb, bmb) if has_bias else 0.0
                cross_b_b = self_b_b if has_bias else None

                def run_bwd(g):
                    def fb(ps, xx):
                        if uniform_stages:
                            return bodies_by_stage[0](ps, xx, self_b_b, cross_b_b)
                        return lax.switch(
                            stage, bodies_by_stage, ps, xx, self_b_b, cross_b_b
                        )

                    _, vjp = jax.vjp(fb, local, x_b)
                    dps_, dx_ = vjp(g)
                    # pin the branch exit INSIDE the branch (divergence-safety
                    # invariant (b), pipeline_1f1b.py)
                    dps_ = [
                        jax.tree.map(
                            lambda a: S.constrain(a, mesh, S.replicated_spec(a.ndim)), t
                        )
                        for t in dps_
                    ]
                    return dps_, S.constrain(dx_, mesh, pair_spec)

                def zero_bwd(g):
                    return jax.tree.map(jnp.zeros_like, local), jnp.zeros_like(x_b)

                if mask_not_branch:
                    dps, dx = run_bwd(g_in * xt["bwd_v"][stage].astype(act_dtype))
                else:
                    dps, dx = lax.cond(xt["bwd_v"][stage], run_bwd, zero_bwd, g_in)
                sgrads = jax.tree.map(jnp.add, sgrads, dps)

                # [uniform] head + loss on the exiting decoder hidden, gated
                # on head_v (stage-uniform; see pipeline_1f1b.py)
                e = xt["head_mb"]
                labels_e = gather_mb(labels_mb, e)
                mask_e = gather_mb(mask_mb, e) if has_mask else None
                w_e = weights[jnp.clip(e, 0, chunks - 1)]

                def _pin_tree(t):
                    return jax.tree.map(
                        lambda a: S.constrain(a, mesh, S.replicated_spec(a.ndim)), t
                    )

                def run_head():
                    l_e, head_vjp = jax.vjp(
                        lambda vp, yy: head_loss(vp, yy, labels_e, mask_e, w_e),
                        vparams, y_exit,
                    )
                    dvp, dy_h = head_vjp(jnp.ones((), jnp.float32))
                    return l_e, _pin_tree(dvp), S.constrain(dy_h, mesh, mb_spec)

                l_e, dvp_head, dy_h = lax.cond(
                    xt["head_v"],
                    run_head,
                    lambda: (
                        jnp.zeros((), jnp.float32),
                        _pin_tree(jax.tree.map(jnp.zeros_like, vparams)),
                        S.constrain(jnp.zeros_like(y_exit), mesh, mb_spec),
                    ),
                )
                loss = loss + l_e
                vgrads = jax.tree.map(jnp.add, vgrads, dvp_head)
                dy_new = jnp.stack([dy_h, dy_h * 0.0]).astype(act_dtype)

                # [uniform] encoder / decoder embedding backwards (stage 0's
                # and stage pe's bwd, lagged), each gated on its validity
                def _embed_bwd(valid, tokens, cot):
                    def run():
                        _, evjp = jax.vjp(
                            lambda vp: embed_fwd(vp, tokens).astype(act_dtype), vparams
                        )
                        (d,) = evjp(cot)
                        return _pin_tree(d)

                    return lax.cond(
                        valid, run,
                        lambda: _pin_tree(jax.tree.map(jnp.zeros_like, vparams)),
                    )

                dvp_e = _embed_bwd(xt["emb_v"], gather_mb(enc_mb, xt["emb_mb"]), dx0)
                vgrads = jax.tree.map(jnp.add, vgrads, dvp_e)
                dvp_d = _embed_bwd(xt["emb2_v"], gather_mb(dec_mb, xt["emb2_mb"]), dx_pe)
                vgrads = jax.tree.map(jnp.add, vgrads, dvp_d)

                return (
                    y, dx, dy_new, stash, loss, sgrads, vgrads,
                ), None

            deps = jax.tree.leaves(vparams) + jax.tree.leaves(
                (enc_mb, dec_mb, labels_mb, mask_mb, key_bias_mb, weights)
            )
            y0 = lax.optimization_barrier(
                tuple([jnp.zeros((2, mb, Sq, H), act_dtype)] + deps)
            )[0]
            carry0 = (
                y0,
                jnp.zeros((2, mb, Sq, H), act_dtype),
                jnp.zeros((2, mb, Sq, H), act_dtype),
                jnp.zeros((sched.stash, 2, mb, Sq, H), act_dtype),
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
            in_specs=(
                pp_specs, rep_tree(vparams_stored),
                P(), P(), P(), P(), P(), P(), rep_tree(xs),
            ),
            out_specs=(P(), pp_specs, rep_tree(vparams_stored)),
            axis_names={PP_AXIS},
            check_vma=False,
        )
        from galvatron_tpu.models.t5 import t5_vocab_pipeline_specs

        vspecs_local = t5_vocab_pipeline_specs(cfg, hp, storage=False)
        vparams_local = jax.tree.map(
            lambda sp, t: S.constrain(t, mesh, sp),
            {k: vspecs_local[k] for k in vparams_stored}, vparams_stored,
            is_leaf=lambda x: isinstance(x, P),
        )
        loss, sgrads, vgrads = smap(
            stages, vparams_local, enc_mb, dec_mb, labels_mb,
            mask_mb, key_bias_mb, weights, xs,
        )

        # restore the rel-bias tie: same-type stages hold copies of one
        # table, so their gradient is the SUM over that range, broadcast back
        # (identical grads + identical init keep the copies in lockstep under
        # any elementwise optimizer)
        rel_g = sgrads[0]["rel_bias"]  # (pp, buckets, nh)
        enc_sum = jnp.sum(rel_g[:pe], axis=0, keepdims=True)
        dec_sum = jnp.sum(rel_g[pe:], axis=0, keepdims=True)
        sgrads[0]["rel_bias"] = jnp.concatenate(
            [jnp.broadcast_to(enc_sum, (pe,) + rel_g.shape[1:]),
             jnp.broadcast_to(dec_sum, (pp - pe,) + rel_g.shape[1:])], axis=0
        )

        grads = dict(vgrads)
        grads["stages"] = sgrads
        return loss, grads

    return loss_and_grad
