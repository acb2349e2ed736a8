"""Hybrid-parallel model construction and the jitted train step.

TPU-native equivalent of the reference's 6-step model assembly
(galvatron/core/runtime/hybrid_parallel_model.py:165-326: comm groups -> TP
rewrite -> sequential split -> relocation -> PipelineParallel -> FSDP -> ckpt)
and of `GalvatronModel.forward_backward` (:42-70). Here the assembly is:

  1. build one named Mesh (parallel/mesh.py — replaces gen_comm_groups);
  2. build per-layer param/activation PartitionSpecs (replaces the TP rewrite,
     FSDP wrapping, and Module_with_relocation);
  3. jit one train-step function whose gradient accumulation loop over
     microbatches replaces the GPipe/1F1B/no-pp schedule dispatch (pp>1 runs
     the scan/ppermute pipeline from parallel/pipeline.py);
  4. ZeRO grad/optimizer-state semantics are sharding constraints on the
     accumulator and the adam moments (replaces grad_reduce.py's no_sync +
     manual FSDP flush).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.embed_head import table_is_looked_up
from galvatron_tpu.obs import tracing
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import build_mesh, layer_axes, pipeline_vocab_axes, vocab_axes
from galvatron_tpu.runtime.optimizer import opt_state_specs

Params = Dict[str, Any]


def _is_spec(x):
    return isinstance(x, P)


@dataclass
class HybridParallelModel:
    cfg: TransformerConfig
    hp: HybridParallelConfig
    mesh: Mesh
    param_specs: Params
    loss_fn: Callable  # (params, batch) -> loss
    forward_fn: Callable  # (params, batch) -> logits
    init_fn: Optional[Callable] = None  # (rng) -> params; families with their
    # own param tree (t5/swin) supply this instead of base.init_model_params
    grad_fn: Optional[Callable] = None  # (params, batch) -> (loss, grads);
    # set by the 1f1b pipeline, whose hand-written schedule produces gradients
    # directly instead of going through jax.value_and_grad
    eval_loss_fn: Optional[Callable] = None  # forward-only (params, batch) ->
    # loss for evaluation: under the 1f1b engines, loss_fn is the grad-bearing
    # schedule (loss and grads come out of one scan, so XLA cannot DCE the
    # backward); this is the cheap path (reference evaluation is forward-only)
    local_loss_fn: Optional[Callable] = None  # the CONSTRAINT-FREE local
    # loss (models/base loss_fns with hp=None/mesh=None): the body of the
    # quantized grad-sync shard_map (parallel/quant_collectives.py), where
    # each dp shard computes grads on its local batch with no
    # with_sharding_constraint in scope. Base families only; None refuses
    # the quantized path with GLS013.
    loss_parts_fn: Optional[Callable] = None  # (params, batch) -> (loss,
    # parts): a routed-experts config's objective with its terms and the
    # expert load, a looped stack's with its passes' (models/base.lm_loss_fn
    # with_parts), which the step hands back in `metrics` beside the loss;
    # None (a dense config) leaves the step as it is
    cast_first: Optional[Params] = None  # tree of bools like param_specs:
    # the leaves this family's loss reads only through a cast to the compute
    # dtype that comes first (parallel/spec.cast_first_tree); None (a custom
    # loss, whose reads nobody declared) gives no leaf a compute copy
    table_as_stored: bool = False  # loss_fn and forward_fn look an untied
    # `embed.wte` up by `vocab_parallel_lookup` in the form `table_spec()` asks
    # for (construct_hybrid_parallel_model's own); False (a family that reads
    # the table its own way) keeps it as `param_specs` lays it out, as a custom
    # loss does (`_zero_splits_state`)
    # memoized NamedSharding trees per batch signature (key set + ranks), so
    # the per-step shard_batch is ONE device_put of the whole tree with no
    # per-key NamedSharding construction on the hot path
    _batch_shardings: Dict[Tuple, Dict[str, NamedSharding]] = field(
        default_factory=dict, repr=False)
    _copied: Optional[Params] = field(default=None, repr=False)  # memo of copied_leaves()
    _stored: Optional[Params] = field(default=None, repr=False)  # memo of state_specs()

    @property
    def eval_loss(self) -> Callable:
        """The loss to use for evaluation: forward-only when available. It
        takes the parameters as the state holds them and reads the copy the
        train step reads."""
        loss = self.eval_loss_fn or self.loss_fn
        if not any(jax.tree.leaves(self.copied_leaves())):
            return loss
        return lambda params, batch: loss(self.compute_params(params), batch)

    # ------------------------------------------------------------------ params
    def shardings(self, specs=None):
        """NamedShardings of a spec tree; of the parameters as the train
        state STORES them (`state_specs`) when none is given."""
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs if specs is not None else self.state_specs(),
            is_leaf=_is_spec,
        )

    def _zero_splits_state(self) -> bool:
        """Whether ZeRO's dp axes may split a leaf of the STATE further than
        `param_specs` does. Not where nobody declared how the loss reads its
        leaves (a custom loss), nor where the model's code sums a leaf's
        gradient itself, in the dtype and the layout the leaf comes in:
        GPipe's scan (pp > 1) over the microbatches, the manual TP path's
        regions over dp, the 1F1B engines (`grad_fn`) and the quantized grad
        sync, whose regions are written for the `param_specs` layout.
        The model's own losses are handed the answer (`zero_splits_state`)
        and `models/base.run_layers` then asks for a scanned run's stacked
        cotangent in ZeRO's layout too: one predicate for the state's split
        and for the gradient summed into it."""
        from galvatron_tpu.parallel import quant_collectives as QC

        return not (self.cast_first is None or self.hp.pp > 1 or self.grad_fn is not None
                    or self.hp.tp_comm_mode != "gspmd" or QC.wants_quant_comm(self.hp))

    def copied_leaves(self) -> Params:
        """Tree of bools like param_specs: the leaves ZeRO-2 stores split
        over dp, in the layout of Adam's moments and the accumulated
        gradient, and of which the step gathers a compute-dtype copy once
        (`compute_params`). Such a leaf has ZeRO axes that split it further
        (not ddp, not dp = 1, not a ZeRO-3 leaf, which is dp-sharded
        already), is wider than the compute dtype, and is read by the model
        only through a cast to it (`cast_first`); and the state may be split
        at all (`_zero_splits_state`). Of the other leaves the token table
        may be stored split too, without a copy (`state_specs`); the rest is
        stored as `param_specs` lays it out for the forward, and gathered
        after the update in its own dtype."""
        if self._copied is None:
            if not self._zero_splits_state():
                self._copied = jax.tree.map(lambda _: False, self.param_specs, is_leaf=_is_spec)
            else:
                narrow = jnp.dtype(self.cfg.compute_dtype).itemsize
                self._copied = jax.tree.map(
                    lambda spec, split, shp, cast: bool(
                        cast and split != spec and shp.dtype.itemsize > narrow),
                    self.param_specs, self.grad_accum_specs(), self.abstract_params(),
                    self.cast_first, is_leaf=_is_spec,
                )
        return self._copied

    def state_specs(self) -> Params:
        """The layout the parameters are stored in: what the step takes and
        returns, `init_params` produces and a checkpoint restores into. A
        leaf of the state has a logical shape, this spec, and the physical
        tiling the compiler gives an entry parameter of that shape; nobody
        states another (PERF.md section 3, PR 48: where a gradient lies
        otherwise it is the gradient that is relaid, `parts/mlp.grad_as_stored`)
        and a checkpoint holds the first two. Three cases:

        - a copied leaf (`copied_leaves`) lies as `grad_accum_specs` splits it
          and the step reads a gathered copy in the compute dtype;
        - the token table of a loss that looks it up as stored
          (`table_as_stored`) lies so too where the state may be split
          (`_zero_splits_state`), and NOTHING gathers it: the lookup sends
          ids, rows and cotangents over dp instead
          (embed_head.vocab_parallel_lookup), its gradient comes out in this
          layout, and the update returns it in this layout;
        - every other leaf lies as `param_specs` has it."""
        if self._stored is None:
            split = self.grad_accum_specs()
            self._stored = jax.tree.map(
                lambda spec, split, copied: split if copied else spec,
                self.param_specs, split, self.copied_leaves(), is_leaf=_is_spec,
            )
            if self.table_as_stored and self._zero_splits_state():
                self._stored["embed"] = {**self._stored["embed"], "wte": split["embed"]["wte"]}
        return self._stored

    def table_spec(self) -> Optional[P]:
        """The spec the token table is stored in (`state_specs`), which the
        model's own losses hand to the lookup; None without a table."""
        return self.state_specs().get("embed", {}).get("wte")

    def compute_params(self, params: Params) -> Params:
        """What forward, recomputation and backward read: of a copied leaf
        its value in the compute dtype, whole over dp as `param_specs` lays
        it out (ZeRO-2's parameter all-gather, at the compute dtype's bytes);
        every other leaf as it is, a token table stored split among them
        (`state_specs`). The model's own `.astype(compute_dtype)` of a copied
        leaf is then the identity."""
        copied = self.copied_leaves()
        if not any(jax.tree.leaves(copied)):
            return params
        dtype = self.cfg.compute_dtype
        with jax.named_scope(tracing.PARAM_GATHER):
            return jax.tree.map(
                lambda c, p, s: jax.lax.with_sharding_constraint(p.astype(dtype), s) if c else p,
                copied, params, self.shardings(self.param_specs),
            )

    def _init_fn(self, rng) -> Params:
        if self.init_fn is not None:
            return self.init_fn(rng)
        params = M.init_model_params(rng, self.cfg)
        if self.hp.pp > 1:
            from galvatron_tpu.parallel.pipeline import stack_params

            params["stages"] = stack_params(params.pop("layers"), self.hp)
        return params

    def abstract_params(self) -> Params:
        """Abstract (ShapeDtypeStruct) params tree for this model — the
        shared currency of cross-layout checkpoint restore and live
        in-memory migration (structure + shapes, no device work)."""
        return jax.eval_shape(self._init_fn, jax.random.PRNGKey(0))

    def init_params(self, rng) -> Params:
        """Sharded init: jit with out_shardings so each device materialises
        only its shard (the analogue of meta-device init + shard streaming,
        reference runtime/initialize.py:8-112)."""
        if self.init_fn is None and self.hp.pp > 1:
            # GSPMD hazard (WA006; seen on jax 0.4.37, not ruled out on the
            # installed jax): fusing the per-layer init with the
            # jnp.stack into `stages` in ONE jitted program whose
            # out_shardings put the pp axis on the new stacked dim produces
            # silently wrong values in some stacked entries (eager init is
            # correct; measured 0.2-0.3 absolute error on layer kernels,
            # the test_pipelined_bert_mlm parity failure). Init the
            # canonical per-layer tree jitted, stack it op-by-op OUTSIDE
            # the jitted program, then place onto the stacked shardings —
            # the same path the parity-test fixtures use.
            from galvatron_tpu.parallel.pipeline import stack_params

            params = jax.jit(lambda r: M.init_model_params(r, self.cfg))(rng)
            params["stages"] = stack_params(params.pop("layers"), self.hp)
            return jax.device_put(params, self.shardings())
        init = jax.jit(self._init_fn, out_shardings=self.shardings())
        return init(rng)

    def _batch_spec_for(self, x) -> P:
        """(B, S) token-shaped entries shard over (dp, seq); rank-1 labels over
        dp; higher-rank entries (pixels) shard batch only."""
        vax = vocab_axes(self.hp)
        ndim = getattr(x, "ndim", None) or len(getattr(x, "shape", ()))
        if ndim == 2:
            return P(S._ax(vax.batch_axes), S._ax(vax.seq_axes))
        if ndim == 1:
            return P(S._ax(vax.batch_axes))
        return P(*([S._ax(vax.batch_axes)] + [None] * (ndim - 1)))

    def batch_specs(self, batch_example: Dict[str, Any]):
        return {k: self._batch_spec_for(v) for k, v in batch_example.items()}

    def shard_batch(self, batch):
        """One sharded transfer for the whole batch: the sharding tree is
        precomputed per batch signature and the entire dict goes through a
        single ``jax.device_put`` — no per-key Python round trips, and the
        runtime can overlap the per-leaf copies (the prefetch thread issues
        this ahead of the step that consumes it)."""
        sig = tuple(sorted(
            (k, getattr(v, "ndim", None) or len(getattr(v, "shape", ())))
            for k, v in batch.items()
        ))
        shardings = self._batch_shardings.get(sig)
        if shardings is None:
            shardings = {
                k: NamedSharding(self.mesh, self._batch_spec_for(v))
                for k, v in batch.items()
            }
            self._batch_shardings[sig] = shardings
        return jax.device_put(batch, shardings)

    # -------------------------------------------------------------- train step
    def zero_axes_tree(self):
        """Per-param dp axes over which to shard adam moments (ZeRO-1/2/3)."""

        def for_axes(ax, tree):
            zax = S.zero_axes(ax)
            return jax.tree.map(lambda _: zax, tree)

        ps = self.param_specs
        vax = vocab_axes(self.hp)
        layer_lists = ("layers", "stages", "enc_layers", "dec_layers", "blocks")
        out = {}
        offset = 0
        for key, sub in ps.items():
            if key in layer_lists:
                out[key] = [
                    for_axes(layer_axes(self.hp, offset + i), sub[i]) for i in range(len(sub))
                ]
                offset += len(sub)
            else:
                out[key] = for_axes(vax, sub)
        return out

    def grad_accum_specs(self):
        """Accumulated-grad shardings: dp-sharded wherever ZeRO applies
        (`parallel/spec.zero_split_spec`), so that the per-microbatch
        reduction can be a reduce-scatter and not an all-reduce (reference
        grad_reduce.py:47-64 no-sync + flush semantics). The step asks for
        this layout AFTER the backward (`to_accum`), which decides nothing
        about a sum the backward has already finished. What makes the
        reduce-scatter true: the head's and the unrolled layers' cotangents
        reach `to_accum` unconstrained, so the compiler ends their sums in
        this layout; a scanned run's stacked cotangent is asked for in this
        layout INSIDE the scan's body (`models/base.run_layers`, through
        `stacked_layer_grad_specs` and `spec.constrain_grad_as`, where
        `_zero_splits_state` holds), and `to_accum` is then a no-op on it.
        Everywhere else (GPipe, the manual TP path, the 1F1B engines, the
        quantized sync) the model's code sums a gradient whole over dp and
        this is a slice of the sum."""
        shapes = self.abstract_params()
        mesh_shape = dict(self.mesh.shape)

        return jax.tree.map(
            lambda spec, shp, zax: S.zero_split_spec(spec, shp.shape, tuple(zax), mesh_shape),
            self.param_specs,
            shapes,
            self.zero_axes_tree(),
            is_leaf=_is_spec,
        )

    def make_train_step(self, tx: optax.GradientTransformation, *,
                        guard_anomalies: bool = False, donate: bool = True,
                        sdc_check: str = "off"):
        """The jitted (params, opt_state, batch[, spike_cap]) -> (params,
        opt_state, metrics) step. With `guard_anomalies` the step takes a
        fourth `spike_cap` scalar and refuses to apply an update whose loss
        or grad norm is non-finite or whose loss exceeds the cap: params and
        opt_state pass through unchanged and metrics["anomalous"] is set.
        The select must live INSIDE the step — inputs are donated, so the
        host cannot keep the old state around to retry with.

        `donate=False` keeps params/opt_state un-donated (two resident
        copies of the model state), for a caller that runs the step again
        on the same inputs (parity tests). The training loop always donates.

        `sdc_check` (runtime/sdc.py) adds silent-corruption side-outputs.
        "digest": metrics gain the layout-invariant integrity fold +
        sum-of-squares of the returned params — near-zero cost, composes
        with donation, pp scan, and every tp_comm_mode; the update program
        is untouched, so sentinel-on and sentinel-off trajectories stay
        bitwise identical. "vote" additionally digests each device's
        *input-param* replica under a shard_map manual over the dp axes
        (metrics["sdc_votes"], flat order = sdc.vote_device_ids) and
        freezes params/opt_state through the same keep-old select the
        anomaly guard uses whenever the replicas disagree
        (metrics["sdc_mismatch"]) — a lying device cannot leak into the
        psummed update, and the driver repairs + re-executes at drain time.
        Voting requires sdc.vote_supported; callers downgrade to "digest"
        on unsupported layouts (the driver logs it, strategy_lint warns
        GLS103). Note the manual shard_map region legally shifts GSPMD
        partitioning decisions for the rest of the module, so a "vote" run
        may differ from an "off" run in last-bit float rounding; it is
        deterministic, and re-execution after a repair is bitwise identical
        to a clean run *in the same mode* — which is the comparison the
        fault sims make."""
        hp, mesh = self.hp, self.mesh
        from galvatron_tpu.runtime import sdc as SDC

        if sdc_check not in SDC.SDC_MODES:
            raise ValueError("sdc_check must be one of %r, got %r"
                             % (SDC.SDC_MODES, sdc_check))
        vote_fn = None
        if sdc_check == "vote":
            reason = SDC.vote_reason(hp)
            if reason is not None:
                raise ValueError(
                    "sdc_check='vote' unsupported for this layout (%s); "
                    "callers should downgrade to 'digest'" % reason)
            vote_fn = SDC.make_vote_digest_fn(self)
        # pp>1: the scan pipeline consumes the whole batch as `chunks`
        # microbatches itself — no outer accumulation loop.
        chunks = 1 if hp.pp > 1 else hp.chunks
        accum_shardings = self.shardings(self.grad_accum_specs())

        # quantized comm-precision path (parallel/quant_collectives.py): the
        # strategy's per-layer grad/param comm dtypes route the whole
        # loss+grad computation through the explicit shard_map grad ring.
        # Unsupported configs refuse with GLS013 here (and at lint time);
        # the guard combination is part of that refusal contract.
        quant_fn = None
        from galvatron_tpu.parallel import quant_collectives as QC

        if self.grad_fn is None and QC.wants_quant_comm(hp):
            QC.assert_quant_comm_supported(self.cfg, hp,
                                           anomaly_guard=guard_anomalies)
            quant_fn = QC.make_quant_loss_and_grads(self)

        with_parts = self.loss_parts_fn is not None and self.grad_fn is None and quant_fn is None

        def to_accum(g, p, s):
            # a copied leaf's cotangent comes in the compute dtype: widened
            # here as the cast's own transpose widened it, before any sum
            return jax.lax.with_sharding_constraint(g.astype(p.dtype), s)

        def train_step(params, opt_state, batch, spike_cap=None):
            mb_loss = self.loss_parts_fn if with_parts else self.loss_fn
            parts = {}
            # once a step, whatever `chunks` is; `params` stay what Adam,
            # the guard and the digest read
            read = self.compute_params(params)

            if self.grad_fn is not None:
                # 1f1b pipeline: loss and grads come out of the hand-written
                # warmup/steady/cooldown schedule in one pass. The reshard to
                # accumulator shardings happens HERE, outside the schedule's
                # scan, so no ZeRO dp-sharding constraint can propagate into
                # its stage-divergent branches; the per-leaf reshards are
                # chained so independent global collectives cannot be entered
                # in different orders by stages whose executor timelines
                # diverged in the schedule (see the divergence-safety notes in
                # pipeline_1f1b.make_loss_and_grad).
                loss, grads = self.grad_fn(params, batch)
                leaves, treedef = jax.tree.flatten(grads)
                slvs = jax.tree.leaves(accum_shardings, is_leaf=lambda x: isinstance(x, NamedSharding))
                out, prev = [], None
                for g, s in zip(leaves, slvs, strict=True):
                    if prev is not None:
                        g = jax.lax.optimization_barrier((g, prev))[0]
                    g = jax.lax.with_sharding_constraint(g, s)
                    out.append(g)
                    prev = g
                grads = jax.tree.unflatten(treedef, out)
            elif quant_fn is not None:
                # explicit quantized grad sync: microbatching and the dp
                # reduction happen inside the shard_map body; the grads come
                # out already in the accumulator shardings (the constraints
                # below are no-ops that keep the update program identical)
                loss, grads = quant_fn(params, batch)
                grads = jax.tree.map(
                    lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, accum_shardings
                )
            elif chunks == 1:
                loss, grads = jax.value_and_grad(mb_loss, has_aux=with_parts)(read, batch)
                if with_parts:
                    loss, parts = loss
                with jax.named_scope(tracing.GRAD_ACCUM):
                    grads = jax.tree.map(to_accum, grads, params, accum_shardings)
            else:
                # microbatch loop: python-unrolled so XLA can overlap each
                # microbatch's reduce-scatter with the next one's compute
                # (the reference's async_grad_reduce, runtime/arguments.py).
                def split(x):
                    return x.reshape((chunks, x.shape[0] // chunks) + x.shape[1:])

                mbs = jax.tree.map(split, batch)
                # per-microbatch weights: each microbatch loss is a mean over
                # its own valid tokens, so weight by its share of the valid
                # tokens to keep the chunked objective identical to chunks=1
                if "loss_mask" in batch:
                    mask_sums = jnp.sum(
                        mbs["loss_mask"].astype(jnp.float32), axis=tuple(range(1, batch["loss_mask"].ndim + 1))
                    )
                    weights = mask_sums / jnp.maximum(jnp.sum(mask_sums), 1.0)
                else:
                    weights = jnp.full((chunks,), 1.0 / chunks, jnp.float32)
                grads = None
                loss = 0.0
                for c in range(chunks):
                    mb = jax.tree.map(lambda x: x[c], mbs)
                    l, g = jax.value_and_grad(mb_loss, has_aux=with_parts)(read, mb)
                    w = weights[c]
                    if with_parts:
                        # the terms weighted as the loss is; the load of the
                        # fullest microbatch, the counts of all (PART_FOLDS)
                        l, mp = l
                        mp = {k: v if k in M.PART_FOLDS else v * w for k, v in mp.items()}
                        parts = mp if not parts else {
                            k: M.PART_FOLDS.get(k, jnp.add)(parts[k], v) for k, v in mp.items()}
                    with jax.named_scope(tracing.GRAD_ACCUM):
                        g = jax.tree.map(
                            lambda gi, p, s: jax.lax.with_sharding_constraint(
                                gi.astype(p.dtype) * w, s),
                            g,
                            params,
                            accum_shardings,
                        )
                        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
                    loss = loss + l * w
            with jax.named_scope(tracing.OPTIMIZER):
                updates, new_opt_state = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                grad_norm = optax.global_norm(grads)
                if M.ROUTER_COUNTS in parts:
                    # the one piece of state no gradient moves: the routers'
                    # bias steps against the sign of each expert's load over
                    # the whole batch (the counts arrive summed over dp and
                    # over the microbatches); Adam holds no state of it
                    # (runtime/optimizer.NO_GRADIENT_KEYS)
                    new_params = M.update_router_bias(
                        new_params, parts.pop(M.ROUTER_COUNTS), self.cfg.router_bias_update_rate)
            metrics = {"loss": loss, "grad_norm": grad_norm, **parts}
            if guard_anomalies:
                bad = jnp.logical_or(
                    jnp.logical_or(~jnp.isfinite(loss), ~jnp.isfinite(grad_norm)),
                    loss > spike_cap,
                )
                keep = lambda new, old: jnp.where(bad, old, new)  # noqa: E731
                with jax.named_scope(tracing.GUARD):
                    new_params = jax.tree.map(keep, new_params, params)
                    # the skipped step also must not advance the optimizer (adam
                    # moments AND the schedule counter stay put)
                    new_opt_state = jax.tree.map(keep, new_opt_state, opt_state)
                metrics["anomalous"] = bad
            if vote_fn is not None:
                # per-replica digests of the INPUT params: the dp redundancy
                # the layout already pays for. On any disagreement the state
                # freezes through the same keep-old select the guard uses —
                # the lying replica stays localized to its device instead of
                # riding the psummed update onto every replica; the driver
                # repairs from a healthy replica and re-executes.
                votes = vote_fn(params)
                mismatch = jnp.any(votes != jnp.ravel(votes)[0])
                keep_sdc = lambda new, old: jnp.where(mismatch, old, new)  # noqa: E731
                with jax.named_scope(tracing.GUARD):
                    new_params = jax.tree.map(keep_sdc, new_params, params)
                    new_opt_state = jax.tree.map(keep_sdc, new_opt_state, opt_state)
                metrics["sdc_votes"] = votes
                metrics["sdc_mismatch"] = mismatch
            if sdc_check != "off":
                # layout-invariant digest of the state this step hands back
                # (post-select): pure side-outputs, so the trajectory is
                # bitwise identical to a sentinel-off run
                fold, sumsq = SDC.tree_fold_metrics(new_params)
                metrics["sdc_fold"] = fold
                metrics["sdc_sumsq"] = sumsq
            return new_params, new_opt_state, metrics

        donate_argnums = (0, 1) if donate else ()
        # The state comes back in the shardings it went in with (the
        # parameters' `state_specs`). Left to GSPMD, an output may pick
        # another layout (a replicated norm scale comes back dp-sharded): the
        # next call then sees new input shardings, which an AOT executable
        # refuses and plain jit answers with a silent second compile, and the
        # donated buffer is not reused.
        out_shardings = (
            self.shardings(),
            self.opt_state_shardings(tx, self.abstract_params()),
            None,
        )
        if not guard_anomalies:
            def plain_step(params, opt_state, batch):
                return train_step(params, opt_state, batch)

            return jax.jit(plain_step, donate_argnums=donate_argnums,
                           out_shardings=out_shardings)
        return jax.jit(train_step, donate_argnums=donate_argnums,
                       out_shardings=out_shardings)

    def opt_state_shardings(self, tx: optax.GradientTransformation, params: Params):
        state_shape = jax.eval_shape(tx.init, params)
        shapes = jax.tree.map(lambda x: x, jax.eval_shape(lambda p: p, params))
        specs = opt_state_specs(state_shape, self.param_specs, shapes, self.zero_axes_tree(), self.mesh)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs, is_leaf=_is_spec)

    def init_opt_state(self, tx: optax.GradientTransformation, params: Params):
        return jax.jit(tx.init, out_shardings=self.opt_state_shardings(tx, params))(params)


# The share of a device's memory that a step must be left with beyond its
# state and its scanned runs' stacks, for the float32 stacks to stay. The
# benchmark's cells take 2.6 to 4.9 GiB of a v5e's 15.75 there (activations,
# the head, the update's temporaries; PERF.md section 6, PR 61), EvaByte's four
# layers at 8192 positions ask 2.18 of the 1.98 that 13.77 GiB leave: the
# compiler refuses that step, 15.95 of 15.75.
SCAN_STACKS_ROOM = 0.15


def device_memory_limit(device) -> Optional[int]:
    """What the device's allocator may hand out, in bytes (`memory_stats()`'s
    `bytes_limit`); None where the backend does not say (the CPU, a described
    topology's devices)."""
    try:
        return (device.memory_stats() or {}).get("bytes_limit") or None
    except Exception:  # a device that is described and not attached
        return None


def scan_stacks_are_tight(model: "HybridParallelModel", tx: optax.GradientTransformation,
                          limit_bytes: Optional[int] = None) -> bool:
    """Whether the model's scanned runs should stack their cotangents in the
    compute dtype (`hp.narrow_scan_grads`, models/base.run_layers): decided by
    what the launch can observe before anything is compiled, a device's share
    of the state (parameters and the optimizer's, as they are stored), of
    what a scanned run stacks beside it (its leaves' cotangents in the dtype
    they are stored in, and the copy the scan reads: the compute dtype's for a
    leaf read through a cast, `spec.cast_first_tree`), and the device's memory
    (`device_memory_limit`, or `limit_bytes`). True where the two leave less
    than `SCAN_STACKS_ROOM` of the memory for everything else. False where
    nothing is scanned, under pp (a stage's layers are stacked otherwise) and
    where the device does not say what it holds."""
    hp, cfg = model.hp, model.cfg
    if limit_bytes is None:
        limit_bytes = device_memory_limit(model.mesh.devices.flat[0])
    if not (limit_bytes and hp.scan_layers and hp.pp == 1 and isinstance(cfg, TransformerConfig)):
        return False

    def a_device(leaf, sharding, itemsize=None):
        size = 1
        for n in sharding.shard_shape(leaf.shape):
            size *= n
        return size * (itemsize or leaf.dtype.itemsize)

    params, shardings = model.abstract_params(), model.shardings()
    state = sum(jax.tree.leaves(jax.tree.map(a_device, params, shardings))) + sum(jax.tree.leaves(jax.tree.map(
        a_device, jax.eval_shape(tx.init, params), model.opt_state_shardings(tx, params))))
    kinds, narrow = cfg.layer_kinds(), jnp.dtype(cfg.compute_dtype).itemsize
    stacks = 0
    for run in M.layer_runs(hp, M.model_layer_kinds(cfg)):
        if run.length < 2:
            continue
        cast_first = S.cast_first_tree(M.layer_param_specs(
            cfg.layer_config(kinds[run.start]), layer_axes(hp, run.start)), table_stored=False)
        for i in run.layer_indices:
            stacks += sum(jax.tree.leaves(jax.tree.map(
                lambda first, leaf, sharding: a_device(leaf, sharding) + a_device(
                    leaf, sharding, min(narrow, leaf.dtype.itemsize) if first else None),
                cast_first, params["layers"][i], shardings["layers"][i])))
    return state + stacks > (1.0 - SCAN_STACKS_ROOM) * limit_bytes


def construct_hybrid_parallel_model(
    cfg: TransformerConfig,
    hp: HybridParallelConfig,
    devices=None,
    loss_fn=None,
) -> HybridParallelModel:
    M.refuse_unsupported(cfg, hp)  # GLS018: where a config first meets a layout
    mesh = build_mesh(hp, devices)
    specs = M.model_param_specs(cfg, hp)
    grad_fn = None
    loss_parts = None
    eval_loss = None
    local_loss = None
    if hp.pp > 1 and hp.pipeline_type == "pipedream_flush":
        from galvatron_tpu.parallel import pipeline_1f1b
        from galvatron_tpu.parallel.pipeline import make_pipelined_loss, stack_layer_specs, vocab_param_specs

        specs = vocab_param_specs(cfg, hp)
        specs["stages"] = stack_layer_specs(cfg, hp)
        del specs["layers"]
        grad_fn = pipeline_1f1b.make_loss_and_grad(cfg, hp, mesh)
        base_loss = lambda p, b: grad_fn(p, b)[0]
        # forward-only eval: the gpipe scan computes the identical loss
        # without the 1F1B backward slots whenever the config fits its
        # contract (even divisions, stage-uniform strategies, no cp — it
        # validates on construction); otherwise eval falls back to the
        # grad-bearing schedule
        try:
            eval_loss = make_pipelined_loss(cfg, hp, mesh)
        except ValueError:
            eval_loss = None
        fwd = None
    elif hp.pp > 1:
        from galvatron_tpu.parallel.pipeline import make_pipelined_loss, stack_layer_specs, vocab_param_specs

        specs = vocab_param_specs(cfg, hp)
        specs["stages"] = stack_layer_specs(cfg, hp)
        del specs["layers"]
        base_loss = make_pipelined_loss(cfg, hp, mesh)
        fwd = None
    elif cfg.head_type == "classification":
        base_loss = lambda p, b: M.classification_loss_fn(
            p, b, cfg, hp, mesh, model.table_spec(), zero_splits_state=model._zero_splits_state())
        fwd = lambda p, b: M.model_forward(
            p, b.get("pixels", b.get("tokens")), b.get("positions"), cfg, hp, mesh,
            attn_mask=b.get("attn_mask"), table_spec=model.table_spec(),
        )
        local_loss = lambda p, b: M.classification_loss_fn(p, b, cfg)
    else:
        base_loss = lambda p, b: M.lm_loss_fn(
            p, b, cfg, hp, mesh, table_spec=model.table_spec(), zero_splits_state=model._zero_splits_state())
        fwd = lambda p, b: M.model_forward(
            p, b["tokens"], b["positions"], cfg, hp, mesh,
            token_type_ids=b.get("token_type_ids"), attn_mask=b.get("attn_mask"),
            table_spec=model.table_spec(),
        )
        local_loss = lambda p, b: M.lm_loss_fn(p, b, cfg)
        if loss_fn is None and (getattr(cfg, "layer_aux", False) or getattr(cfg, "loop_steps", 1) > 1):
            loss_parts = lambda p, b: M.lm_loss_fn(
                p, b, cfg, hp, mesh, with_parts=True, table_spec=model.table_spec(),
                zero_splits_state=model._zero_splits_state())
    if hp.pp > 1 or loss_fn is not None:
        # custom losses have no constraint-free local form; pp>1 never takes
        # the quantized path (GLS013)
        local_loss = None
    looked_up = table_is_looked_up(pipeline_vocab_axes(hp))
    # the pp = 1 losses above read the table in the layout THIS model stores it in
    model = HybridParallelModel(
        cfg=cfg,
        hp=hp,
        mesh=mesh,
        param_specs=specs,
        loss_fn=loss_fn or base_loss,
        forward_fn=fwd,
        grad_fn=grad_fn,
        eval_loss_fn=None if loss_fn is not None else eval_loss,
        local_loss_fn=local_loss,
        loss_parts_fn=loss_parts,
        cast_first=None if loss_fn is not None else S.cast_first_tree(
            specs, table_stored=looked_up or cfg.tie_embeddings),
        table_as_stored=cfg.input_type != "patches" and looked_up and not cfg.tie_embeddings,
    )
    return model
