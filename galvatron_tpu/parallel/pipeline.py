"""SPMD pipeline parallelism: scan over microbatch ticks + ppermute stage shift.

TPU-native replacement for the reference's hand-rolled pipeline engine
(galvatron/core/runtime/pipeline/pipeline.py: GPipe :718-883, 1F1B :375-701,
batched P2P :1080-1257). Instead of per-rank send/recv of activations, the
whole pipeline is ONE jitted SPMD program:

- layer parameters are *stacked across stages* with a leading ``pp`` dim
  sharded over the ``pp`` mesh axis, so stage s's weights live only on its
  devices;
- activations live in a ``(pp, mb, S, H)`` rolling buffer, also ``pp``-sharded;
- each scan tick vmaps the stage body over the pp dim (GSPMD partitions it so
  every stage group computes only its own slice — MPMD from vmap+sharding),
  then ``jnp.roll`` shifts outputs to the next stage: XLA lowers the roll of a
  pp-sharded buffer to a single collective-permute over ICI, the analogue of
  the reference's `batch_isend_irecv` p2p (pipeline.py:1095-1127);
- microbatch t enters stage 0 at tick t and exits stage pp-1 at tick t+pp-1;
  total ticks = num_microbatches + pp - 1 (the GPipe bubble).

The backward pass is jax autodiff through the scan — including the reversed
collective-permutes — which also makes tied-embedding gradients (used by both
stage 0 and the last stage) correct with no embedding-group all-reduce
(reference grad_reduce.py:68-124).

This module is the GPipe schedule; `pipeline_type="pipedream_flush"` runs the
true 1F1B engine in parallel/pipeline_1f1b.py (bounded activation stash,
hand-written backward, heterogeneous per-stage strategies).

GPipe-scan restrictions (asserted): equal layers per stage; within-stage layer
strategies — including checkpoint flags — uniform across stages (the vmapped
body is one program; heterogeneous configs must use 1F1B); no ring-attention
CP inside pp>1.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import PP_AXIS, layer_axes, pipeline_vocab_axes, vocab_axes

Params = Dict[str, Any]

def validate_pipeline_config(hp: HybridParallelConfig):
    if hp.pp <= 1:
        return
    div = hp.pp_division
    if len(set(div)) != 1:
        raise ValueError(
            "pipelined execution requires equal layers per stage, got pp_division=%s "
            "(pad the model or use pp_division of equal parts)" % (div,)
        )
    lps = div[0]
    for j in range(lps):
        strategies = {hp.layers[s * lps + j] for s in range(hp.pp)}
        if len(strategies) != 1:
            raise ValueError(
                "within-stage layer %d must use the same strategy on every stage "
                "for the gpipe scan pipeline (use pipeline_type='pipedream_flush' "
                "for per-stage heterogeneous strategies); got %s" % (j, strategies)
            )
    for s in hp.layers:
        if s.cp > 1:
            raise ValueError(
                "cp>1 with pp>1 runs through the 1F1B engine "
                "(pipeline_type='pipedream_flush'), not the scan pipeline: "
                "the vmapped body here computes attention without the ring "
                "shard_map, which is wrong for zigzag-permuted cp layouts"
            )
    if hp.global_bsz % hp.chunks != 0:
        raise ValueError("global_bsz must divide into chunks")


def layers_per_stage(hp: HybridParallelConfig) -> int:
    """Slot count of the stacked layout: max layers on any stage. Equal
    divisions (the gpipe contract) make every slot live on every stage;
    the 1F1B engine also accepts UNEVEN divisions (reference slices
    arbitrary model_ranks, pipeline.py:110-112) — stages with fewer layers
    hold zero-filled padding in the trailing slots, statically skipped by
    their stage body and receiving exactly-zero gradients."""
    return max(hp.pp_division)


def stage_layer_offsets(hp: HybridParallelConfig) -> List[int]:
    """Global index of each stage's first layer."""
    out, acc = [], 0
    for n in hp.pp_division:
        out.append(acc)
        acc += n
    return out


# ------------------------------------------------------- stacked param layout
def stack_layer_specs(cfg, hp: HybridParallelConfig):
    """Param specs for the stacked layout: for each within-stage layer index j,
    the per-layer spec prefixed with the pp axis."""
    from galvatron_tpu.models.base import layer_param_specs

    lps = layers_per_stage(hp)
    out = []
    for j in range(lps):
        # storage-layout hint only: slot j is keyed to GLOBAL layer j's axes
        # (always valid: max(div) <= total layers); the within-stage layout
        # is resolved by GSPMD inside the manual-over-pp shard_map, and the
        # stage bodies reshard per layer
        ax = layer_axes(hp, j)
        spec_j = layer_param_specs(cfg, ax)
        out.append(jax.tree.map(lambda sp: P(PP_AXIS, *sp), spec_j, is_leaf=lambda x: isinstance(x, P)))
    return out


def vocab_param_specs(cfg, hp: HybridParallelConfig) -> Params:
    """The model's parameter specs with the vocabulary layers as a pipeline
    stores them, for both engines: the vocabulary dim of the token table, of
    an untied head's kernel and of an MLM head's bias is split over
    ``('pp',) + vocab_tp`` (`pipeline_vocab_axes`), so a chip holds
    1/(pp * vocab_tp) of their state and no stage holds what another holds.
    The scan pipeline computes them in that layout too (`make_pipelined_loss`);
    the 1F1B engine gathers a within-stage copy once a step. Under vocab-SP
    the vocabulary is dense: the scan reads it as `model_param_specs` lays it
    out, the 1F1B engine, which gathers anyway, stores it over pp alone."""
    from galvatron_tpu.models import base as M

    specs = M.model_param_specs(cfg, hp)
    vax = pipeline_vocab_axes(hp)
    if vax.ulysses and hp.pipeline_type != "pipedream_flush":
        return specs
    vocab_ax = S._ax((PP_AXIS,) if vax.ulysses else vax.tp)
    if cfg.input_type != "patches":
        specs["embed"]["wte"] = P(vocab_ax, S._ax(vax.dp) if vax.zero3 else None)
    if cfg.head_type in ("lm", "mlm") and not cfg.tie_embeddings:
        specs["lm_head"]["kernel"] = P(None, vocab_ax)
    if cfg.head_type == "mlm":
        specs["head"]["bias"] = P(vocab_ax)
    return specs


def stack_params(layer_params: List[Params], hp: HybridParallelConfig) -> List[Params]:
    """[n_layers trees] -> [layers_per_stage trees with leading pp dim].
    Uneven divisions pad the short stages' trailing slots with zeros (all
    layers of a family share one tree shape)."""
    lps = layers_per_stage(hp)
    offs = stage_layer_offsets(hp)
    zero = jax.tree.map(jnp.zeros_like, layer_params[0])
    stacked = []
    for j in range(lps):
        per_stage = [
            layer_params[offs[s] + j] if j < hp.pp_division[s] else zero
            for s in range(hp.pp)
        ]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage))
    return stacked


def unstack_params(stacked: List[Params], hp: HybridParallelConfig) -> List[Params]:
    offs = stage_layer_offsets(hp)
    layers: List[Params] = [None] * len(hp.layers)  # type: ignore
    for j, tree in enumerate(stacked):
        for s in range(hp.pp):
            if j < hp.pp_division[s]:
                layers[offs[s] + j] = jax.tree.map(lambda x: x[s], tree)
    return layers


# ----------------------------------------------------------------- the engine
def pipeline_apply(
    stacked_layers: List[Params],
    x_mb: jax.Array,  # (num_mb, mb, S, H) embedded microbatches
    positions_mb: jax.Array,  # (num_mb, mb, S)
    cfg,
    hp: HybridParallelConfig,
    mesh: Mesh,
    attn_bias_mb: Optional[jax.Array] = None,  # (num_mb, mb, 1, 1, S)
) -> jax.Array:
    """Run the scan pipeline; returns (num_mb, mb, S, H) last-stage outputs."""
    from galvatron_tpu.models.base import layer_forward
    from galvatron_tpu.ops.kernels import KernelSharding

    pp, num_mb = hp.pp, hp.chunks
    lps = layers_per_stage(hp)

    # the mask is threaded through the scan only when present — a None here is
    # a trace-time constant, so maskless runs keep `bias is None` inside
    # layer_forward and the flash-attention dispatch stays eligible
    use_bias = attn_bias_mb is not None

    def stage_body(stage_layers: List[Params], x, pos, bias=None):
        for j in range(lps):
            # mesh=None: GSPMD lays the vmapped body out from the stacked
            # weights alone. The flash kernel is the exception — it cannot
            # be partitioned, so it gets its layout explicitly; the vmap's
            # spmd_axis_name below puts the stage dim into its manual region
            fwd = partial(
                layer_forward, cfg=cfg, mesh=None, axes=None, attn_bias=bias,
                attn_sharding=KernelSharding.for_layer(mesh, layer_axes(hp, j)),
            )
            if hp.layers[j].checkpoint:
                fwd = jax.checkpoint(fwd)
            x = fwd(stage_layers[j], x, pos)
        return x

    vstage = jax.vmap(stage_body, in_axes=(0, 0, 0, 0) if use_bias else (0, 0, 0),
                      spmd_axis_name=PP_AXIS)

    ax0 = layer_axes(hp, 0)
    buf_spec = P(PP_AXIS, S._ax(ax0.batch_axes), S._ax(ax0.seq_axes), None)
    pos_buf_spec = P(PP_AXIS, S._ax(ax0.batch_axes), S._ax(ax0.seq_axes))

    mb_shape = x_mb.shape[1:]
    total = num_mb + pp - 1
    pad = total - num_mb

    def padded(t):
        return jnp.concatenate([t, jnp.zeros((pad,) + t.shape[1:], t.dtype)], 0)

    carry0 = [jnp.zeros((pp,) + mb_shape, x_mb.dtype),
              jnp.zeros((pp,) + positions_mb.shape[1:], positions_mb.dtype)]
    xs = [padded(x_mb), padded(positions_mb)]
    if use_bias:
        carry0.append(jnp.zeros((pp,) + attn_bias_mb.shape[1:], attn_bias_mb.dtype))
        xs.append(padded(attn_bias_mb))

    def tick(carry, xt):
        # shift previous outputs to the next stage; microbatch enters stage 0.
        shifted = [jnp.roll(c, 1, axis=0).at[0].set(inp) for c, inp in zip(carry, xt)]
        shifted[0] = S.constrain(shifted[0], mesh, buf_spec)
        shifted[1] = S.constrain(shifted[1], mesh, pos_buf_spec)
        out = vstage(stacked_layers, *shifted)
        out = S.constrain(out, mesh, buf_spec)
        return [out] + shifted[1:], out[-1]

    _, ys = jax.lax.scan(tick, carry0, tuple(xs))
    return ys[pp - 1 :]


def make_pipelined_loss(cfg, hp: HybridParallelConfig, mesh: Mesh):
    """Loss over the pipelined model; batch is split into `chunks` microbatches
    INSIDE this function, so the train step's grad-accumulation loop must not
    split again (model_api handles this). Serves every head type of the
    generic tree (lm / mlm / classification — the reference's per-model `Cls_`
    stages, GPTModel_sequential.py:201-215)."""
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.parts.embed_head import (embed_patches, embed_tokens, model_head, softmax_nll,
                                                       vocab_parallel_cross_entropy)

    validate_pipeline_config(hp)
    # activations between the layers lie as the stage's vocabulary axes have
    # them; the table's rows and the logits' columns are split over pp too
    vax, pvax = vocab_axes(hp), pipeline_vocab_axes(hp)

    def loss_fn(params, batch):
        if pvax != vax:
            forms.took(forms.VOCAB_SPLIT, ",".join(pvax.tp))
        num_mb = hp.chunks
        with jax.named_scope(tracing.EMBED):
            if cfg.input_type == "patches":
                inputs = batch["pixels"]
                x = embed_patches(params["embed"], inputs, cfg)
                positions = jnp.zeros(x.shape[:2], jnp.int32)
            else:
                inputs = batch["tokens"]
                positions = batch["positions"]
                x = embed_tokens(params["embed"], inputs, positions, cfg, mesh, pvax,
                                 token_type_ids=batch.get("token_type_ids"))
        B = x.shape[0]
        mb = B // num_mb

        # GSPMD hazard (WA005; seen on jax 0.4.37, not ruled out on the
        # installed jax; sibling of the stack_layer_run finding in
        # models/base.py): reshaping a dp-SHARDED batch dim into
        # (num_mb, mb, ...) and feeding the result straight into the tick
        # scan MISCOMPILES — silently wrong values, no error, and only when
        # the incoming batch is sharded (an unsharded batch computes the
        # pp=1 loss exactly; measured 4e-4 loss drift in float64, the
        # test_pipeline_matches_dp failures). Pinning the microbatch layout
        # explicitly (microbatch dim unsharded, per-microbatch batch dim on
        # the dp axes) right after the reshape makes the result
        # layout-independent again; tests pin this parity.
        def split(t, seq_dim=2):
            r = t.reshape((num_mb, mb) + t.shape[1:])
            entries = [None, S._ax(vax.batch_axes)] + [None] * (r.ndim - 2)
            if seq_dim is not None and r.ndim > seq_dim:
                entries[seq_dim] = S._ax(vax.seq_axes)
            return S.constrain(r, mesh, P(*entries))

        bias_mb = None
        if batch.get("attn_mask") is not None:
            # the bias' trailing dim is key positions, not the activation
            # sequence layout — keep it (and the singleton dims) unsharded
            bias_mb = split(M.padding_attn_bias(batch["attn_mask"]), seq_dim=None)
        # embed all microbatches up-front: every chip looks its own rows of
        # the table up and the sum over pp x vocab_tp hands every stage the
        # whole batch (under vocab-SP: replicated across pp groups)
        outs = pipeline_apply(params["stages"], split(x), split(positions), cfg, hp, mesh,
                              attn_bias_mb=bias_mb)
        h = outs.reshape((B,) + x.shape[1:])
        h = S.constrain(h, mesh, S.act_spec(vax))
        with jax.named_scope(tracing.HEAD_LOSS):
            logits = model_head(params, h, cfg)
            if cfg.head_type == "classification":
                return softmax_nll(logits, batch["labels"])
            logits = S.constrain(logits, mesh, S.logits_spec(pvax))
            return vocab_parallel_cross_entropy(
                logits, batch["labels"], batch.get("loss_mask"))

    return loss_fn
