"""PartitionSpec builders: per-layer parameter / activation shardings.

This module replaces three reference subsystems at once:

- per-layer FSDP wrapping with ShardingStrategy {NO_SHARD, SHARD_GRAD_OP,
  FULL_SHARD} (reference: galvatron/core/runtime/parallel.py:92-199) — here,
  ZeRO-3 is a parameter sharding over the layer's dp sub-axes and ZeRO-1/2 is
  an optimizer-state/grad-accumulator sharding: `zero_split_spec` is the one
  statement of where dp goes on a leaf, read by the moments
  (runtime/optimizer.opt_state_specs), the accumulated gradient and the
  stored state (runtime/model_api.grad_accum_specs) and a scanned run's
  cotangent (models/base.stacked_layer_grad_specs). That the per-microbatch
  sum over dp ENDS in those shards, a reduce-scatter and not an all-reduce
  that is sliced afterwards, is made true where the sum is made: for the
  scanned layers inside the backward scan's body, by `constrain_grad_as` on
  the run's stacked leaves (models/base.run_layers);
- Megatron Column/RowParallelLinear weight partitioning with per-layer groups
  (reference: site_package/megatron/core/tensor_parallel/layers.py:126-228) —
  here, a column kernel is `P(..., tp)` and a row kernel `P(tp, ...)`;
- activation redistribution between layers with different strategies
  (reference: galvatron/core/runtime/redistribute.py, parallel.py:279-313) —
  here, `jax.lax.with_sharding_constraint` on the layer boundary makes XLA
  insert exactly the split/all-gather/all-to-all collectives the reference
  hand-writes.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.parallel.mesh import LayerAxes

Axes = Union[None, str, Tuple[str, ...]]


def _entry_axes(e: Axes) -> Tuple[str, ...]:
    if e is None:
        return ()
    if isinstance(e, str):
        return (e,)
    return tuple(e)


def _ax(axes: Sequence[str]) -> Axes:
    """Collapse an axis-name tuple for use inside a PartitionSpec."""
    axes = tuple(axes)
    if not axes:
        return None
    if len(axes) == 1:
        return axes[0]
    return axes


# ----------------------------------------------------------------- activations
def act_spec(ax: LayerAxes, *, seq_dim: int = 1, ndim: int = 3) -> P:
    """Sharding of a (batch, seq, hidden) activation *between* layers.

    Batch is sharded over dp; sequence over cp (+ tp when the layer runs
    ulysses or megatron-sp). The hidden dim stays unsharded between layers —
    inside a TP layer XLA re-partitions as the matmuls require."""
    entries = [None] * ndim
    entries[0] = _ax(ax.batch_axes)
    entries[seq_dim] = _ax(ax.seq_axes)
    return P(*entries)


def logits_spec(ax: LayerAxes) -> P:
    """(batch, seq, vocab) logits. vocab_sp=0: vocab sharded over tp
    (vocab-parallel lm head + loss). vocab_sp=1 (ulysses/vocab-SP): sequence
    stays tp-sharded and vocab is dense (reference
    vocab_sequence_parallel_cross_entropy, site_package/megatron/core/
    tensor_parallel/cross_entropy.py:174-219)."""
    if ax.ulysses:
        return P(_ax(ax.batch_axes), _ax(ax.seq_axes), None)
    return P(_ax(ax.batch_axes), _ax(ax.cp), _ax(ax.tp))


# ------------------------------------------------------------------ parameters
def _zero3_axes(ax: LayerAxes) -> Tuple[str, ...]:
    return tuple(ax.dp) if ax.zero3 else ()


def col_kernel_spec(ax: LayerAxes) -> P:
    """Column-parallel kernel (in_dim, out_dim): out over tp; ZeRO-3 shards the
    in dim over dp. With ulysses the tp axes hold sequence, so the kernel is
    *not* tp-sharded (reference transformer.py:2065-2177 keeps dense weights)."""
    tp = () if ax.ulysses else ax.tp
    return P(_ax(_zero3_axes(ax) or ()), _ax(tp))


def row_kernel_spec(ax: LayerAxes) -> P:
    """Row-parallel kernel (in_dim, out_dim): in over tp; ZeRO-3 shards out."""
    tp = () if ax.ulysses else ax.tp
    return P(_ax(tp), _ax(_zero3_axes(ax) or ()))


def replicated_1d_spec(ax: LayerAxes) -> P:
    """LayerNorm scales / row-parallel biases: replicated over tp; ZeRO-3
    shards over dp (the FSDP flat-param analogue)."""
    return P(_ax(_zero3_axes(ax) or ()))


def vocab_embed_spec(ax: LayerAxes) -> P:
    """(vocab, hidden) embedding table, vocab-parallel over tp
    (reference: VocabParallelEmbedding, models/gpt_hf/GPTModel_tensor_parallel.py:84-132).
    Under vocab-SP (ulysses) the tp axes carry sequence, so the table stays
    vocab-dense (matching logits_spec) and ZeRO-3 shards the vocab dim."""
    if ax.ulysses:
        return P(_ax(_zero3_axes(ax) or ()), None)
    return P(_ax(ax.tp), _ax(_zero3_axes(ax) or ()))


# ------------------------------------------------ where ZeRO keeps a gradient
def zero_axes(ax: LayerAxes) -> Tuple[str, ...]:
    """The dp axes ZeRO splits a layer's moments and gradient over: the
    layer's dp axes under ZeRO-1/2/3, none under ddp."""
    return tuple(ax.dp) if ax.zero_opt else ()


def zero_split_spec(param_spec: P, shape, dp_axes, mesh_shape) -> P:
    """Where ZeRO puts dp on a leaf, stated once: the dp sub-axes go on the
    first dim that is unsharded and divisible, the flat-param shard analogue
    of FSDP SHARD_GRAD_OP (reference parallel.py:107-111, cost_model.py:99-110).
    The layout of Adam's moments (runtime/optimizer.opt_state_specs), of the
    accumulated gradient and a copied leaf of the state
    (runtime/model_api.grad_accum_specs) and of a scanned run's cotangent
    (models/base.stacked_layer_grad_specs). `param_spec` itself, the very
    object, where nothing is to split: no dp axes (ddp, dp = 1), a leaf
    already split over dp (ZeRO-3), a leaf no dim of which divides."""
    if not dp_axes:
        return param_spec
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))
    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh_shape[a]
    used = {x for e in entries for x in _entry_axes(e)}
    if any(a in used for a in dp_axes):
        return param_spec  # already dp-sharded (zero3 param)
    for i, e in enumerate(entries):
        if e is None and shape[i] % dp_size == 0:
            entries[i] = _ax(dp_axes)
            return P(*entries)
    return param_spec


# ------------------------------------------------- how the models read a leaf
def cast_first_tree(param_specs, *, table_stored: bool):
    """Tree of bools shaped like `param_specs`: True where a model reads the
    leaf ONCE, in code GSPMD partitions, and only through
    `.astype(compute_dtype)` that comes first, so that a copy rounded
    beforehand gives the forward the same values and the backward the same
    cotangent, summed over dp as before (runtime/model_api.compute_params:
    what ZeRO-2 gathers over dp is then that copy, not the float32 leaf).

    Every family's dense kernels and biases, position and type tables are
    such leaves, and the token table read once as `wte.astype(dtype)[tokens]`.
    Read in the dtype they are STORED in, and so False: a norm's scale and
    bias (any dict that holds a "scale"); the relative-position tables of T5
    and Swin; of a routed block (a dict that holds a "router") the router's
    kernel (float32 logits) and the experts' kernels, which enter
    `ops/moe.moe_ffn`'s manual region whole, so that its boundary sums their
    cotangents over dp in the dtype they came in; of a linear-attention mixer
    its convolution's taps and its gate's `A_log` and `dt_bias` (float32
    arithmetic); an EVA attention layer's `phi` and `mu` (the dict "eva": the
    pooling is float32); a looped stack's exit gate (the dict "exit_gate":
    float32 logits); hyper-connections' leaves (the dicts "hc1", "hc2": float32
    coefficients); and with `table_stored` the
    token table: one that `vocab_parallel_lookup` takes into its manual
    region (rows gathered from the stored shard, the gradient scatter-added
    in the stored dtype), or a tied one, whose uses' cotangents are each
    widened before they are summed. Kept out of the copy is not gathered
    after the update: the looked-up, untied table is stored split over dp
    as well (runtime/model_api.state_specs' third case) and the lookup reads
    it as it lies; a tied one lies as `param_specs` has it.
    tests/models/test_compute_copy.py holds this to every family's traced
    loss."""

    def walk(node, stored):
        if isinstance(node, P):
            return not stored
        if isinstance(node, dict):
            norm = "scale" in node
            routed = ("router", "wi", "wo_mlp") if "router" in node else ()
            return {
                k: walk(v, stored or norm or k in routed or k.endswith("rel_bias")
                        or k in ("conv", "A_log", "dt_bias", "eva", "exit_gate", "hc1", "hc2")
                        or (table_stored and k == "wte"))
                for k, v in node.items()
            }
        return [walk(v, stored) for v in node]

    return walk(param_specs, False)


# ------------------------------------------------------------------- utilities
def named(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def constrain(x, mesh: Mesh, spec: P):
    """Reshard an activation to `spec` — the XLA-native Module_with_relocation
    (reference parallel.py:279-313): collectives are inserted by the compiler."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _constrain_both_ways(x, sharding: NamedSharding, grad_sharding: NamedSharding):
    return jax.lax.with_sharding_constraint(x, sharding)


_constrain_both_ways.defvjp(
    lambda x, sharding, grad_sharding: (_constrain_both_ways(x, sharding, grad_sharding), None),
    lambda sharding, grad_sharding, _, g: (jax.lax.with_sharding_constraint(g, grad_sharding),))


def constrain_grad_as(x, mesh: Mesh, spec: P, grad_spec: P):
    """`constrain(x, mesh, spec)` whose cotangent is constrained to
    `grad_spec` instead. The transpose of a sharding constraint is the same
    constraint on the cotangent: a leaf the forward reads whole over dp has
    its gradient pinned whole over dp, and a sum over dp that could end in
    the shards ZeRO keeps (a reduce-scatter) has to end whole on every chip
    (an all-reduce). Equal specs give the plain constraint, and the jaxpr it
    always gave."""
    if spec == grad_spec:
        return constrain(x, mesh, spec)
    return _constrain_both_ways(x, NamedSharding(mesh, spec), NamedSharding(mesh, grad_spec))


def meet_spec(a: P, b: P, ndim: int) -> P:
    """Per-dim longest common prefix of two PartitionSpecs.

    Resharding a -> meet -> b is *axis-monotone*: every step only drops or
    appends trailing mesh axes on each dim, so XLA lowers it with group-scoped
    collectives (all-gather / slice) and never an axis-reassigning
    collective-permute. That property is what makes heterogeneous per-layer
    reshards safe inside the 1F1B schedule's stage-divergent branches, where a
    collective-permute (whose XLA rendezvous spans ALL devices) would deadlock
    across stages running different branches."""
    ea = list(a) + [None] * (ndim - len(a))
    eb = list(b) + [None] * (ndim - len(b))
    out = []
    for xa, xb in zip(ea, eb):
        ta, tb = _entry_axes(xa), _entry_axes(xb)
        common = []
        for i in range(min(len(ta), len(tb))):
            if ta[i] != tb[i]:
                break
            common.append(ta[i])
        out.append(_ax(common))
    return P(*out)


def monotone_constrain(x, mesh: Mesh, from_spec: P, to_spec: P):
    """Constrain `x` (currently sharded as `from_spec`) to `to_spec`, routing
    through the per-dim meet when the direct transition would reassign a dim
    between different mesh axes. Trace-time decision: when the transition is
    already nested (meet equals one endpoint) no extra constraint is emitted."""
    meet = meet_spec(from_spec, to_spec, x.ndim)
    norm = lambda s: tuple(list(s) + [None] * (x.ndim - len(s)))
    if norm(meet) not in (norm(from_spec), norm(to_spec)):
        x = constrain(x, mesh, meet)
    return constrain(x, mesh, to_spec)


def replicated_spec(ndim: int) -> P:
    return P(*([None] * ndim))
