"""The two ends of the stack: the embedding with its vocabulary-parallel
lookup, and the head with its written backward and the cross entropies."""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import Params, _dense, _norm
from galvatron_tpu.obs import forms
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes, mesh_axis_size


def vocab_parallel_lookup(wte: jax.Array, tokens: jax.Array, dtype, mesh: Mesh, vax: LayerAxes,
                          table_spec: Optional[P] = None) -> jax.Array:
    """Rows of a (vocab, hidden) table whose vocabulary is split over
    ``vax.tp``: Megatron's VocabParallelEmbedding (reference
    GPTModel_tensor_parallel.py:84-132), written out. Each device shifts the
    ids by its first row, gathers the rows it holds, has zeros for the ids it
    does not hold, and the partial results are summed over the tp axes.

    A manual region, not ``wte[tokens]`` left to GSPMD: there the gather and
    its scatter-add are device-local, where GSPMD runs a one-hot matmul as a
    matmul and partitions the scatter-add of a sharded gather with
    collective-permutes (parallel/pipeline_1f1b.py embed_fwd). The rows are
    gathered from the stored shard and cast afterwards, so the table's
    gradient accumulates over repeated ids in the parameter's dtype. The
    result is whole over tp; under Megatron-SP the caller's constraint to
    `act_spec` slices it into sequence shards (the compiler makes a
    reduce-scatter of sum and slice).

    Two forms, chosen by `table_spec`, the spec the table is STORED in (None:
    `vocab_embed_spec(vax)`, as `param_specs` places it):

    - whole over dp, ``P(tp, None)``: the psum over tp is the only collective,
      and the region's transpose sums the table's cotangent over dp;
    - its hidden dim split over the ZeRO axes, ``P(tp, dp)`` (a ZeRO-3 table,
      and the one ZeRO-2's train step stores in the layout of Adam's moments,
      runtime/model_api.state_specs): the region is manual over those axes
      too, and what crosses dp is the lookup's, not the table's. The ids are
      gathered over dp, every replica's rows are read from the (vocab/tp,
      hidden/dp) shard, and ONE all_to_all hands each replica its token rows
      whole, (B, S, H/dp) -> (B/dp, S, H); the psum over tp comes last, on the
      same operand as in the first form. Nothing is written for the backward:
      the transposes give a scatter-add of every replica's cotangents into a
      (vocab/tp, hidden/dp) array in the table's dtype, complete on its chip,
      with no sum over dp. Token rows the dp axes do not divide are whole on
      every replica already: no id gather and no all_to_all, the rows
      leave the region split over dp on the hidden dim as the table is, and
      GSPMD gathers them where the caller asks for them whole."""
    tp = tuple(vax.tp)
    rows = wte.shape[0] // mesh_axis_size(mesh, tp)
    if table_spec is None:
        table_spec = S.vocab_embed_spec(vax)
    over = table_split_axes(table_spec, vax)
    forms.took(forms.TABLE_LOOKUP, "rows_over_dp" if over else "table_whole")

    # serve hands in (1, ctx) and (slots, 1): rows the dp axes do not divide stay whole
    split_rows = tokens.shape[0] % mesh_axis_size(mesh, vax.batch_axes) == 0
    tok_spec = P(S._ax(vax.batch_axes) if split_rows else None, S._ax(vax.cp))

    def local(table, tok):
        if over and split_rows:
            tok = jax.lax.all_gather(tok, over, axis=0, tiled=True)
        idx = tok - jax.lax.axis_index(tp) * rows
        # an id of another device's rows goes out of bounds: the gather fills
        # it with zeros, and its transpose drops the update
        idx = jnp.where((idx >= 0) & (idx < rows), idx, rows)
        x = table.at[idx].get(mode="fill", fill_value=0).astype(dtype)
        if over and split_rows:
            x = jax.lax.all_to_all(x, over, 0, x.ndim - 1, tiled=True)
        return jax.lax.psum(x, tp)

    # whole token rows leave the region with the hidden dim as the table has
    # it, and the caller's constraint gathers it
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(S._ax(tp), S._ax(over)), tok_spec),
        out_specs=P(*tok_spec, None if split_rows else S._ax(over)),
    )(wte, tokens)


def table_split_axes(table_spec: P, vax: LayerAxes) -> Tuple[str, ...]:
    """The ZeRO axes of the vocabulary's `LayerAxes` where a token table
    stored as `table_spec` is split over them on its hidden dim, else ()."""
    hidden = S._entry_axes(table_spec[1]) if len(table_spec) > 1 else ()
    zero = tuple(vax.dp) if vax.zero_opt else ()
    return zero if zero and hidden == zero else ()


def table_is_looked_up(vax: Optional[LayerAxes]) -> bool:
    """Whether `embed_tokens` reads the token table by `vocab_parallel_lookup`
    (from the stored shard, cast afterwards) and not as `wte.astype(dtype)`."""
    return vax is not None and len(vax.tp) > 0 and not vax.ulysses


def embed_tokens(p_embed: Params, tokens: jax.Array, positions: jax.Array, cfg: TransformerConfig,
                 mesh: Optional[Mesh] = None, vax: Optional[LayerAxes] = None,
                 token_type_ids: Optional[jax.Array] = None,
                 table_spec: Optional[P] = None) -> jax.Array:
    """Token (+ position, + token-type) embedding. A table split over the
    vocabulary (vocab_tp > 1, not ulysses) is read by `vocab_parallel_lookup`,
    in the form the spec it is stored in asks for (`table_spec`; None: as
    `param_specs` places it); any other table is whole on the vocab dim and
    read by a plain gather."""
    wte = p_embed["wte"]
    if table_is_looked_up(vax):
        x = vocab_parallel_lookup(wte, tokens, cfg.compute_dtype, mesh, vax, table_spec)
    else:
        x = wte.astype(cfg.compute_dtype)[tokens]
    if cfg.position_type == "learned":
        x = x + p_embed["wpe"].astype(cfg.compute_dtype)[positions]
    if cfg.type_vocab_size:
        tti = token_type_ids if token_type_ids is not None else jnp.zeros_like(tokens)
        x = x + p_embed["tte"].astype(cfg.compute_dtype)[tti]
    if cfg.embed_norm:
        x = _norm(x, p_embed["norm"], cfg)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def patchify(pixels: jax.Array, patch: int) -> jax.Array:
    """(B, H, W, C) image -> (B, N, patch*patch*C) patch vectors. A dense on
    this layout equals the stride-`patch` conv patch embedding (HF ViT
    projection) and keeps the op a plain MXU matmul."""
    b, hh, ww, c = pixels.shape
    gh, gw = hh // patch, ww // patch
    x = pixels.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, patch * patch * c)


def embed_patches(p_embed: Params, pixels: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """ViT patch embedding: patchify + dense + [cls token] + learned positions."""
    dtype = cfg.compute_dtype
    x = patchify(pixels.astype(dtype), cfg.patch_size)
    x = _dense(x, p_embed["patch"], dtype)
    if cfg.use_cls_token:
        cls = jnp.broadcast_to(
            p_embed["cls_token"].astype(dtype), (x.shape[0], 1, cfg.hidden_size)
        )
        x = jnp.concatenate([cls, x], axis=1)
    x = x + p_embed["wpe"].astype(dtype)[: x.shape[1]]
    if cfg.embed_norm:
        x = _norm(x, p_embed["norm"], cfg)
    return x


def _times_kernel(x: jax.Array, kernel: jax.Array, tied: bool) -> jax.Array:
    return x @ (kernel.T if tied else kernel)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _head_matmul(x: jax.Array, kernel: jax.Array, tied: bool) -> jax.Array:
    """`x @ kernel` (`x @ kernel.T` for the tied table) for the head's kernel
    in the compute dtype. Evaluated, it is just that. Differentiated, its two
    barriers keep apart what the TPU compiler otherwise fuses into the
    backward's matmuls, to their cost (PERF.md, PR 30):

    - a kernel cast from a wider parameter is made once and forward, input
      gradient and kernel gradient read that one array; folded into each
      matmul, the (hidden, V) cast is redone for every tile of tokens (one
      that arrives in the compute dtype has no cast, and the barrier holds
      the array as it came);
    - the input gradient is written before the final norm's backward reads
      it; as the matmul's epilogue a LayerNorm's reductions held it at 79 %
      of the MXU."""
    return _times_kernel(x, kernel, tied)


def _head_matmul_fwd(x, kernel, tied):
    kernel = jax.lax.optimization_barrier(kernel)
    return _times_kernel(x, kernel, tied), (x, kernel)


def _head_matmul_bwd(tied, res, g):
    x, kernel = res
    lead = tuple(range(x.ndim - 1))
    dx = jax.lax.optimization_barrier(_times_kernel(g, kernel, not tied))
    dkernel = jax.lax.dot_general(*((g, x) if tied else (x, g)), ((lead, lead), ((), ())))
    return dx, dkernel


_head_matmul.defvjp(_head_matmul_fwd, _head_matmul_bwd)


def head_logits(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """`x` times the vocabulary kernel (`lm_head.kernel`, or the tied table
    transposed) in the compute dtype. A head of several predictions a position
    (`pred_heads` > 1) is the same ONE matmul on `pred_heads` x vocab_size
    columns, head i's the i-th run of vocab_size (`next_tokens_cross_entropy`
    splits them)."""
    tied = cfg.tie_embeddings
    stored = params["embed"]["wte"] if tied else params["lm_head"]["kernel"]
    logits = _head_matmul(x, stored.astype(cfg.compute_dtype), tied)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def lm_logits(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    if cfg.pre_norm:
        x = _norm(x, params["final_norm"], cfg)
    return head_logits(params, x, cfg)


def model_head(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Dispatch to the family's output head (reference `Cls_` modules,
    models/gpt_hf/GPTModel_sequential.py:201-215 and the bert/vit analogues)."""
    if cfg.head_type == "lm":
        return lm_logits(params, x, cfg)
    if cfg.head_type == "mlm":
        if cfg.pre_norm:
            x = _norm(x, params["final_norm"], cfg)
        hp_ = params["head"]
        y = _dense(x, hp_["transform"], cfg.compute_dtype)
        y = jax.nn.gelu(y, approximate=False)
        y = _norm(y, hp_["norm"], cfg)
        return head_logits(params, y, cfg) + hp_["bias"].astype(cfg.compute_dtype)
    if cfg.head_type == "classification":
        if cfg.pre_norm:
            x = _norm(x, params["final_norm"], cfg)
        pooled = x[:, 0] if cfg.pool_type == "cls" else jnp.mean(x, axis=1)
        return _dense(pooled, params["head"], cfg.compute_dtype)
    raise ValueError(cfg.head_type)


def _label_mask(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Where a row's label sits. A compare against an iota and never a gather,
    so each vocabulary shard answers for its own columns and XLA inserts the
    psum of what is reduced over it."""
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return vocab_iota == labels[..., None]


@jax.custom_vjp
def _token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Float32 cross entropy a token, `lse(logits) - logits[label]`, with a
    written backward: `(softmax - onehot) * g`, formed once from the logits as
    they came and the row's maximum and sum, rounded once to the logits' dtype.
    Autodiff of the forward also differentiates the row maximum, whose
    gradient is zero by algebra, and pays a second sweep of the logits with
    its own `exp` to find that out."""
    return _token_nll_fwd(logits, labels)[0]


def _token_nll_fwd(logits, labels):
    # one maximum, then one sweep for the sum of exponentials and the label's logit
    logits32 = logits.astype(jnp.float32)
    m = jnp.max(logits32, axis=-1, keepdims=True)
    s = jnp.sum(jnp.exp(logits32 - m), axis=-1)
    label_logit = jnp.sum(jnp.where(_label_mask(logits, labels), logits32, 0.0), axis=-1)
    return jnp.log(s) + m[..., 0] - label_logit, (logits, m, s, labels)


def _token_nll_bwd(res, g):
    # term by term what autodiff forms with the maximum held constant: a
    # column that is not its row's maximum gets autodiff's own float
    logits, m, s, labels = res
    p_g = jnp.exp(logits.astype(jnp.float32) - m) * (g / s)[..., None]
    dlogits = p_g - jnp.where(_label_mask(logits, labels), g[..., None], 0.0)
    return dlogits.astype(logits.dtype), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def token_cross_entropies(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """The float32 cross entropy of every position, (B, S): `vocab_parallel_cross_entropy` before its mean,
    for an objective that weighs positions itself (a looped stack's exit distribution, models/base.looped_loss)."""
    return _token_nll(logits, labels)


def vocab_parallel_cross_entropy(logits: jax.Array, labels: jax.Array,
                                 loss_mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean cross entropy, safe for vocab-sharded logits.

    The label-logit extraction uses a masked reduction over the vocab dim
    instead of a gather, so each vocab shard contributes only its own slice
    and XLA inserts the psum — the compiler-derived form of the reference's
    vocab_parallel_cross_entropy (site_package/megatron/core/tensor_parallel/
    cross_entropy.py:174-219)."""
    losses = _token_nll(logits, labels)
    if loss_mask is None:
        return jnp.mean(losses)
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(losses * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


def next_tokens_cross_entropy(logits: jax.Array, labels: jax.Array, loss_mask: Optional[jax.Array],
                              heads: int) -> jax.Array:
    """The loss of a head that predicts the next `heads` tokens of every
    position from one matmul: logits (B, S, heads x V), head i the i-th run of V
    columns, read in float32; `labels[t]` is the token after position t, so head
    i's target at t is `labels[t + i]`, and a target past the sequence's end
    (the last i positions) is masked, as is one `loss_mask` masks at t + i.
    -> the mean over the heads of each head's mean cross entropy (equal weights)."""
    b, s, _ = logits.shape
    counted = jnp.ones((b, s), jnp.float32) if loss_mask is None else loss_mask.astype(jnp.float32)
    inside = jnp.arange(s)[None, :, None] < s - jnp.arange(heads)  # (1, S, heads): t + i is a position
    targets = jnp.stack([jnp.roll(labels, -i, axis=1) for i in range(heads)], axis=2)
    counted = jnp.stack([jnp.roll(counted, -i, axis=1) for i in range(heads)], axis=2) * inside
    losses = _token_nll(logits.astype(jnp.float32).reshape(b, s, heads, -1), targets)
    return jnp.mean(jnp.sum(losses * counted, axis=(0, 1)) / jnp.maximum(jnp.sum(counted, axis=(0, 1)), 1.0))


def softmax_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross entropy over (B, C) logits / (B,) integer labels."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)
