"""Routed (sparse) experts: a float32 router, top-k, dropless dispatch.

The feed-forward half of a mixture-of-experts block as OLMoE runs it
(arXiv:2409.02060; HF `OlmoeSparseMoeBlock`): every token is sent to the
`k` experts its router ranks highest and the result is the sum of their
outputs weighted by the router's probabilities. **Dropless**: there is no
capacity factor, no padding to a fixed capacity and no dropped token; the
`tokens x k` assignments are sorted by expert, the rows gathered in that
order, and each expert multiplies its own ragged group of rows
(`grouped_matmul`: on a TPU the Pallas megablox kernels at a measured tiling,
elsewhere `jax.lax.ragged_dot`; `scripts/moe_gmm_sweep.py` is the chip
measurement behind the choice).

The router runs in float32 at `highest` matmul precision whatever the
compute dtype: the choice of experts is a discrete function of its logits,
and a bf16 router flips near-ties. Its two auxiliary losses come back with
the output, as the sums and counts they are made of, so that a caller that
splits the tokens over devices can add them up first:

    load balancing   E x sum_e f_e P_e   f_e = assignments to e / tokens
                                         P_e = mean router probability of e
    router z-loss    mean_t logsumexp(logits_t)^2

**The layout is k-major**: assignment `j x tokens + t` is token `t`'s `j`-th
choice, so whatever is in token order is `k` slabs of `(tokens, hidden)` and
the sums over `k` (the combine's forward, the dispatch's backward) add slabs.
A TPU tiles an array's two minor dimensions by 8 x 128: with `k` there, as in
`(tokens, k, hidden)`, every `k` that is not a multiple of 8 is padded to one,
each reshape to `(tokens x k, hidden)` moves every row, and the compiler
fuses nothing across it (float32 copies of all rows; PERF.md, PR 34). The ROWS
enter the grouped matmuls as they always did, expert by expert and token by
token within one: the sort's key is `expert x tokens + t`.

Dispatch and combine are permutations, so their transposes are gathers too
(`_dispatch`, `_combine`), not the scatter-adds autodiff would derive, and the
combine's backward works in expert order, where the rows and their cotangent
live: it keeps the bf16 rows and never a float32 array of every row.

A second router (`score="sigmoid"`: DeepSeek-V3's, as GLM-4.7-Flash
configures it, `topk_method: noaux_tc`): each expert's score is the sigmoid
of its own logit, the `k` experts are chosen by score PLUS a bias that takes
no gradient, and the weights are the chosen experts' scores alone,
renormalised and scaled. That router has no auxiliary loss; it hands back the
assignments an expert got, which the train step moves the bias by.

**A share of the experts** (`held=(first, count)`): the router ranks all the
experts, this program holds the kernels of `count` of them and computes their
part of the result for the tokens sent to them; what the experts held
elsewhere would add is left out. Every assignment is still sorted and
gathered (the shapes are static), and the grouped matmul runs over the held
experts' rows alone, so its time follows the routing.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.attention import KernelSharding

# load_max_over_mean always; load_balance, router_z (the softmax router's
# losses); counts (E,) and bias_abs_max (a router with a bias); rows_held (a
# share of the experts): moe_aux_names says which for a configuration
Aux = Dict[str, jax.Array]


def moe_aux_names(score: str, bias: bool, held: bool) -> Tuple[str, ...]:
    return (("load_max_over_mean",)
            + (("load_balance", "router_z") if score == "softmax" else ())
            + (("counts", "bias_abs_max") if bias else ())
            + (("rows_held",) if held else ()))


# (rows, K, N) tiles of the megablox kernels, measured on a v5e at OLMoE's
# shapes (65536 rows, K and N of 1024 and 2048; scripts/moe_gmm_sweep.py):
# forward + backward of the two matmuls 17.5 ms against 24.2 for XLA:TPU's own
# `ragged_dot` kernel and 188 at megablox's default 128-tiles; larger tiles
# run out of the 16 MiB of scoped VMEM, as these do for 4-byte operands, whose
# K and N tiles are therefore half the size
GMM_TILING = (512, 1024, 1024)


def grouped_matmul(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array,
                   on_tpu: bool = False, first_group: Optional[int] = None) -> jax.Array:
    """(M, K) rows sorted by group x (G, K, N) kernels -> (M, N): row i is
    multiplied by the kernel of the group it falls in. Groups may be empty.
    On a TPU, where the rows fill whole tiles, the megablox kernels (their
    names carry the caller's scope into a trace; XLA's `ragged-dot` custom
    call carries none); otherwise `jax.lax.ragged_dot`.

    `first_group`: the kernels are those of groups `first_group` to
    `first_group + G` of more groups than G (`group_sizes` counts them all);
    the rows of the other groups come back zero, and send no gradient. The
    megablox kernels visit the held groups' tiles alone (`group_offset`,
    their own form of a sharded expert dim)."""
    if on_tpu and rows.shape[0] % GMM_TILING[0] == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = GMM_TILING
        wide = rows.dtype.itemsize // 2  # 1 for bf16, 2 for float32
        offset = None if first_group is None else jnp.int32(first_group)
        return gmm(rows, kernels, group_sizes, rows.dtype, (tm, tk // wide, tn // wide), offset)
    if first_group is not None:
        # ragged_dot has no such form: zero kernels stand in the other groups' places
        after = group_sizes.shape[0] - first_group - kernels.shape[0]
        kernels = jnp.pad(kernels, ((first_group, after), (0, 0), (0, 0)))
    return jax.lax.ragged_dot(rows, kernels, group_sizes)


def _k_major(x):
    """(tokens, k) -> (k x tokens,): entry j x tokens + t is x[t, j]. Columns
    laid end to end: a transpose and a reshape would be a relayout on a TPU
    wherever k does not fill a tile."""
    return jnp.concatenate([x[:, j] for j in range(x.shape[1])])


@jax.custom_vjp
def _dispatch(y, order, inv_order):
    """Row order[i] % tokens of y, for every assignment i in expert order:
    each token's row k times over. The cotangent of token t is the sum of its
    k assignments' cotangents, gathered back into token order and summed over
    the major axis."""
    return y[order % y.shape[0]]


def _dispatch_fwd(y, order, inv_order):
    return _dispatch(y, order, inv_order), (inv_order, y.shape[0])


def _dispatch_bwd(res, g):
    inv_order, tokens = res
    return _sum_over_k(g, inv_order, tokens, None), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _sum_over_k(rows, inv_order, tokens, weights):
    """sum_j rows[inv_order[j x tokens + t]] (x weights[t, j]) in float32, j
    = 0 .. k-1 in that order, rounded once to the rows' dtype: (k x tokens,
    H) rows in expert order -> (tokens, H). One gather into token order, then
    its k slabs of `tokens` rows as slices, and no reshape between the gather
    and the sum: a reshape there the TPU compiler moves off the gather and
    then fuses nothing across (a float32 copy of every row, written and read
    again). A gather a slab instead: 1 % faster at k = 4 in the one routed
    cell, 1 % slower at k = 8 in the other and 2 % more memory there (PERF.md,
    PR 34)."""
    total = None
    rows = rows[inv_order]
    for j in range(inv_order.shape[0] // tokens):
        term = rows[j * tokens:(j + 1) * tokens].astype(jnp.float32)
        if weights is not None:
            term = term * weights[:, j, None]
        total = term if total is None else term + total
    return total.astype(rows.dtype)


@jax.custom_vjp
def _combine(out, weights, order, inv_order):
    """(k x tokens, H) rows in expert order, float32 (tokens, k) weights ->
    (tokens, H): token t's k rows, weighted and summed in float32."""
    return _sum_over_k(out, inv_order, weights.shape[0], weights)


def _combine_fwd(out, weights, order, inv_order):
    return _combine(out, weights, order, inv_order), (out, weights, order, inv_order)


def _combine_bwd(res, g):
    # in EXPERT order, where the rows and their cotangent live: the token's
    # cotangent gathered to each of its assignments (as `_dispatch` gathers
    # the token's row), then one pass over it and the rows
    out, weights, order, inv_order = res
    tokens, k = weights.shape
    g = g[order % tokens].astype(jnp.float32)
    w = _k_major(weights)[order]
    d_out = (g * w[:, None]).astype(out.dtype)
    d_w = jnp.sum(out.astype(jnp.float32) * g, axis=-1)[inv_order]
    d_w = jnp.stack([d_w[j * tokens:(j + 1) * tokens] for j in range(k)], axis=1)
    return d_out, d_w, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def router_logits(y: jax.Array, router_kernel: jax.Array) -> jax.Array:
    """(T, H) x (H, E) -> float32 (T, E), multiplied in float32."""
    return jnp.dot(y.astype(jnp.float32), router_kernel.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _local_moe(y, router_kernel, bias, wi, wo, *, k: int, norm_topk_prob: bool, activate, dtype,
               on_tpu: bool, score: str = "softmax", scale: float = 1.0,
               held: Optional[Tuple[int, int]] = None, stat_axes: Tuple[str, ...] = ()):
    """The block on the tokens one device holds; `stat_axes` are the mesh
    axes the router's statistics are summed over (the batch's)."""
    tokens = y.shape[0]
    num_experts = router_kernel.shape[-1]
    with jax.named_scope(tracing.MOE_ROUTER):
        logits = router_logits(y, router_kernel)
        if score == "softmax":
            probs = jax.nn.softmax(logits, axis=-1)
            weights, experts = jax.lax.top_k(probs, k)  # (T, k)
            if norm_topk_prob:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
            z_sum = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
            prob_sum = jnp.sum(probs, axis=0)  # (E,)
        else:
            scores = jax.nn.sigmoid(logits)
            ranked = scores if bias is None else scores + jax.lax.stop_gradient(bias)
            experts = jax.lax.top_k(ranked, k)[1]
            weights = jnp.take_along_axis(scores, experts, axis=-1)  # of the scores alone
            if norm_topk_prob:
                weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        if scale != 1.0:
            weights = weights * scale
    with jax.named_scope(tracing.MOE_DISPATCH):
        # k-major: assignment j x tokens + t is token t's j-th choice
        flat = _k_major(experts)
        assert num_experts * tokens < 2**31
        slot = jnp.arange(k * tokens, dtype=jnp.int32)
        # a token's k experts are distinct, so the keys are: by expert, by token within one
        order = jnp.argsort(flat * tokens + slot % tokens).astype(jnp.int32)
        inv_order = jnp.zeros_like(order).at[order].set(slot, unique_indices=True)
        counts = jnp.sum(flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype),
                         axis=0, dtype=jnp.int32)
        rows = _dispatch(y, order, inv_order)  # (T*k, H), sorted by expert
    share = {} if held is None else {"first_group": held[0]}
    with jax.named_scope(tracing.MOE_EXPERTS):
        with jax.named_scope(tracing.MOE_GMM_IN):
            mid = grouped_matmul(rows, wi.astype(dtype), counts, on_tpu, **share)
        mid = activate(mid)
        with jax.named_scope(tracing.MOE_GMM_OUT):
            out = grouped_matmul(mid, wo.astype(dtype), counts, on_tpu, **share)
    with jax.named_scope(tracing.MOE_COMBINE):
        out = _combine(out, weights, order, inv_order).astype(dtype)
    with jax.named_scope(tracing.MOE_ROUTER):
        total = jnp.float32(tokens)
        counts_f = counts.astype(jnp.float32)
        if score == "softmax":
            if stat_axes:
                z_sum, prob_sum, counts_f, total = jax.lax.psum(
                    (z_sum, prob_sum, counts_f, total), stat_axes)
            aux = {
                "load_balance": num_experts * jnp.sum(counts_f / total * (prob_sum / total)),
                "router_z": z_sum / total,
            }
        else:
            if stat_axes:
                counts_f = jax.lax.psum(counts_f, stat_axes)
            aux = {}
        aux["load_max_over_mean"] = jnp.max(counts_f) / jnp.mean(counts_f)
        if bias is not None:
            # the whole batch's assignments an expert: what the step moves the bias by
            aux["counts"] = counts_f
            aux["bias_abs_max"] = jnp.max(jnp.abs(bias))
        if held is not None:
            aux["rows_held"] = jnp.sum(
                jax.lax.dynamic_slice_in_dim(counts_f, held[0], held[1]))
    return out, aux


def swiglu(mid: jax.Array) -> jax.Array:
    """(M, 2F), gate beside up -> silu(gate) x up, (M, F)."""
    ffn = mid.shape[-1] // 2
    return jax.nn.silu(mid[:, :ffn]) * mid[:, ffn:]


def moe_ffn(y: jax.Array, router_kernel: jax.Array, wi: jax.Array, wo: jax.Array, *,
            experts_per_token: int, norm_topk_prob: bool = False, activate=swiglu,
            dtype=jnp.bfloat16, sharding: Optional[KernelSharding] = None,
            score: str = "softmax", bias: Optional[jax.Array] = None, scale: float = 1.0,
            held: Optional[Tuple[int, int]] = None) -> Tuple[jax.Array, Aux]:
    """y (B, S, H) -> (B, S, H) and the router's auxiliary terms
    (`moe_aux_names`). `score`, `bias` (E,), `scale` and `held`: the second
    router and a share of the experts, as the module's docstring has them;
    with `held`, `wi` and `wo` lead with the held experts' count.

    `router_kernel` (H, E); `wi` (E, H, W) with W = 2F for SwiGLU, the gate's
    F columns beside the up projection's (one matmul, and no reshape of a
    kernel whose minor dims a TPU tiles); `wo` (E, F, H); `activate` maps the
    (rows, W) product to (rows, F). The platform is read off the mesh, as
    attention does, so a compile for a described TPU takes the chip's branch.
    On a mesh of more than one device (`sharding`, as attention's) each
    device sorts and multiplies the batch rows it holds against whole
    experts, in a region manual over every mesh axis, and the router's
    statistics are summed over the batch axes; there is no expert
    parallelism here, so the experts' kernels enter the region whole."""
    b, s, h = y.shape
    on_tpu = sharding.on_tpu if sharding is not None else jax.default_backend() == "tpu"
    kw = dict(k=experts_per_token, norm_topk_prob=norm_topk_prob, activate=activate,
              dtype=dtype, on_tpu=on_tpu, score=score, scale=scale, held=held)
    if sharding is None or sharding.mesh.size == 1:
        out, aux = _local_moe(y.reshape(b * s, h), router_kernel, bias, wi, wo, **kw)
        return out.reshape(b, s, h), aux

    batch_axes = tuple(sharding.batch_axes)

    def body(y, router_kernel, wi, wo, *bias):
        lb, ls, _ = y.shape
        out, aux = _local_moe(y.reshape(lb * ls, h), router_kernel, bias[0] if bias else None,
                              wi, wo, stat_axes=batch_axes, **kw)
        return out.reshape(lb, ls, h), aux

    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = sharding.mesh if ctx.empty else ctx
    tokens = P(batch_axes or None, None, None)
    operands = (y, router_kernel, wi, wo) + (() if bias is None else (bias,))
    names = moe_aux_names(score, bias is not None, held is not None)
    return jax.shard_map(
        body, mesh=use_mesh, in_specs=(tokens,) + (P(),) * (len(operands) - 1),
        out_specs=(tokens, {name: P() for name in names}),
        axis_names=set(use_mesh.axis_names) - set(use_mesh.manual_axes),
        check_vma=False,
    )(*operands)
