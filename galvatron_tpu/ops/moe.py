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

Dispatch and combine are permutations, so their transposes are gathers too
(`_take_rows`, `_dispatch`), not the scatter-adds autodiff would derive.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.attention import KernelSharding

Aux = Dict[str, jax.Array]  # load_balance, router_z, load_max_over_mean


# (rows, K, N) tiles of the megablox kernels, measured on a v5e at OLMoE's
# shapes (65536 rows, K and N of 1024 and 2048; scripts/moe_gmm_sweep.py):
# forward + backward of the two matmuls 17.5 ms against 24.2 for XLA:TPU's own
# `ragged_dot` kernel and 188 at megablox's default 128-tiles; larger tiles
# run out of the 16 MiB of scoped VMEM, as these do for 4-byte operands, whose
# K and N tiles are therefore half the size
GMM_TILING = (512, 1024, 1024)


def grouped_matmul(rows: jax.Array, kernels: jax.Array, group_sizes: jax.Array,
                   on_tpu: bool = False) -> jax.Array:
    """(M, K) rows sorted by group x (G, K, N) kernels -> (M, N): row i is
    multiplied by the kernel of the group it falls in. Groups may be empty.
    On a TPU, where the rows fill whole tiles, the megablox kernels (their
    names carry the caller's scope into a trace; XLA's `ragged-dot` custom
    call carries none); otherwise `jax.lax.ragged_dot`."""
    if on_tpu and rows.shape[0] % GMM_TILING[0] == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm, tk, tn = GMM_TILING
        wide = rows.dtype.itemsize // 2  # 1 for bf16, 2 for float32
        return gmm(rows, kernels, group_sizes, preferred_element_type=rows.dtype,
                   tiling=(tm, tk // wide, tn // wide))
    return jax.lax.ragged_dot(rows, kernels, group_sizes)


@jax.custom_vjp
def _take_rows(x, perm, inv_perm):
    """x[perm] for a permutation `perm` whose inverse is `inv_perm`; the
    cotangent is gathered back by the inverse, not scattered."""
    return x[perm]


def _take_rows_fwd(x, perm, inv_perm):
    return x[perm], (perm, inv_perm)


def _take_rows_bwd(res, g):
    perm, inv_perm = res
    return g[inv_perm], None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


@jax.custom_vjp
def _dispatch(y, order, inv_order):
    """Row order[i] // k of y, for every assignment i in expert order: each
    token's row k times over. The cotangent of token t is the sum of its k
    assignments' cotangents, gathered back into token order."""
    return y[order // (order.shape[0] // y.shape[0])]


def _dispatch_fwd(y, order, inv_order):
    return _dispatch(y, order, inv_order), (inv_order, y.shape[0])


def _dispatch_bwd(res, g):
    inv_order, tokens = res
    g = g[inv_order].reshape(tokens, -1, g.shape[-1])
    return jnp.sum(g.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def router_logits(y: jax.Array, router_kernel: jax.Array) -> jax.Array:
    """(T, H) x (H, E) -> float32 (T, E), multiplied in float32."""
    return jnp.dot(y.astype(jnp.float32), router_kernel.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _local_moe(y, router_kernel, wi, wo, *, k: int, norm_topk_prob: bool, activate, dtype,
               on_tpu: bool, stat_axes: Tuple[str, ...] = ()):
    """The block on the tokens one device holds; `stat_axes` are the mesh
    axes the router's statistics are summed over (the batch's)."""
    tokens, hidden = y.shape
    num_experts = router_kernel.shape[-1]
    with jax.named_scope(tracing.MOE_ROUTER):
        logits = router_logits(y, router_kernel)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, k)  # (T, k)
        if norm_topk_prob:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        z_sum = jnp.sum(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        prob_sum = jnp.sum(probs, axis=0)  # (E,)
    with jax.named_scope(tracing.MOE_DISPATCH):
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        inv_order = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=jnp.int32), unique_indices=True)
        counts = jnp.sum(flat[:, None] == jnp.arange(num_experts, dtype=flat.dtype),
                         axis=0, dtype=jnp.int32)
        rows = _dispatch(y, order, inv_order)  # (T*k, H), sorted by expert
    with jax.named_scope(tracing.MOE_EXPERTS):
        with jax.named_scope(tracing.MOE_GMM_IN):
            mid = grouped_matmul(rows, wi.astype(dtype), counts, on_tpu)
        mid = activate(mid)
        with jax.named_scope(tracing.MOE_GMM_OUT):
            out = grouped_matmul(mid, wo.astype(dtype), counts, on_tpu)
    with jax.named_scope(tracing.MOE_COMBINE):
        out = _take_rows(out, inv_order, order).reshape(tokens, k, hidden)
        out = jnp.sum(out.astype(jnp.float32) * weights[..., None], axis=1).astype(dtype)
    with jax.named_scope(tracing.MOE_ROUTER):
        total = jnp.float32(tokens)
        counts_f = counts.astype(jnp.float32)
        if stat_axes:
            z_sum, prob_sum, counts_f, total = jax.lax.psum(
                (z_sum, prob_sum, counts_f, total), stat_axes)
        aux = {
            "load_balance": num_experts * jnp.sum(counts_f / total * (prob_sum / total)),
            "router_z": z_sum / total,
            "load_max_over_mean": jnp.max(counts_f) / jnp.mean(counts_f),
        }
    return out, aux


def swiglu(mid: jax.Array) -> jax.Array:
    """(M, 2F), gate beside up -> silu(gate) x up, (M, F)."""
    ffn = mid.shape[-1] // 2
    return jax.nn.silu(mid[:, :ffn]) * mid[:, ffn:]


def moe_ffn(y: jax.Array, router_kernel: jax.Array, wi: jax.Array, wo: jax.Array, *,
            experts_per_token: int, norm_topk_prob: bool = False, activate=swiglu,
            dtype=jnp.bfloat16, sharding: Optional[KernelSharding] = None) -> Tuple[jax.Array, Aux]:
    """y (B, S, H) -> (B, S, H) and the router's auxiliary terms.

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
              dtype=dtype, on_tpu=on_tpu)
    if sharding is None or sharding.mesh.size == 1:
        out, aux = _local_moe(y.reshape(b * s, h), router_kernel, wi, wo, **kw)
        return out.reshape(b, s, h), aux

    batch_axes = tuple(sharding.batch_axes)

    def body(y, router_kernel, wi, wo):
        lb, ls, _ = y.shape
        out, aux = _local_moe(y.reshape(lb * ls, h), router_kernel, wi, wo,
                              stat_axes=batch_axes, **kw)
        return out.reshape(lb, ls, h), aux

    ctx = jax.sharding.get_abstract_mesh()
    use_mesh = sharding.mesh if ctx.empty else ctx
    tokens = P(batch_axes or None, None, None)
    return jax.shard_map(
        body, mesh=use_mesh, in_specs=(tokens, P(), P(), P()),
        out_specs=(tokens, {name: P() for name in ("load_balance", "router_z",
                                                   "load_max_over_mean")}),
        axis_names=set(use_mesh.axis_names) - set(use_mesh.manual_axes),
        check_vma=False,
    )(y, router_kernel, wi, wo)
