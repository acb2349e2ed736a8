"""Strategy-sharded KV cache for serving.

The cache is a plain pytree of preallocated buffers — one (k, v) pair per
layer, each shaped ``(max_slots, max_ctx, num_kv_heads, head_dim)`` — plus a
``lengths`` vector tracking how many valid tokens each slot holds. Its
per-layer PartitionSpec is DERIVED from that layer's searched strategy
(parallel/mesh.layer_axes), the same derivation the training forward uses:

- slot dim: sharded over the layer's dp axes (each data-parallel group owns a
  subset of concurrent requests — the serving analogue of batch sharding);
- kv-head dim: sharded over the layer's tp axes, exactly like the wkv kernel
  (models/base.layer_param_specs), so decode attention reads cache shards that
  are already co-located with the head-sharded q/wo compute;
- sequence ("page") dim: replicated — decode's length-1 query attends over
  the whole context, so sequence-sharding the cache would turn every decode
  step into a gather.

Layouts a decode cache cannot realise are REFUSED here (and by the GLS014
lint): ring context parallelism (cp>1) never materialises full per-layer k/v,
and Ulysses repurposes the tp axes for sequence all-to-alls that a one-token
query cannot amortise.

Context lengths are bucketed into pages: a request occupies
``bucket_pages(len) * page_size`` cache columns, and serve/engine.py compiles
one decode executable per page count, so admission at any prompt length hits
an already-compiled bucket.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import layer_axes, mesh_axis_size

# Matches models/base.padding_attn_bias and the XLA attention path's additive
# masking contract: exp(-1e9) == 0.0 in fp32, same as DEFAULT_MASK_VALUE.
MASK_VALUE = -1e9


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Static serving-cache geometry (fixed at engine build time)."""

    max_slots: int = 8  # max concurrent requests (cache rows)
    page_size: int = 16  # context-length quantum (bucket granularity)
    max_pages: int = 4  # max_ctx = page_size * max_pages

    @property
    def max_ctx(self) -> int:
        return self.page_size * self.max_pages

    def __post_init__(self):
        if self.max_slots < 1 or self.page_size < 1 or self.max_pages < 1:
            raise ValueError("KVCacheConfig fields must be >= 1: %s" % (self,))


def bucket_pages(length: int, page_size: int, max_pages: int) -> int:
    """Smallest page count whose context covers `length` tokens PLUS the one
    being decoded into it. Raises when the request cannot fit at all."""
    pages = -(-(int(length) + 1) // page_size)
    if pages > max_pages:
        raise ValueError(
            "request length %d needs %d pages > max_pages %d"
            % (length, pages, max_pages)
        )
    return max(1, pages)


def request_fits(kv_cfg: KVCacheConfig, prompt_len: int, max_new_tokens: int) -> bool:
    """Admission/replay feasibility for this cache geometry: the prompt plus
    every token the request may still generate must fit in max_ctx. Shared
    by ContinuousBatcher._admit (fresh requests) and migrate_to (journal
    re-prefill into a possibly smaller post-degradation cache)."""
    return int(prompt_len) + int(max_new_tokens) <= kv_cfg.max_ctx


def layer_kv_spec(
    hp: HybridParallelConfig,
    layer_idx: int,
    mesh: Mesh,
    cfg: TransformerConfig,
    max_slots: Optional[int] = None,
) -> P:
    """PartitionSpec for one layer's (slots, ctx, nkv, hd) cache buffer,
    derived from that layer's searched strategy. `max_slots` (when known)
    gates the slot-dim dp sharding on divisibility — an off-grid concurrency
    replicates slots rather than refusing (the search objective only emits
    divisible concurrencies; hand-set --serve_max_concurrency may not)."""
    axes = layer_axes(hp, layer_idx)
    s = hp.layers[layer_idx]
    if s.cp > 1:
        raise ValueError(
            "layer %d: decode KV cache cannot realise ring context "
            "parallelism (cp=%d) — serve layouts require cp=1 (GLS014)"
            % (layer_idx, s.cp)
        )
    if axes.ulysses:
        raise ValueError(
            "layer %d: Ulysses sequence parallelism repurposes the tp axes "
            "for sequence all-to-alls; a length-1 decode query cannot use "
            "them — serve layouts require sp=0 (GLS014)" % layer_idx
        )
    tp_ax = S._ax(axes.tp)
    if tp_ax is not None:
        tp_deg = mesh_axis_size(mesh, axes.tp)
        if cfg.num_kv_heads % max(tp_deg, 1) != 0:
            # GQA with fewer kv heads than the tp degree: the training path
            # replicates kv there too (repeat_kv happens inside attention).
            tp_ax = None
    dp_ax = S._ax(axes.dp)
    if dp_ax is not None and max_slots is not None:
        dp_deg = mesh_axis_size(mesh, axes.dp)
        if max_slots % max(dp_deg, 1) != 0:
            dp_ax = None
    return P(dp_ax, None, tp_ax, None)


def kv_cache_specs(
    hp: HybridParallelConfig, mesh: Mesh, cfg: TransformerConfig,
    max_slots: Optional[int] = None,
) -> Dict[str, Any]:
    """PartitionSpecs matching init_kv_cache's pytree structure."""
    per_layer = [layer_kv_spec(hp, i, mesh, cfg, max_slots)
                 for i in range(cfg.num_layers)]
    return {
        "k": list(per_layer),
        "v": list(per_layer),
        "lengths": P(),
    }


def kv_cache_shardings(
    hp: HybridParallelConfig, mesh: Mesh, cfg: TransformerConfig,
    max_slots: Optional[int] = None,
) -> Dict[str, Any]:
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sp),
        kv_cache_specs(hp, mesh, cfg, max_slots),
        is_leaf=lambda x: isinstance(x, P),
    )


def init_kv_cache(
    cfg: TransformerConfig,
    kv_cfg: KVCacheConfig,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
    dtype: Any = None,
) -> Dict[str, Any]:
    """Allocate the zeroed cache pytree; sharded per-strategy when hp/mesh
    are given, replicated otherwise (single-process tests)."""
    dtype = dtype or cfg.compute_dtype
    shape = (kv_cfg.max_slots, kv_cfg.max_ctx, cfg.num_kv_heads, cfg.head_dim)

    def alloc():
        return {
            "k": [jnp.zeros(shape, dtype) for _ in range(cfg.num_layers)],
            "v": [jnp.zeros(shape, dtype) for _ in range(cfg.num_layers)],
            "lengths": jnp.zeros((kv_cfg.max_slots,), jnp.int32),
        }

    cache = alloc()
    if hp is not None and mesh is not None:
        cache = jax.device_put(
            cache, kv_cache_shardings(hp, mesh, cfg, kv_cfg.max_slots))
    return cache


def length_bias(lengths: jax.Array, ctx: int, write_pos: Optional[jax.Array] = None) -> jax.Array:
    """Additive attention bias (B, 1, 1, ctx) admitting cache columns
    ``0 .. write_pos`` inclusive (default ``write_pos = lengths``: the decode
    step attends over everything cached so far plus the k/v it just wrote at
    position `lengths`). Carries BOTH causality and slot-length masking, so
    decode attention runs with causal=False (models/base.decode_layer_forward)."""
    if write_pos is None:
        write_pos = lengths
    cols = jnp.arange(ctx, dtype=jnp.int32)
    keep = cols[None, :] <= write_pos[:, None]
    return jnp.where(keep, 0.0, MASK_VALUE)[:, None, None, :].astype(jnp.float32)


def kv_bytes_per_slot(
    cfg: TransformerConfig, max_ctx: int, dtype_bytes: int = 2
) -> int:
    """Total KV bytes one request slot pins across all layers (k AND v) —
    the per-concurrent-request memory the serve search objective budgets."""
    return 2 * cfg.num_layers * max_ctx * cfg.num_kv_heads * cfg.head_dim * dtype_bytes


def write_prompt_kv(
    cache: Dict[str, Any],
    kvs: List[Tuple[jax.Array, jax.Array]],
    slot: jax.Array,
    prompt_len: jax.Array,
) -> Dict[str, Any]:
    """Write a prefill's per-layer (1, S_bucket, nkv, hd) k/v blocks into row
    `slot`, columns [0, S_bucket), and set lengths[slot] = prompt_len.
    Columns past prompt_len hold padding garbage; they are masked by
    length_bias until overwritten by decode steps."""
    k_list, v_list = list(cache["k"]), list(cache["v"])
    for li, (k, v) in enumerate(kvs):
        blk_k = k[0].astype(k_list[li].dtype)
        blk_v = v[0].astype(v_list[li].dtype)
        k_list[li] = jax.lax.dynamic_update_slice(k_list[li], blk_k[None], (slot, 0, 0, 0))
        v_list[li] = jax.lax.dynamic_update_slice(v_list[li], blk_v[None], (slot, 0, 0, 0))
    lengths = jax.lax.dynamic_update_slice(
        cache["lengths"], prompt_len.astype(jnp.int32)[None], (slot,)
    )
    return {"k": k_list, "v": v_list, "lengths": lengths}
