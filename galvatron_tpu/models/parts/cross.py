"""A cross layer of SambaY's cross-decoder (Phi-4-mini-flash's seven layers in
thirty-two): differential attention with NO keys or values of its own, its
queries on the keys and values ONE earlier full-attention layer published."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.attention import (diff_attention_mixer, diff_specs, init_diff, place_diff)
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense_init, _proj_std, no_form
from galvatron_tpu.obs import tracing
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes


def _validate(cfg: TransformerConfig) -> None:
    if not cfg.diff_attention or cfg.latent_attention:
        raise ValueError("cross layers read the keys and values a DIFFERENTIAL full-attention layer publishes "
                         "(diff_attention; the one form written), not latent attention's; got diff_attention=%s "
                         "kv_lora_rank=%d" % (cfg.diff_attention, cfg.kv_lora_rank))


UNSUPPORTED = no_form(
    "cross layers",
    serve="no cache that one layer writes and later layers read (serve/kv_cache.py holds a layer's own keys and "
          "values)",
    autotune="a cross layer as self-attention",
    pp="carry no tensor a layer publishes for later layers across stages (the keys and values a cross layer reads)",
    tp="cross layers (the keys and values are one full-attention layer's, whole on its chip)",
    quant="a layer that reads another layer's tensor",
)


def _init_cross(ks, cfg: TransformerConfig) -> Params:
    """q's and the output's projections as the attention part lays them out
    (`wq` (h, nh, hd) + bias, `wo`), and differential attention's own leaves."""
    h, hd, nh = cfg.hidden_size, cfg.head_dim, cfg.num_heads
    p: Params = {"wq": {"kernel": _dense_init(ks[0], (h, nh, hd), cfg.init_std, cfg.param_dtype)},
                 "wo": {"kernel": _dense_init(ks[1], (nh * hd, h), _proj_std(cfg), cfg.param_dtype)},
                 "diff": init_diff(jax.random.fold_in(ks[0], 2), cfg)}
    if cfg.qkv_bias:
        p["wq"]["bias"] = jnp.zeros((nh, hd), cfg.param_dtype)
    if cfg.out_bias:
        p["wo"]["bias"] = jnp.zeros((h,), cfg.param_dtype)
    return p


def cross_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *, shared, **how):
    """`q = y Wq + b` alone; K and V are the last full-attention layer's before
    it (`shared`), the mask causal over the whole sequence; the differential form,
    its own four lambda vectors, its own sub-norm and `Wo + b`
    (`parts/attention.diff_attention_mixer`). The two projections under
    `gt.attn.cross`. -> out, None, None."""
    return diff_attention_mixer(p, y, positions, cfg, attn_bias=how.get("attn_bias"),
                                attn_sharding=how.get("attn_sharding"), scope=tracing.ATTN_CROSS, shared=shared)


def _cross_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    sp: Params = {"wq": {"kernel": P(z3, None, None)}, "wo": {"kernel": P(None, z3)}, "diff": diff_specs(axes)}
    if cfg.qkv_bias:
        sp["wq"]["bias"] = P(None, None)
    if cfg.out_bias:
        sp["wo"]["bias"] = r1
    return sp


CROSS = LayerPart(_init_cross, cross_mixer, _cross_specs, (tracing.ATTN_CROSS, tracing.ATTN_DIFF), validate=_validate,
                  unsupported=lambda cfg: UNSUPPORTED, reads=("k", "v"), place=place_diff)
