"""EVA attention, EvaByte's token mixer in every layer: softmax attention that
is exact inside the query's own window and sees everything before the window
as one pooled key and value a chunk, in the same softmax
(ops/eva_attention.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.attention import ATTENTION, qkv_projection
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, no_form
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.eva_attention import aggregate, pooled
from galvatron_tpu.ops.rope import apply_rotary

POOLED_MASS = "eva_pooled_mass"  # the layer's counter (obs/telemetry.EVA_STEP_FIELDS)
COUNTERS = {POOLED_MASS: "mean"}  # how the stack folds the layers' values (parts/__init__.COUNTERS)


def _validate(cfg: TransformerConfig) -> None:
    if (cfg.eva_chunk < 1 or cfg.eva_window < cfg.eva_chunk or cfg.eva_window % cfg.eva_chunk
            or cfg.max_seq_len % cfg.eva_chunk or not cfg.causal or cfg.position_type != "rope"
            or cfg.num_kv_heads != cfg.num_heads or cfg.qk_norm or cfg.attn_output_gate or cfg.attn_head_gate
            or cfg.latent_attention or cfg.diff_attention or cfg.partial_rotary_factor != 1.0 or cfg.rope_scaling):
        raise ValueError(
            "EVA attention layers want eva_window a multiple of eva_chunk, a sequence of whole chunks, and causal "
            "attention of as many key heads as query heads under plain rope on whole heads, with no gate, QK-norm, "
            "latent or differential form (the one form written: EvaByte's); got eva_window=%d eva_chunk=%d "
            "max_seq_len=%d, %d heads on %d, position_type %r"
            % (cfg.eva_window, cfg.eva_chunk, cfg.max_seq_len, cfg.num_heads, cfg.num_kv_heads, cfg.position_type))


# the decode engine's cache holds every key of a slot and pools none; the ring passes whole blocks
# of keys and the pooled keys of earlier windows would have to cross context ranks; the heads have
# not been split over tensor-parallel ranks; the pipeline engines' last stage runs the ordinary
# cross entropy, not the head of several predictions that comes with these layers; no cost-model row
UNSUPPORTED = no_form(
    "EVA attention layers",
    serve="no cache of EVA attention's pooled keys and values (serve/kv_cache.py holds every key of a slot)",
    autotune="an EVA attention layer as full attention",
    pp="run no head of several predictions a position after the last stage, which EVA attention layers come with",
    tp="EVA attention layers (the pooled keys of earlier windows have no form across context or sequence ranks, "
       "and the heads have not been split over tensor-parallel ranks)",
    quant="an EVA attention layer's counter",
)


def _init_eva(ks, cfg: TransformerConfig) -> Params:
    """The attention part's projections (`wqkv`, `wo`) and, under `eva`, a
    head's two learned vectors: `phi`, which weighs a chunk's positions, and
    `mu`, added to the pooled key; both `clamp(N(0, 1), -1, 1) x head_dim^-1/2`,
    float32 (EvaByte's initialisation as recalled: `assumed` in the benchmark's
    configuration)."""
    p = ATTENTION.init(ks, cfg)
    kphi, kmu = jax.random.split(jax.random.fold_in(ks[0], 3))
    shape, scale = (cfg.num_heads, cfg.head_dim), cfg.head_dim ** -0.5
    p["eva"] = {"phi": jnp.clip(jax.random.normal(kphi, shape, jnp.float32), -1.0, 1.0) * scale,
                "mu": jnp.clip(jax.random.normal(kmu, shape, jnp.float32), -1.0, 1.0) * scale}
    return p


def eva_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *, attn_sharding=None, **_):
    """EVA attention on normed activations (B, S, H): q, k, v projected without
    a bias, q and k turned by rope on whole heads BEFORE anything else; each
    chunk of `eva_chunk` positions pooled to one key and value a head
    (`ops/eva_attention.pooled`); a query's own window and the pooled chunks of
    every earlier window under ONE softmax (`aggregate`); then `wo`. -> out,
    None, the layer's counter (`eva_pooled_mass`: the mean, over heads and the
    queries past window 0, of the softmax mass on pooled keys). Scopes: the
    pooling under `gt.attn.eva_prep`, the aggregation under `gt.attn.eva_agg`,
    all else under `gt.attn.eva`. `attn_sharding` tells the aggregation where
    its operands lie: on TPUs it runs as Pallas kernels."""
    dtype = cfg.compute_dtype
    with jax.named_scope(tracing.ATTN_EVA):
        q, k, v = qkv_projection(p, y, cfg, dtype)
        q, k = apply_rotary(q, positions, cfg.rope_theta), apply_rotary(k, positions, cfg.rope_theta)
    with jax.named_scope(tracing.ATTN_EVA_PREP):
        kp, vp = pooled(k, v, p["eva"]["phi"], p["eva"]["mu"], chunk=cfg.eva_chunk)
    with jax.named_scope(tracing.ATTN_EVA_AGG):
        attn, mass = aggregate(q, k, v, kp, vp, window=cfg.eva_window, chunk=cfg.eva_chunk,
                               sm_scale=cfg.head_dim ** -0.5, impl="xla" if cfg.attn_impl == "xla" else "auto",
                               sharding=attn_sharding)
        past = jax.lax.stop_gradient(mass[:, :, cfg.eva_window:])
        said = {POOLED_MASS: jnp.mean(past) if past.shape[2] else jnp.zeros((), jnp.float32)}
    with jax.named_scope(tracing.ATTN_EVA):
        o = _dense(attn.reshape(attn.shape[0], attn.shape[1], -1), p["wo"], dtype)
    return o, None, said


def _eva_specs(cfg: TransformerConfig, axes) -> Params:
    # (tp, sp, cp are refused, GLS018: the attention part's specs lay the projections out for dp and ZeRO)
    return {**ATTENTION.specs(cfg, axes), "eva": {"phi": P(None, None), "mu": P(None, None)}}


EVA = LayerPart(_init_eva, eva_mixer, _eva_specs, (tracing.ATTN_EVA, tracing.ATTN_EVA_PREP, tracing.ATTN_EVA_AGG),
                counters=True, validate=_validate, unsupported=lambda cfg: UNSUPPORTED)
