"""T5 family (reference: galvatron/models/T5/).

Encoder-decoder with TWO layer types — the reference's multi-layer-type path
(dynamic_programming.py:170-189; T5 search space enumerates encoder and
decoder strategies independently). Here `hp.layers` covers
`enc_layers + dec_layers` in order, so per-layer hybrid strategies apply to
both halves and the search engine's multi-layer-type DP maps 1:1.

Architecture (matching HF T5ForConditionalGeneration): rmsnorm pre-LN, no
biases, relative-position-bucket attention bias shared across layers within
each stack, unscaled attention logits (the 1/sqrt(d) is folded into init),
relu or gated-gelu MLP, tied embeddings with d_model**-0.5 logit scaling."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.registry import ModelFamily, register
from galvatron_tpu.ops.attention import core_attention
from galvatron_tpu.ops.norms import rms_norm
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import PP_AXIS, LayerAxes, layer_axes, vocab_axes

Params = Dict[str, Any]

META_CONFIGS = {
    # smoke tier: CI / dryrun shapes (compiles in seconds on one core)
    "t5-test": dict(hidden_size=64, num_heads=4, num_enc_layers=2, num_dec_layers=2,
                    head_dim=16, ffn_hidden=128, vocab_size=512),
    "t5-small": dict(hidden_size=512, num_heads=8, num_enc_layers=6, num_dec_layers=6,
                     head_dim=64, ffn_hidden=2048),
    "t5-base": dict(hidden_size=768, num_heads=12, num_enc_layers=12, num_dec_layers=12,
                    head_dim=64, ffn_hidden=3072),
    "t5-large": dict(hidden_size=1024, num_heads=16, num_enc_layers=24, num_dec_layers=24,
                     head_dim=64, ffn_hidden=4096),
    "t5-3b": dict(hidden_size=1024, num_heads=32, num_enc_layers=24, num_dec_layers=24,
                  head_dim=128, ffn_hidden=16384),
}


@dataclass
class T5Config:
    hidden_size: int
    num_heads: int
    num_enc_layers: int
    num_dec_layers: int
    vocab_size: int = 32128
    head_dim: int = 64
    ffn_hidden: Optional[int] = None
    activation: str = "relu"  # relu | gated-gelu
    rel_buckets: int = 32
    rel_max_distance: int = 128
    layernorm_eps: float = 1e-6
    tie_embeddings: bool = True
    max_seq_len: int = 512
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    init_std: float = 0.02
    attn_impl: str = "auto"

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size

    @property
    def num_layers(self) -> int:
        return self.num_enc_layers + self.num_dec_layers

    # generic-model compatibility (profiler / cli metadata)
    head_type = "lm"
    input_type = "tokens"


def t5_config(model_size: str = "t5-base", **overrides) -> T5Config:
    base = dict(META_CONFIGS[model_size])
    base.update(overrides)
    return T5Config(**base)


def t5_config_from_hf(hf_config, **overrides) -> T5Config:
    proj = hf_config.feed_forward_proj
    if getattr(hf_config, "is_gated_act", False) or "gated" in proj:
        act = "gated-gelu"
    elif "gelu" in proj:
        act = "gelu"
    else:
        act = "relu"
    return T5Config(
        hidden_size=hf_config.d_model,
        num_heads=hf_config.num_heads,
        num_enc_layers=hf_config.num_layers,
        num_dec_layers=hf_config.num_decoder_layers,
        vocab_size=hf_config.vocab_size,
        head_dim=hf_config.d_kv,
        ffn_hidden=hf_config.d_ff,
        activation=act,
        rel_buckets=hf_config.relative_attention_num_buckets,
        rel_max_distance=getattr(hf_config, "relative_attention_max_distance", 128),
        layernorm_eps=hf_config.layer_norm_epsilon,
        tie_embeddings=hf_config.tie_word_embeddings,
        **overrides,
    )


# ===================================================================== params
from galvatron_tpu.models.parts.common import _dense_init


def _attn_params(rng, cfg: T5Config) -> Params:
    ks = jax.random.split(rng, 4)
    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    # T5 init: q ~ (h*hd)^-0.5, k/v ~ h^-0.5, o ~ (nh*hd)^-0.5
    return {
        "wq": {"kernel": _dense_init(ks[0], (h, nh, hd), (h * hd) ** -0.5, cfg.param_dtype)},
        "wk": {"kernel": _dense_init(ks[1], (h, nh, hd), h ** -0.5, cfg.param_dtype)},
        "wv": {"kernel": _dense_init(ks[2], (h, nh, hd), h ** -0.5, cfg.param_dtype)},
        "wo": {"kernel": _dense_init(ks[3], (nh * hd, h), (nh * hd) ** -0.5, cfg.param_dtype)},
    }


def _mlp_params(rng, cfg: T5Config) -> Params:
    ks = jax.random.split(rng, 2)
    h, ff = cfg.hidden_size, cfg.ffn_hidden
    fan_in = (2, ff) if cfg.activation == "gated-gelu" else (ff,)
    return {
        "wi": {"kernel": _dense_init(ks[0], (h,) + fan_in, h ** -0.5, cfg.param_dtype)},
        "wo_mlp": {"kernel": _dense_init(ks[1], (ff, h), ff ** -0.5, cfg.param_dtype)},
    }


def _norm_p(cfg):
    return {"scale": jnp.ones((cfg.hidden_size,), cfg.param_dtype)}


def init_enc_layer(rng, cfg: T5Config) -> Params:
    k1, k2 = jax.random.split(rng)
    p = {"ln1": _norm_p(cfg), "ln2": _norm_p(cfg)}
    p.update(_attn_params(k1, cfg))
    p.update(_mlp_params(k2, cfg))
    return p


def init_dec_layer(rng, cfg: T5Config) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    p = {"ln1": _norm_p(cfg), "ln_cross": _norm_p(cfg), "ln2": _norm_p(cfg)}
    p.update(_attn_params(k1, cfg))
    p["cross"] = _attn_params(k2, cfg)
    p.update(_mlp_params(k3, cfg))
    return p


def init_t5_params(rng: jax.Array, cfg: T5Config) -> Params:
    ks = jax.random.split(rng, cfg.num_layers + 5)
    params: Params = {
        "embed": {"wte": _dense_init(ks[0], (cfg.vocab_size, cfg.hidden_size), 1.0, cfg.param_dtype)},
        "enc_layers": [init_enc_layer(ks[1 + i], cfg) for i in range(cfg.num_enc_layers)],
        "dec_layers": [
            init_dec_layer(ks[1 + cfg.num_enc_layers + i], cfg) for i in range(cfg.num_dec_layers)
        ],
        "enc_rel_bias": _dense_init(
            ks[-3], (cfg.rel_buckets, cfg.num_heads), cfg.hidden_size ** -0.5, cfg.param_dtype
        ),
        "dec_rel_bias": _dense_init(
            ks[-2], (cfg.rel_buckets, cfg.num_heads), cfg.hidden_size ** -0.5, cfg.param_dtype
        ),
        "enc_norm": _norm_p(cfg),
        "dec_norm": _norm_p(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": _dense_init(ks[-1], (cfg.hidden_size, cfg.vocab_size), cfg.init_std, cfg.param_dtype)
        }
    return params


# ============================================================== rel-pos bias
def relative_position_bucket(rel_pos: jax.Array, *, bidirectional: bool,
                             num_buckets: int, max_distance: int) -> jax.Array:
    """HF T5's log-spaced relative-position bucketing."""
    ret = jnp.zeros_like(rel_pos)
    if bidirectional:
        num_buckets //= 2
        ret = ret + (rel_pos > 0).astype(jnp.int32) * num_buckets
        rel = jnp.abs(rel_pos)
    else:
        rel = -jnp.minimum(rel_pos, 0)
    max_exact = num_buckets // 2
    is_small = rel < max_exact
    val_large = max_exact + (
        jnp.log(rel.astype(jnp.float32) / max_exact + 1e-6)
        / np.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_large = jnp.minimum(val_large, num_buckets - 1)
    return ret + jnp.where(is_small, rel, val_large)


def rel_bias(table: jax.Array, sq: int, sk: int, cfg: T5Config, *, bidirectional: bool) -> jax.Array:
    """(buckets, nh) table -> (1, nh, sq, sk) additive attention bias."""
    q_pos = jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    bucket = relative_position_bucket(
        k_pos - q_pos, bidirectional=bidirectional,
        num_buckets=cfg.rel_buckets, max_distance=cfg.rel_max_distance,
    )
    values = table.astype(jnp.float32)[bucket]  # (sq, sk, nh)
    return values.transpose(2, 0, 1)[None]


# ================================================================== forward
def _rms(x, p, cfg):
    return rms_norm(x, p["scale"], cfg.layernorm_eps)


def _proj_heads(x, kernel, dtype):
    return jnp.einsum("bsh,hnd->bsnd", x, kernel.astype(dtype))


def _attention(p: Params, x, kv_src, cfg: T5Config, *, causal: bool, bias) -> jax.Array:
    dtype = cfg.compute_dtype
    q = _proj_heads(x, p["wq"]["kernel"], dtype)
    k = _proj_heads(kv_src, p["wk"]["kernel"], dtype)
    v = _proj_heads(kv_src, p["wv"]["kernel"], dtype)
    attn = core_attention(q, k, v, causal=causal, sm_scale=1.0, bias=bias, impl=cfg.attn_impl)
    attn = attn.reshape(attn.shape[0], attn.shape[1], cfg.num_heads * cfg.head_dim)
    return attn @ p["wo"]["kernel"].astype(dtype)


def _mlp(p: Params, x, cfg: T5Config) -> jax.Array:
    dtype = cfg.compute_dtype
    y = jnp.einsum("bsh,h...->bs...", x, p["wi"]["kernel"].astype(dtype))
    if cfg.activation == "gated-gelu":
        y = jax.nn.gelu(y[:, :, 0], approximate=False) * y[:, :, 1]
    elif cfg.activation == "gelu":
        y = jax.nn.gelu(y, approximate=False)
    else:
        y = jax.nn.relu(y)
    return y @ p["wo_mlp"]["kernel"].astype(dtype)


def enc_layer_forward(p: Params, x, cfg: T5Config, bias, *, mesh=None, axes=None):
    y = _rms(x, p["ln1"], cfg)
    x = x + _attention(p, y, y, cfg, causal=False, bias=bias)
    if mesh is not None and axes is not None:
        x = S.constrain(x, mesh, S.act_spec(axes))
    x = x + _mlp(p, _rms(x, p["ln2"], cfg), cfg)
    return x


def dec_layer_forward(p: Params, x, enc_out, cfg: T5Config, self_bias, *, cross_bias=None,
                      mesh=None, axes=None):
    y = _rms(x, p["ln1"], cfg)
    x = x + _attention(p, y, y, cfg, causal=True, bias=self_bias)
    x = x + _attention(
        p["cross"], _rms(x, p["ln_cross"], cfg), enc_out, cfg, causal=False, bias=cross_bias
    )
    if mesh is not None and axes is not None:
        x = S.constrain(x, mesh, S.act_spec(axes))
    x = x + _mlp(p, _rms(x, p["ln2"], cfg), cfg)
    return x


def t5_forward(
    params: Params,
    enc_tokens: jax.Array,
    dec_tokens: jax.Array,
    cfg: T5Config,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
    enc_attn_mask: Optional[jax.Array] = None,
) -> jax.Array:
    use_hp = hp is not None and mesh is not None
    dtype = cfg.compute_dtype
    wte = params["embed"]["wte"]

    se, sd = enc_tokens.shape[1], dec_tokens.shape[1]
    enc_bias = rel_bias(params["enc_rel_bias"], se, se, cfg, bidirectional=True)
    cross_bias = None
    if enc_attn_mask is not None:
        # padded encoder keys are masked in encoder self-attn AND in every
        # decoder cross-attn (keys come from the encoder output)
        key_bias = (1.0 - enc_attn_mask.astype(jnp.float32))[:, None, None, :] * -1e9
        enc_bias = enc_bias + key_bias
        cross_bias = key_bias
    x = wte.astype(dtype)[enc_tokens]
    for i, lp in enumerate(params["enc_layers"]):
        axes = layer_axes(hp, i) if use_hp else None
        if use_hp:
            x = S.constrain(x, mesh, S.act_spec(axes))
        fwd = partial(enc_layer_forward, cfg=cfg, mesh=mesh, axes=axes)
        if use_hp and hp.layers[i].checkpoint:
            fwd = jax.checkpoint(fwd)
        x = fwd(lp, x, bias=enc_bias)
    enc_out = _rms(x, params["enc_norm"], cfg)

    dec_bias = rel_bias(params["dec_rel_bias"], sd, sd, cfg, bidirectional=False)
    y = wte.astype(dtype)[dec_tokens]
    off = cfg.num_enc_layers
    for i, lp in enumerate(params["dec_layers"]):
        axes = layer_axes(hp, off + i) if use_hp else None
        if use_hp:
            y = S.constrain(y, mesh, S.act_spec(axes))
        fwd = partial(dec_layer_forward, cfg=cfg, mesh=mesh, axes=axes)
        if use_hp and hp.layers[off + i].checkpoint:
            fwd = jax.checkpoint(fwd)
        y = fwd(lp, y, enc_out, self_bias=dec_bias, cross_bias=cross_bias)
    y = _rms(y, params["dec_norm"], cfg)

    if cfg.tie_embeddings:
        y = y * (cfg.hidden_size ** -0.5)
        logits = y @ wte.astype(dtype).T
    else:
        logits = y @ params["lm_head"]["kernel"].astype(dtype)
    if use_hp:
        vax = vocab_axes(hp)
        logits = S.constrain(logits, mesh, S.logits_spec(vax))
    return logits


def t5_loss_fn(params, batch, cfg: T5Config, hp=None, mesh=None):
    """batch: dict(tokens [enc], dec_tokens, labels, loss_mask?, attn_mask?)."""
    from galvatron_tpu.models.parts.embed_head import vocab_parallel_cross_entropy

    logits = t5_forward(
        params, batch["tokens"], batch["dec_tokens"], cfg, hp, mesh,
        enc_attn_mask=batch.get("attn_mask"),
    )
    return vocab_parallel_cross_entropy(logits, batch["labels"], batch.get("loss_mask"))


# ============================================================== param specs
def _attn_specs(ax: LayerAxes) -> Params:
    tp = None if ax.ulysses else S._ax(ax.tp)
    z3 = S._ax(tuple(ax.dp)) if ax.zero3 else None
    return {
        "wq": {"kernel": P(z3, tp, None)},
        "wk": {"kernel": P(z3, tp, None)},
        "wv": {"kernel": P(z3, tp, None)},
        "wo": {"kernel": P(tp, z3)},
    }


def _mlp_specs(cfg: T5Config, ax: LayerAxes) -> Params:
    tp = None if ax.ulysses else S._ax(ax.tp)
    z3 = S._ax(tuple(ax.dp)) if ax.zero3 else None
    wi = P(z3, None, tp) if cfg.activation == "gated-gelu" else P(z3, tp)
    return {"wi": {"kernel": wi}, "wo_mlp": {"kernel": P(tp, z3)}}


def enc_layer_specs(cfg: T5Config, ax: LayerAxes) -> Params:
    r1 = S.replicated_1d_spec(ax)
    sp = {"ln1": {"scale": r1}, "ln2": {"scale": r1}}
    sp.update(_attn_specs(ax))
    sp.update(_mlp_specs(cfg, ax))
    return sp


def dec_layer_specs(cfg: T5Config, ax: LayerAxes) -> Params:
    sp = enc_layer_specs(cfg, ax)
    sp["ln_cross"] = {"scale": S.replicated_1d_spec(ax)}
    sp["cross"] = _attn_specs(ax)
    return sp


def t5_param_specs(cfg: T5Config, hp: HybridParallelConfig) -> Params:
    vax = vocab_axes(hp)
    specs: Params = {
        "embed": {"wte": S.vocab_embed_spec(vax)},
        "enc_layers": [enc_layer_specs(cfg, layer_axes(hp, i)) for i in range(cfg.num_enc_layers)],
        "dec_layers": [
            dec_layer_specs(cfg, layer_axes(hp, cfg.num_enc_layers + i))
            for i in range(cfg.num_dec_layers)
        ],
        "enc_rel_bias": P(None, None),
        "dec_rel_bias": P(None, None),
        "enc_norm": {"scale": S.replicated_1d_spec(vax)},
        "dec_norm": {"scale": S.replicated_1d_spec(vax)},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"kernel": P(None, None) if vax.ulysses else P(None, S._ax(vax.tp))}
    return specs


# ============================================================ HF conversion
from galvatron_tpu.models.hf_utils import to_np as _np


def _heads(w, h, nh, hd):
    """torch Linear (nh*hd, h) -> (h, nh, hd)."""
    return w.T.reshape(h, nh, hd)


def convert_hf_t5(state_dict: Dict[str, Any], cfg: T5Config) -> Params:
    """HF T5ForConditionalGeneration state dict -> galvatron_tpu param tree."""
    g = lambda n: _np(state_dict[n])
    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim

    def attn(prefix):
        return {
            "wq": {"kernel": jnp.asarray(_heads(g(prefix + "q.weight"), h, nh, hd))},
            "wk": {"kernel": jnp.asarray(_heads(g(prefix + "k.weight"), h, nh, hd))},
            "wv": {"kernel": jnp.asarray(_heads(g(prefix + "v.weight"), h, nh, hd))},
            "wo": {"kernel": jnp.asarray(g(prefix + "o.weight").T)},
        }

    def mlp(prefix):
        if cfg.activation == "gated-gelu":
            wi = np.stack([g(prefix + "wi_0.weight").T, g(prefix + "wi_1.weight").T], axis=1)
        else:
            wi = g(prefix + "wi.weight").T
        return {"wi": {"kernel": jnp.asarray(wi)},
                "wo_mlp": {"kernel": jnp.asarray(g(prefix + "wo.weight").T)}}

    params: Params = {
        "embed": {"wte": jnp.asarray(g("shared.weight"))},
        "enc_layers": [],
        "dec_layers": [],
        "enc_rel_bias": jnp.asarray(
            g("encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight")
        ),
        "dec_rel_bias": jnp.asarray(
            g("decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight")
        ),
        "enc_norm": {"scale": jnp.asarray(g("encoder.final_layer_norm.weight"))},
        "dec_norm": {"scale": jnp.asarray(g("decoder.final_layer_norm.weight"))},
    }
    for i in range(cfg.num_enc_layers):
        pre = "encoder.block.%d.layer." % i
        lp = {"ln1": {"scale": jnp.asarray(g(pre + "0.layer_norm.weight"))},
              "ln2": {"scale": jnp.asarray(g(pre + "1.layer_norm.weight"))}}
        lp.update(attn(pre + "0.SelfAttention."))
        lp.update(mlp(pre + "1.DenseReluDense."))
        params["enc_layers"].append(lp)
    for i in range(cfg.num_dec_layers):
        pre = "decoder.block.%d.layer." % i
        lp = {"ln1": {"scale": jnp.asarray(g(pre + "0.layer_norm.weight"))},
              "ln_cross": {"scale": jnp.asarray(g(pre + "1.layer_norm.weight"))},
              "ln2": {"scale": jnp.asarray(g(pre + "2.layer_norm.weight"))}}
        lp.update(attn(pre + "0.SelfAttention."))
        lp["cross"] = attn(pre + "1.EncDecAttention.")
        lp.update(mlp(pre + "2.DenseReluDense."))
        params["dec_layers"].append(lp)
    if not cfg.tie_embeddings:
        params["lm_head"] = {"kernel": jnp.asarray(g("lm_head.weight").T)}
    return params


# ================================================================ constructor
def t5_vocab_pipeline_specs(cfg: T5Config, hp: HybridParallelConfig, *, storage: bool) -> Params:
    """Specs for the non-stage params under the enc-dec pipeline.
    storage=True: the wte vocab dim shards over ('pp',) + vocab_tp (state is
    1/(pp*vtp) per device, cf. pipeline.vocab_param_specs); False: the
    within-stage layout the schedule computes in."""
    vax = vocab_axes(hp)
    vocab_ax = S._ax(((PP_AXIS,) if storage else ()) + (() if vax.ulysses else tuple(vax.tp)))
    z3 = S._ax(vax.dp) if vax.zero3 else None
    specs: Params = {
        "embed": {"wte": P(vocab_ax, z3)},
        "dec_norm": {"scale": P(None)},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = {"kernel": P(z3, vocab_ax)}
    return specs


def t5_pad_batch(batch: Params) -> Params:
    """Pad encoder and decoder streams to a common sequence length (the
    pipeline channel is one static shape); padded encoder keys are masked via
    attn_mask, padded decoder positions via loss_mask."""
    se = batch["tokens"].shape[1]
    sd = batch["dec_tokens"].shape[1]
    if se == sd:
        return batch
    Sq = max(se, sd)
    b = dict(batch)
    B = batch["tokens"].shape[0]
    if se < Sq:
        pad = Sq - se
        b["tokens"] = jnp.pad(batch["tokens"], ((0, 0), (0, pad)))
        mask = batch.get("attn_mask")
        mask = mask if mask is not None else jnp.ones((B, se), jnp.float32)
        b["attn_mask"] = jnp.pad(mask, ((0, 0), (0, pad)))
    if sd < Sq:
        pad = Sq - sd
        b["dec_tokens"] = jnp.pad(batch["dec_tokens"], ((0, 0), (0, pad)))
        b["labels"] = jnp.pad(batch["labels"], ((0, 0), (0, pad)))
        lmask = batch.get("loss_mask")
        lmask = lmask if lmask is not None else jnp.ones((B, sd), jnp.float32)
        b["loss_mask"] = jnp.pad(lmask, ((0, 0), (0, pad)))
    return b


def construct_t5_model(cfg: T5Config, hp: HybridParallelConfig, devices=None):
    """Family-specific build (ModelFamily.build hook): two-layer-type param
    tree with per-layer strategies over enc+dec; pp>1 runs the enc-dec 1F1B
    schedule (parallel/pipeline_1f1b_encdec.py — the reference's
    multi-tensor-send T5 pipeline, pipeline.py:1442-1580)."""
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.runtime.model_api import HybridParallelModel

    if len(hp.layers) != cfg.num_layers:
        raise ValueError(
            "hp covers %d layers but t5 has %d (enc %d + dec %d)"
            % (len(hp.layers), cfg.num_layers, cfg.num_enc_layers, cfg.num_dec_layers)
        )
    mesh = build_mesh(hp, devices)
    if hp.pp > 1:
        if hp.pipeline_type != "pipedream_flush":
            # t5 has no gpipe scan path, and the 1F1B engine's microbatch
            # divisibility validation (config/strategy.py) only fires for
            # pipedream_flush — running it under a gpipe-labelled config
            # would skip the deadlock-preventing check
            raise ValueError(
                "t5 pipeline parallelism runs the enc-dec 1F1B engine: set "
                "pipeline_type='pipedream_flush' (got %r)" % (hp.pipeline_type,)
            )
        from galvatron_tpu.parallel.pipeline_1f1b_encdec import (
            make_encdec_loss_and_grad,
            stack_t5_layer_specs,
            stack_t5_params,
            unstack_t5_params,
            validate_encdec_config,
        )

        validate_encdec_config(cfg, hp)
        specs = t5_vocab_pipeline_specs(cfg, hp, storage=True)
        specs["stages"] = stack_t5_layer_specs(cfg, hp)
        raw_grad_fn = make_encdec_loss_and_grad(cfg, hp, mesh)
        grad_fn = lambda p, b: raw_grad_fn(p, t5_pad_batch(b))

        def init_fn(rng):
            canonical = init_t5_params(rng, cfg)
            out = {"embed": canonical["embed"], "dec_norm": canonical["dec_norm"]}
            if not cfg.tie_embeddings:
                out["lm_head"] = canonical["lm_head"]
            out["stages"] = stack_t5_params(canonical, cfg, hp)
            return out

        def eval_loss(p, b):
            # forward-only eval: recover the canonical tree from the stacked
            # slots (pure slicing under jit) and run the unpipelined forward —
            # same loss, no 1F1B backward slots (reference eval is fwd-only)
            canonical = {"embed": p["embed"], "dec_norm": p["dec_norm"]}
            if not cfg.tie_embeddings:
                canonical["lm_head"] = p["lm_head"]
            canonical.update(unstack_t5_params(p["stages"], cfg, hp))
            return t5_loss_fn(canonical, b, cfg, hp, mesh)

        # Only a win at small pp: the unpipelined forward replicates the FULL
        # model per pipeline group (~1.0 fwd/device + cross-pp weight gathers)
        # vs the 1F1B loss's ~3/pp fwd-equivalents/device on 1/pp-resident
        # weights — at pp>=3 it is slower AND raises eval peak memory on
        # configs where pp was chosen because a stage barely fits HBM
        if hp.pp > 2:
            eval_loss = None

        return HybridParallelModel(
            cfg=cfg,
            hp=hp,
            mesh=mesh,
            param_specs=specs,
            loss_fn=lambda p, b: grad_fn(p, b)[0],
            forward_fn=None,
            init_fn=init_fn,
            grad_fn=grad_fn,
            eval_loss_fn=eval_loss,
        )
    specs = t5_param_specs(cfg, hp)
    return HybridParallelModel(
        cfg=cfg,
        hp=hp,
        mesh=mesh,
        param_specs=specs,
        # the table feeds the encoder and the decoder, and a tied head
        cast_first=S.cast_first_tree(specs, table_stored=True),
        loss_fn=lambda p, b: t5_loss_fn(p, b, cfg, hp, mesh),
        forward_fn=lambda p, b: t5_forward(
            p, b["tokens"], b["dec_tokens"], cfg, hp, mesh, enc_attn_mask=b.get("attn_mask")
        ),
        init_fn=lambda rng: init_t5_params(rng, cfg),
    )


def _t5_layer_configs(cfg: T5Config):
    return [
        {"hidden_size": cfg.hidden_size, "seq_len": cfg.max_seq_len, "layer_num": cfg.num_enc_layers},
        {"hidden_size": cfg.hidden_size, "seq_len": cfg.max_seq_len, "layer_num": cfg.num_dec_layers},
    ]


def _t5_profiler(cfg, model_name, args):
    from galvatron_tpu.profiler.model import T5ModelProfiler

    return T5ModelProfiler(cfg, model_name, args)


def export_hf_t5(params: Params, cfg: T5Config) -> Dict[str, np.ndarray]:
    """galvatron_tpu param tree -> HF T5ForConditionalGeneration state dict
    arrays — exact inverse of convert_hf_t5 (reference g2h analogue)."""
    h, nh, hd = cfg.hidden_size, cfg.num_heads, cfg.head_dim
    a = lambda x: np.asarray(x, np.float32)

    def attn_out(out, prefix, ap):
        for role in ("q", "k", "v"):
            out[prefix + "%s.weight" % role] = a(
                ap["w" + role]["kernel"]
            ).reshape(h, nh * hd).T
        out[prefix + "o.weight"] = a(ap["wo"]["kernel"]).T

    def mlp_out(out, prefix, lp):
        wi = a(lp["wi"]["kernel"])
        if cfg.activation == "gated-gelu":
            out[prefix + "wi_0.weight"] = wi[:, 0].T
            out[prefix + "wi_1.weight"] = wi[:, 1].T
        else:
            out[prefix + "wi.weight"] = wi.T
        out[prefix + "wo.weight"] = a(lp["wo_mlp"]["kernel"]).T

    wte = a(params["embed"]["wte"])
    out: Dict[str, np.ndarray] = {
        "shared.weight": wte,
        # HF materialises the tied encoder/decoder embedding copies
        "encoder.embed_tokens.weight": wte,
        "decoder.embed_tokens.weight": wte,
        "encoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight": a(
            params["enc_rel_bias"]
        ),
        "decoder.block.0.layer.0.SelfAttention.relative_attention_bias.weight": a(
            params["dec_rel_bias"]
        ),
        "encoder.final_layer_norm.weight": a(params["enc_norm"]["scale"]),
        "decoder.final_layer_norm.weight": a(params["dec_norm"]["scale"]),
    }
    if cfg.tie_embeddings:
        out["lm_head.weight"] = a(params["embed"]["wte"])
    else:
        out["lm_head.weight"] = a(params["lm_head"]["kernel"]).T
    for i, lp in enumerate(params["enc_layers"]):
        pre = "encoder.block.%d.layer." % i
        out[pre + "0.layer_norm.weight"] = a(lp["ln1"]["scale"])
        out[pre + "1.layer_norm.weight"] = a(lp["ln2"]["scale"])
        attn_out(out, pre + "0.SelfAttention.", lp)
        mlp_out(out, pre + "1.DenseReluDense.", lp)
    for i, lp in enumerate(params["dec_layers"]):
        pre = "decoder.block.%d.layer." % i
        out[pre + "0.layer_norm.weight"] = a(lp["ln1"]["scale"])
        out[pre + "1.layer_norm.weight"] = a(lp["ln_cross"]["scale"])
        out[pre + "2.layer_norm.weight"] = a(lp["ln2"]["scale"])
        attn_out(out, pre + "0.SelfAttention.", lp)
        attn_out(out, pre + "1.EncDecAttention.", lp["cross"])
        mlp_out(out, pre + "2.DenseReluDense.", lp)
    return out


register(
    ModelFamily(
        name="t5",
        config_fn=t5_config,
        meta_configs=META_CONFIGS,
        default_size="t5-base",
        data_kind="seq2seq",
        convert_from_hf=convert_hf_t5,
        export_to_hf=export_hf_t5,
        config_from_hf=t5_config_from_hf,
        build=construct_t5_model,
        layer_configs_fn=_t5_layer_configs,
        make_profiler=_t5_profiler,
    )
)
