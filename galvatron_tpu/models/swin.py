"""Swin Transformer family (reference: galvatron/models/swin/).

Hierarchical vision transformer: window attention with shifted windows and
relative-position bias, patch merging between stages. The reference profiles
swin with per-stage layer lists (`layernum_listed`, model_profiler.py:71-75)
and per-stage sequence lengths (:96-100); here `hp.layers` indexes the flat
block list across stages the same way.

Window partitioning is pure reshape/transpose (layout ops XLA fuses away);
each window-batch attention is one MXU matmul batch. Shift masks and
relative-position indices are static per (H, W, window) and precomputed in
numpy at trace time."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.registry import ModelFamily, register
from galvatron_tpu.ops.norms import layer_norm
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes, layer_axes

Params = Dict[str, Any]

META_CONFIGS = {
    # smoke tier: CI / dryrun shapes (compiles in seconds on one core)
    "swin-test": dict(embed_dim=32, depths=(1, 1, 2, 1), num_heads=(2, 2, 2, 2),
                      image_size=64, window=4, num_classes=10),
    "swin-tiny": dict(embed_dim=96, depths=(2, 2, 6, 2), num_heads=(3, 6, 12, 24)),
    "swin-base": dict(embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32)),
    "swin-large": dict(embed_dim=192, depths=(2, 2, 18, 2), num_heads=(6, 12, 24, 48)),
    "swin-huge": dict(embed_dim=320, depths=(2, 2, 26, 2), num_heads=(10, 20, 40, 80), window=14),
}


@dataclass
class SwinConfig:
    embed_dim: int = 96
    depths: Tuple[int, ...] = (2, 2, 6, 2)
    num_heads: Tuple[int, ...] = (3, 6, 12, 24)
    image_size: int = 224
    patch_size: int = 4
    num_channels: int = 3
    window: int = 7
    mlp_ratio: float = 4.0
    qkv_bias: bool = True
    layernorm_eps: float = 1e-5
    num_classes: int = 1000
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    init_std: float = 0.02

    def __post_init__(self):
        if self.image_size % self.patch_size != 0:
            raise ValueError(
                "image_size %d not divisible by patch_size %d" % (self.image_size, self.patch_size)
            )
        for s in range(len(self.depths)):
            res = self.stage_resolution(s)
            w = min(self.window, res)
            if res % w != 0:
                raise ValueError(
                    "stage %d resolution %d not divisible by window %d (HF pads; "
                    "pick image_size/patch_size/window so every stage tiles)"
                    % (s, res, w)
                )

    @property
    def num_layers(self) -> int:
        return int(sum(self.depths))

    @property
    def num_stages(self) -> int:
        return len(self.depths)

    def stage_dim(self, s: int) -> int:
        return self.embed_dim * (2 ** s)

    def stage_resolution(self, s: int) -> int:
        return self.image_size // self.patch_size // (2 ** s)

    def stage_of_block(self, i: int) -> int:
        for s, d in enumerate(np.cumsum(self.depths)):
            if i < d:
                return s
        raise IndexError(i)

    # generic-model metadata
    head_type = "classification"
    input_type = "patches"


def swin_config(model_size: str = "swin-tiny", **overrides) -> SwinConfig:
    base = dict(META_CONFIGS[model_size])
    base.update(overrides)
    return SwinConfig(**base)


def swin_config_from_hf(hf_config, num_classes: int = 1000, **overrides) -> SwinConfig:
    return SwinConfig(
        embed_dim=hf_config.embed_dim,
        depths=tuple(hf_config.depths),
        num_heads=tuple(hf_config.num_heads),
        image_size=hf_config.image_size,
        patch_size=hf_config.patch_size,
        num_channels=hf_config.num_channels,
        window=hf_config.window_size,
        mlp_ratio=hf_config.mlp_ratio,
        qkv_bias=hf_config.qkv_bias,
        layernorm_eps=hf_config.layer_norm_eps,
        num_classes=num_classes,
        **overrides,
    )


# ===================================================================== params
from galvatron_tpu.models.parts.common import _dense_init


def _ln_p(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype), "bias": jnp.zeros((dim,), dtype)}


def init_block_params(rng, cfg: SwinConfig, stage: int) -> Params:
    c = cfg.stage_dim(stage)
    nh = cfg.num_heads[stage]
    hd = c // nh
    w = min(cfg.window, cfg.stage_resolution(stage))
    ff = int(c * cfg.mlp_ratio)
    ks = jax.random.split(rng, 5)
    p: Params = {
        "ln1": _ln_p(c, cfg.param_dtype),
        "ln2": _ln_p(c, cfg.param_dtype),
        "wqkv": {"kernel": _dense_init(ks[0], (c, 3, nh, hd), cfg.init_std, cfg.param_dtype)},
        "wo": {
            "kernel": _dense_init(ks[1], (c, c), cfg.init_std, cfg.param_dtype),
            "bias": jnp.zeros((c,), cfg.param_dtype),
        },
        "wi": {
            "kernel": _dense_init(ks[2], (c, ff), cfg.init_std, cfg.param_dtype),
            "bias": jnp.zeros((ff,), cfg.param_dtype),
        },
        "wo_mlp": {
            "kernel": _dense_init(ks[3], (ff, c), cfg.init_std, cfg.param_dtype),
            "bias": jnp.zeros((c,), cfg.param_dtype),
        },
        "rel_bias": _dense_init(ks[4], ((2 * w - 1) ** 2, nh), cfg.init_std, cfg.param_dtype),
    }
    if cfg.qkv_bias:
        p["wqkv"]["bias"] = jnp.zeros((3, nh, hd), cfg.param_dtype)
    return p


def init_swin_params(rng: jax.Array, cfg: SwinConfig) -> Params:
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
    n = cfg.num_layers
    ks = jax.random.split(rng, n + cfg.num_stages + 3)
    params: Params = {
        "embed": {
            "patch": {
                "kernel": _dense_init(ks[0], (patch_dim, cfg.embed_dim), cfg.init_std, cfg.param_dtype),
                "bias": jnp.zeros((cfg.embed_dim,), cfg.param_dtype),
            },
            "norm": _ln_p(cfg.embed_dim, cfg.param_dtype),
        },
        "blocks": [init_block_params(ks[1 + i], cfg, cfg.stage_of_block(i)) for i in range(n)],
        "merges": [],
        "final_norm": _ln_p(cfg.stage_dim(cfg.num_stages - 1), cfg.param_dtype),
        "head": {
            "kernel": _dense_init(
                ks[-1], (cfg.stage_dim(cfg.num_stages - 1), cfg.num_classes),
                cfg.init_std, cfg.param_dtype,
            ),
            "bias": jnp.zeros((cfg.num_classes,), cfg.param_dtype),
        },
    }
    for s in range(cfg.num_stages - 1):
        c = cfg.stage_dim(s)
        params["merges"].append(
            {
                "norm": _ln_p(4 * c, cfg.param_dtype),
                "reduction": {
                    "kernel": _dense_init(ks[1 + n + s], (4 * c, 2 * c), cfg.init_std, cfg.param_dtype)
                },
            }
        )
    return params


# ============================================================ window machinery
def _rel_index(w: int) -> np.ndarray:
    """Standard Swin relative-position index: (w*w, w*w) into a (2w-1)^2 table."""
    coords = np.stack(np.meshgrid(np.arange(w), np.arange(w), indexing="ij"))  # (2, w, w)
    flat = coords.reshape(2, -1)
    rel = flat[:, :, None] - flat[:, None, :]  # (2, w*w, w*w)
    rel = rel.transpose(1, 2, 0)
    rel[:, :, 0] += w - 1
    rel[:, :, 1] += w - 1
    rel[:, :, 0] *= 2 * w - 1
    return rel.sum(-1)


def _shift_mask(h: int, wdt: int, w: int, s: int) -> np.ndarray:
    """(nW, w*w, w*w) additive mask for shifted-window attention."""
    img = np.zeros((h, wdt))
    cnt = 0
    for hs in (slice(0, -w), slice(-w, -s), slice(-s, None)):
        for ws in (slice(0, -w), slice(-w, -s), slice(-s, None)):
            img[hs, ws] = cnt
            cnt += 1
    wins = img.reshape(h // w, w, wdt // w, w).transpose(0, 2, 1, 3).reshape(-1, w * w)
    diff = wins[:, :, None] - wins[:, None, :]
    return np.where(diff == 0, 0.0, -1e9).astype(np.float32)


def window_partition(x: jax.Array, w: int) -> jax.Array:
    """(B, H, W, C) -> (B, nW, w*w, C)."""
    b, h, wdt, c = x.shape
    x = x.reshape(b, h // w, w, wdt // w, w, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // w) * (wdt // w), w * w, c)


def window_unpartition(x: jax.Array, w: int, h: int, wdt: int) -> jax.Array:
    b = x.shape[0]
    c = x.shape[-1]
    x = x.reshape(b, h // w, wdt // w, w, w, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, h, wdt, c)


def block_forward(
    p: Params,
    x: jax.Array,  # (B, H, W, C)
    cfg: SwinConfig,
    stage: int,
    shift: bool,
    *,
    mesh: Optional[Mesh] = None,
    axes: Optional[LayerAxes] = None,
) -> jax.Array:
    dtype = cfg.compute_dtype
    b, h, wdt, c = x.shape
    nh = cfg.num_heads[stage]
    hd = c // nh
    w = min(cfg.window, min(h, wdt))
    s = w // 2 if (shift and w < min(h, wdt)) else 0

    shortcut = x
    y = layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], cfg.layernorm_eps)
    if s:
        y = jnp.roll(y, (-s, -s), axis=(1, 2))
    wins = window_partition(y, w)  # (B, nW, w*w, C)
    qkv = jnp.einsum("bnsc,cthd->bnsthd", wins, p["wqkv"]["kernel"].astype(dtype))
    if "bias" in p["wqkv"]:
        qkv = qkv + p["wqkv"]["bias"].astype(dtype)
    q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]  # (B, nW, w*w, nh, hd)
    logits = jnp.einsum("bnqhd,bnkhd->bnhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * (hd ** -0.5)
    bias = p["rel_bias"].astype(jnp.float32)[_rel_index(w)]  # (w*w, w*w, nh)
    logits = logits + bias.transpose(2, 0, 1)[None, None]
    if s:
        logits = logits + _shift_mask(h, wdt, w, s)[None, :, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    attn = jnp.einsum("bnhqk,bnkhd->bnqhd", probs, v).reshape(b, -1, w * w, c)
    attn = attn @ p["wo"]["kernel"].astype(dtype) + p["wo"]["bias"].astype(dtype)
    y = window_unpartition(attn, w, h, wdt)
    if s:
        y = jnp.roll(y, (s, s), axis=(1, 2))
    x = shortcut + y
    if mesh is not None and axes is not None:
        x = S.constrain(x, mesh, P(S._ax(axes.dp), None, None, None))

    y = layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], cfg.layernorm_eps)
    y = y @ p["wi"]["kernel"].astype(dtype) + p["wi"]["bias"].astype(dtype)
    y = jax.nn.gelu(y, approximate=False)
    y = y @ p["wo_mlp"]["kernel"].astype(dtype) + p["wo_mlp"]["bias"].astype(dtype)
    x = x + y
    if mesh is not None and axes is not None:
        x = S.constrain(x, mesh, P(S._ax(axes.dp), None, None, None))
    return x


def patch_merge(p: Params, x: jax.Array, cfg: SwinConfig) -> jax.Array:
    """(B, H, W, C) -> (B, H/2, W/2, 2C): concat 2x2 neighbours (HF order:
    [0::2,0::2], [1::2,0::2], [0::2,1::2], [1::2,1::2]) -> LN -> reduction."""
    x0 = x[:, 0::2, 0::2]
    x1 = x[:, 1::2, 0::2]
    x2 = x[:, 0::2, 1::2]
    x3 = x[:, 1::2, 1::2]
    y = jnp.concatenate([x0, x1, x2, x3], axis=-1)
    y = layer_norm(y, p["norm"]["scale"], p["norm"]["bias"], cfg.layernorm_eps)
    return y @ p["reduction"]["kernel"].astype(cfg.compute_dtype)


def swin_forward(
    params: Params,
    pixels: jax.Array,  # (B, H, W, C)
    cfg: SwinConfig,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    from galvatron_tpu.models.parts.embed_head import patchify

    use_hp = hp is not None and mesh is not None
    dtype = cfg.compute_dtype
    x = patchify(pixels.astype(dtype), cfg.patch_size)
    x = x @ params["embed"]["patch"]["kernel"].astype(dtype) + params["embed"]["patch"]["bias"].astype(dtype)
    x = layer_norm(x, params["embed"]["norm"]["scale"], params["embed"]["norm"]["bias"], cfg.layernorm_eps)
    res = cfg.stage_resolution(0)
    x = x.reshape(x.shape[0], res, res, cfg.embed_dim)

    block_i = 0
    for stage in range(cfg.num_stages):
        for d in range(cfg.depths[stage]):
            axes = layer_axes(hp, block_i) if use_hp else None
            fwd = partial(block_forward, cfg=cfg, stage=stage, shift=(d % 2 == 1), mesh=mesh, axes=axes)
            if use_hp and hp.layers[block_i].checkpoint:
                fwd = jax.checkpoint(fwd)
            x = fwd(params["blocks"][block_i], x)
            block_i += 1
        if stage < cfg.num_stages - 1:
            x = patch_merge(params["merges"][stage], x, cfg)

    x = x.reshape(x.shape[0], -1, x.shape[-1])
    x = layer_norm(x, params["final_norm"]["scale"], params["final_norm"]["bias"], cfg.layernorm_eps)
    pooled = jnp.mean(x, axis=1)
    return pooled @ params["head"]["kernel"].astype(dtype) + params["head"]["bias"].astype(dtype)


def swin_loss_fn(params, batch, cfg: SwinConfig, hp=None, mesh=None):
    from galvatron_tpu.models.parts.embed_head import softmax_nll

    logits = swin_forward(params, batch["pixels"], cfg, hp, mesh)
    return softmax_nll(logits, batch["labels"])


# ============================================================== param specs
def block_param_specs(cfg: SwinConfig, stage: int, ax: LayerAxes) -> Params:
    tp = None if ax.ulysses else S._ax(ax.tp)
    z3 = S._ax(tuple(ax.dp)) if ax.zero3 else None
    r1 = P(None)
    sp: Params = {
        "ln1": {"scale": r1, "bias": r1},
        "ln2": {"scale": r1, "bias": r1},
        "wqkv": {"kernel": P(z3, None, tp, None)},
        "wo": {"kernel": P(tp, z3), "bias": r1},
        "wi": {"kernel": P(z3, tp), "bias": P(tp)},
        "wo_mlp": {"kernel": P(tp, z3), "bias": r1},
        "rel_bias": P(None, tp),
    }
    if cfg.qkv_bias:
        sp["wqkv"]["bias"] = P(None, tp, None)
    return sp


def swin_param_specs(cfg: SwinConfig, hp: HybridParallelConfig) -> Params:
    r1 = P(None)
    specs: Params = {
        "embed": {
            "patch": {"kernel": P(None, None), "bias": r1},
            "norm": {"scale": r1, "bias": r1},
        },
        "blocks": [
            block_param_specs(cfg, cfg.stage_of_block(i), layer_axes(hp, i))
            for i in range(cfg.num_layers)
        ],
        "merges": [
            {"norm": {"scale": r1, "bias": r1}, "reduction": {"kernel": P(None, None)}}
            for _ in range(cfg.num_stages - 1)
        ],
        "final_norm": {"scale": r1, "bias": r1},
        "head": {"kernel": P(None, None), "bias": r1},
    }
    return specs


# ============================================================ HF conversion
from galvatron_tpu.models.hf_utils import stack_qkv, to_np as _np


def convert_hf_swin(state_dict: Dict[str, Any], cfg: SwinConfig) -> Params:
    """HF SwinForImageClassification state dict -> galvatron_tpu param tree."""
    g = lambda n: _np(state_dict[n])
    conv = g("swin.embeddings.patch_embeddings.projection.weight")  # (E, C, P, P)
    Ppat = cfg.patch_size
    params: Params = {
        "embed": {
            "patch": {
                "kernel": jnp.asarray(
                    conv.transpose(2, 3, 1, 0).reshape(Ppat * Ppat * cfg.num_channels, cfg.embed_dim)
                ),
                "bias": jnp.asarray(g("swin.embeddings.patch_embeddings.projection.bias")),
            },
            "norm": {
                "scale": jnp.asarray(g("swin.embeddings.norm.weight")),
                "bias": jnp.asarray(g("swin.embeddings.norm.bias")),
            },
        },
        "blocks": [],
        "merges": [],
        "final_norm": {
            "scale": jnp.asarray(g("swin.layernorm.weight")),
            "bias": jnp.asarray(g("swin.layernorm.bias")),
        },
        "head": {
            "kernel": jnp.asarray(g("classifier.weight").T),
            "bias": jnp.asarray(g("classifier.bias")),
        },
    }
    for i in range(cfg.num_layers):
        stage = cfg.stage_of_block(i)
        d = i - int(np.sum(cfg.depths[:stage]))
        c = cfg.stage_dim(stage)
        nh = cfg.num_heads[stage]
        hd = c // nh
        pre = "swin.encoder.layers.%d.blocks.%d." % (stage, d)
        qkv_k, qkv_b = stack_qkv(state_dict, pre + "attention.self.", c, nh, hd)
        params["blocks"].append(
            {
                "ln1": {
                    "scale": jnp.asarray(g(pre + "layernorm_before.weight")),
                    "bias": jnp.asarray(g(pre + "layernorm_before.bias")),
                },
                "ln2": {
                    "scale": jnp.asarray(g(pre + "layernorm_after.weight")),
                    "bias": jnp.asarray(g(pre + "layernorm_after.bias")),
                },
                "wqkv": {
                    "kernel": jnp.asarray(qkv_k),
                    "bias": jnp.asarray(qkv_b),
                },
                "wo": {
                    "kernel": jnp.asarray(g(pre + "attention.output.dense.weight").T),
                    "bias": jnp.asarray(g(pre + "attention.output.dense.bias")),
                },
                "wi": {
                    "kernel": jnp.asarray(g(pre + "intermediate.dense.weight").T),
                    "bias": jnp.asarray(g(pre + "intermediate.dense.bias")),
                },
                "wo_mlp": {
                    "kernel": jnp.asarray(g(pre + "output.dense.weight").T),
                    "bias": jnp.asarray(g(pre + "output.dense.bias")),
                },
                "rel_bias": jnp.asarray(g(pre + "attention.self.relative_position_bias_table")),
            }
        )
    for s in range(cfg.num_stages - 1):
        pre = "swin.encoder.layers.%d.downsample." % s
        params["merges"].append(
            {
                "norm": {
                    "scale": jnp.asarray(g(pre + "norm.weight")),
                    "bias": jnp.asarray(g(pre + "norm.bias")),
                },
                "reduction": {"kernel": jnp.asarray(g(pre + "reduction.weight").T)},
            }
        )
    return params


def export_hf_swin(params: Params, cfg: SwinConfig) -> Dict[str, np.ndarray]:
    """galvatron_tpu param tree -> HF SwinForImageClassification state dict
    arrays — exact inverse of convert_hf_swin (reference g2h analogue)."""
    Ppat, C, E = cfg.patch_size, cfg.num_channels, cfg.embed_dim
    a = lambda x: np.asarray(x, np.float32)
    out: Dict[str, np.ndarray] = {
        "swin.embeddings.patch_embeddings.projection.weight": a(
            params["embed"]["patch"]["kernel"]
        ).reshape(Ppat, Ppat, C, E).transpose(3, 2, 0, 1),
        "swin.embeddings.patch_embeddings.projection.bias": a(params["embed"]["patch"]["bias"]),
        "swin.embeddings.norm.weight": a(params["embed"]["norm"]["scale"]),
        "swin.embeddings.norm.bias": a(params["embed"]["norm"]["bias"]),
        "swin.layernorm.weight": a(params["final_norm"]["scale"]),
        "swin.layernorm.bias": a(params["final_norm"]["bias"]),
        "classifier.weight": a(params["head"]["kernel"]).T,
        "classifier.bias": a(params["head"]["bias"]),
    }
    for i, bp in enumerate(params["blocks"]):
        stage = cfg.stage_of_block(i)
        d = i - int(np.sum(cfg.depths[:stage]))
        c = cfg.stage_dim(stage)
        nh = cfg.num_heads[stage]
        hd = c // nh
        pre = "swin.encoder.layers.%d.blocks.%d." % (stage, d)
        qkv = a(bp["wqkv"]["kernel"])  # (c, 3, nh, hd)
        qkv_b = a(bp["wqkv"]["bias"])  # (3, nh, hd)
        for j, role in enumerate(("query", "key", "value")):
            out[pre + "attention.self.%s.weight" % role] = qkv[:, j].reshape(c, nh * hd).T
            out[pre + "attention.self.%s.bias" % role] = qkv_b[j].reshape(nh * hd)
        out[pre + "attention.self.relative_position_bias_table"] = a(bp["rel_bias"])
        out[pre + "attention.output.dense.weight"] = a(bp["wo"]["kernel"]).T
        out[pre + "attention.output.dense.bias"] = a(bp["wo"]["bias"])
        out[pre + "intermediate.dense.weight"] = a(bp["wi"]["kernel"]).T
        out[pre + "intermediate.dense.bias"] = a(bp["wi"]["bias"])
        out[pre + "output.dense.weight"] = a(bp["wo_mlp"]["kernel"]).T
        out[pre + "output.dense.bias"] = a(bp["wo_mlp"]["bias"])
        out[pre + "layernorm_before.weight"] = a(bp["ln1"]["scale"])
        out[pre + "layernorm_before.bias"] = a(bp["ln1"]["bias"])
        out[pre + "layernorm_after.weight"] = a(bp["ln2"]["scale"])
        out[pre + "layernorm_after.bias"] = a(bp["ln2"]["bias"])
    for s, mp in enumerate(params["merges"]):
        pre = "swin.encoder.layers.%d.downsample." % s
        out[pre + "norm.weight"] = a(mp["norm"]["scale"])
        out[pre + "norm.bias"] = a(mp["norm"]["bias"])
        out[pre + "reduction.weight"] = a(mp["reduction"]["kernel"]).T
    return out


# ================================================================ constructor
def construct_swin_model(cfg: SwinConfig, hp: HybridParallelConfig, devices=None):
    from galvatron_tpu.parallel.mesh import build_mesh
    from galvatron_tpu.runtime.model_api import HybridParallelModel

    if len(hp.layers) != cfg.num_layers:
        raise ValueError(
            "hp covers %d layers but swin has %d blocks (depths %s)"
            % (len(hp.layers), cfg.num_layers, list(cfg.depths))
        )
    # cp/sp are inapplicable at ANY pp degree (windowed attention has no
    # sequence dimension): validate unconditionally, not just under pp>1
    from galvatron_tpu.parallel.pipeline_1f1b_swin import validate_swin_config

    validate_swin_config(cfg, hp)
    for i, ls in enumerate(hp.layers):
        nh = cfg.num_heads[cfg.stage_of_block(i)]
        if ls.tp > 1 and nh % ls.tp != 0:
            raise ValueError(
                "block %d (stage %d) has %d heads, not divisible by tp=%d"
                % (i, cfg.stage_of_block(i), nh, ls.tp)
            )
    mesh = build_mesh(hp, devices)
    if hp.pp > 1:
        if hp.pipeline_type != "pipedream_flush":
            # swin has no gpipe scan path (stage shapes differ); the 1F1B
            # engine's microbatch validation only fires for pipedream_flush
            raise ValueError(
                "swin pipeline parallelism runs the hierarchical 1F1B engine: "
                "set pipeline_type='pipedream_flush' (got %r)" % (hp.pipeline_type,)
            )
        from galvatron_tpu.parallel.pipeline_1f1b_swin import (
            make_swin_loss_and_grad,
            stack_swin_layer_specs,
            stack_swin_params,
            unstack_swin_params,
        )

        specs = {
            k: v for k, v in swin_param_specs(cfg, hp).items() if k != "blocks" and k != "merges"
        }
        specs["stages"] = stack_swin_layer_specs(cfg, hp)
        grad_fn = make_swin_loss_and_grad(cfg, hp, mesh)

        def init_fn(rng):
            canonical = init_swin_params(rng, cfg)
            out = {
                "embed": canonical["embed"],
                "final_norm": canonical["final_norm"],
                "head": canonical["head"],
            }
            out["stages"] = stack_swin_params(canonical, cfg, hp)
            return out

        def eval_loss(p, b):
            # forward-only eval: recover canonical blocks/merges from the
            # padded slots (pure slicing under jit; outside any stage-divergent
            # branch, so the padded-dim slice collectives are deadlock-safe)
            # and run the unpipelined forward — same loss, no backward slots
            canonical = {"embed": p["embed"], "final_norm": p["final_norm"],
                         "head": p["head"]}
            canonical.update(unstack_swin_params(p["stages"], cfg, hp))
            return swin_loss_fn(canonical, b, cfg, hp, mesh)

        # only a win at small pp — see the identical gate in models/t5.py:
        # at pp>=3 the replicated full forward costs more time and memory
        # than the 1F1B schedule it would replace
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
    specs = swin_param_specs(cfg, hp)
    return HybridParallelModel(
        cfg=cfg,
        hp=hp,
        mesh=mesh,
        param_specs=specs,
        cast_first=S.cast_first_tree(specs, table_stored=False),
        loss_fn=lambda p, b: swin_loss_fn(p, b, cfg, hp, mesh),
        forward_fn=lambda p, b: swin_forward(p, b["pixels"], cfg, hp, mesh),
        init_fn=lambda rng: init_swin_params(rng, cfg),
    )


def _swin_layer_configs(cfg: SwinConfig):
    """One layer type per stage, with the stage's own width and token count
    (reference layernum_listed + per-stage seqlens, model_profiler.py:71-100)."""
    return [
        {
            "hidden_size": cfg.stage_dim(s),
            "seq_len": cfg.stage_resolution(s) ** 2,
            "layer_num": cfg.depths[s],
        }
        for s in range(cfg.num_stages)
    ]


def _swin_profiler(cfg, model_name, args):
    from galvatron_tpu.profiler.model import SwinModelProfiler

    return SwinModelProfiler(cfg, model_name, args)


register(
    ModelFamily(
        name="swin",
        config_fn=swin_config,
        meta_configs=META_CONFIGS,
        default_size="swin-tiny",
        data_kind="vision",
        convert_from_hf=convert_hf_swin,
        export_to_hf=export_hf_swin,
        config_from_hf=swin_config_from_hf,
        build=construct_swin_model,
        layer_configs_fn=_swin_layer_configs,
        make_profiler=_swin_profiler,
        mid_stage_type_boundaries=True,
        supports_sequence_sharding=False,
    )
)
