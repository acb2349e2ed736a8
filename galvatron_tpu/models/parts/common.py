"""What the parts of a layer share: the shape of a table entry, the askers
a part answers to, and the few initialisers and primitives every part uses."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.ops.norms import layer_norm, rms_norm

Params = Dict[str, Any]

# who may have no form of a part: the two driver modes, the autotuner, the
# five things a layout can ask for, and the two cost-model tools
ASKERS = ("serve", "autotune", "pp", "tp", "vocab_tp", "tp_comm", "quant", "search", "profile")


@dataclass(frozen=True)
class LayerPart:
    """One entry of `MIXERS` or `MLP_HALVES`: ALL the stack, the config and
    the refusals need of a part of a layer (ROADMAP D6, D14). Adding a part
    is adding one of these, in a module of its own."""
    init: Callable  # (keys, cfg) -> the part's entries of the layer's tree
    # (p, y, positions, cfg, mesh=, axes=, attn_bias=, attn_sharding=, return_kv=)
    # -> (out, kv | None, counters | None), y the normed activations
    forward: Callable
    specs: Callable  # (cfg, axes) -> their PartitionSpecs
    scopes: Tuple[str, ...]  # what its ops carry beside the layer run's (obs/tracing.py)
    counters: bool = False  # whether `forward` hands back auxiliary terms
    validate: Callable = lambda cfg: None  # (cfg): the part's clause of TransformerConfig.__post_init__
    # (cfg) -> {asker: what the part says to an asker that has no form of it},
    # an asker of `ASKERS` it leaves out has one
    unsupported: Callable = lambda cfg: {}
    # (p, y, positions, cfg, k_cache=, v_cache=, write_index=, mesh=, axes=, attn_bias=)
    # -> (out, k_cache, v_cache): single-token decode, where the part has it
    decode: Optional[Callable] = None
    # (cfg) -> the paths, in the layer's parameters, of the part's gated
    # (hidden, 2, ffn) kernels: leaves whose gradient the matmul yields in
    # another tiling than the train state stores them in; the stack reads them
    # through `parts/mlp.grad_as_stored` where that pays (models/base.run_layers)
    gated_kernels: Callable = lambda cfg: ()
    # the named tensors the part can hand on to later layers beside the residual stream, and
    # those it reads of earlier layers' (`TransformerConfig.shared`): a part that publishes
    # takes `publish=` (the names a later layer reads; absent where none does) and then hands
    # back a fourth value, {name: tensor}; a part that reads takes `shared=` {name: tensor}
    publishes: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()
    # (layer tree, cfg, the layer's PUBLISHED index) -> the tree with the leaves set that depend
    # on the layer's place in the published stack (`init_layer_params`)
    place: Callable = lambda p, cfg, index: p
    # the half a layer of ONE half lacks (`parts/absent.py`): the stack gives it no norm and does not run it
    absent: bool = False


def no_form(name: str, **says: str) -> Mapping[str, str]:
    """A part with no form under any asker: what it `says` to each of serve,
    autotune, pp, tp and quant, and its name to the others."""
    return {**dict.fromkeys(ASKERS, name), **says}


# ===================================================================== init
def _dense_init(rng, shape, std, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


def _proj_std(cfg: TransformerConfig) -> float:
    return cfg.init_std / (2 * cfg.num_layers) ** 0.5


def _norm_scale(shape, cfg: TransformerConfig) -> jax.Array:
    """An RMSNorm's or LayerNorm's scale as the model starts it: 1, or 0
    where the norm multiplies by (1 + w)."""
    return (jnp.zeros if cfg.norm_zero_centered else jnp.ones)(shape, cfg.param_dtype)


def _norm_params(cfg: TransformerConfig) -> Params:
    p = {"scale": _norm_scale((cfg.hidden_size,), cfg)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((cfg.hidden_size,), cfg.param_dtype)
    return p


# ================================================================ primitives
def _norm(x, p, cfg: TransformerConfig):
    if cfg.norm_type == "rmsnorm":
        scale = 1.0 + p["scale"] if cfg.norm_zero_centered else p["scale"]
        return rms_norm(x, scale, cfg.layernorm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.layernorm_eps)


def _dense(x, p, dtype):
    y = x @ p["kernel"].astype(dtype)
    if "bias" in p:
        y = y + p["bias"].astype(dtype)
    return y


def _activation(x, cfg: TransformerConfig):
    # swiglu is handled at the call site on the fused (..., 2, ffn) layout
    if cfg.activation == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if cfg.activation == "gelu_exact":
        return jax.nn.gelu(x, approximate=False)
    if cfg.activation == "relu":
        return jax.nn.relu(x)
    if cfg.activation == "relu2":  # relu(x)^2 (Nemotron-H's `mlp_hidden_act`); relu2(0) = 0, so a zero column stays one
        return jnp.square(jax.nn.relu(x))
    raise ValueError(cfg.activation)


def _unit(t: jax.Array) -> jax.Array:
    """L2-normalised over a head's dims in float32, as HF's l2norm (the delta-rule mixers' q and k)."""
    t32 = t.astype(jnp.float32)
    return t32 * jax.lax.rsqrt(jnp.sum(jnp.square(t32), axis=-1, keepdims=True) + 1e-6)
