"""The MLP half of a layer: dense, or routed experts (ops/moe.py) with the
shared expert and its gate beside them."""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.attention import _written_out
from galvatron_tpu.models.parts.common import (LayerPart, Params, _activation, _dense, _dense_init, _proj_std,
                                               no_form)
from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops.moe import moe_ffn, swiglu
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes

ROUTER_BIAS = "e_score_correction_bias"  # HF's name: (num_experts,) float32, no gradient


# ===================================================================== dense
def _init_dense(ks, cfg: TransformerConfig) -> Params:
    h = cfg.hidden_size
    p = {"wi": {"kernel": _dense_init(ks[2], (h,) + cfg.mlp_fan_in, cfg.init_std, cfg.param_dtype)},
         "wo_mlp": {"kernel": _dense_init(ks[3], (cfg.ffn_hidden, h), _proj_std(cfg), cfg.param_dtype)}}
    if cfg.mlp_bias:
        p["wi"]["bias"] = jnp.zeros(cfg.mlp_fan_in, cfg.param_dtype)
        p["wo_mlp"]["bias"] = jnp.zeros((h,), cfg.param_dtype)
    return p


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def grad_as_stored(kernel: jax.Array, held_in=None) -> jax.Array:
    """The identity on a SwiGLU's up kernel (hidden, 2, ffn), a leaf of the
    train state, which says where its gradient lies until the update reads it.
    A TPU stores the float32 leaf and its two Adam moments in tiles of 2 x 128
    over (2, ffn); the backward's matmul yields the gradient in tiles of 8 x
    128 over (hidden, ffn), the pair axis major. Where a layer runs unrolled
    that gradient reaches the fused Adam update as it is, and the compiler
    settles the mismatch by copying parameter, `mu` and `nu` of EVERY leaf of
    that shape into the gradient's tiling and back, every step (Granite: 60
    copies of 134 MB, 8 % of its step; PERF.md section 6, PR 48). The backward
    here holds the cotangent pair-axis-major, as the matmul yields it and as
    a scan stacks it (the first constraint, on the transposed array: no data
    moves), and asks for the leaf's own layout after it (the second): one
    relayout of the gradient, which the compiler fuses into the update's read,
    and no transient inside a scanned run.

    `held_in`: the dtype an UNROLLED layer's gradient waits in (the compute
    dtype: what the matmul's jaxpr yields, and what the compiler without this
    rule kept of such a layer until the update: the same values at the same
    bytes); None inside a scanned run, whose float32 stack stays as it is."""
    return kernel


def _grad_as_stored_bwd(held_in, _, g):
    rows_major = Layout((0, 1, 2))  # of (2, hidden, ffn): the matmul's; of (hidden, 2, ffn): the leaf's
    held = with_layout_constraint(jnp.transpose(g if held_in is None else g.astype(held_in), (1, 0, 2)), rows_major)
    return (with_layout_constraint(jnp.transpose(held, (1, 0, 2)), rows_major).astype(g.dtype),)


grad_as_stored.defvjp(lambda kernel, held_in: (kernel, None), _grad_as_stored_bwd)


@jax.custom_vjp
def _matmul_of_written_out(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """x @ kernel whose FORWARD product reads x as an array of its own (an
    optimization barrier), so that x's producer is no operand fusion of this
    matmul; the backward is the matmul's own transpose. An exact GELU (`erf`
    as a polynomial: 28 multiplies, an exponential and two divisions an
    element) folded into the down projection's operand holds that matmul at
    47 % of a v5e's MXU (11.8 ms a layer of `gpt67-c1-s2k`); reading an array
    it runs at 94 % (5.96 ms; PERF.md section 6, PR 67).

    The residual is x AS IT CAME, not the barrier's result. Under `jax.grad`
    the first forward and `jax.checkpoint`'s recomputation are one jvp'd jaxpr
    (this rule's `fwd`), so a barrier on the VALUE (`attention._written_out`,
    the form for a value whose recomputation writes it anyway, or that is not
    recomputed) is in both: on the activation, the recomputed up projection
    then writes three (4, 2048, 16384) arrays where it wrote one,
    `gpt67-c1-s2k`'s `temp` 5,838,919,680 -> 7,246,128,640 B and
    `step_hbm_gib` 12.334 -> 13.644 (rehearsal, ISSUE 67). Here the
    recomputation's product is dead and goes with its barrier, and what the
    backward keeps is the unbarriered activation, which XLA fuses and stores
    as it did: `temp` to the byte."""
    return jax.lax.optimization_barrier(x) @ kernel


_matmul_of_written_out.defvjp(lambda x, kernel: (jax.lax.optimization_barrier(x) @ kernel, (x, kernel)),
                              lambda res, g: jax.vjp(jnp.matmul, *res)[1](g))


def dense_mlp(p: Params, y: jax.Array, cfg: TransformerConfig, dtype) -> jax.Array:
    """The dense MLP half on normed activations (B, S, H). An activation that
    is transcendental and not gated (the GELUs) is a pass of its own in the
    forward, from an array into an array: the down projection reads it
    written out (`_matmul_of_written_out`), and it reads the pre-activation
    written out (`_written_out`: the one array the recomputed up projection
    writes in any case, so the barrier costs the recomputation nothing). As
    the up projection's epilogue the GELU costs the same 1.9 ms a layer, but
    is taken of the matmul's float32 sum before it is rounded to the compute
    dtype, which the recomputation's is: with the pre-activation written out
    every loss is the unbarriered program's to the bit. SiLU x gate, ReLU and
    its square cost an operand fusion nothing and stay folded into it."""
    wi_out = jnp.einsum("bsh,h...->bs...", y, p["wi"]["kernel"].astype(dtype))
    if "bias" in p["wi"]:
        wi_out = wi_out + p["wi"]["bias"].astype(dtype)
    written_out = cfg.activation in ("gelu", "gelu_exact")
    forms.took(forms.MLP_ACTIVATION, "written_out" if written_out else "folded")
    if cfg.activation == "swiglu":
        hmid = jax.nn.silu(wi_out[:, :, 0]) * wi_out[:, :, 1]
    else:
        hmid = _activation(_written_out(wi_out) if written_out else wi_out, cfg)
    if not written_out:
        return _dense(hmid, p["wo_mlp"], dtype)
    out = _matmul_of_written_out(hmid, p["wo_mlp"]["kernel"].astype(dtype))  # and the bias as `_dense` adds it
    return out + p["wo_mlp"]["bias"].astype(dtype) if "bias" in p["wo_mlp"] else out


def _dense_forward(p: Params, y: jax.Array, positions, cfg: TransformerConfig, **_):
    # named here and not inside dense_mlp, which the shared expert calls
    # under its own scope: an op carries one scope nested in its run's
    with jax.named_scope(tracing.MLP):
        return dense_mlp(p, y, cfg, cfg.compute_dtype), None, None


def _dense_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    """The tp axes sit on the ffn dim; ZeRO-3 shards the other large dim over
    dp. Ulysses layers keep dense (non-tp-sharded) weights (reference
    transformer.py:2065-2177)."""
    tp = None if axes.ulysses else S._ax(axes.tp)
    z3 = S._ax(axes.dp) if axes.zero3 else None
    swi = cfg.activation == "swiglu"
    sp: Params = {"wi": {"kernel": P(z3, None, tp) if swi else P(z3, tp)},
                  "wo_mlp": {"kernel": P(tp, z3)}}
    if cfg.mlp_bias:
        sp["wi"]["bias"] = P(None, tp) if swi else P(tp)
        sp["wo_mlp"]["bias"] = S.replicated_1d_spec(axes)
    return sp


# ==================================================================== routed
def _init_routed(ks, cfg: TransformerConfig) -> Params:
    # one kernel a matrix with the experts leading: (E, h, 2F) the gate's
    # columns beside the up projection's (flat: a TPU tiles the minor
    # dims, and a (2, F) pair there costs a copy a use; (E, h, F) where the
    # activation has no gate: "relu2", Nemotron-H's), (E, F, h) down;
    # the router (h, E) stays float32 in the forward. The shared expert is a
    # dense MLP of the same activation, `cfg.shared_ffn` wide
    h, proj_std = cfg.hidden_size, _proj_std(cfg)
    e, fan_in = cfg.num_experts, math.prod(cfg.mlp_fan_in)
    kr = jax.random.fold_in(ks[2], 1)
    p: Params = {"router": {"kernel": _dense_init(kr, (h, e), cfg.init_std, cfg.param_dtype)}}
    if cfg.router_bias:
        p["router"][ROUTER_BIAS] = jnp.zeros((e,), jnp.float32)
    held = cfg.held_experts[1]  # the router ranks all e; these are held
    p["wi"] = {"kernel": _dense_init(ks[2], (held, h, fan_in), cfg.init_std, cfg.param_dtype)}
    p["wo_mlp"] = {"kernel": _dense_init(ks[3], (held, cfg.ffn_hidden, h), proj_std, cfg.param_dtype)}
    if cfg.num_shared_experts:
        wide = cfg.shared_ffn
        ksh = jax.random.split(jax.random.fold_in(ks[3], 1), 2)
        shared_in = (h, 2, wide) if cfg.activation == "swiglu" else (h, wide)
        p["shared"] = {
            "wi": {"kernel": _dense_init(ksh[0], shared_in, cfg.init_std, cfg.param_dtype)},
            "wo_mlp": {"kernel": _dense_init(ksh[1], (wide, h), proj_std, cfg.param_dtype)},
        }
        if cfg.shared_expert_gate:
            p["shared"]["gate"] = {"kernel": _dense_init(
                jax.random.fold_in(ks[3], 2), (h, 1), cfg.init_std, cfg.param_dtype)}
    return p


def _routed_forward(p: Params, y: jax.Array, positions, cfg: TransformerConfig, *, attn_sharding=None, **_):
    """-> the experts' (and the shared expert's) output, None, and the
    router's auxiliary terms (ops/moe.py)."""
    dtype = cfg.compute_dtype
    out, aux = moe_ffn(
        y, p["router"]["kernel"], p["wi"]["kernel"], p["wo_mlp"]["kernel"],
        experts_per_token=cfg.experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
        activate=swiglu if cfg.activation == "swiglu" else partial(_activation, cfg=cfg),
        dtype=dtype, sharding=attn_sharding, score=cfg.router_score,
        bias=p["router"].get(ROUTER_BIAS), scale=cfg.routed_scaling_factor,
        held=cfg.held_experts if cfg.experts_held else None)
    if "shared" in p:
        # every chip of the deployment computes it alike, whole
        with jax.named_scope(tracing.MOE_SHARED):
            shared = dense_mlp(p["shared"], y, cfg, dtype)
            if "gate" in p["shared"]:
                shared = shared * jax.nn.sigmoid(_dense(y, p["shared"]["gate"], dtype))
            out = out + shared
    return out, None, aux


def _routed_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp is refused, GLS018): ZeRO-3 splits the experts
    # over dp, and they enter the block whole (ops/moe.moe_ffn)
    z3 = S._ax(axes.dp) if axes.zero3 else None
    sp: Params = {"router": {"kernel": P(None, None)},
                  "wi": {"kernel": P(z3, None, None)}, "wo_mlp": {"kernel": P(z3, None, None)}}
    if cfg.router_bias:
        sp["router"][ROUTER_BIAS] = P(None)
    if cfg.num_shared_experts:
        sp["shared"] = {
            "wi": {"kernel": P(z3, None, None) if cfg.activation == "swiglu" else P(z3, None)},
            "wo_mlp": {"kernel": P(None, z3)},
        }
        if cfg.shared_expert_gate:
            sp["shared"]["gate"] = {"kernel": P(None, None)}
    return sp


# experts are ordinary parameters under dp and ZeRO-1/2/3; no other axis has
# an expert form yet (`ep` is the next step)
UNSUPPORTED = no_form(
    "routed experts",
    serve="no expert form",
    autotune="the block as dense",
    pp="carry no router losses between stages",
    tp="the experts' kernels and the dropless dispatch",
    quant="router statistics",
)

DENSE = LayerPart(_init_dense, _dense_forward, _dense_specs, (tracing.MLP,),
                  gated_kernels=lambda cfg: (("wi", "kernel"),) if cfg.activation == "swiglu" else ())
ROUTED = LayerPart(
    _init_routed, _routed_forward, _routed_specs,
    (tracing.MOE_ROUTER, tracing.MOE_DISPATCH, tracing.MOE_EXPERTS, tracing.MOE_COMBINE, tracing.MOE_SHARED),
    counters=True, unsupported=lambda cfg: UNSUPPORTED)
