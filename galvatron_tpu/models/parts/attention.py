"""Softmax attention, the token mixer of every model but a few, and latent
attention (MLA) with it: the two share the one attention call."""

from __future__ import annotations

import contextlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.common import (LayerPart, Params, _dense, _dense_init, _norm, _norm_scale,
                                               _proj_std, no_form)
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.attention import core_attention, window_takes_kernels
from galvatron_tpu.ops.kernels import KernelSharding
from galvatron_tpu.ops.norms import rms_norm
from galvatron_tpu.ops.rope import apply_rotary, checked_scaling, half_split_tables
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes


def _validate(cfg: TransformerConfig) -> None:
    checked_scaling(cfg.rope_scaling)  # a `rope_type` with no form is refused by name
    if cfg.diff_attention and (cfg.latent_attention or cfg.attn_output_gate or cfg.attn_head_gate or cfg.qk_norm
                               or cfg.num_heads % 2 or cfg.num_kv_heads % 2 or not cfg.causal
                               or cfg.position_type == "rope"):
        raise ValueError("diff_attention (a difference of two softmax maps a PAIR of heads) wants an even number "
                         "of query heads and of key heads, causal attention without rope (the one form written: "
                         "SambaY's has no positions), and stands alone: not beside latent attention, a gate or a "
                         "QK-norm; got %d heads on %d, position_type %r"
                         % (cfg.num_heads, cfg.num_kv_heads, cfg.position_type))
    if cfg.attn_head_gate and (cfg.attn_output_gate or cfg.latent_attention):
        raise ValueError("attn_head_gate (a gate a head, Wg (hidden, heads)) stands alone: not beside "
                         "attn_output_gate (a gate a head AND dim, projected with q) nor latent attention")
    if not cfg.latent_attention:
        return
    widest = max(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim)
    if cfg.head_dim is None:
        cfg.head_dim = widest
    if (cfg.head_dim < widest or cfg.q_lora_rank < 0
            or min(cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim) < 1):
        raise ValueError(
            "latent attention runs as ONE attention call at head_dim, to which q and k "
            "(qk_nope + qk_rope dims) and v are padded: head_dim %r >= qk_nope %d + "
            "qk_rope %d and >= v_head_dim %d is asked, each of the three 1 or more, and "
            "q_lora_rank %d >= 0" % (cfg.head_dim, cfg.qk_nope_head_dim,
                                     cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.q_lora_rank))


# latent attention's low-rank projections have no tensor-, context- or
# sequence-parallel form and no cache in the decode engine, and the
# multi-token-prediction module that comes with it none under pp; the cost
# models price it, as full-rank (no `search` / `profile` statement)
_LATENT = dict(
    serve="no cache of latent attention's compressed k/v",
    autotune="latent attention as full-rank",
    pp="run no multi-token-prediction module after the last stage",
    tp="latent attention (MLA: kv_lora_rank > 0)",
    vocab_tp="latent attention", tp_comm="latent attention",
    quant="the multi-token-prediction module's term")


# the decode path (`attention_decode`) multiplies by no gate
_HEAD_GATE = dict(serve="no per-head output gate on a decoded token's attention")


# a layer's `lambda_init` is a constant of its PUBLISHED index, which a pipeline's stacked
# stages, the decode engine and the cost models do not carry; the pairs of heads have not
# been split over tensor-parallel ranks
_DIFF = no_form(
    "differential attention layers",
    serve="no form of differential attention (two softmax maps a pair of heads, a constant of the layer's index)",
    autotune="a differential attention layer as softmax attention",
    pp="carry no tensor a layer publishes for later layers across stages (a full layer's keys and values), nor "
       "a constant of a layer's published index",
    tp="differential attention layers (the pairs of heads have not been split over tensor-parallel ranks)",
    quant="a layer whose constant no gradient moves",
)


def _unsupported(cfg: TransformerConfig):
    if cfg.diff_attention:
        return _DIFF
    said = _LATENT if cfg.latent_attention or cfg.mtp_layers else {}
    return {**_HEAD_GATE, **said} if cfg.attn_head_gate else said


def _init_attention(ks, cfg: TransformerConfig) -> Params:
    """The softmax-attention mixer's leaves. QKV kernels are stored
    head-major — (h, 3, nh, hd) fused, or separate (h, nh, hd) + (h, 2, nkv,
    hd) for GQA — so the tp sharding sits on the *heads* dim and the q/k/v
    split slices an unsharded dim (no resharding). This replaces Megatron's
    interleaved fused-QKV layout (reference transformer.py:512-900,
    checkpoint QKV re-layout GPTModel_checkpoint.py:17-140). With an output
    gate a head's query dims lie beside its gate dims: (h, nh, 2 hd)."""
    h, hd, nh, nkv = cfg.hidden_size, cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    p: Params = {}
    if cfg.latent_attention:
        # HF's names: q_a_proj, q_a_layernorm, q_b_proj, kv_a_proj_with_mqa,
        # kv_a_layernorm, kv_b_proj; the up projections head-major, so that a
        # head's [nope | rope] and [k_nope | v] split an unsharded minor dim
        # (with no low-rank q, `q_lora_rank` 0: HF's q_proj, `wq` a head)
        ql, kvl, rope = cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_rope_head_dim
        qk = cfg.qk_nope_head_dim + rope
        kq = jax.random.split(ks[0], 2)
        kkv = jax.random.split(ks[4], 2)
        if ql:
            p["wq_a"] = {"kernel": _dense_init(kq[0], (h, ql), cfg.init_std, cfg.param_dtype)}
            p["q_a_norm"] = {"scale": jnp.ones((ql,), cfg.param_dtype)}
            p["wq_b"] = {"kernel": _dense_init(kq[1], (ql, nh, qk), cfg.init_std, cfg.param_dtype)}
        else:
            p["wq"] = {"kernel": _dense_init(ks[0], (h, nh, qk), cfg.init_std, cfg.param_dtype)}
        p["wkv_a"] = {"kernel": _dense_init(kkv[0], (h, kvl + rope), cfg.init_std, cfg.param_dtype)}
        p["kv_a_norm"] = {"scale": jnp.ones((kvl,), cfg.param_dtype)}
        p["wkv_b"] = {"kernel": _dense_init(
            kkv[1], (kvl, nh, cfg.qk_nope_head_dim + cfg.v_head_dim), cfg.init_std, cfg.param_dtype)}
    elif cfg.fused_qkv:
        p["wqkv"] = {"kernel": _dense_init(ks[0], (h, 3, nh, hd), cfg.init_std, cfg.param_dtype)}
        if cfg.qkv_bias:
            p["wqkv"]["bias"] = jnp.zeros((3, nh, hd), cfg.param_dtype)
    else:
        q_dims = 2 * hd if cfg.attn_output_gate else hd
        p["wq"] = {"kernel": _dense_init(ks[0], (h, nh, q_dims), cfg.init_std, cfg.param_dtype)}
        p["wkv"] = {"kernel": _dense_init(ks[4], (h, 2, nkv, hd), cfg.init_std, cfg.param_dtype)}
        if cfg.qkv_bias:
            p["wq"]["bias"] = jnp.zeros((nh, q_dims), cfg.param_dtype)
            p["wkv"]["bias"] = jnp.zeros((2, nkv, hd), cfg.param_dtype)
    out_dim = cfg.v_head_dim if cfg.latent_attention else hd  # a head's width into `wo`
    p["wo"] = {"kernel": _dense_init(ks[1], (nh * out_dim, h), _proj_std(cfg), cfg.param_dtype)}
    if cfg.out_bias:
        p["wo"]["bias"] = jnp.zeros((h,), cfg.param_dtype)
    if cfg.qk_norm == "head":
        p["q_norm"] = {"scale": _norm_scale((hd,), cfg)}
        p["k_norm"] = {"scale": _norm_scale((hd,), cfg)}
    elif cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((nh * hd,), cfg.param_dtype)}
        p["k_norm"] = {"scale": jnp.ones((nkv * hd,), cfg.param_dtype)}
    if cfg.attn_head_gate:
        p["wg"] = {"kernel": _dense_init(jax.random.fold_in(ks[0], 1), (h, nh), cfg.init_std, cfg.param_dtype)}
    if cfg.diff_attention:
        p["diff"] = init_diff(jax.random.fold_in(ks[0], 2), cfg)
    return p


# =============================================================== differential
LAMBDA_INIT = "lambda_init"  # a float32 scalar a layer that no gradient moves (runtime/optimizer.NO_GRADIENT_KEYS)


def lambda_init(index: int) -> float:
    """Differential attention's constant of the layer's PUBLISHED 0-based index (arXiv:2410.05258, 3)."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def init_diff(key, cfg: TransformerConfig) -> Params:
    """Differential attention's own leaves, under `diff`: the four `lambda`
    vectors (head_dim,) ~ N(0, 0.1^2), the sub-norm's weight over a pair's 2 x
    head_dim dims (1), and `lambda_init`, which `place_diff` sets."""
    ks = jax.random.split(key, 4)
    p = {name: 0.1 * jax.random.normal(k, (cfg.head_dim,), jnp.float32)
         for name, k in zip(("lq1", "lk1", "lq2", "lk2"), ks)}
    p["subln"] = {"scale": jnp.ones((2 * cfg.head_dim,), cfg.param_dtype)}
    p[LAMBDA_INIT] = jnp.zeros((), jnp.float32)
    return p


def place_diff(p: Params, cfg: TransformerConfig, index: int) -> Params:
    """`LayerPart.place`: the layer's `lambda_init` from its published index."""
    if "diff" not in p:
        return p
    return {**p, "diff": {**p["diff"], LAMBDA_INIT: jnp.asarray(lambda_init(index), jnp.float32)}}


def diff_specs(axes: LayerAxes) -> Params:
    r1 = S.replicated_1d_spec(axes)
    return {"lq1": r1, "lk1": r1, "lq2": r1, "lk2": r1, "subln": {"scale": r1}, LAMBDA_INIT: P()}


def diff_arranged(q: jax.Array, k: jax.Array, v: jax.Array):
    """The heads handed to ONE attention call at twice the head's width. Query
    heads (2j, 2j + 1) are a pair (q1, q2), key heads (2m, 2m + 1) a pair (k1,
    k2) that serves g = nh / nkv query pairs, the pair's value [v_2m | v_2m+1].
    Map s of pair j is `softmax(q_s k_s^T) v`: q_s and k_s padded with zeros to
    the value's width (zeros add nothing to a score), the value repeated for
    both maps, the query heads ordered (m, s, j') so that a key head's g query
    heads lie side by side as GQA wants them. At head_dim 64 the call runs at
    128, a whole lane tile. (B, S, nh, hd), (B, S, nkv, hd) x 2 -> (B, S, nh, 2
    hd), (B, S, nkv, 2 hd) x 2."""
    b, s, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    pad = ((0, 0),) * 3 + ((0, hd),)
    qa = q.reshape(b, s, nkv // 2, g, 2, hd).transpose(0, 1, 2, 4, 3, 5).reshape(b, s, nh, hd)
    va = jnp.broadcast_to(v.reshape(b, s, nkv // 2, 1, 2 * hd), (b, s, nkv // 2, 2, 2 * hd))
    return jnp.pad(qa, pad), jnp.pad(k, pad), va.reshape(b, s, nkv, 2 * hd)


def diff_combined(p: Params, attn: jax.Array, nkv: int, cfg: TransformerConfig) -> jax.Array:
    """(B, S, nh, 2 hd) as `diff_arranged` ordered the heads -> (B, S, nh x hd):
    `RMSNorm(a_1 - lambda a_2; w) (1 - lambda_init)` a pair, float32, with
    `lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`."""
    b, s, nh, wide = attn.shape
    init = jax.lax.stop_gradient(p[LAMBDA_INIT].astype(jnp.float32))
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + init
    maps = attn.reshape(b, s, nkv // 2, 2, nh // nkv, wide).astype(jnp.float32)
    o = rms_norm(maps[:, :, :, 0] - lam * maps[:, :, :, 1], p["subln"]["scale"], cfg.layernorm_eps) * (1.0 - init)
    return o.astype(attn.dtype).reshape(b, s, nh * wide // 2)


def diff_attention_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
                         attn_bias=None, attn_sharding=None, scope: str = tracing.ATTN_PROJ,
                         window: Optional[int] = None, shared=None, publish=(), **_):
    """Differential attention on normed activations (B, S, H) (arXiv:2410.05258;
    `diff_arranged`, `diff_combined`): a full layer, a window layer (`window`)
    or, with another layer's keys and values (`shared`: {"k", "v"}, as projected),
    a cross layer, which projects q alone. -> out, None, None and, where a later
    layer reads them (`publish`), this layer's k and v. The projections under `scope`, the pairing, lambda, the
    subtraction, the sub-norm and its factor under `gt.attn.diff`, the call where
    every attention call is (a window's under `gt.attn.band`). One chip and dp:
    every other layout is refused (GLS018)."""
    dtype = cfg.compute_dtype
    with jax.named_scope(scope):
        if shared is None:
            q, k, v = qkv_projection(p, y, cfg, dtype)
        else:
            q = jnp.einsum("bsh,hnd->bsnd", y, p["wq"]["kernel"].astype(dtype))
            if "bias" in p["wq"]:
                q = q + p["wq"]["bias"].astype(dtype)
            k, v = shared["k"], shared["v"]
    with jax.named_scope(tracing.ATTN_DIFF):
        qa, ka, va = diff_arranged(q, k, v)
    with jax.named_scope(tracing.ATTN_WINDOW_BAND) if window is not None else contextlib.nullcontext():
        attn = core_attention(qa, ka, va, causal=cfg.causal, bias=attn_bias, impl=cfg.attn_impl,
                              bias_type="key_padding", sharding=attn_sharding, window=window,
                              sm_scale=cfg.attention_multiplier or cfg.head_dim ** -0.5)
    with jax.named_scope(tracing.ATTN_DIFF):
        attn = diff_combined(p["diff"], attn, k.shape[2], cfg)
    with jax.named_scope(scope):
        o = _dense(attn, p["wo"], dtype)
    return (o, None, None, {"k": k, "v": v}) if publish else (o, None, None)


@jax.custom_vjp
def _written_out(x: jax.Array) -> jax.Array:
    """x, as an array of its own in the forward (an optimization barrier; the
    cotangent passes as it comes): what reads it cannot fold x's producer into
    its operand."""
    return jax.lax.optimization_barrier(x)


_written_out.defvjp(lambda x: (jax.lax.optimization_barrier(x), None), lambda _, g: (g,))


def qkv_projection(p: Params, y: jax.Array, cfg: TransformerConfig, dtype, flat_q: bool = False):
    """y: (B, S, H) -> q (B,S,nh,hd), k/v (B,S,nkv,hd). `flat_q`: q's matmul
    on its kernel as a (H, nh x hd) matrix, the same numbers: what it writes is
    the (B, S, nh x hd) array the window kernels read as it lies, and their dq
    enters the backward's matmuls as they wrote it (a contraction over (nh, hd)
    against the (H, nh, hd) kernel relays a q-sized dq first, on a TPU). The
    flat bf16 kernel is written out once (32 MiB, 0.09 ms at Laguna's widths):
    left to itself XLA:TPU folds the kernel's relayout into the operand of the
    forward's matmul and of dx's, which then run at 71 and 54 % of the MXU
    where they reach 93 (PERF.md section 6, PR 50)."""

    def proj(pk, flat=False):
        kernel = pk["kernel"]
        if flat:  # (the stored kernel is reshaped, then cast: the other order transposes dq for dW, on a TPU)
            flat_kernel = _written_out(kernel.reshape(kernel.shape[0], -1).astype(dtype))
            out = jnp.einsum("bsh,hk->bsk", y, flat_kernel).reshape(y.shape[:2] + kernel.shape[1:])
        else:
            out = jnp.einsum("bsh,h...->bs...", y, kernel.astype(dtype))
        if "bias" in pk:
            out = out + pk["bias"].astype(dtype)
        return out

    if cfg.fused_qkv:
        qkv = proj(p["wqkv"])  # (B, S, 3, nh, hd)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = proj(p["wq"], flat_q)
    kv = proj(p["wkv"])  # (B, S, 2, nkv, hd)
    return q, kv[:, :, 0], kv[:, :, 1]


def qk_normed(p: Params, q: jax.Array, k: jax.Array, cfg: TransformerConfig):
    """OLMoE's q_norm / k_norm: an RMSNorm over the WHOLE projected q
    (nh x hd) and the whole projected k, before rope. Taken over the last two
    dims in place: flattening them would merge the heads dim, which tp shards.
    `qk_norm == "head"` (Qwen3-Next): the model's own norm over each head's
    dims, one (hd,) scale for every head."""
    if cfg.qk_norm == "head":
        return _norm(q, p["q_norm"], cfg), _norm(k, p["k_norm"], cfg)

    def whole(t, scale):
        x32 = t.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=(-2, -1), keepdims=True)
        y = x32 * jnp.reciprocal(jnp.sqrt(var + cfg.layernorm_eps))
        return (y * scale.astype(jnp.float32).reshape(t.shape[-2:])).astype(t.dtype)

    return whole(q, p["q_norm"]["scale"]), whole(k, p["k_norm"]["scale"])


def latent_qkv_projection(p: Params, y: jax.Array, positions: jax.Array,
                          cfg: TransformerConfig, dtype):
    """Latent attention's q, k, v (B, S, nh, head_dim) from normed
    activations (B, S, H), rope applied (DeepSeek-V2's MLA as GLM-4.7-Flash
    configures it; HF `Glm4MoeLiteAttention`):

        cq = RMSNorm(y Wqa);  q_h = cq Wqb_h = [q_nope_h | q_rope_h]
        [ckv | kr] = y Wkva;  [k_nope_h | v_h] = RMSNorm(ckv) Wkvb_h
        q_h = [q_nope_h | rope(q_rope_h)],  k_h = [k_nope_h | rope(kr)]

    The rotated half of k is one vector a token, the same for every head.
    Kimi-Linear's (HF `KimiMLAAttention`) has no low-rank q (`q_lora_rank` 0:
    q_h = y Wq_h) and no positions (`position_type` "none": q_rope_h and kr
    enter as they are). Xing4.0's (DeepSeek-V3's own head) turns the rope dims
    under yarn (`rope_scaling`: the scaled frequencies of a head of `qk_rope`
    dims, cos and sin x its `attention_factor`); the softmax's scale is the
    caller's. q and k are (nope + rope) wide, v `v_head_dim`: the caller pads
    them to the one attention call's `head_dim`."""
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    eps, theta = cfg.layernorm_eps, cfg.rope_theta
    if cfg.q_lora_rank:
        cq = rms_norm(_dense(y, p["wq_a"], dtype), p["q_a_norm"]["scale"], eps)
        q = jnp.einsum("bsr,rnd->bsnd", cq, p["wq_b"]["kernel"].astype(dtype))
    else:
        q = jnp.einsum("bsh,hnd->bsnd", y, p["wq"]["kernel"].astype(dtype))
    ckv_kr = _dense(y, p["wkv_a"], dtype)
    ckv = rms_norm(ckv_kr[..., :cfg.kv_lora_rank], p["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("bsr,rnd->bsnd", ckv, p["wkv_b"]["kernel"].astype(dtype))
    if cfg.position_type == "rope":
        q_rope = apply_rotary(q[..., nope:], positions, theta, scaling=cfg.rope_scaling)
        k_rope = apply_rotary(ckv_kr[:, :, None, cfg.kv_lora_rank:], positions, theta, scaling=cfg.rope_scaling)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    else:
        k_rope = ckv_kr[:, :, None, cfg.kv_lora_rank:]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, k_rope.shape[:2] + (cfg.num_heads, rope))], axis=-1)
    return q, k, kv[..., nope:]


def attention_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
                    mesh, axes, attn_bias, attn_sharding, return_kv: bool,
                    scope: Optional[str] = None, window: Optional[int] = None, publish=()):
    """Softmax attention on normed activations (B, S_local, H) -> the
    output projection's result, the post-rope (k, v) where asked, and no
    counters. Seq-sharded activations (megatron-sp / ulysses) are re-gathered
    into head-sharded full-sequence tensors for attention (all-gather or
    all-to-all inserted by XLA — the hand-written collectives of reference
    transformer.py:1928-2177). `scope`, `window`: the window part's call
    (`parts/window.py`), everything but the attention call under a scope of
    its own and the call over a window of so many keys. Where that call runs as
    the window kernels (`ops/attention.window_takes_kernels`) they read q AS
    PROJECTED: q's rope, where it is the half-split turn of whole heads at the
    plain frequencies, and the head's gate are the kernels' (`q_rope`,
    `head_gate`) and make no pass of their own."""
    if cfg.diff_attention:  # the differential form, its own function: (`publish`: its k and v, handed on)
        return diff_attention_mixer(p, y, positions, cfg, attn_bias=attn_bias, attn_sharding=attn_sharding,
                                    scope=scope or tracing.ATTN_PROJ, window=window, publish=publish)
    dtype = cfg.compute_dtype
    if cfg.position_type == "rope" and mesh is not None and axes is not None:
        # Pin positions to THIS layer's sharding so each layer derives its
        # own rope cos/sin tables in its own layout. Without this, XLA CSEs
        # the identical table computation across adjacent layers with
        # different strategies and reshards the shared result — under the
        # 1F1B schedule's divergent branches that reshard can be a
        # collective-permute, which deadlocks across stages (see
        # parallel/pipeline_1f1b.py divergence-safety invariant).
        pin = lambda pos: S.constrain(pos, mesh, S.act_spec(axes, ndim=2))  # noqa: E731
    else:
        pin = lambda pos: pos  # noqa: E731
    # one scope for everything of the mixer but the attention call: a block
    # before it and a block after it
    scope = scope or (tracing.ATTN_LATENT if cfg.latent_attention else tracing.ATTN_PROJ)
    gate, sm_scale, q_rope, head_gate = None, cfg.attention_multiplier, None, None
    with jax.named_scope(scope):
        if cfg.latent_attention:
            q, k, v = latent_qkv_projection(p, y, pin(positions), cfg, dtype)
            if q.shape[-1] != cfg.head_dim:  # zeros add nothing to a score
                sm_scale = sm_scale or q.shape[-1] ** -0.5
            # (a v padded with zeros gives zeros in the dims cut off below: exact)
            q, k, v = (t if t.shape[-1] == cfg.head_dim else jnp.pad(
                t, ((0, 0),) * 3 + ((0, cfg.head_dim - t.shape[-1]),)) for t in (q, k, v))
        else:
            # a window's call as the window kernels: they read q where the matmul wrote it
            kernels = window is not None and window_takes_kernels(
                y.shape[:2] + (cfg.num_heads, cfg.head_dim), y.shape[:2] + (cfg.num_kv_heads, cfg.head_dim),
                window=window, biased=attn_bias is not None, impl=cfg.attn_impl, sharding=attn_sharding)
            q, k, v = qkv_projection(p, y, cfg, dtype, flat_q=kernels)
            if cfg.attn_output_gate:
                q, gate = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
            elif cfg.attn_head_gate and kernels:  # (B, S, nh) logits: one gate for all of a head's dims
                head_gate = _dense(y, p["wg"], dtype)
            elif cfg.attn_head_gate:
                gate = _dense(y, p["wg"], dtype)[..., None]
            if cfg.qk_norm:
                q, k = qk_normed(p, q, k, cfg)
            if cfg.position_type == "rope":
                positions = pin(positions)
                how = dict(rotary_dim=cfg.rotary_dim, scaling=cfg.rope_scaling)
                if kernels:  # None where the rotation is no product with two tables: q is turned here as ever
                    q_rope = half_split_tables(positions, cfg.head_dim, cfg.rope_theta, **how)
                if q_rope is None:
                    q = apply_rotary(q, positions, cfg.rope_theta, **how)
                k = apply_rotary(k, positions, cfg.rope_theta, **how)
    if mesh is not None and axes is not None and len(axes.tp) + len(axes.cp) > 0:
        # (B, S/x, nh, hd) -> (B, S/cp, nh/tp, hd): XLA inserts the all-to-all
        # (ulysses) or all-gather+split (megatron-sp) when seq was tp-sharded.
        head_spec = P(S._ax(axes.batch_axes), S._ax(axes.cp), S._ax(axes.tp), None)
        q, k, v = (S.constrain(t, mesh, head_spec) for t in (q, k, v))
    kv_out = (k, v) if return_kv else None
    if axes is not None and mesh is not None and len(axes.cp) > 0:
        if return_kv:
            raise ValueError(
                "return_kv is unsupported under ring context parallelism "
                "(cp>1): blockwise ring attention never materialises the "
                "full per-layer k/v — serve refuses cp layouts (GLS014)"
            )
        from galvatron_tpu.ops.ring_attention import ring_attention

        attn = ring_attention(
            q, k, v, positions, mesh=mesh, axes=axes, causal=cfg.causal,
            bias=attn_bias,
        )
    else:
        # the generic tree's attn_bias is always padding_attn_bias output, so
        # the flash path may lower it to segment ids instead of falling back
        # (jax's flash kernels' calls carry no nested scope: the benchmark finds them by name; the repo's own
        # causal pair runs under gt.attn.core, which ops/attention._pallas_causal opens, a window's under gt.attn.band)
        with jax.named_scope(tracing.ATTN_WINDOW_BAND) if window is not None else contextlib.nullcontext():
            attn = core_attention(q, k, v, causal=cfg.causal, bias=attn_bias,
                                  impl=cfg.attn_impl, bias_type="key_padding",
                                  sharding=attn_sharding, sm_scale=sm_scale, window=window,
                                  q_rope=q_rope, head_gate=head_gate)
    with jax.named_scope(scope):
        if gate is not None:
            attn = attn * jax.nn.sigmoid(gate)
        if cfg.latent_attention and cfg.v_head_dim != cfg.head_dim:
            attn = attn[..., :cfg.v_head_dim]
        attn = attn.reshape(attn.shape[0], attn.shape[1], -1)
        o = _dense(attn, p["wo"], dtype)
    return o, kv_out, None


def _append_token_kv(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write the (B, T, nkv, hd) `new` k/v block at per-row position `idx`
    of the (B, S_cache, nkv, hd) cache (vmapped dynamic_update_slice — the
    row dim is the vmapped dim, so a slot-sharded cache updates locally)."""
    return jax.vmap(
        lambda c, t, i: jax.lax.dynamic_update_slice(c, t, (i, 0, 0))
    )(cache, new, idx)


def attention_decode(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
                     k_cache: jax.Array, v_cache: jax.Array, write_index: jax.Array,
                     mesh=None, axes: Optional[LayerAxes] = None, attn_bias=None):
    """Softmax attention of ONE new token a cache slot on normed activations
    (B, 1, H): project this token's k/v, append them at ``write_index``, and
    attend the length-1 query against the updated cache with ``attn_bias``
    carrying BOTH causality and slot-length masking (the causal iota mask is
    meaningless for a length-1 query, so ``causal=False`` and the additive
    bias from serve/kv_cache.length_bias does the whole job).
    -> (the output projection's result, k_cache, v_cache)."""
    dtype = cfg.compute_dtype
    with jax.named_scope(tracing.ATTN_PROJ):
        q, k, v = qkv_projection(p, y, cfg, dtype)
        if cfg.qk_norm:
            q, k = qk_normed(p, q, k, cfg)
        if cfg.position_type == "rope":
            q = apply_rotary(q, positions, cfg.rope_theta, rotary_dim=cfg.rotary_dim)
            k = apply_rotary(k, positions, cfg.rope_theta, rotary_dim=cfg.rotary_dim)
    k_cache = _append_token_kv(k_cache, k.astype(k_cache.dtype), write_index)
    v_cache = _append_token_kv(v_cache, v.astype(v_cache.dtype), write_index)
    if mesh is not None and axes is not None and len(axes.tp) > 0:
        # decode head layout: slots on the batch axes, kv-heads on tp (the
        # cache's own layout, serve/kv_cache.layer_kv_spec); no cp/seq axes —
        # serve refuses those layouts before tracing (GLS014)
        head_spec = P(S._ax(axes.batch_axes), None, S._ax(axes.tp), None)
        q = S.constrain(q, mesh, head_spec)
        k_cache = S.constrain(k_cache, mesh, head_spec)
        v_cache = S.constrain(v_cache, mesh, head_spec)
    attn = core_attention(
        q, k_cache.astype(dtype), v_cache.astype(dtype), causal=False,
        bias=attn_bias, impl=cfg.attn_impl,
        sharding=(KernelSharding.for_layer(mesh, axes)
                  if mesh is not None and axes is not None else None),
    )
    with jax.named_scope(tracing.ATTN_PROJ):
        attn = attn.reshape(attn.shape[0], attn.shape[1], cfg.num_heads * cfg.head_dim)
        o = _dense(attn, p["wo"], dtype)
    return o, k_cache, v_cache


def _attention_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    tp = None if axes.ulysses else S._ax(axes.tp)
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    sp: Params = {}
    if cfg.latent_attention:
        # ordinary leaves (tp is refused, GLS018): ZeRO-3 splits the input dim
        if cfg.q_lora_rank:
            sp["wq_a"] = {"kernel": P(z3, None)}
            sp["q_a_norm"] = {"scale": r1}
            sp["wq_b"] = {"kernel": P(z3, None, None)}
        else:
            sp["wq"] = {"kernel": P(z3, None, None)}
        sp["wkv_a"] = {"kernel": P(z3, None)}
        sp["kv_a_norm"] = {"scale": r1}
        sp["wkv_b"] = {"kernel": P(z3, None, None)}
    elif cfg.fused_qkv:
        sp["wqkv"] = {"kernel": P(z3, None, tp, None)}
        if cfg.qkv_bias:
            sp["wqkv"]["bias"] = P(None, tp, None)
    else:
        sp["wq"] = {"kernel": P(z3, tp, None)}
        sp["wkv"] = {"kernel": P(z3, None, tp, None)}
        if cfg.qkv_bias:
            sp["wq"]["bias"] = P(tp, None)
            sp["wkv"]["bias"] = P(None, tp, None)
    sp["wo"] = {"kernel": P(tp, z3)}
    if cfg.out_bias:
        sp["wo"]["bias"] = r1
    if cfg.qk_norm:
        sp["q_norm"] = {"scale": r1}
        sp["k_norm"] = {"scale": r1}
    if cfg.attn_head_gate:
        sp["wg"] = {"kernel": P(z3, tp)}
    if cfg.diff_attention:
        sp["diff"] = diff_specs(axes)
    return sp


# (a full layer's k and v are handed on in the differential form alone: `cross` reads no other)
ATTENTION = LayerPart(_init_attention, attention_mixer, _attention_specs,
                      (tracing.ATTN_PROJ, tracing.ATTN_LATENT, tracing.ATTN_DIFF), validate=_validate,
                      unsupported=_unsupported, decode=attention_decode, publishes=("k", "v"), place=place_diff)
