"""Generic functional transformer: init, per-layer forward, vocab-parallel loss.

This is the TPU-native analogue of the reference's model-integration layer
(`<M>Model_tensor_parallel.py` + `<M>Model_sequential.py`, e.g.
galvatron/models/gpt_hf/GPTModel_tensor_parallel.py:84-132 and
GPTModel_sequential.py:201-248). Where the reference rewrites HF modules into
Megatron ParallelAttention/ParallelMLP with per-layer NCCL groups, here a
model is (config, params-pytree, pure functions); the per-layer parallel
strategy enters only through PartitionSpecs (parallel/spec.py) and sharding
constraints at layer boundaries.

One `TransformerConfig` covers the reference's model zoo:
GPT (learned pos, pre-LN, gelu), LLaMA (rope, rmsnorm, swiglu, GQA),
BERT/ViT (bidirectional, post-LN), T5 (relative bias, enc-dec glue in
models/t5.py).
"""

from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.config.strategy import (
    HybridParallelConfig,
    LayerRun,
    LayerStrategy,
    layer_runs,
    model_layer_kinds,
)
from galvatron_tpu.obs import tracing
from galvatron_tpu.ops.attention import KernelSharding, core_attention
from galvatron_tpu.ops.linear_attention import (Heads, causal_conv, gated_delta_rule, kda_kernel_mixer, kda_layout,
                                                kda_rule, kernel_mixer, linear_layout, mixer_form)
from galvatron_tpu.ops.moe import moe_ffn, swiglu
from galvatron_tpu.ops.norms import layer_norm, rms_norm
from galvatron_tpu.ops.ssd import ssd_scan
from galvatron_tpu.ops.rope import apply_rotary
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes, layer_axes, mesh_axis_size, vocab_axes

Params = Dict[str, Any]


@dataclass
class TransformerConfig:
    hidden_size: int
    num_heads: int
    num_layers: int
    vocab_size: int
    max_seq_len: int = 2048
    num_kv_heads: Optional[int] = None
    ffn_hidden: Optional[int] = None
    head_dim: Optional[int] = None
    norm_type: str = "layernorm"  # layernorm | rmsnorm
    activation: str = "gelu"  # gelu | swiglu | relu
    position_type: str = "learned"  # learned | rope | none
    causal: bool = True
    pre_norm: bool = True
    tie_embeddings: bool = True
    qkv_bias: bool = True
    mlp_bias: bool = True
    out_bias: bool = True
    layernorm_eps: float = 1e-5
    rope_theta: float = 10000.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    # initializer scales
    init_std: float = 0.02
    # --- encoder-family extensions (bert_hf / vit_hf, SURVEY.md §2.4) ---
    type_vocab_size: int = 0  # BERT token-type embeddings
    embed_norm: bool = False  # LayerNorm after the embedding sum (BERT)
    head_type: str = "lm"  # lm | mlm | classification
    num_classes: int = 0
    pool_type: str = "cls"  # cls | mean (classification pooling)
    input_type: str = "tokens"  # tokens | patches (vision)
    image_size: int = 224
    patch_size: int = 16
    num_channels: int = 3
    use_cls_token: bool = False
    # --- what a published sparse-expert config states (OLMoE); the defaults
    # are the dense model, whose step none of these touches ---
    num_experts: int = 0  # > 0: the MLP half is routed experts of width ffn_hidden
    experts_per_token: int = 0
    norm_topk_prob: bool = False  # renormalise the chosen experts' weights
    router_aux_loss_coef: float = 0.0  # x the load-balancing loss
    router_z_loss_coef: float = 0.0  # x the router z-loss
    # a norm of q and of k before rope: True over the WHOLE projection (OLMoE),
    # "head" over each head's dims with one scale for all heads (Qwen3-Next)
    qk_norm: Any = False
    # --- what GLM-4.7-Flash's published config adds (glm4_moe_lite, the
    # DeepSeek-V3 block); again the defaults are the model without them ---
    # latent attention (MLA): q and k/v are projected down to a low rank,
    # normed there and projected up a head; a head's q and k are `qk_nope`
    # dims without positions beside `qk_rope` rotated ones, and the rotated
    # half of k is ONE vector shared by all heads. `q_lora_rank` 0: q is
    # projected a head straight from the hidden state (Kimi-Linear), and
    # `position_type` "none" leaves the `qk_rope` dims unrotated. `head_dim` is
    # the width of the ONE attention call, qk_nope + qk_rope or wider: q and k
    # (at 1 / sqrt(qk_nope + qk_rope)) and a narrower v are padded to it with zeros
    q_lora_rank: int = 0
    kv_lora_rank: int = 0  # > 0: latent attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    first_dense_layers: int = 0  # leading layers whose MLP half is dense, of width
    dense_ffn_hidden: Optional[int] = None  # this (ffn_hidden is then ONE expert's)
    num_shared_experts: int = 0  # dense SwiGLU(s) of the experts' width beside the routed ones
    router_score: str = "softmax"  # softmax | sigmoid (scores an expert independently)
    routed_scaling_factor: float = 1.0  # x the chosen experts' weights
    # `noaux_tc`: the choice of experts adds a bias to the scores that no
    # gradient moves; once a step it moves by this much against the sign of
    # each expert's load (arXiv:2412.19437 2.1.2). 0.0 holds the bias still
    router_bias: bool = False
    router_bias_update_rate: float = 0.0
    # a chip's share of the experts: the router ranks all `num_experts`, this
    # program holds `experts_held` of them from `experts_held_start` on and
    # computes their part of the result (0: all of them)
    experts_held: int = 0
    experts_held_start: int = 0
    mtp_layers: int = 0  # multi-token-prediction modules (0 or 1) after the stack
    mtp_loss_weight: float = 0.0  # x the cross entropy of the token after next
    # --- what Qwen3-Next's published config adds (qwen3_next): layers whose
    # token mixer is a gated-DeltaNet linear attention (`linear_mixer`,
    # ops/linear_attention.py) among layers of gated softmax attention ---
    # > 0: layer i attends where (i + 1) % this == 0 and is linear elsewhere
    full_attention_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0  # each key head serves value / key heads
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 0  # taps of the causal convolution on q, k and v
    partial_rotary_factor: float = 1.0  # rope on this share of a head's leading dims
    attn_output_gate: bool = False  # q is projected beside a gate: attn x sigmoid(gate)
    norm_zero_centered: bool = False  # RMSNorm scales by (1 + w), w from 0
    shared_expert_gate: bool = False  # the shared expert x sigmoid(y w), w (hidden, 1)
    # --- what Granite-4.0-H's published config adds (granitemoehybrid):
    # Mamba-2 state-space layers (`ssm_mixer`, ops/ssd.py) among layers of
    # softmax attention without positions, and four multipliers ---
    # the token mixer of each layer in HF's words, "mamba" (the mixer "ssm"),
    # "kda" (Kimi Delta Attention, `kda_mixer`: its heads and convolution are
    # the `linear_*` fields above) or "attention", where the pattern is a LIST
    # (HF `layer_types`; Kimi-Linear's two lists of layer numbers) and no
    # interval says it. A model cut in depth runs the list's first `num_layers`
    # entries, so the published list may stay whole
    layer_types: Optional[List[str]] = None
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_dim: int = 0  # a head's state is (ssm_head_dim, ssm_state_dim); B and C one group
    ssm_conv_kernel: int = 0  # taps of the causal convolution on [x | B | C], with a bias
    # each a Python float whose default is the model without it: a factor of
    # 1.0 is not multiplied by, so every other model's arithmetic is bit for
    # bit what it was
    embedding_multiplier: float = 1.0  # x the embedding's rows
    residual_multiplier: float = 1.0  # x each half's output before it joins the residual stream
    attention_multiplier: Optional[float] = None  # the softmax's scale in place of 1 / sqrt(head_dim)
    logits_scaling: float = 1.0  # the head's logits are divided by it
    # which `MIXERS` entry ONE layer runs. `layer_config(kind)` sets it; a
    # model's own config leaves it and states the pattern above
    mixer: str = "attention"

    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size
        if self.latent_attention:
            widest = max(self.qk_nope_head_dim + self.qk_rope_head_dim, self.v_head_dim)
            if self.head_dim is None:
                self.head_dim = widest
            if (self.head_dim < widest or self.q_lora_rank < 0
                    or min(self.qk_nope_head_dim, self.qk_rope_head_dim, self.v_head_dim) < 1):
                raise ValueError(
                    "latent attention runs as ONE attention call at head_dim, to which q and k "
                    "(qk_nope + qk_rope dims) and v are padded: head_dim %r >= qk_nope %d + "
                    "qk_rope %d and >= v_head_dim %d is asked, each of the three 1 or more, and "
                    "q_lora_rank %d >= 0" % (self.head_dim, self.qk_nope_head_dim,
                                             self.qk_rope_head_dim, self.v_head_dim, self.q_lora_rank))
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers=%d: one multi-token-prediction module at most"
                             % self.mtp_layers)
        if self.qk_norm not in (False, True, "head"):
            raise ValueError("qk_norm=%r: False, True (the whole projection) or \"head\""
                             % (self.qk_norm,))
        if self.layer_types is not None:
            self.layer_types = list(self.layer_types)
            if (len(self.layer_types) < self.num_layers or self.full_attention_interval
                    or set(self.layer_types) - {"mamba", "kda", "attention"}):
                raise ValueError(
                    "layer_types names the mixer, \"mamba\", \"kda\" or \"attention\", of each of "
                    "the %d layers (or more: the first so many are run), and no "
                    "full_attention_interval beside it; got %r" % (self.num_layers, self.layer_types))
        kda = self.mixer == "kda" or "kda" in (self.mixers() or ())
        if self.full_attention_interval or self.mixer == "linear" or kda:
            # (the module after the stack takes a softmax layer's outputs: `mtp_logits`)
            heads = (self.linear_num_key_heads, self.linear_num_value_heads)
            if (min(heads + (self.linear_key_head_dim, self.linear_value_head_dim,
                             self.linear_conv_kernel)) < 1 or heads[1] % heads[0]
                    or self.mtp_layers or (kda and heads[0] != heads[1])):
                raise ValueError(
                    "linear-attention layers (full_attention_interval=%d, or layer_types naming "
                    "\"kda\") want linear_num_key_heads dividing linear_num_value_heads (equal "
                    "under \"kda\"), head dims and a convolution kernel of 1 or more, and no "
                    "multi-token-prediction module; got heads %r, dims (%d, %d), kernel %d" % (
                        self.full_attention_interval, heads, self.linear_key_head_dim,
                        self.linear_value_head_dim, self.linear_conv_kernel))
        if self.mixer == "ssm" or "ssm" in (self.mixers() or ()):
            if (min(self.ssm_num_heads, self.ssm_head_dim, self.ssm_state_dim, self.ssm_conv_kernel) < 1
                    or self.routed or self.mtp_layers):
                raise ValueError(
                    "state-space layers want ssm_num_heads, ssm_head_dim, ssm_state_dim and a "
                    "convolution kernel of 1 or more, a dense MLP half and no "
                    "multi-token-prediction module; got heads %d x %d, state %d, kernel %d"
                    % (self.ssm_num_heads, self.ssm_head_dim, self.ssm_state_dim, self.ssm_conv_kernel))
        if self.input_type == "patches":
            n_patches = (self.image_size // self.patch_size) ** 2
            self.max_seq_len = n_patches + (1 if self.use_cls_token else 0)

    @property
    def fused_qkv(self) -> bool:
        return self.num_kv_heads == self.num_heads and not self.attn_output_gate

    @property
    def mlp_fan_in(self) -> tuple:
        """MLP input-projection kernel trailing dims: (2, ffn) for swiglu
        (fused gate+up, split on an unsharded leading dim) else (ffn,)."""
        return (2, self.ffn_hidden) if self.activation == "swiglu" else (self.ffn_hidden,)

    @property
    def routed(self) -> bool:
        """Whether the model has layers whose MLP half is routed experts
        (ops/moe.py): all of them but the `first_dense_layers`."""
        return self.num_experts > 0

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    def mixers(self) -> Optional[Tuple[str, ...]]:
        """The `MIXERS` key of each layer where `layer_types` lists them, else None."""
        if self.layer_types is None:
            return None
        return tuple("ssm" if t == "mamba" else t for t in self.layer_types[:self.num_layers])

    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of each layer, what `config/strategy.layer_runs` splits
        runs on beside the layout. A kind names the layer's two halves: its
        MLP half, "dense" or "routed", after its token mixer where that is
        not softmax attention ("linear.routed", "ssm.dense", "kda.routed": `MIXERS`). Which
        layers attend is said by `full_attention_interval` (every so many)
        or, layer by layer, by the list `layer_types`."""
        if not self.routed:
            mlp = ("dense",) * self.num_layers
        else:
            lead = min(self.first_dense_layers, self.num_layers)
            mlp = ("dense",) * lead + ("routed",) * (self.num_layers - lead)
        if self.layer_types is not None:
            return tuple(m if t == "attention" else t + "." + m for t, m in zip(self.mixers(), mlp))
        every = self.full_attention_interval
        if not every:
            return mlp
        return tuple(m if (i + 1) % every == 0 else "linear." + m for i, m in enumerate(mlp))

    def layer_config(self, kind: str) -> "TransformerConfig":
        """The config ONE layer of this kind is built and run from: a dense
        layer of a model that also has routed ones is the same block with no
        experts and the dense width, and a layer of a model that mixes its
        token mixers names its own (`mixer`) and no pattern.
        `init_layer_params`, `layer_forward` and `layer_param_specs` take a
        layer's config."""
        mixer, _, mlp = kind.rpartition(".")
        cfg = self
        if mlp != "routed" and self.routed:
            cfg = dataclasses.replace(
                cfg, num_experts=0, experts_held=0, num_shared_experts=0, router_bias=False,
                ffn_hidden=self.dense_ffn_hidden or self.ffn_hidden)
        if self.full_attention_interval or self.layer_types is not None:
            cfg = dataclasses.replace(cfg, mixer=mixer or "attention", full_attention_interval=0,
                                      layer_types=None)
        return cfg

    @property
    def layer_aux(self) -> bool:
        """Whether a layer hands back auxiliary terms beside its output (a
        router's losses and loads, a linear or state-space mixer's counters):
        of a layer's config its own layer, of a model's config any of its
        layers."""
        return (self.routed or self.mixer != "attention" or self.full_attention_interval > 0
                or any(m != "attention" for m in self.mixers() or ()))

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the experts this program holds."""
        return (self.experts_held_start, self.experts_held) if self.experts_held \
            else (0, self.num_experts)

    @property
    def routed_layers(self) -> int:
        """Routed blocks a step runs: the stack's and the MTP module's."""
        return (sum(kind.endswith("routed") for kind in self.layer_kinds())
                + (self.mtp_layers if self.routed else 0))


# ===================================================================== init
ROUTER_BIAS = "e_score_correction_bias"  # HF's name: (num_experts,) float32, no gradient


def _dense_init(rng, shape, std, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * std).astype(dtype)


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
    return p


def _init_linear(ks, cfg: TransformerConfig) -> Params:
    """The gated-DeltaNet mixer's leaves, under `linear` (HF
    `Qwen3NextGatedDeltaNet`: in_proj_qkvz, in_proj_ba, conv1d, A_log,
    dt_bias, norm, out_proj). `wqkvz`'s columns lie [q | k | v | z], `wba`'s
    [b | a], heads in order within each (HF groups them a key head: on random
    weights a permutation of columns). The gate starts as the Gated DeltaNet
    reference does: A = exp(A_log) ~ U(0, 16) and dt = softplus(dt_bias)
    log-uniform in [0.001, 0.1], so that exp(g) spans 0.2 to 1 a token and
    state crosses chunks; the taps U(-1, 1) / sqrt(taps), PyTorch's default
    for a convolution of that fan-in."""
    h, taps = cfg.hidden_size, cfg.linear_conv_kernel
    nv = cfg.linear_num_value_heads
    key_dim = cfg.linear_num_key_heads * cfg.linear_key_head_dim
    value_dim = nv * cfg.linear_value_head_dim
    kin = jax.random.split(ks[0], 2)
    kgate = jax.random.split(ks[4], 3)
    step = jnp.exp(jax.random.uniform(kgate[2], (nv,), jnp.float32, math.log(1e-3), math.log(0.1)))
    return {"linear": {
        "wqkvz": {"kernel": _dense_init(
            kin[0], (h, 2 * key_dim + 2 * value_dim), cfg.init_std, cfg.param_dtype)},
        "wba": {"kernel": _dense_init(kin[1], (h, 2 * nv), cfg.init_std, cfg.param_dtype)},
        "conv": jax.random.uniform(kgate[0], (2 * key_dim + value_dim, taps), jnp.float32,
                                   -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
        "A_log": jnp.log(jax.random.uniform(kgate[1], (nv,), jnp.float32, 1e-6, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "norm": {"scale": jnp.ones((cfg.linear_value_head_dim,), cfg.param_dtype)},
        "wout": {"kernel": _dense_init(ks[1], (value_dim, h), _proj_std(cfg), cfg.param_dtype)},
    }}


def _init_kda(ks, cfg: TransformerConfig) -> Params:
    """The Kimi-Delta-Attention mixer's leaves, under `kda` (HF
    `KimiDeltaAttention`: q_proj, k_proj, v_proj, their three conv1d,
    f_a_proj / f_b_proj, b_proj, A_log, dt_bias, g_a_proj / g_b_proj, o_norm,
    o_proj). The three projections are ONE kernel `wqkv` whose columns lie
    [q | k | v], heads in order within each, and the three convolutions one
    `conv` over those columns (on random weights, HF's three of each side by
    side); the gate's and the output gate's low-rank pairs `wf_a`, `wf_b` and
    `wg_a`, `wg_b` of rank d_v, no bias. The gate starts as the linear
    mixer's does, `A_log` a head and `dt_bias` a head AND channel: exp(g)
    spans 0.2 to 1 a token, so that state crosses chunks."""
    h, taps, nh = cfg.hidden_size, cfg.linear_conv_kernel, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = nh * dk, nh * dv
    kin = jax.random.split(ks[0], 6)
    kgate = jax.random.split(ks[4], 3)
    step = jnp.exp(jax.random.uniform(kgate[2], (key_dim,), jnp.float32, math.log(1e-3), math.log(0.1)))
    dense = lambda key, shape: {"kernel": _dense_init(key, shape, cfg.init_std, cfg.param_dtype)}  # noqa: E731
    return {"kda": {
        "wqkv": dense(kin[0], (h, 2 * key_dim + value_dim)),
        "wf_a": dense(kin[1], (h, dv)), "wf_b": dense(kin[2], (dv, key_dim)),
        "wg_a": dense(kin[3], (h, dv)), "wg_b": dense(kin[4], (dv, value_dim)),
        "wb": dense(kin[5], (h, nh)),
        "conv": jax.random.uniform(kgate[0], (2 * key_dim + value_dim, taps), jnp.float32,
                                   -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
        "A_log": jnp.log(jax.random.uniform(kgate[1], (nh,), jnp.float32, 1e-6, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "norm": {"scale": jnp.ones((dv,), cfg.param_dtype)},
        "wout": {"kernel": _dense_init(ks[1], (value_dim, h), _proj_std(cfg), cfg.param_dtype)},
    }}


def _init_ssm(ks, cfg: TransformerConfig) -> Params:
    """The Mamba-2 mixer's leaves, under `ssm` (HF `GraniteMoeHybridMambaLayer`:
    in_proj, conv1d, dt_bias, A_log, D, norm, out_proj). `win`'s columns lie
    [z | x | B | C | dt] as HF's. Initialised as the Mamba-2 reference does: A
    = exp(A_log) ~ U(1, 16), dt = softplus(dt_bias) log-uniform in [0.001,
    0.1], D = 1, so that exp(dt A) spans 0.2 to 0.999 a token and state
    crosses chunks; the taps and their bias U(-1, 1) / sqrt(taps), PyTorch's
    default for a convolution of that fan-in."""
    h, taps, nh = cfg.hidden_size, cfg.ssm_conv_kernel, cfg.ssm_num_heads
    inner = nh * cfg.ssm_head_dim
    conv_dim = inner + 2 * cfg.ssm_state_dim
    kgate = jax.random.split(ks[4], 4)
    step = jnp.exp(jax.random.uniform(kgate[2], (nh,), jnp.float32, math.log(1e-3), math.log(0.1)))
    p = {
        "win": {"kernel": _dense_init(ks[0], (h, inner + conv_dim + nh), cfg.init_std, cfg.param_dtype)},
        "conv": {"kernel": jax.random.uniform(kgate[0], (conv_dim, taps), jnp.float32,
                                              -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5,
                 "bias": jax.random.uniform(kgate[3], (conv_dim,), jnp.float32,
                                            -1.0, 1.0).astype(cfg.param_dtype) / taps ** 0.5},
        "A_log": jnp.log(jax.random.uniform(kgate[1], (nh,), jnp.float32, 1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
        "D": jnp.ones((nh,), jnp.float32),
        "norm": {"scale": jnp.ones((inner,), cfg.param_dtype)},
        "wout": {"kernel": _dense_init(ks[1], (inner, h), _proj_std(cfg), cfg.param_dtype)},
    }
    return {"ssm": p}


def _proj_std(cfg: TransformerConfig) -> float:
    return cfg.init_std / (2 * cfg.num_layers) ** 0.5


def _norm_scale(shape, cfg: TransformerConfig) -> jax.Array:
    """An RMSNorm's or LayerNorm's scale as the model starts it: 1, or 0
    where the norm multiplies by (1 + w)."""
    return (jnp.zeros if cfg.norm_zero_centered else jnp.ones)(shape, cfg.param_dtype)


def init_layer_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    """One layer's tree: its two norms, its token mixer's leaves (`MIXERS`)
    and its MLP half's."""
    ks = jax.random.split(rng, 5)
    h = cfg.hidden_size
    p: Params = {}
    norm = {"scale": _norm_scale((h,), cfg)}
    if cfg.norm_type == "layernorm":
        norm["bias"] = jnp.zeros((h,), cfg.param_dtype)
    p["ln1"] = jax.tree.map(jnp.copy, norm)
    p["ln2"] = jax.tree.map(jnp.copy, norm)
    p.update(MIXERS[cfg.mixer].init(ks, cfg))
    proj_std = _proj_std(cfg)
    if cfg.routed:
        # one kernel a matrix with the experts leading: (E, h, 2F) the gate's
        # columns beside the up projection's (flat: a TPU tiles the minor
        # dims, and a (2, F) pair there costs a copy a use), (E, F, h) down;
        # the router (h, E) stays float32 in the forward
        e, fan_in = cfg.num_experts, math.prod(cfg.mlp_fan_in)
        kr = jax.random.fold_in(ks[2], 1)
        p["router"] = {"kernel": _dense_init(kr, (h, e), cfg.init_std, cfg.param_dtype)}
        if cfg.router_bias:
            p["router"][ROUTER_BIAS] = jnp.zeros((e,), jnp.float32)
        held = cfg.held_experts[1]  # the router ranks all e; these are held
        p["wi"] = {"kernel": _dense_init(ks[2], (held, h, fan_in), cfg.init_std, cfg.param_dtype)}
        p["wo_mlp"] = {"kernel": _dense_init(ks[3], (held, cfg.ffn_hidden, h), proj_std, cfg.param_dtype)}
        if cfg.num_shared_experts:
            wide = cfg.num_shared_experts * cfg.ffn_hidden
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
    p["wi"] = {"kernel": _dense_init(ks[2], (h,) + cfg.mlp_fan_in, cfg.init_std, cfg.param_dtype)}
    if cfg.mlp_bias:
        p["wi"]["bias"] = jnp.zeros(cfg.mlp_fan_in, cfg.param_dtype)
    p["wo_mlp"] = {"kernel": _dense_init(ks[3], (cfg.ffn_hidden, h), proj_std, cfg.param_dtype)}
    if cfg.mlp_bias:
        p["wo_mlp"]["bias"] = jnp.zeros((h,), cfg.param_dtype)
    return p


def _norm_params(cfg: TransformerConfig) -> Params:
    p = {"scale": _norm_scale((cfg.hidden_size,), cfg)}
    if cfg.norm_type == "layernorm":
        p["bias"] = jnp.zeros((cfg.hidden_size,), cfg.param_dtype)
    return p


def init_model_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    n = cfg.num_layers
    h = cfg.hidden_size
    ks = jax.random.split(rng, n + 6)
    if cfg.input_type == "patches":
        patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
        embed: Params = {
            "patch": {
                "kernel": _dense_init(ks[0], (patch_dim, h), cfg.init_std, cfg.param_dtype),
                "bias": jnp.zeros((h,), cfg.param_dtype),
            },
            "wpe": _dense_init(ks[1], (cfg.max_seq_len, h), cfg.init_std, cfg.param_dtype),
        }
        if cfg.use_cls_token:
            embed["cls_token"] = jnp.zeros((h,), cfg.param_dtype)
    else:
        embed = {"wte": _dense_init(ks[0], (cfg.vocab_size, h), cfg.init_std, cfg.param_dtype)}
        if cfg.position_type == "learned":
            embed["wpe"] = _dense_init(ks[1], (cfg.max_seq_len, h), cfg.init_std, cfg.param_dtype)
        if cfg.type_vocab_size:
            embed["tte"] = _dense_init(ks[n + 3], (cfg.type_vocab_size, h), cfg.init_std, cfg.param_dtype)
    if cfg.embed_norm:
        embed["norm"] = _norm_params(cfg)
    params: Params = {
        "embed": embed,
        "layers": [init_layer_params(ks[2 + i], cfg.layer_config(kind))
                   for i, kind in enumerate(cfg.layer_kinds())],
    }
    if cfg.mtp_layers:
        # HF's names: enorm, hnorm, eh_proj, the block, shared_head.norm; the
        # embedding and the head are the model's own
        km = jax.random.split(jax.random.fold_in(rng, n), 2)
        params["mtp"] = {
            "enorm": _norm_params(cfg), "hnorm": _norm_params(cfg),
            "eh_proj": {"kernel": _dense_init(km[0], (2 * h, h), cfg.init_std, cfg.param_dtype)},
            "block": init_layer_params(km[1], cfg.layer_config(cfg.layer_kinds()[-1])),
            "norm": _norm_params(cfg),
        }
    # post-LN models (BERT) normalise inside each block; no final norm
    if cfg.pre_norm:
        params["final_norm"] = _norm_params(cfg)
    if cfg.head_type == "classification":
        params["head"] = {
            "kernel": _dense_init(ks[n + 4], (h, cfg.num_classes), cfg.init_std, cfg.param_dtype),
            "bias": jnp.zeros((cfg.num_classes,), cfg.param_dtype),
        }
    elif cfg.head_type == "mlm":
        params["head"] = {
            "transform": {
                "kernel": _dense_init(ks[n + 5], (h, h), cfg.init_std, cfg.param_dtype),
                "bias": jnp.zeros((h,), cfg.param_dtype),
            },
            "norm": _norm_params(cfg),
            "bias": jnp.zeros((cfg.vocab_size,), cfg.param_dtype),
        }
    if cfg.head_type in ("lm", "mlm") and not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": _dense_init(ks[n + 2], (h, cfg.vocab_size), cfg.init_std, cfg.param_dtype)
        }
    return params


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
    raise ValueError(cfg.activation)


def qkv_projection(p: Params, y: jax.Array, cfg: TransformerConfig, dtype):
    """y: (B, S, H) -> q (B,S,nh,hd), k/v (B,S,nkv,hd)."""

    def proj(pk):
        out = jnp.einsum("bsh,h...->bs...", y, pk["kernel"].astype(dtype))
        if "bias" in pk:
            out = out + pk["bias"].astype(dtype)
        return out

    if cfg.fused_qkv:
        qkv = proj(p["wqkv"])  # (B, S, 3, nh, hd)
        return qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    q = proj(p["wq"])
    kv = proj(p["wkv"])  # (B, S, 2, nkv, hd)
    return q, kv[:, :, 0], kv[:, :, 1]


def dense_mlp(p: Params, y: jax.Array, cfg: TransformerConfig, dtype) -> jax.Array:
    """The dense MLP half on normed activations (B, S, H)."""
    wi_out = jnp.einsum("bsh,h...->bs...", y, p["wi"]["kernel"].astype(dtype))
    if "bias" in p["wi"]:
        wi_out = wi_out + p["wi"]["bias"].astype(dtype)
    if cfg.activation == "swiglu":
        hmid = jax.nn.silu(wi_out[:, :, 0]) * wi_out[:, :, 1]
    else:
        hmid = _activation(wi_out, cfg)
    return _dense(hmid, p["wo_mlp"], dtype)


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
    enter as they are). q and k are (nope + rope) wide, v `v_head_dim`: the
    caller pads them to the one attention call's `head_dim`."""
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
        q_rope = apply_rotary(q[..., nope:], positions, theta)
        k_rope = apply_rotary(ckv_kr[:, :, None, cfg.kv_lora_rank:], positions, theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    else:
        k_rope = ckv_kr[:, :, None, cfg.kv_lora_rank:]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, k_rope.shape[:2] + (cfg.num_heads, rope))], axis=-1)
    return q, k, kv[..., nope:]


# ------------------------------------------------ layouts of routed experts
def expert_layout_reason(cfg, hp: Optional[HybridParallelConfig], mode: Optional[str] = None,
                         autotune: Optional[str] = None) -> Optional[str]:
    """Why this layout (or driver mode) cannot run a routed-experts config,
    or None. Experts are ordinary parameters under dp and ZeRO-1/2/3; no
    other axis has an expert form yet (`ep` is the next step), and what has
    none is refused by name (GLS018) at lint time and at trace time, not run
    wrong or priced as dense. The same holds of latent attention (and of the
    multi-token-prediction module that comes with it), whose low-rank
    projections have no tensor-, context- or sequence-parallel form and no
    cache in the decode engine: where it is the reason, it is named. And of
    gated-DeltaNet linear-attention layers among attention layers
    (`full_attention_interval` > 0): the recurrence has no tp, sp, cp or pp
    form, the decode engine no recurrent state, the cost models no row. And,
    alike, of Mamba-2 state-space layers (`layer_types` naming "ssm"): the
    scan's state runs along the whole sequence, the gated norm over all of a
    layer's channels. And of Kimi-Delta-Attention layers (`layer_types` naming
    "kda"): the per-channel delta rule is a recurrence as the scalar one is."""
    latent = bool(getattr(cfg, "latent_attention", False) or getattr(cfg, "mtp_layers", 0))
    linear, ssm, kda = _has_linear(cfg), _has_mixer(cfg, "ssm"), _has_mixer(cfg, "kda")
    if not (getattr(cfg, "routed", False) or latent or linear or ssm or kda):
        return None
    also = (" (nor has latent attention, MLA: kv_lora_rank > 0)" if latent else "") + (
        " (nor have linear-attention layers, full_attention_interval > 0: the delta rule's "
        "state runs along the whole sequence of all a layer's heads)" if linear else "") + (
        " (nor have Kimi-Delta-Attention layers, layer_types naming \"kda\": the per-channel "
        "delta rule's state runs along the whole sequence of all a layer's heads)" if kda else "") + (
        " (nor have state-space layers, layer_types naming \"ssm\": the scan's state runs along "
        "the whole sequence and the gated norm over all of a layer's channels)" if ssm else "")
    if mode == "serve":
        return "serve: the decode engine has no expert form" + (
            ", and no cache of latent attention's compressed k/v" if latent else "") + (
            ", and no recurrent state of a linear-attention layer (serve/kv_cache.py holds "
            "keys and values)" if linear else "") + (
            ", and no recurrent state of a Kimi-Delta-Attention layer, d_k rows a head that "
            "forget separately (serve/kv_cache.py holds keys and values)" if kda else "") + (
            ", and no convolution window or scan state of a state-space layer (serve/kv_cache.py "
            "holds keys and values)" if ssm else "")
    if (autotune or "off") != "off":
        return "autotune=%s: the re-search would price the block as dense" % autotune + (
            ", and latent attention as full-rank" if latent else "") + (
            ", and a linear-attention layer as softmax attention" if linear else "") + (
            ", and a Kimi-Delta-Attention layer as softmax attention" if kda else "") + (
            ", and a state-space layer as softmax attention" if ssm else "")
    if hp is None:
        return None
    if hp.pp > 1:
        return "pp=%d: the pipeline engines carry no router losses between stages" % hp.pp + (
            " and no multi-token-prediction module after the last" if latent else "") + (
            " and stack one kind of layer a stage, not linear-attention layers among "
            "attention layers" if linear else "") + (
            " and stack one kind of layer a stage, not Kimi-Delta-Attention layers among "
            "attention layers" if kda else "") + (
            " and stack one kind of layer a stage, not state-space layers among attention "
            "layers" if ssm else "")
    for i, s in enumerate(hp.layers):
        if s.tp > 1 or s.cp > 1 or s.sp:
            return ("layer %d: tp=%d cp=%d sp=%d: the experts' kernels and the dropless "
                    "dispatch have no tensor-, context- or sequence-parallel form%s"
                    % (i, s.tp, s.cp, int(s.sp), also))
    if hp.vocab_tp > 1:
        return "vocab_tp=%d: tensor parallelism of any layer is unsupported" % hp.vocab_tp
    if hp.tp_comm_mode != "gspmd":
        return "tp_comm_mode=%r: the manual TP path has no expert form%s" % (hp.tp_comm_mode, also)
    from galvatron_tpu.parallel import quant_collectives as QC

    if QC.wants_quant_comm(hp):
        return "quantized grad/param collectives run a local loss with no router statistics"
    return None


def _has_linear(cfg) -> bool:
    return bool(getattr(cfg, "full_attention_interval", 0) or getattr(cfg, "mixer", "") == "linear")


def _has_mixer(cfg, name: str) -> bool:
    """Whether a layer of the config (a model's or ONE layer's) runs this `MIXERS` key."""
    mixers = getattr(cfg, "mixers", None)
    return getattr(cfg, "mixer", "") == name or name in ((mixers() if callable(mixers) else None) or ())


def linear_layers_reason(cfg) -> Optional[str]:
    """What `search` and `profile` say of a config with linear-attention,
    Kimi-Delta-Attention or state-space layers, or None for one without."""
    if _has_mixer(cfg, "ssm"):
        return "state-space layers (layer_types naming \"ssm\") have no row in the cost models"
    if _has_mixer(cfg, "kda"):
        return "Kimi-Delta-Attention layers (layer_types naming \"kda\") have no row in the cost models"
    if _has_linear(cfg):
        return "linear-attention layers (full_attention_interval > 0) have no row in the cost models"
    return None


def expert_layout_diagnostic(reason: str):
    """The GLS018 diagnostic for a reason of `expert_layout_reason`."""
    from galvatron_tpu.analysis import diagnostics as D

    return D.make(
        "GLS018", "routed experts (num_experts > 0), latent attention, linear-attention, "
        "Kimi-Delta-Attention or state-space layers refused: %s; such a config runs on one "
        "chip and under dp with ZeRO-1/2/3" % reason, key="num_experts")


def refuse_expert_layout(reason: str):
    from galvatron_tpu.analysis.diagnostics import DiagnosticError

    raise DiagnosticError([expert_layout_diagnostic(reason)])


def assert_expert_layout_supported(cfg, hp: Optional[HybridParallelConfig]):
    """Trace-time half of GLS018 (strategy_lint.lint_hp reports it pre-trace)."""
    reason = expert_layout_reason(cfg, hp)
    if reason is not None:
        refuse_expert_layout(reason)


# ============================================================== layer forward
def attention_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
                    mesh, axes, attn_bias, attn_sharding, return_kv: bool):
    """Softmax attention on normed activations (B, S_local, H) -> the
    output projection's result, the post-rope (k, v) where asked, and no
    counters. Seq-sharded activations (megatron-sp / ulysses) are re-gathered
    into head-sharded full-sequence tensors for attention (all-gather or
    all-to-all inserted by XLA — the hand-written collectives of reference
    transformer.py:1928-2177)."""
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
    scope = tracing.ATTN_LATENT if cfg.latent_attention else tracing.ATTN_PROJ
    gate, sm_scale = None, cfg.attention_multiplier
    with jax.named_scope(scope):
        if cfg.latent_attention:
            q, k, v = latent_qkv_projection(p, y, pin(positions), cfg, dtype)
            if q.shape[-1] != cfg.head_dim:  # zeros add nothing to a score
                sm_scale = sm_scale or q.shape[-1] ** -0.5
            # (a v padded with zeros gives zeros in the dims cut off below: exact)
            q, k, v = (t if t.shape[-1] == cfg.head_dim else jnp.pad(
                t, ((0, 0),) * 3 + ((0, cfg.head_dim - t.shape[-1]),)) for t in (q, k, v))
        else:
            q, k, v = qkv_projection(p, y, cfg, dtype)
            if cfg.attn_output_gate:
                q, gate = q[..., :cfg.head_dim], q[..., cfg.head_dim:]
            if cfg.qk_norm:
                q, k = qk_normed(p, q, k, cfg)
            if cfg.position_type == "rope":
                positions = pin(positions)
                q = apply_rotary(q, positions, cfg.rope_theta, rotary_dim=cfg.rotary_dim)
                k = apply_rotary(k, positions, cfg.rope_theta, rotary_dim=cfg.rotary_dim)
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
        attn = core_attention(q, k, v, causal=cfg.causal, bias=attn_bias,
                              impl=cfg.attn_impl, bias_type="key_padding",
                              sharding=attn_sharding, sm_scale=sm_scale)
    with jax.named_scope(scope):
        if gate is not None:
            attn = attn * jax.nn.sigmoid(gate)
        if cfg.latent_attention and cfg.v_head_dim != cfg.head_dim:
            attn = attn[..., :cfg.v_head_dim]
        attn = attn.reshape(attn.shape[0], attn.shape[1], -1)
        o = _dense(attn, p["wo"], dtype)
    return o, kv_out, None


def _unit(t: jax.Array) -> jax.Array:
    """L2-normalised over a head's dims in float32, as HF's l2norm (the linear mixers' q and k)."""
    t32 = t.astype(jnp.float32)
    return t32 * jax.lax.rsqrt(jnp.sum(jnp.square(t32), axis=-1, keepdims=True) + 1e-6)


def linear_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
                 attn_sharding: Optional[KernelSharding] = None, **_):
    """Gated DeltaNet on normed activations (B, S, H) (HF
    `Qwen3NextGatedDeltaNet`; arXiv:2412.06464), p the layer's tree:

        [q, k, v, z] = y Wqkvz;  [b, a] = y Wba
        [q, k, v] = silu(conv([q, k, v]))             causal, depthwise, a channel
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)   float32, <= 0
        q, k L2-normalised a head, q / sqrt(d_k); each key head serves
        value / key heads
        o = gated_delta_rule(q, k, v, g, beta)         ops/linear_attention.py
        out = (RMSNorm(o; w) silu(z)) Wout             a head; the norm BEFORE the gate

    -> out, None, and the layer's counters: the mean gate `exp(g)` (how much
    state a token keeps) and the largest magnitude in any head's final state.
    Scopes: the core under `gt.attn.delta`, all else under `gt.attn.linear`.
    No position enters: the order is the recurrence's. `attn_sharding` tells
    the core where its operands lie (on TPUs it runs as Pallas kernels)."""
    p, dtype = p["linear"], cfg.compute_dtype
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim, value_dim = nk * dk, nv * dv
    b, s, _ = y.shape

    with jax.named_scope(tracing.ATTN_LINEAR):
        qkvz = _dense(y, p["wqkvz"], dtype)
        ba = _dense(y, p["wba"], dtype).astype(jnp.float32)
        beta = jax.nn.sigmoid(ba[..., :nv])
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            ba[..., nv:] + p["dt_bias"].astype(jnp.float32))
    layout = linear_layout(Heads(nk, dk, nv, dv))
    if mixer_form(qkvz, p["conv"], layout, sharding=attn_sharding) == "pallas":
        # the same arithmetic as lane-aligned passes around the core's kernels
        o, state = kernel_mixer(qkvz, p["conv"], p["norm"]["scale"], g, beta, layout,
                                eps=cfg.layernorm_eps, sharding=attn_sharding)
    else:
        with jax.named_scope(tracing.ATTN_LINEAR):
            qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * key_dim + value_dim], p["conv"]))
            z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, s, nv, dv)
            q = (_unit(qkv[..., :key_dim].reshape(b, s, nk, dk)) * dk ** -0.5).astype(dtype)
            k = _unit(qkv[..., key_dim:2 * key_dim].reshape(b, s, nk, dk)).astype(dtype)
            v = qkv[..., 2 * key_dim:].reshape(b, s, nv, dv)
        with jax.named_scope(tracing.ATTN_DELTA):
            o, state = gated_delta_rule(q, k, v, g, beta, sharding=attn_sharding)
        with jax.named_scope(tracing.ATTN_LINEAR):
            o = rms_norm(o.astype(jnp.float32), p["norm"]["scale"], cfg.layernorm_eps)
            o = (o * jax.nn.silu(z.astype(jnp.float32))).astype(dtype).reshape(b, s, value_dim)
    with jax.named_scope(tracing.ATTN_LINEAR):
        out = _dense(o, p["wout"], dtype)
        stats = {"decay_mean": jnp.mean(jnp.exp(g)), "state_abs_max": jnp.max(jnp.abs(state))}
    return out, None, stats


def kda_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, *,
              attn_sharding: Optional[KernelSharding] = None, **_):
    """Kimi Delta Attention on normed activations (B, S, H) (HF
    `KimiDeltaAttention`; arXiv:2510.26692), p the layer's tree:

        [q, k, v] = silu(conv(y Wqkv))                causal, depthwise, a channel
        q, k L2-normalised a head, q / sqrt(d_k)
        g = -exp(A_log) softplus((y Wfa) Wfb + dt_bias)   (heads, d_k) a token, float32, <= 0
        beta = sigmoid(y Wb)
        o = kda_rule(q, k, v, g, beta)                ops/linear_attention.py
        out = (RMSNorm(o; w) sigmoid((y Wga) Wgb)) Wout   a head; the norm BEFORE the gate

    The delta rule whose gate is a vector over the key's channels, each row of
    a head's (d_k, d_v) state forgetting at its own rate. -> out, None, and
    the linear mixer's counters: the mean gate `exp(g)` and the largest
    magnitude in any head's final state. Scopes: the core under
    `gt.attn.kda_rule`, all else under `gt.attn.kda_mixer`. No position enters.
    `attn_sharding` tells the kernels where their operands lie. On TPUs the
    matmuls alone are XLA's: the core runs as two Pallas kernels (`kda_fwd`,
    `kda_bwd`) and what lies between the projections and the core as
    lane-aligned Pallas passes over the projections' (B, S, channels) results
    (`conv_norm_*`, `kda_gate_*`, `gated_norm_*`; `kda_kernel_mixer`: one
    rule with the core), no (tokens, heads, 128) view of an activation
    anywhere. The arithmetic written out below is the definition: what the
    CPU runs, and the passes' oracle."""
    p, dtype = p["kda"], cfg.compute_dtype
    nh, dk, dv = cfg.linear_num_value_heads, cfg.linear_key_head_dim, cfg.linear_value_head_dim
    key_dim = nh * dk
    b, s, _ = y.shape

    with jax.named_scope(tracing.ATTN_KDA):
        qkv = _dense(y, p["wqkv"], dtype)
        f = _dense(_dense(y, p["wf_a"], dtype), p["wf_b"], dtype)
        beta = jax.nn.sigmoid(_dense(y, p["wb"], dtype).astype(jnp.float32))
        gate = _dense(_dense(y, p["wg_a"], dtype), p["wg_b"], dtype)
    layout = kda_layout(Heads(nh, dk, nh, dv))
    if mixer_form(qkv, p["conv"], layout, sharding=attn_sharding) == "pallas":
        o, state, decay = kda_kernel_mixer(qkv, p["conv"], p["norm"]["scale"], f, p["dt_bias"], p["A_log"], gate,
                                           beta, layout, eps=cfg.layernorm_eps, sharding=attn_sharding)
    else:
        with jax.named_scope(tracing.ATTN_KDA):
            qkv = jax.nn.silu(causal_conv(qkv, p["conv"]))
            q = (_unit(qkv[..., :key_dim].reshape(b, s, nh, dk)) * dk ** -0.5).astype(dtype)
            k = _unit(qkv[..., key_dim:2 * key_dim].reshape(b, s, nh, dk)).astype(dtype)
            v = qkv[..., 2 * key_dim:].reshape(b, s, nh, dv)
            g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
                f.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)).reshape(b, s, nh, dk)
        with jax.named_scope(tracing.ATTN_KDA_RULE):
            o, state = kda_rule(q, k, v, g, beta, sharding=attn_sharding)
        with jax.named_scope(tracing.ATTN_KDA):
            o = rms_norm(o.astype(jnp.float32), p["norm"]["scale"], cfg.layernorm_eps)
            o = (o * jax.nn.sigmoid(gate.reshape(b, s, nh, dv).astype(jnp.float32))).astype(dtype)
            o, decay = o.reshape(b, s, nh * dv), jnp.exp(g)
    with jax.named_scope(tracing.ATTN_KDA):
        out = _dense(o, p["wout"], dtype)
        stats = {"decay_mean": jnp.mean(decay), "state_abs_max": jnp.max(jnp.abs(state))}
    return out, None, stats


def ssm_mixer(p: Params, y: jax.Array, positions: jax.Array, cfg: TransformerConfig, **_):
    """Mamba-2 on normed activations (B, S, H) (HF `GraniteMoeHybridMambaLayer`;
    arXiv:2405.21060), p the layer's tree:

        [z | xBC | dt] = y Win
        xBC = silu(conv(xBC) + b)                     causal, depthwise, a channel
        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)   float32
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T;  y_t = h_t C_t + D x_t   ops/ssd.py
        out = (RMSNorm(y silu(z); w)) Wout            the gate BEFORE the norm, the
                                                      norm over ALL the mixer's channels

    B and C are one group's: every head reads the same. -> out, None, and the
    layer's counter: the largest magnitude of any head's state at any chunk's
    end. Scopes: the scan under `gt.attn.ssd`, all else under `gt.attn.ssm`.
    No position enters: the order is the recurrence's. The convolution and
    the gated norm are XLA's (`causal_conv`; the Pallas passes of
    ops/linear_attention.py norm a head's 128 lanes and know no bias)."""
    p, dtype = p["ssm"], cfg.compute_dtype
    nh, hd, ds = cfg.ssm_num_heads, cfg.ssm_head_dim, cfg.ssm_state_dim
    inner = nh * hd
    b, s, _ = y.shape
    with jax.named_scope(tracing.ATTN_SSM):
        zxbcdt = _dense(y, p["win"], dtype)
        z = zxbcdt[..., :inner]
        xbc = causal_conv(zxbcdt[..., inner:2 * inner + 2 * ds], p["conv"]["kernel"])
        xbc = jax.nn.silu((xbc.astype(jnp.float32) + p["conv"]["bias"].astype(jnp.float32)).astype(dtype))
        dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * ds:].astype(jnp.float32)
                             + p["dt_bias"].astype(jnp.float32))
        a = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope(tracing.ATTN_SSD):
        o, _, peak = ssd_scan(xbc[..., :inner].reshape(b, s, nh, hd), dt, a,
                              xbc[..., inner:inner + ds], xbc[..., inner + ds:], p["D"])
    with jax.named_scope(tracing.ATTN_SSM):
        o = o.reshape(b, s, inner).astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        o = rms_norm(o, p["norm"]["scale"], cfg.layernorm_eps).astype(dtype)
        out = _dense(o, p["wout"], dtype)
    return out, None, {"ssm_state_abs_max": peak}


@dataclass(frozen=True)
class TokenMixer:
    """What a kind of token mixer brings to a layer (ROADMAP D6, at the size
    the zoo needs): its leaves, its forward on normed activations, their
    PartitionSpecs, its forward FLOPs a token (the name of the function in
    `obs/flops.py`, which imports no jax) and the scopes its ops carry beside
    the layer run's."""
    init: Any  # (keys, cfg) -> the mixer's entries of the layer's tree
    forward: Any  # (p, y, positions, cfg, mesh=, axes=, ...) -> (out, kv | None, counters | None)
    specs: Any  # (cfg, axes) -> their PartitionSpecs
    flops: str
    scopes: Tuple[str, ...]


def layer_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    axes: Optional[LayerAxes] = None,
    attn_bias: Optional[jax.Array] = None,
    return_kv: bool = False,
    attn_sharding: Optional[KernelSharding] = None,
):
    """One transformer block on (B, S_local, H) activations: x + Mixer(norm
    x), then + MLP(norm x), the mixer `MIXERS[cfg.mixer]`'s.

    Under GSPMD the parallel form is implied by weight shardings plus the
    activation constraints here and in the mixer.

    ``return_kv`` additionally returns this layer's post-rope (k, v)
    projections — the serving prefill's cache-write side outputs
    (serve/engine.py). Unsupported under ring context parallelism, whose
    blockwise k/v never materialise per-layer.

    ``attn_sharding`` is the attention kernel's layout for callers that run
    this body with ``mesh=None`` under their own mapping (the GPipe stage
    vmap); with a mesh and axes it is derived here.

    A config with ``layer_aux`` (routed experts, a linear mixer) returns
    ``(x, aux)``: the block's output, and its router's auxiliary terms
    (ops/moe.py) and its mixer's counters in one dict."""
    dtype = cfg.compute_dtype
    if (cfg.layer_aux or cfg.latent_attention) and return_kv:
        refuse_expert_layout("serving (the prefill's k/v outputs)")
    if mesh is not None and axes is not None:
        attn_sharding = KernelSharding.for_layer(mesh, axes)

    residual = x
    y = _norm(x, p["ln1"], cfg) if cfg.pre_norm else x
    o, kv_out, counters = MIXERS[cfg.mixer].forward(
        p, y, positions, cfg, mesh=mesh, axes=axes, attn_bias=attn_bias,
        attn_sharding=attn_sharding, return_kv=return_kv)
    if mesh is not None and axes is not None:
        o = S.constrain(o, mesh, S.act_spec(axes))
    if cfg.residual_multiplier != 1.0:
        o = o * cfg.residual_multiplier
    x = residual + o
    if not cfg.pre_norm:
        x = _norm(x, p["ln1"], cfg)

    residual = x
    y = _norm(x, p["ln2"], cfg) if cfg.pre_norm else x
    if cfg.routed:
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
    else:
        # named here and not inside dense_mlp, which the shared expert calls
        # under its own scope: an op carries one scope nested in its run's
        with jax.named_scope(tracing.MLP):
            out, aux = dense_mlp(p, y, cfg, dtype), None
    if mesh is not None and axes is not None:
        out = S.constrain(out, mesh, S.act_spec(axes))
    if cfg.residual_multiplier != 1.0:
        out = out * cfg.residual_multiplier
    x = residual + out
    if not cfg.pre_norm:
        x = _norm(x, p["ln2"], cfg)
    if return_kv:
        return x, kv_out
    if cfg.layer_aux:
        return x, {**(aux or {}), **(counters or {})}
    return x


def _append_token_kv(cache: jax.Array, new: jax.Array, idx: jax.Array) -> jax.Array:
    """Write the (B, T, nkv, hd) `new` k/v block at per-row position `idx`
    of the (B, S_cache, nkv, hd) cache (vmapped dynamic_update_slice — the
    row dim is the vmapped dim, so a slot-sharded cache updates locally)."""
    return jax.vmap(
        lambda c, t, i: jax.lax.dynamic_update_slice(c, t, (i, 0, 0))
    )(cache, new, idx)


def decode_layer_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    *,
    k_cache: jax.Array,
    v_cache: jax.Array,
    write_index: jax.Array,
    mesh: Optional[Mesh] = None,
    axes: Optional[LayerAxes] = None,
    attn_bias: Optional[jax.Array] = None,
):
    """One transformer block for single-token decode over a preallocated KV
    cache. ``x``: (B, 1, H) — one new token per cache slot; ``k_cache`` /
    ``v_cache``: (B, S_cache, nkv, hd); ``write_index``: (B,) int32, the new
    token's position per slot. The layer projects this token's k/v, appends
    them at ``write_index``, and attends the length-1 query against the
    updated cache with ``attn_bias`` carrying BOTH causality and slot-length
    masking (the causal iota mask is meaningless for a length-1 query, so
    ``causal=False`` and the additive bias from serve/kv_cache.length_bias
    does the whole job). Every non-attention op mirrors ``layer_forward``
    exactly, so incremental decode reproduces the full-forward logits within
    float tolerance (tests/serve/test_decode_parity.py)."""
    dtype = cfg.compute_dtype
    if cfg.layer_aux or cfg.latent_attention:
        refuse_expert_layout("serving (single-token decode)")

    residual = x
    y = _norm(x, p["ln1"], cfg) if cfg.pre_norm else x
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
    if mesh is not None and axes is not None:
        o = S.constrain(o, mesh, P(S._ax(axes.batch_axes), None, None))
    x = residual + o
    if not cfg.pre_norm:
        x = _norm(x, p["ln1"], cfg)

    residual = x
    y = _norm(x, p["ln2"], cfg) if cfg.pre_norm else x
    with jax.named_scope(tracing.MLP):
        out = dense_mlp(p, y, cfg, dtype)
    if mesh is not None and axes is not None:
        out = S.constrain(out, mesh, P(S._ax(axes.batch_axes), None, None))
    x = residual + out
    if not cfg.pre_norm:
        x = _norm(x, p["ln2"], cfg)
    return x, k_cache, v_cache


# ============================================================== model forward
def vocab_parallel_lookup(wte: jax.Array, tokens: jax.Array, dtype, mesh: Mesh,
                          vax: LayerAxes) -> jax.Array:
    """Rows of a (vocab, hidden) table whose vocabulary is split over
    ``vax.tp``: Megatron's VocabParallelEmbedding (reference
    GPTModel_tensor_parallel.py:84-132), written out. Each device shifts the
    ids by its first row, gathers the rows it holds, has zeros for the ids it
    does not hold, and the partial results are summed over the tp axes.

    A manual region, not ``wte[tokens]`` left to GSPMD: there the gather and
    its scatter-add are device-local and the psum is the only collective,
    where GSPMD runs a one-hot matmul as a matmul and partitions the
    scatter-add of a sharded gather with collective-permutes
    (parallel/pipeline_1f1b.py embed_fwd). The rows are gathered from the
    stored shard and cast afterwards, so the table's gradient accumulates
    over repeated ids in the parameter's dtype. The result is whole over tp;
    under Megatron-SP the caller's constraint to `act_spec` slices it into
    sequence shards (the compiler makes a reduce-scatter of sum and slice)."""
    tp = tuple(vax.tp)
    rows = wte.shape[0] // mesh_axis_size(mesh, tp)

    # serve hands in (1, ctx) and (slots, 1): rows the dp axes do not divide stay whole
    split_rows = tokens.shape[0] % mesh_axis_size(mesh, vax.batch_axes) == 0
    tok_spec = P(S._ax(vax.batch_axes) if split_rows else None, S._ax(vax.cp))

    def local(table, tok):
        idx = tok - jax.lax.axis_index(tp) * rows
        # an id of another device's rows goes out of bounds: the gather fills
        # it with zeros, and its transpose drops the update
        idx = jnp.where((idx >= 0) & (idx < rows), idx, rows)
        return jax.lax.psum(table.at[idx].get(mode="fill", fill_value=0).astype(dtype), tp)

    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(S._ax(tp), None), tok_spec), out_specs=P(*tok_spec, None),
    )(wte, tokens)


def table_is_looked_up(vax: Optional[LayerAxes]) -> bool:
    """Whether `embed_tokens` reads the token table by `vocab_parallel_lookup`
    (from the stored shard, cast afterwards) and not as `wte.astype(dtype)`."""
    return vax is not None and len(vax.tp) > 0 and not vax.ulysses


def embed_tokens(p_embed: Params, tokens: jax.Array, positions: jax.Array, cfg: TransformerConfig,
                 mesh: Optional[Mesh] = None, vax: Optional[LayerAxes] = None,
                 token_type_ids: Optional[jax.Array] = None) -> jax.Array:
    """Token (+ position, + token-type) embedding. A table split over the
    vocabulary (vocab_tp > 1, not ulysses) is read by `vocab_parallel_lookup`;
    any other table is whole on the vocab dim and read by a plain gather."""
    wte = p_embed["wte"]
    if table_is_looked_up(vax):
        x = vocab_parallel_lookup(wte, tokens, cfg.compute_dtype, mesh, vax)
    else:
        x = wte.astype(cfg.compute_dtype)[tokens]
    if cfg.position_type == "learned":
        x = x + p_embed["wpe"].astype(cfg.compute_dtype)[positions]
    if cfg.type_vocab_size:
        tti = token_type_ids if token_type_ids is not None else jnp.zeros_like(tokens)
        x = x + p_embed["tte"].astype(cfg.compute_dtype)[tti]
    if cfg.embed_norm:
        x = _norm(x, p_embed["norm"], cfg)
    if cfg.embedding_multiplier != 1.0:
        x = x * cfg.embedding_multiplier
    return x


def patchify(pixels: jax.Array, patch: int) -> jax.Array:
    """(B, H, W, C) image -> (B, N, patch*patch*C) patch vectors. A dense on
    this layout equals the stride-`patch` conv patch embedding (HF ViT
    projection) and keeps the op a plain MXU matmul."""
    b, hh, ww, c = pixels.shape
    gh, gw = hh // patch, ww // patch
    x = pixels.reshape(b, gh, patch, gw, patch, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, gh * gw, patch * patch * c)


def embed_patches(p_embed: Params, pixels: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """ViT patch embedding: patchify + dense + [cls token] + learned positions."""
    dtype = cfg.compute_dtype
    x = patchify(pixels.astype(dtype), cfg.patch_size)
    x = _dense(x, p_embed["patch"], dtype)
    if cfg.use_cls_token:
        cls = jnp.broadcast_to(
            p_embed["cls_token"].astype(dtype), (x.shape[0], 1, cfg.hidden_size)
        )
        x = jnp.concatenate([cls, x], axis=1)
    x = x + p_embed["wpe"].astype(dtype)[: x.shape[1]]
    if cfg.embed_norm:
        x = _norm(x, p_embed["norm"], cfg)
    return x


def _times_kernel(x: jax.Array, kernel: jax.Array, tied: bool) -> jax.Array:
    return x @ (kernel.T if tied else kernel)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _head_matmul(x: jax.Array, kernel: jax.Array, tied: bool) -> jax.Array:
    """`x @ kernel` (`x @ kernel.T` for the tied table) for the head's kernel
    in the compute dtype. Evaluated, it is just that. Differentiated, its two
    barriers keep apart what the TPU compiler otherwise fuses into the
    backward's matmuls, to their cost (PERF.md, PR 30):

    - a kernel cast from a wider parameter is made once and forward, input
      gradient and kernel gradient read that one array; folded into each
      matmul, the (hidden, V) cast is redone for every tile of tokens (one
      that arrives in the compute dtype has no cast, and the barrier holds
      the array as it came);
    - the input gradient is written before the final norm's backward reads
      it; as the matmul's epilogue a LayerNorm's reductions held it at 79 %
      of the MXU."""
    return _times_kernel(x, kernel, tied)


def _head_matmul_fwd(x, kernel, tied):
    kernel = jax.lax.optimization_barrier(kernel)
    return _times_kernel(x, kernel, tied), (x, kernel)


def _head_matmul_bwd(tied, res, g):
    x, kernel = res
    lead = tuple(range(x.ndim - 1))
    dx = jax.lax.optimization_barrier(_times_kernel(g, kernel, not tied))
    dkernel = jax.lax.dot_general(*((g, x) if tied else (x, g)), ((lead, lead), ((), ())))
    return dx, dkernel


_head_matmul.defvjp(_head_matmul_fwd, _head_matmul_bwd)


def head_logits(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """`x` times the vocabulary kernel (`lm_head.kernel`, or the tied table
    transposed) in the compute dtype."""
    tied = cfg.tie_embeddings
    stored = params["embed"]["wte"] if tied else params["lm_head"]["kernel"]
    logits = _head_matmul(x, stored.astype(cfg.compute_dtype), tied)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits


def lm_logits(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    if cfg.pre_norm:
        x = _norm(x, params["final_norm"], cfg)
    return head_logits(params, x, cfg)


def model_head(params: Params, x: jax.Array, cfg: TransformerConfig) -> jax.Array:
    """Dispatch to the family's output head (reference `Cls_` modules,
    models/gpt_hf/GPTModel_sequential.py:201-215 and the bert/vit analogues)."""
    if cfg.head_type == "lm":
        return lm_logits(params, x, cfg)
    if cfg.head_type == "mlm":
        if cfg.pre_norm:
            x = _norm(x, params["final_norm"], cfg)
        hp_ = params["head"]
        y = _dense(x, hp_["transform"], cfg.compute_dtype)
        y = jax.nn.gelu(y, approximate=False)
        y = _norm(y, hp_["norm"], cfg)
        return head_logits(params, y, cfg) + hp_["bias"].astype(cfg.compute_dtype)
    if cfg.head_type == "classification":
        if cfg.pre_norm:
            x = _norm(x, params["final_norm"], cfg)
        pooled = x[:, 0] if cfg.pool_type == "cls" else jnp.mean(x, axis=1)
        return _dense(pooled, params["head"], cfg.compute_dtype)
    raise ValueError(cfg.head_type)


def _label_mask(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Where a row's label sits. A compare against an iota and never a gather,
    so each vocabulary shard answers for its own columns and XLA inserts the
    psum of what is reduced over it."""
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return vocab_iota == labels[..., None]


@jax.custom_vjp
def _token_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Float32 cross entropy a token, `lse(logits) - logits[label]`, with a
    written backward: `(softmax - onehot) * g`, formed once from the logits as
    they came and the row's maximum and sum, rounded once to the logits' dtype.
    Autodiff of the forward also differentiates the row maximum, whose
    gradient is zero by algebra, and pays a second sweep of the logits with
    its own `exp` to find that out."""
    return _token_nll_fwd(logits, labels)[0]


def _token_nll_fwd(logits, labels):
    # one maximum, then one sweep for the sum of exponentials and the label's logit
    logits32 = logits.astype(jnp.float32)
    m = jnp.max(logits32, axis=-1, keepdims=True)
    s = jnp.sum(jnp.exp(logits32 - m), axis=-1)
    label_logit = jnp.sum(jnp.where(_label_mask(logits, labels), logits32, 0.0), axis=-1)
    return jnp.log(s) + m[..., 0] - label_logit, (logits, m, s, labels)


def _token_nll_bwd(res, g):
    # term by term what autodiff forms with the maximum held constant: a
    # column that is not its row's maximum gets autodiff's own float
    logits, m, s, labels = res
    p_g = jnp.exp(logits.astype(jnp.float32) - m) * (g / s)[..., None]
    dlogits = p_g - jnp.where(_label_mask(logits, labels), g[..., None], 0.0)
    return dlogits.astype(logits.dtype), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def vocab_parallel_cross_entropy(logits: jax.Array, labels: jax.Array,
                                 loss_mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean cross entropy, safe for vocab-sharded logits.

    The label-logit extraction uses a masked reduction over the vocab dim
    instead of a gather, so each vocab shard contributes only its own slice
    and XLA inserts the psum — the compiler-derived form of the reference's
    vocab_parallel_cross_entropy (site_package/megatron/core/tensor_parallel/
    cross_entropy.py:174-219)."""
    losses = _token_nll(logits, labels)
    if loss_mask is None:
        return jnp.mean(losses)
    loss_mask = loss_mask.astype(jnp.float32)
    return jnp.sum(losses * loss_mask) / jnp.maximum(jnp.sum(loss_mask), 1.0)


# ------------------------------------------------- scan-over-layer-runs
def _layer_fwd_fn(cfg, hp, mesh, axes, attn_bias, strategy):
    """The per-layer forward for one run: the GSPMD `layer_forward` by
    default; under ``tp_comm_mode in (shard_map, overlap)`` the manual
    shard_map path (parallel/tp_shard_map.py) for layers that actually have
    TP collectives — refusing loudly (GLS012) on configs it cannot express.
    tp=1 layers have no TP collectives and compile to the identical GSPMD
    program either way (the linter warns that the knob is inert)."""
    from galvatron_tpu.parallel import tp_shard_map as T

    if T.wants_manual_tp(hp, axes):
        # refusal is per-run at trace time; the train driver's lint_hp pass
        # reports the same GLS012 before any tracing
        T.assert_manual_tp_supported(cfg, hp, strategy)
        return partial(T.manual_layer_forward, cfg=cfg, mesh=mesh, axes=axes,
                       hp=hp, attn_bias=attn_bias, mode=hp.tp_comm_mode)
    return partial(layer_forward, cfg=cfg, mesh=mesh, axes=axes,
                   attn_bias=attn_bias)


def _remat(fn, policy: str):
    """jax.checkpoint with the configured saveable policy. "full" (and the
    caller-filtered "none") is jax.checkpoint's default — save nothing,
    rematerialise everything; the other names select the matching
    jax.checkpoint_policies member."""
    if policy in ("full", "none"):
        return jax.checkpoint(fn)
    from jax import checkpoint_policies as _policies

    return jax.checkpoint(fn, policy=getattr(_policies, policy))


def stack_layer_run(layer_params: List[Params]) -> Params:
    """Stack a run's per-layer param trees along a new leading layer axis.

    `jnp.stack` (expand_dims per layer + one concatenate along the NEW,
    never-sharded axis) and not the cheaper concatenate-then-reshape trick:
    reshape-splitting a dim that is tp-sharded (the row-parallel `wo` /
    `wo_mlp` kernels, P(tp, ...)) MISCOMPILED in the GSPMD partitioner
    inside a scan (WA004; seen on jax 0.4.37 XLA:CPU, not ruled out on the
    installed jax) — silently wrong layer outputs, not an error. The
    per-layer expand_dims are pure layout equations; XLA
    compile time stays governed by the per-RUN body, which is what the
    trace-cost test asserts (tests/models/test_scan_layers.py)."""
    if len(layer_params) == 1:
        return jax.tree.map(lambda t: t[None], layer_params[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)


def stacked_layer_param_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    """layer_param_specs with an unsharded leading layer axis, matching
    stack_layer_run's layout (every layer of the run shares `axes`, so the
    per-layer spec is prefix-extended verbatim)."""
    return jax.tree.map(
        lambda sp: P(None, *sp), layer_param_specs(cfg, axes),
        is_leaf=lambda t: isinstance(t, P),
    )


def run_layers(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
    attn_bias: Optional[jax.Array] = None,
    scan: Optional[bool] = None,
    collect_kv: bool = False,
):
    """The encoder stack with per-layer sharding constraints and remat.

    Layers are partitioned into maximal same-strategy runs
    (config/strategy.layer_runs); each run of length >= 2 executes as ONE
    `jax.lax.scan` over weight-stacked params, so trace size and XLA compile
    time are proportional to the number of DISTINCT strategies, not to
    depth. Strategy boundaries and length-1 runs fall back to the unrolled
    per-layer path; `scan=False` (or `hp.scan_layers=False`, the
    `--no_scan_layers` escape hatch) unrolls everything, reproducing the
    pre-scan trace exactly.

    ``collect_kv=True`` (the serving prefill, serve/engine.py) additionally
    returns one post-rope (k, v) pair per layer, in layer order — scan runs
    emit them as stacked side outputs of the SAME scan, so prefill keeps the
    depth-constant trace. The collecting path is GSPMD-only and forward-only
    (no manual-TP shard_map body, no remat): serve lints away the layouts
    that would need either.

    A config with ``layer_aux`` returns ``(x, auxs)``, the layers' auxiliary
    terms (the routers', the linear mixers' counters) a layer or a scanned
    run, for `_fold_aux`. Any other config carries nothing and traces what
    it did."""
    use_hp = hp is not None and mesh is not None
    assert_expert_layout_supported(cfg, hp)
    layers = params["layers"]
    if scan is None:
        scan = hp.scan_layers if hp is not None else True
    kvs: List[Tuple[jax.Array, jax.Array]] = []
    auxs: List[Dict[str, jax.Array]] = []  # routed: a layer's, or a scanned run's stacked

    kinds = cfg.layer_kinds()

    def unrolled(x, indices):
        for i in indices:
            lp = layers[i]
            lcfg = cfg.layer_config(kinds[i])
            axes = layer_axes(hp, i) if use_hp else None
            if use_hp:
                x = S.constrain(x, mesh, S.act_spec(axes))
            if collect_kv:
                x, kv = layer_forward(
                    lp, x, positions, lcfg, mesh=mesh, axes=axes,
                    attn_bias=attn_bias, return_kv=True,
                )
                kvs.append(kv)
                continue
            fwd = _layer_fwd_fn(lcfg, hp if use_hp else None, mesh, axes,
                                attn_bias, hp.layers[i] if use_hp else None)
            # the per-layer serialized policy decides (checkpoint=1 layers
            # default to "full"); the global --remat_policy flag was folded
            # in at construction (config/strategy precedence rule)
            if use_hp:
                pol = hp.layers[i].effective_remat_policy
                if pol != "none":
                    fwd = _remat(fwd, pol)
            x = fwd(lp, x, positions)
            if lcfg.layer_aux:
                x, aux = x
                auxs.append(aux)
        return x

    def one_run(x, run):
        if not scan or run.length < 2:
            return unrolled(x, run.layer_indices)
        lcfg = cfg.layer_config(kinds[run.start])  # a run is of one kind
        axes = layer_axes(hp, run.start) if use_hp else None
        stacked = stack_layer_run([layers[i] for i in run.layer_indices])
        if use_hp:
            stacked = jax.tree.map(
                lambda t, sp: S.constrain(t, mesh, sp),
                stacked, stacked_layer_param_specs(lcfg, axes),
            )
        if collect_kv:
            body = partial(layer_forward, cfg=lcfg, mesh=mesh, axes=axes,
                           attn_bias=attn_bias, return_kv=True)

            def step_kv(carry, lp, _body=body, _axes=axes):
                if use_hp:
                    carry = S.constrain(carry, mesh, S.act_spec(_axes))
                out, kv = _body(lp, carry, positions)
                return out, kv

            x, kv_stacked = jax.lax.scan(step_kv, x, stacked)
            for j in range(run.length):
                kvs.append(jax.tree.map(lambda t, _j=j: t[_j], kv_stacked))
            return x
        body = _layer_fwd_fn(lcfg, hp if use_hp else None, mesh, axes,
                             attn_bias, run.strategy if use_hp else None)
        if use_hp:
            # a run is maximal over (axes, effective policy, stage, kind) —
            # config/strategy.layer_runs splits on differing remat_policy
            # exactly like the checkpoint flag, so one policy wraps the
            # whole scanned body
            run_pol = run.strategy.effective_remat_policy
            if run_pol != "none":
                body = _remat(body, run_pol)

        def step(carry, lp, _body=body, _axes=axes):
            if use_hp:
                carry = S.constrain(carry, mesh, S.act_spec(_axes))
            out = _body(lp, carry, positions)
            return out if lcfg.layer_aux else (out, None)

        x, run_aux = jax.lax.scan(step, x, stacked)
        if lcfg.layer_aux:
            auxs.append(run_aux)
        return x

    if use_hp:
        runs = layer_runs(hp, model_layer_kinds(cfg))
    else:
        # no strategy info: one homogeneous run a kind of layer
        starts = [i for i in range(len(layers)) if i == 0 or kinds[i] != kinds[i - 1]]
        runs = [LayerRun(start=a, stop=b, strategy=LayerStrategy())
                for a, b in zip(starts, starts[1:] + [len(layers)])]
    for k, run in enumerate(runs):
        # one scope a run, scanned or unrolled; k is the `layer_run` event's
        with jax.named_scope(tracing.layers_scope(k)):
            x = one_run(x, run)
    if collect_kv:
        return x, kvs
    if cfg.layer_aux:
        return x, auxs
    return x


def _fold_aux(auxs: List[Dict[str, jax.Array]]) -> Dict[str, jax.Array]:
    """The layers' auxiliary terms as one, each over the layers that have
    it: a loss and the linear mixers' gate the mean, the load, the bias and
    the state's magnitude the worst layer's, the rows held their sum, and
    `counts` a row a routed block, in the blocks' order
    (`router_bias_leaves`'). An entry is a layer's values or a scanned run's,
    stacked along the layer axis."""
    def total(name, reduce):
        return reduce(jnp.stack([reduce(jnp.atleast_1d(a[name])) for a in auxs if name in a]))

    def mean(name):
        layers = sum(jnp.atleast_1d(a[name]).shape[0] for a in auxs if name in a)
        return total(name, jnp.sum) / layers

    fold = {
        "load_balance": mean, "router_z": mean, "decay_mean": mean,
        "load_max_over_mean": lambda n: total(n, jnp.max),
        "bias_abs_max": lambda n: total(n, jnp.max),
        "state_abs_max": lambda n: total(n, jnp.max),
        "ssm_state_abs_max": lambda n: total(n, jnp.max),
        "rows_held": lambda n: total(n, jnp.sum),
        "counts": lambda n: jnp.concatenate([jnp.atleast_2d(a[n]) for a in auxs if n in a]),
    }
    return {name: fold[name](name) for name in dict.fromkeys(n for a in auxs for n in a)}


def padding_attn_bias(attn_mask: jax.Array) -> jax.Array:
    """(B, S) 1/0 key-validity mask -> additive (B, 1, 1, S) bias."""
    return (1.0 - attn_mask.astype(jnp.float32))[:, None, None, :] * -1e9


def model_forward(params, tokens, positions, cfg, hp=None, mesh=None, **inputs) -> jax.Array:
    """Full forward to logits (single pipeline stage; pipelined execution lives
    in parallel/pipeline.py)."""
    return _forward(params, tokens, positions, cfg, hp, mesh, **inputs)[0]


def _forward(
    params: Params,
    tokens: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
    token_type_ids: Optional[jax.Array] = None,
    attn_mask: Optional[jax.Array] = None,
):
    """-> (logits, the last layer's output before the final norm, the routed
    blocks' auxiliary terms as `run_layers` lists them or None)."""
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    if positions is None and cfg.input_type != "patches":
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    with jax.named_scope(tracing.EMBED):
        if cfg.input_type == "patches":
            x = embed_patches(params["embed"], tokens, cfg)
        else:
            x = embed_tokens(params["embed"], tokens, positions, cfg, mesh, vax,
                             token_type_ids=token_type_ids)
    if use_hp:
        x = S.constrain(x, mesh, S.act_spec(vax))
    bias = padding_attn_bias(attn_mask) if attn_mask is not None else None
    x = run_layers(params, x, positions, cfg, hp, mesh, attn_bias=bias)
    x, auxs = x if cfg.layer_aux else (x, None)
    if use_hp:
        x = S.constrain(x, mesh, S.act_spec(vax))
    # the head is the first half of gt.head_loss; the loss functions below
    # put their cross entropy under the same name
    with jax.named_scope(tracing.HEAD_LOSS):
        logits = model_head(params, x, cfg)
        if use_hp and cfg.head_type in ("lm", "mlm"):
            logits = S.constrain(logits, mesh, S.logits_spec(vax))
    return logits, x, auxs


def mtp_logits(params: Params, hidden: jax.Array, batch, cfg: TransformerConfig,
               hp: Optional[HybridParallelConfig] = None, mesh: Optional[Mesh] = None):
    """The multi-token-prediction module (DeepSeek-V3's, depth 1; arXiv:2412.19437
    2.2): for position i of a sequence t,

        m_i = [RMSNorm_h(hidden_i) ; RMSNorm_e(Emb(t_{i+1}))] Weh,  m'_i = Block(m_i)

    and the logits of t_{i+2} are the model's own head on RMSNorm(m'_i).
    `hidden` is the last layer's output before the final norm, `Emb` the
    model's own table, and t_{i+1} is `batch["labels"]`. The block is one
    more layer of the last layer's kind, in its layout and under its
    recomputation policy. -> (logits, the block's auxiliary terms or None)."""
    mp, dtype = params["mtp"], cfg.compute_dtype
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    last = cfg.num_layers - 1
    lcfg = cfg.layer_config(cfg.layer_kinds()[last])
    axes = layer_axes(hp, last) if use_hp else None
    positions = batch["positions"]
    with jax.named_scope(tracing.MTP):
        e = embed_tokens(params["embed"], batch["labels"], positions, cfg, mesh, vax)
        m = jnp.concatenate([_norm(hidden, mp["hnorm"], cfg), _norm(e, mp["enorm"], cfg)], axis=-1)
        m = _dense(m, mp["eh_proj"], dtype)
        if use_hp:
            m = S.constrain(m, mesh, S.act_spec(axes))
        attn_bias = padding_attn_bias(batch["attn_mask"]) if "attn_mask" in batch else None
        block = partial(layer_forward, cfg=lcfg, mesh=mesh, axes=axes, attn_bias=attn_bias)
        policy = hp.layers[last].effective_remat_policy if use_hp else "none"
        if policy != "none":
            block = _remat(block, policy)
        m = block(mp["block"], m, positions)
        m, aux = m if lcfg.routed else (m, None)
        if use_hp:
            m = S.constrain(m, mesh, S.act_spec(vax))
    with jax.named_scope(tracing.HEAD_LOSS):
        logits = head_logits(params, _norm(m, mp["norm"], cfg), cfg)
        if use_hp:
            logits = S.constrain(logits, mesh, S.logits_spec(vax))
    return logits, aux


EXPERT_LOAD = "expert_load_max_over_mean"  # the fullest expert's tokens over the mean
ROUTER_COUNTS = "router_counts"  # (routed blocks, E): the step's; no metric
# how the microbatch loop folds a part that is not a loss term (those are
# weighted as the loss is)
PART_FOLDS = {EXPERT_LOAD: jnp.maximum, "router_bias_abs_max": jnp.maximum,
              ROUTER_COUNTS: jnp.add, "expert_rows_held": jnp.add,
              "linear_state_abs_max": jnp.maximum, "ssm_state_abs_max": jnp.maximum}


def lm_loss_fn(params, batch, cfg, hp=None, mesh=None, with_parts: bool = False):
    """batch: dict(tokens, positions, labels, loss_mask?, token_type_ids?,
    attn_mask?). Serves lm and mlm heads (token-level CE).

    A routed-experts config's loss is the cross entropy plus
    `router_aux_loss_coef` x load balancing plus `router_z_loss_coef` x router
    z-loss (each the mean over the routed blocks, over all tokens; a sigmoid
    router has neither), and with a multi-token-prediction module plus
    `mtp_loss_weight` x the cross entropy of the token after next (labels
    shifted by one more; a sequence's last position has none). `with_parts`
    returns `(loss, parts)`: the terms, the worst block's expert load and
    the other counters of the `step` event (`telemetry.EXPERT_STEP_FIELDS`,
    `LINEAR_STEP_FIELDS` for linear-attention layers, `SSM_STEP_FIELDS` for
    state-space layers),
    and for a router with a bias the blocks' assignment counts
    (`ROUTER_COUNTS`), which the train step moves the bias by."""
    logits, hidden, auxs = _forward(
        params, batch["tokens"], batch["positions"], cfg, hp, mesh,
        token_type_ids=batch.get("token_type_ids"), attn_mask=batch.get("attn_mask"),
    )
    labels, mask = batch["labels"], batch.get("loss_mask")
    with jax.named_scope(tracing.HEAD_LOSS):
        loss = vocab_parallel_cross_entropy(logits, labels, mask)
    parts = {"loss_ce": loss}
    if cfg.mtp_layers:
        logits2, aux = mtp_logits(params, hidden, batch, cfg, hp, mesh)
        auxs = auxs + [aux] if aux is not None else auxs
        # the label of position i + 1, where it is one; none at a sequence's end
        ahead = jnp.ones(labels.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)
        ahead = jnp.roll(ahead, -1, axis=1).at[:, -1].set(0.0)
        with jax.named_scope(tracing.HEAD_LOSS):
            parts["loss_mtp"] = vocab_parallel_cross_entropy(
                logits2, jnp.roll(labels, -1, axis=1), ahead)
        loss = loss + cfg.mtp_loss_weight * parts["loss_mtp"]
    if not auxs:
        return (loss, parts) if with_parts else loss
    aux = _fold_aux(auxs)
    if "load_balance" in aux:
        parts["loss_load_balance"] = aux["load_balance"]
        parts["loss_router_z"] = aux["router_z"]
        loss = (loss + cfg.router_aux_loss_coef * aux["load_balance"]
                + cfg.router_z_loss_coef * aux["router_z"])
    if "decay_mean" in aux:  # the linear mixers' counters (telemetry.LINEAR_STEP_FIELDS)
        parts["linear_decay_mean"] = aux["decay_mean"]
        parts["linear_state_abs_max"] = aux["state_abs_max"]
    if "ssm_state_abs_max" in aux:  # the state-space mixers' counter (telemetry.SSM_STEP_FIELDS)
        parts["ssm_state_abs_max"] = aux["ssm_state_abs_max"]
    if "load_max_over_mean" in aux:  # a router (linear layers over dense MLPs have none)
        parts[EXPERT_LOAD] = aux["load_max_over_mean"]
    if "rows_held" in aux:
        even = (cfg.routed_layers * labels.size * cfg.experts_per_token
                * cfg.held_experts[1] / cfg.num_experts)
        parts["expert_rows_held"] = aux["rows_held"]
        parts["expert_rows_held_over_even"] = aux["rows_held"] / even
    if "counts" in aux:
        parts["router_bias_abs_max"] = aux["bias_abs_max"]
        parts[ROUTER_COUNTS] = aux["counts"]
    return (loss, parts) if with_parts else loss


def router_bias_leaves(params: Params) -> List[Dict[str, jax.Array]]:
    """The `router` dicts that hold a bias, in the order of `ROUTER_COUNTS`'
    rows: the stack's routed layers, then the MTP module's block."""
    blocks = list(params["layers"]) + ([params["mtp"]["block"]] if "mtp" in params else [])
    return [b["router"] for b in blocks if ROUTER_BIAS in b.get("router", {})]


def update_router_bias(params: Params, counts: jax.Array, rate: float) -> Params:
    """`b_e += rate x sign(mean(c) - c_e)` a routed block, `c` the block's row
    of `counts`: the auxiliary-loss-free balancing rule (arXiv:2412.19437
    2.1.2). An expert with more than its share of the batch's assignments is
    ranked lower next step, one with fewer higher. -> params, new dicts on
    the way to each bias and every other leaf as it was."""
    rows = iter(counts)

    def block(b):
        if ROUTER_BIAS not in b.get("router", {}):
            return b
        c = next(rows)
        bias = b["router"][ROUTER_BIAS]
        moved = bias + rate * jnp.sign(jnp.mean(c) - c).astype(bias.dtype)
        return {**b, "router": {**b["router"], ROUTER_BIAS: moved}}

    out = {**params, "layers": [block(b) for b in params["layers"]]}
    if "mtp" in params:
        out["mtp"] = {**params["mtp"], "block": block(params["mtp"]["block"])}
    return out


def softmax_nll(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross entropy over (B, C) logits / (B,) integer labels."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def classification_loss_fn(params, batch, cfg, hp=None, mesh=None):
    """batch: dict(pixels | tokens, labels). Mean softmax CE over classes
    (reference vit/swin `Cls_` heads)."""
    inputs = batch.get("pixels", batch.get("tokens"))
    logits = model_forward(params, inputs, batch.get("positions"), cfg, hp, mesh,
                           attn_mask=batch.get("attn_mask"))
    with jax.named_scope(tracing.HEAD_LOSS):
        return softmax_nll(logits, batch["labels"])


# ============================================================== param specs
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
    return sp


def _linear_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp, sp, cp are refused, GLS018): ZeRO-3 splits the
    # projections' input dim; the small leaves are whole everywhere
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    return {"linear": {
        "wqkvz": {"kernel": P(z3, None)}, "wba": {"kernel": P(z3, None)},
        "conv": P(None, None), "A_log": r1, "dt_bias": r1, "norm": {"scale": r1},
        "wout": {"kernel": P(z3, None)},
    }}


def _kda_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves, as the linear mixer's
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    wide = {"kernel": P(z3, None)}
    return {"kda": {
        "wqkv": wide, "wf_a": wide, "wf_b": {"kernel": P(None, None)}, "wg_a": wide,
        "wg_b": {"kernel": P(None, None)}, "wb": wide,
        "conv": P(None, None), "A_log": r1, "dt_bias": r1, "norm": {"scale": r1}, "wout": wide,
    }}


def _ssm_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    # ordinary leaves (tp, sp, cp are refused, GLS018): ZeRO-3 splits the
    # projections' input dim; the small leaves are whole everywhere
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    return {"ssm": {
        "win": {"kernel": P(z3, None)}, "conv": {"kernel": P(None, None), "bias": r1},
        "A_log": r1, "dt_bias": r1, "D": r1,
        "norm": {"scale": r1}, "wout": {"kernel": P(z3, None)},
    }}


# a layer's kind (`TransformerConfig.layer_kinds`) names its mixer before its
# MLP half; softmax attention, every model's but one, goes unnamed
MIXERS = {
    "attention": TokenMixer(_init_attention, attention_mixer, _attention_specs,
                            "attention_fwd_flops_a_token", (tracing.ATTN_PROJ, tracing.ATTN_LATENT)),
    "linear": TokenMixer(_init_linear, linear_mixer, _linear_specs,
                         "linear_fwd_flops_a_token", (tracing.ATTN_LINEAR, tracing.ATTN_DELTA)),
    "ssm": TokenMixer(_init_ssm, ssm_mixer, _ssm_specs,
                      "ssm_fwd_flops_a_token", (tracing.ATTN_SSM, tracing.ATTN_SSD)),
    "kda": TokenMixer(_init_kda, kda_mixer, _kda_specs,
                      "kda_fwd_flops_a_token", (tracing.ATTN_KDA, tracing.ATTN_KDA_RULE)),
}


def layer_param_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    """PartitionSpec tree matching init_layer_params output. The tp axes sit on
    the heads / ffn dim; ZeRO-3 shards the other large dim over dp. Ulysses
    layers keep dense (non-tp-sharded) weights (reference transformer.py:2065-2177)."""
    tp = None if axes.ulysses else S._ax(axes.tp)
    z3 = S._ax(axes.dp) if axes.zero3 else None
    r1 = S.replicated_1d_spec(axes)
    norm = {"scale": r1} if cfg.norm_type == "rmsnorm" else {"scale": r1, "bias": r1}
    sp: Params = {"ln1": dict(norm), "ln2": dict(norm)}
    sp.update(MIXERS[cfg.mixer].specs(cfg, axes))
    if cfg.routed:
        # ordinary leaves (tp is refused, GLS018): ZeRO-3 splits the experts
        # over dp, and they enter the block whole (ops/moe.moe_ffn)
        sp["router"] = {"kernel": P(None, None)}
        if cfg.router_bias:
            sp["router"][ROUTER_BIAS] = P(None)
        sp["wi"] = {"kernel": P(z3, None, None)}
        sp["wo_mlp"] = {"kernel": P(z3, None, None)}
        if cfg.num_shared_experts:
            sp["shared"] = {
                "wi": {"kernel": P(z3, None, None) if cfg.activation == "swiglu" else P(z3, None)},
                "wo_mlp": {"kernel": P(None, z3)},
            }
            if cfg.shared_expert_gate:
                sp["shared"]["gate"] = {"kernel": P(None, None)}
        return sp
    if cfg.activation == "swiglu":
        sp["wi"] = {"kernel": P(z3, None, tp)}
        if cfg.mlp_bias:
            sp["wi"]["bias"] = P(None, tp)
    else:
        sp["wi"] = {"kernel": P(z3, tp)}
        if cfg.mlp_bias:
            sp["wi"]["bias"] = P(tp)
    sp["wo_mlp"] = {"kernel": P(tp, z3)}
    if cfg.mlp_bias:
        sp["wo_mlp"]["bias"] = r1
    return sp


def model_param_specs(cfg: TransformerConfig, hp: HybridParallelConfig) -> Params:
    vax = vocab_axes(hp)
    r1 = S.replicated_1d_spec(vax)
    norm_spec = {"scale": r1} if cfg.norm_type == "rmsnorm" else {"scale": r1, "bias": r1}
    if cfg.input_type == "patches":
        embed: Params = {"patch": {"kernel": P(None, None), "bias": r1}, "wpe": P(None, None)}
        if cfg.use_cls_token:
            embed["cls_token"] = r1
    else:
        embed = {"wte": S.vocab_embed_spec(vax)}
        if cfg.position_type == "learned":
            embed["wpe"] = P(None, None)
        if cfg.type_vocab_size:
            embed["tte"] = P(None, None)
    if cfg.embed_norm:
        embed["norm"] = dict(norm_spec)
    specs: Params = {
        "embed": embed,
        "layers": [layer_param_specs(cfg.layer_config(kind), layer_axes(hp, i))
                   for i, kind in enumerate(cfg.layer_kinds())],
    }
    if cfg.mtp_layers:
        specs["mtp"] = {
            "enorm": dict(norm_spec), "hnorm": dict(norm_spec),
            "eh_proj": {"kernel": P(S._ax(vax.dp) if vax.zero3 else None, None)},
            "block": specs["layers"][-1], "norm": dict(norm_spec),
        }
    if cfg.pre_norm:
        specs["final_norm"] = dict(norm_spec)
    vocab_col = P(None, None) if vax.ulysses else P(None, S._ax(vax.tp))
    if cfg.head_type == "classification":
        specs["head"] = {"kernel": P(None, None), "bias": P(None)}
    elif cfg.head_type == "mlm":
        specs["head"] = {
            "transform": {"kernel": P(None, None), "bias": r1},
            "norm": dict(norm_spec),
            "bias": P(None) if vax.ulysses else P(S._ax(vax.tp)),
        }
    if cfg.head_type in ("lm", "mlm") and not cfg.tie_embeddings:
        # lm head is column-parallel over the vocab dim (vocab-parallel
        # logits); vocab-dense under vocab-SP, matching logits_spec
        specs["lm_head"] = {"kernel": vocab_col}
    return specs
