"""`TransformerConfig`: what a published model states, and which parts its layers are built of.

One config covers the reference's model zoo: GPT (learned pos, pre-LN, gelu),
LLaMA (rope, rmsnorm, swiglu, GQA), BERT/ViT (bidirectional, post-LN), T5
(relative bias, enc-dec glue in models/t5.py), and the sparse-expert,
latent-attention, linear-attention, state-space, short-convolution, window-attention,
selective-scan / shared-memory (SambaY), compressed-context (EVA), looped (LoopLM), hyper-connected (mHC) and
one-half-a-block (Nemotron-H) families. A layer is two
entries of the tables in `models/parts`, a token mixer and an MLP half, either of which may be the absent
one ("none": a published block that is a mixer ALONE or an MLP alone):
`mixers()` and `mlp_halves()` name them a layer, and what an entry asks of the
config (`validate`) and hands back (`counters`) is the entry's to say."""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, List, Mapping, Optional, Tuple

import jax.numpy as jnp


# HF's words in `layer_types` -> the `MIXERS` key
_MIXER_ALIASES = {"mamba": "ssm", "full_attention": "attention", "sliding_attention": "window",
                  "cross_attention": "cross"}


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
    activation: str = "gelu"  # gelu | gelu_exact | swiglu | relu | relu2 (relu squared; no gate matrix)
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
    num_shared_experts: int = 0  # dense MLP(s) of the experts' kind and width beside the routed ones
    shared_expert_ffn: Optional[int] = None  # the shared expert's width where it is its own (None: shared x ffn_hidden)
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
    # the token mixer of each layer, a key of `MIXERS` or HF's "mamba" (the
    # mixer "ssm"; "kda", Kimi Delta Attention, takes its heads and convolution
    # from the `linear_*` fields above), where the pattern is a LIST (HF
    # `layer_types`; Kimi-Linear's two lists of layer numbers) and no interval
    # says it. A model cut in depth runs the list's first `num_layers`
    # entries, so the published list may stay whole
    layer_types: Optional[List[str]] = None
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_dim: int = 0  # a head's state is (ssm_head_dim, ssm_state_dim)
    # B and C are shared by the ssm_num_heads / ssm_groups consecutive heads of a GROUP, and the gated norm
    # runs over a group's channels (1: Granite's, every head reads the same B and C and the norm all channels)
    ssm_groups: int = 1
    ssm_conv_kernel: int = 0  # taps of the causal convolution on [x | B | C], with a bias
    # each a Python float whose default is the model without it: a factor of
    # 1.0 is not multiplied by, so every other model's arithmetic is bit for
    # bit what it was
    embedding_multiplier: float = 1.0  # x the embedding's rows
    residual_multiplier: float = 1.0  # x each half's output before it joins the residual stream
    attention_multiplier: Optional[float] = None  # the softmax's scale in place of 1 / sqrt(head_dim)
    logits_scaling: float = 1.0  # the head's logits are divided by it
    # --- what LFM2's published config adds (lfm2_moe): layers whose token
    # mixer is a gated short convolution (`conv_mixer`, the mixer "conv") ---
    short_conv_kernel: int = 0  # taps of the causal depthwise convolution between the two gates
    # --- what Laguna's published config adds (laguna): layers of softmax
    # attention over a WINDOW (`models/parts/window.py`, the mixer "window";
    # HF's "sliding_attention" in `layer_types`) among layers of full
    # attention, each kind of layer with its own head count and rope ---
    sliding_window: int = 0  # a window layer's query i sees the keys i - this < j <= i
    # the window layers' heads, rope base and rotary share, where they differ from the full
    # layers' `num_heads`, `rope_theta`, `partial_rotary_factor` (None: the same); the key
    # heads and `head_dim` are the model's. `layer_config("window.*")` hands them on as
    # the ordinary three, with no `rope_scaling`
    window_num_heads: Optional[int] = None
    window_rope_theta: Optional[float] = None
    window_partial_rotary_factor: Optional[float] = None
    # the full layers' rope scaling: None, or yarn's numbers beside `rope_type`
    # "yarn" (`ops/rope.YARN_KEYS`); any other `rope_type` is refused by name
    rope_scaling: Optional[Mapping[str, Any]] = None
    attn_head_gate: bool = False  # attn x sigmoid(y Wg) a HEAD, Wg (hidden, heads)
    # --- what Phi-4-mini-flash's published config adds (phi4flash, SambaY): Mamba-1
    # selective-scan layers (`models/parts/mamba.py`, the mixer "mamba1"; ops/selective_scan.py),
    # DIFFERENTIAL softmax attention, and a cross-decoder whose layers have no keys, values or
    # scan of their own: gated memory units (the mixer "gmu") read ONE Mamba-1 layer's scan
    # output and cross layers (the mixer "cross") ONE full-attention layer's keys and values ---
    mamba_d_state: int = 0  # N: a channel's state
    mamba_d_conv: int = 0  # taps of the causal convolution on x, with a bias
    mamba_expand: int = 0  # d_inner = this x hidden_size channels
    mamba_dt_rank: int = 0  # R: dt is projected down to it and up again
    # attention as a difference of two softmax maps (arXiv:2410.05258): consecutive heads
    # (2j, 2j + 1) pair up, q and k a map each and v the pair's two heads side by side;
    # `lambda` from four learned vectors a layer and a constant of the LAYER'S PUBLISHED INDEX
    diff_attention: bool = False
    # --- what EvaByte's published config adds (evabyte): EVA attention (`models/parts/eva.py`, the
    # mixer "eva"; ops/eva_attention.py), exact over the query's own window of `eva_window` positions
    # and over ONE pooled key and value for each `eva_chunk` positions before that window, in one
    # softmax; and a head that emits `pred_heads` predictions a position from one matmul, head i the
    # token i + 1 places on (1: the ordinary head, whose arithmetic is bit for bit what it was) ---
    eva_window: int = 0
    eva_chunk: int = 0
    pred_heads: int = 1
    # the PUBLISHED index of each layer run, where a cut in depth is no prefix of the stack:
    # layer i of the program is entry `layer_indices[i]` of `layer_types` (None: 0, 1, 2, ...)
    layer_indices: Optional[List[int]] = None
    # --- what Ouro's published config adds (ouro, LoopLM): the WHOLE stack applied `loop_steps` times over
    # the same parameters, the final norm after every pass (the normed state feeds the head and re-enters the
    # stack; models/base.looped_states), a norm on each half's OUTPUT before it joins the stream (`post_norm`,
    # sandwich norms: the leaves `ln1_post`, `ln2_post`), and an exit gate: a Linear(hidden, 1) on each of
    # the first `loop_steps` - 1 normed states whose sigmoids make the distribution the passes' cross
    # entropies are weighted by, less `exit_entropy_coef` x its entropy (models/parts/loop.py). The
    # defaults are the model without them, whose step none of these touches ---
    loop_steps: int = 1
    post_norm: bool = False
    exit_gate: bool = False
    exit_entropy_coef: float = 0.0
    # --- what Xing4.0's published config adds (xing4): manifold-constrained hyper-connections
    # (models/parts/hyper.py; arXiv:2512.24880). A token's state is `hc_mult` residual streams of hidden_size;
    # each half of a layer reads one vector out of them (H_pre), and writes H_res X + H_post o back, H_res made
    # doubly stochastic by `hc_sinkhorn_iters` Sinkhorn-Knopp steps from exp of a logit clipped to
    # `hc_res_clamp` (min, max); `hc_eps` guards the streams' RMS and the steps' denominators. The three
    # learned gates START at `hc_init_gate` (no published config says where): 0.01 is the identity-like start
    # of arXiv:2409.19606, at which an untrained half IS a pre-norm residual half; at 1 `x~ Phi` comes through
    # whole and every token mixes its streams its own way from the first step. The defaults are one stream,
    # whose `residual + o` none of these touches ---
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 1e-6
    hc_res_clamp: Optional[List[float]] = None  # [min, max]
    hc_init_gate: float = 0.01
    # --- what Nemotron-H's published config adds (nemotron_h): a block is ONE half, `x + f(norm x)` with a
    # Mamba-2 mixer, an attention mixer or an MLP alone. `layer_types` then names "none" where a block has no
    # mixer, and this list, as long, the `MLP_HALVES` key of each published layer ("none": no MLP half) ---
    mlp_types: Optional[List[str]] = None
    # which `MIXERS` entry ONE layer runs, and which `MLP_HALVES` entry where that is not what the widths say
    # (None: "routed" with experts, else "dense"). `layer_config(kind)` sets them; a model's own config
    # leaves them and states the pattern above
    mixer: str = "attention"
    mlp: Optional[str] = None


    def __post_init__(self):
        if self.num_kv_heads is None:
            self.num_kv_heads = self.num_heads
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size
        if self.mtp_layers not in (0, 1):
            raise ValueError("mtp_layers=%d: one multi-token-prediction module at most"
                             % self.mtp_layers)
        if self.qk_norm not in (False, True, "head"):
            raise ValueError("qk_norm=%r: False, True (the whole projection) or \"head\""
                             % (self.qk_norm,))
        if self.pred_heads < 1 or (self.pred_heads > 1 and (
                self.tie_embeddings or self.mtp_layers or self.head_type != "lm")):
            raise ValueError("pred_heads=%d: 1 or more heads of vocab_size columns each in ONE untied lm head "
                             "(tie_embeddings False), and no multi-token-prediction module beside them"
                             % self.pred_heads)
        if self.loop_steps < 1 or (self.loop_steps == 1 and (self.exit_gate or self.exit_entropy_coef)) or (
                self.exit_entropy_coef and not self.exit_gate):
            raise ValueError("loop_steps=%d, exit_gate=%r, exit_entropy_coef=%r: a gate weighs the passes of a stack "
                             "run 2 or more times, and the entropy is the gate's distribution's"
                             % (self.loop_steps, self.exit_gate, self.exit_entropy_coef))
        if (self.loop_steps > 1 or self.post_norm) and not self.pre_norm:
            raise ValueError("loop_steps=%d, post_norm=%r: the loop re-enters a pre-norm stack through its final "
                             "norm, and a post-norm block has no sandwich form" % (self.loop_steps, self.post_norm))
        if self.layer_types is not None:
            from galvatron_tpu.models.parts import MIXERS  # looked up on use, as `parts()` does

            self.layer_types = list(self.layer_types)
            named = sorted(set(MIXERS) | set(_MIXER_ALIASES))
            if (len(self.layer_types) < self.num_layers or self.full_attention_interval
                    or set(self.layer_types) - set(named)):
                raise ValueError(
                    "layer_types names the mixer, one of %s, of each of "
                    "the %d layers (or more: the first so many are run), and no "
                    "full_attention_interval beside it; got %r"
                    % (", ".join('"%s"' % n for n in named), self.num_layers, self.layer_types))
        if self.mlp_types is not None:
            from galvatron_tpu.models.parts import MLP_HALVES

            self.mlp_types = list(self.mlp_types)
            both_absent = [i for i, (m, h) in enumerate(zip(self.layer_types or (), self.mlp_types))
                           if m == h == "none"]
            if (self.layer_types is None or len(self.mlp_types) != len(self.layer_types)
                    or set(self.mlp_types) - set(MLP_HALVES) or both_absent):
                raise ValueError(
                    "mlp_types names the MLP half, one of %s, of each published layer beside layer_types' %d "
                    "mixers, and a layer has at least one of the two; got %r%s"
                    % (", ".join('"%s"' % n for n in sorted(MLP_HALVES)), len(self.layer_types or ()),
                       self.mlp_types, " (published layers %s have neither)" % both_absent if both_absent else ""))
        if self.layer_indices is not None:
            self.layer_indices = list(self.layer_indices)
            known = len(self.layer_types or ())
            if (len(self.layer_indices) != self.num_layers or sorted(set(self.layer_indices)) != self.layer_indices
                    or not 0 <= self.layer_indices[0] <= self.layer_indices[-1] < known):
                raise ValueError(
                    "layer_indices names the published index, an entry of layer_types' %d, of each of the %d "
                    "layers run, in rising order; got %r" % (known, self.num_layers, self.layer_indices))
        for part in self.parts():  # each part's own clause (latent attention's may set head_dim)
            part.validate(self)
        from galvatron_tpu.models.parts import hyper  # looked up on use, as `parts()` does

        hyper.validate(self)  # (hyper-connections' clause; it states the clamp as a list of two floats)
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        self.shared()  # a layer that reads what no earlier layer publishes is refused by name
        shares = any(handed or read for handed, read in self.shared())
        if self.loop_steps > 1 and (self.layer_aux or shares or self.mtp_layers or self.pred_heads > 1
                                    or self.head_type != "lm"):
            raise ValueError(
                "loop_steps=%d: the loop carries the residual stream alone through a stack of layers without "
                "counters or published tensors, to ONE lm head of one prediction a position and no "
                "multi-token-prediction module; this config has %s" % (self.loop_steps, " and ".join(
                    what for what, has in (("layers that hand back counters", self.layer_aux),
                                           ("layers that publish", shares),
                                           ("an MTP module", self.mtp_layers), ("pred_heads > 1", self.pred_heads > 1),
                                           ("head_type %r" % self.head_type, self.head_type != "lm")) if has)))
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

    def mixers(self) -> Tuple[str, ...]:
        """The `MIXERS` key of each layer, however the pattern is stated: the
        list `layer_types` (HF's "mamba" is the mixer "ssm"), every so many
        (`full_attention_interval`: layer i attends where (i + 1) % it == 0
        and is linear elsewhere), or one mixer for all (`mixer`). Nothing
        else reads the two statements."""
        if self.layer_types is not None:
            return tuple(_MIXER_ALIASES.get(self.layer_types[i], self.layer_types[i])
                         for i in self.published_indices())
        every = self.full_attention_interval
        if every:
            return tuple("attention" if (i + 1) % every == 0 else "linear"
                         for i in range(self.num_layers))
        return (self.mixer,) * self.num_layers

    def published_indices(self) -> Tuple[int, ...]:
        """The published index of each layer run: `layer_indices`, else 0, 1, 2, ..."""
        return tuple(self.layer_indices if self.layer_indices is not None else range(self.num_layers))

    def shared(self) -> Tuple[Tuple[Tuple[str, ...], Tuple[str, ...]], ...]:
        """What each layer (hands on, reads) of the named tensors layers SHARE
        beside the residual stream (`LayerPart.publishes`, `.reads`): a layer
        hands on the names its mixer publishes that a LATER layer reads before
        another publishes them anew, so a reader gets the latest of each name.
        Empty pairs for every config none of whose mixers reads (all but
        Phi-4-mini-flash's family), which the stack then runs as it always did. A stated pattern (`layer_types`) in
        which a layer reads what no earlier layer publishes is refused."""
        from galvatron_tpu.models.parts import MIXERS

        mixers = self.mixers()
        reads = [MIXERS[m].reads for m in mixers]
        out, have = [], set()
        for i, m in enumerate(mixers):
            missing = [n for n in reads[i] if n not in have]
            if missing and self.layer_types is not None:  # (one layer's config states no pattern)
                raise ValueError(
                    "layer %d (published %d, mixer %r) reads %s, which no earlier layer publishes: among %r"
                    % (i, self.published_indices()[i], m, " and ".join('"%s"' % n for n in missing), mixers[:i]))
            def read_next(name):  # before a later layer publishes the name anew
                for later in range(i + 1, len(mixers)):
                    if name in reads[later] or name in MIXERS[mixers[later]].publishes:
                        return name in reads[later]
                return False

            out.append((tuple(n for n in MIXERS[m].publishes if read_next(n)), reads[i]))
            have.update(MIXERS[m].publishes)
        return tuple(out)

    def mlp_halves(self) -> Tuple[str, ...]:
        """The `MLP_HALVES` key of each layer: read from the pattern where one
        is stated (`mlp_types`, a published layer an entry; ONE layer's config
        names its own, `mlp`); else "routed" but for the leading
        `first_dense_layers` of a model with experts, "dense" without."""
        if self.mlp_types is not None:
            return tuple(self.mlp_types[i] for i in self.published_indices())
        if self.mlp is not None:
            return (self.mlp,) * self.num_layers
        lead = min(self.first_dense_layers, self.num_layers) if self.routed else self.num_layers
        return ("dense",) * lead + ("routed",) * (self.num_layers - lead)

    def parts(self) -> tuple:
        """The table entries this config's layers are built of, each once,
        the MLP halves' before the mixers'; and the entry of what the config
        states, experts or latent attention, whether or not a layer of this
        depth runs it."""
        # looked up on use: the parts' modules import this one for the config they read
        from galvatron_tpu.models.parts import MIXERS, MLP_HALVES

        halves = self.mlp_halves() + (("routed",) if self.routed else ())
        mixers = self.mixers() + (("attention",) if self.latent_attention else ())
        return tuple(MLP_HALVES[h] for h in dict.fromkeys(halves)) + tuple(MIXERS[m] for m in dict.fromkeys(mixers))

    def layer_kinds(self) -> Tuple[str, ...]:
        """The kind of each layer, what `config/strategy.layer_runs` splits
        runs on beside the layout. A kind names the layer's two halves: its
        MLP half, "dense", "routed" or "none", after its token mixer where that is
        not softmax attention ("linear.routed", "ssm.dense", "kda.routed", "conv.dense"; of a layer of ONE
        half "ssm.none", "none.routed", and "none" for attention alone). A
        layer that publishes (`shared`) is of the kind of one that does not: one
        part serves both, an output nobody reads is dead code, and what sets a
        publishing layer apart, that it is never scanned, is `run_layers`' to see."""
        return tuple(h if m == "attention" else m + "." + h
                     for m, h in zip(self.mixers(), self.mlp_halves()))

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
        if self.mlp_types is not None:  # (before the mixers' list goes, which this one is held to)
            cfg = dataclasses.replace(cfg, mlp_types=None, mlp=mlp)
        if self.mixers() != (self.mixer,) * self.num_layers:
            cfg = dataclasses.replace(cfg, mixer=mixer or "attention", full_attention_interval=0,
                                      layer_types=None, layer_indices=None)
        if mixer == "window":  # the window layers' own heads and rope as the fields every part reads
            cfg = dataclasses.replace(
                cfg, mixer="window", rope_scaling=None,
                window_num_heads=None, window_rope_theta=None, window_partial_rotary_factor=None,
                **{field: value for field, value in (
                    ("num_heads", self.window_num_heads), ("rope_theta", self.window_rope_theta),
                    ("partial_rotary_factor", self.window_partial_rotary_factor)) if value is not None})
        return cfg

    @property
    def mlp_half(self) -> str:
        """The `MLP_HALVES` key of ONE layer's config (`layer_config`), beside its `mixer`."""
        return self.mlp or ("routed" if self.routed else "dense")

    @property
    def layer_aux(self) -> bool:
        """Whether a layer hands back auxiliary terms beside its output (a
        router's losses and loads, a linear or state-space mixer's counters,
        hyper-connections' column error): of a layer's config its own layer,
        of a model's config any of its layers."""
        return any(part.counters for part in self.parts()) or self.hc_mult > 1

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first, count) of the experts this program holds."""
        return (self.experts_held_start, self.experts_held) if self.experts_held \
            else (0, self.num_experts)

    @property
    def shared_ffn(self) -> int:
        """The shared expert's width: its own where the config states one, else the shared experts' side by side."""
        return self.shared_expert_ffn or self.num_shared_experts * self.ffn_hidden

    @property
    def routed_layers(self) -> int:
        """Routed blocks a step runs: the stack's and the MTP module's."""
        return self.mlp_halves().count("routed") + (self.mtp_layers if self.routed else 0)
