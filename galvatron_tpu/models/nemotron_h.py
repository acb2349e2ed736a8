"""Nemotron-H's family (HF `NemotronHForCausalLM`, `model_type: nemotron_h`;
NVIDIA-Nemotron-3-Nano-30B-A3B): blocks of ONE half in a published order.

Every published block is `x + f(RMSNorm(x))` with one norm and ONE of three
`f`, named a character of `hybrid_override_pattern`: `M` a Mamba-2 mixer
(`models/parts/ssm.ssm_mixer` with `n_groups` groups of B and C and a gated
norm a group; `d_inner` is `mamba_num_heads x mamba_head_dim`, NOT `expand x
hidden_size`), `*` softmax attention (GQA, no bias, and NO position of any
kind: HF's `NemotronHAttention` applies no rotary embedding, `rope_theta` and
`partial_rotary_factor` are in the config and no layer reads them), `E` a
routed MLP (DeepSeek-V3's sigmoid router chosen by score plus
`e_score_correction_bias`, weights renormalised and scaled by
`routed_scaling_factor`, dropless: ops/moe.py; experts and the shared expert
`down(relu(up x)^2)`, two matrices and no gate, the shared one
`moe_shared_expert_intermediate_size` wide). Two mixers meet (`M*`) with no
MLP between them, so no layer is "mixer then MLP" throughout.

**One published block is one layer** (`pattern_layers`): `layer_types` names
the block's mixer ("none" for an `E`) and `mlp_types` its MLP half ("none"
for an `M` or a `*`); the absent half is `models/parts/absent.py`'s entry.
Layer i IS published block i, so every message, the cut in depth
(`num_layers`: the pattern's first so many blocks) and a checkpoint's
`backbone.layers.{i}` speak one numbering, and `--checkpoint 1` keeps a
block's input. The other form, a block of kind `E` paired with the mixer
before it, would scan the `ME ME` runs (7 traced bodies for the first nine
blocks against 9 here) at the price of a second numbering; PERF.md section 6,
PR 71, has the compile times. The preset carries the PUBLISHED config with its source (ROADMAP D12).

`n_group` 1 and `topk_group` 1 make the group-limited choice the plain
top-k; another grouping, a bias anywhere, a dense MLP block (`-`) or another
activation is refused, not dropped. A program may hold a share of the experts
(`experts_held`, `experts_held_start`: the router still ranks all
`n_routed_experts`).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no form of the state-space layers or of the experts and refuse such a
config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import List, Tuple

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, register

NEMOTRON_3_NANO_SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "nemotron-3-nano-30b-a3b": {
        "source": NEMOTRON_3_NANO_SOURCE,
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
        "hidden_size": 2688,
        "hybrid_override_pattern": "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
        "mamba_hidden_act": "silu", "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False, "mlp_hidden_act": "relu2",
        "model_type": "nemotron_h", "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
        "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True,
        "use_mamba_kernels": True, "vocab_size": 131072,
    },
}
# what DeepSeek-V3's report states of the bias this router is built on and HF's
# config does not carry (arXiv:2412.19437 section 4.2), as GLM-4.7-Flash's preset
ROUTER_BIAS_UPDATE_RATE = 0.001
INITIALIZER_RANGE = 0.02  # HF's `NemotronHConfig` default, which the published file keeps

# a character of `hybrid_override_pattern` -> (the block's mixer, its MLP half), HF's words for the first
BLOCKS = {"M": ("mamba", "none"), "*": ("attention", "none"), "E": ("none", "routed")}


def pattern_layers(pattern: str) -> Tuple[List[str], List[str]]:
    """`hybrid_override_pattern` -> (`layer_types`, `mlp_types`), an entry a
    published block, in published order. A character the program has no block
    for (`-`, a dense MLP block, among them) is refused with its place."""
    unknown = [(i, c) for i, c in enumerate(pattern) if c not in BLOCKS]
    if unknown or not pattern:
        raise ValueError("hybrid_override_pattern %r: a block is one of %s (M a Mamba-2 mixer, * attention, "
                         "E a routed MLP); %s" % (pattern, ", ".join(BLOCKS), ", ".join(
                             "block %d is %r" % u for u in unknown) or "it names no block"))
    mixers, mlps = zip(*(BLOCKS[c] for c in pattern))
    return list(mixers), list(mlps)


def nemotron_h_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `NemotronHConfig` (or anything with its attributes). What
    the program does not model is refused, not dropped. The pattern is handed
    on whole: a model cut in depth (`num_layers` overridden) runs its first so
    many blocks."""
    for key, modelled in (("n_group", 1), ("topk_group", 1), ("mamba_proj_bias", False), ("use_bias", False),
                          ("use_conv_bias", True), ("attention_bias", False), ("mlp_bias", False),
                          ("mlp_hidden_act", "relu2"), ("mamba_hidden_act", "silu"), ("sliding_window", None)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Nemotron-3-Nano-30B-A3B has %r)"
                             % (key, getattr(hf_config, key), modelled))
    layer_types, mlp_types = pattern_layers(hf_config.hybrid_override_pattern)
    if len(layer_types) != hf_config.num_hidden_layers:
        raise ValueError("hybrid_override_pattern names %d blocks, num_hidden_layers %d"
                         % (len(layer_types), hf_config.num_hidden_layers))
    fields = dict(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.head_dim,
        num_layers=hf_config.num_hidden_layers,
        vocab_size=hf_config.vocab_size,
        max_seq_len=hf_config.max_position_embeddings,
        norm_type="rmsnorm", activation="relu2", causal=True, pre_norm=True,
        qkv_bias=False, out_bias=False, mlp_bias=False,
        layernorm_eps=hf_config.layer_norm_epsilon,
        init_std=getattr(hf_config, "initializer_range", INITIALIZER_RANGE),
        position_type="none",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        layer_types=layer_types,
        mlp_types=mlp_types,
        ssm_num_heads=hf_config.mamba_num_heads,
        ssm_head_dim=hf_config.mamba_head_dim,
        ssm_state_dim=hf_config.ssm_state_size,
        ssm_conv_kernel=hf_config.conv_kernel,
        ssm_groups=hf_config.n_groups,
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        num_experts=hf_config.n_routed_experts,
        experts_per_token=hf_config.num_experts_per_tok,
        num_shared_experts=hf_config.n_shared_experts,
        shared_expert_ffn=hf_config.moe_shared_expert_intermediate_size,
        norm_topk_prob=hf_config.norm_topk_prob,
        router_score="sigmoid",
        routed_scaling_factor=hf_config.routed_scaling_factor,
        router_bias=True,
        router_bias_update_rate=ROUTER_BIAS_UPDATE_RATE,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def nemotron_h_config(model_size: str = "nemotron-3-nano-30b-a3b", **overrides) -> TransformerConfig:
    return nemotron_h_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="nemotron_h", config_fn=nemotron_h_config, meta_configs=META_CONFIGS,
                     default_size="nemotron-3-nano-30b-a3b", config_from_hf=nemotron_h_config_from_hf))
