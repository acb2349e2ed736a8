"""Laguna's family (poolside, `model_type: laguna`): layers of softmax
attention over a WINDOW among layers of full attention, each kind with its
own head count and rope, a per-head output gate, a leading dense layer and
then softmax-routed experts beside a shared one.

The block is `models/base.py`'s with the config's switches set: RMSNorm,
SwiGLU, no biases, an untied head. **Which layers attend over a window is a
LIST** (`layer_types`, "full_attention" or "sliding_attention" a layer, which
`TransformerConfig.layer_types` reads as the mixers "attention" and "window"):
the published XS.2 is [full, sliding, sliding, sliding] x 10. **A layer's
head count follows its type** (`num_attention_heads_per_layer`: 48 on a full
layer, 64 on a sliding layer, over the same 8 key heads of 128), and so does
its **rope** (`rope_parameters`): the full layers turn the first half of a
head's dims (`partial_rotary_factor` 0.5) under yarn (theta 500000, factor 64
over an original 4096 positions; `ops/rope.py`), the sliding layers all of
them at theta 10000 with no scaling. The sliding layers' query i sees the
keys `i - sliding_window < j <= i` (`models/parts/window.py`,
`ops/attention.core_attention(window=)`). **`gating`**: the attention output
of every layer is multiplied, a HEAD, by sigmoid(y Wg), Wg (hidden, heads)
(`attn_head_gate`; the sibling Laguna-S-2.1 writes "per-head", and XS.2's
published 33.4 B only add up with it). The MLP half follows `mlp_layer_types`:
"dense" layers a SwiGLU of `intermediate_size`, "sparse" ones `num_experts`
experts of `moe_intermediate_size` with `num_experts_per_tok` a token by a
softmax router renormalised over its pick x `moe_routed_scaling_factor`, on
the experts' OUTPUT, beside one shared expert of
`shared_expert_intermediate_size` (ops/moe.py). The preset carries the
PUBLISHED config with its source (ROADMAP D12).

What the published file has no key for is absent here: no QK-norm, no gate on
the shared expert, no selection bias, no auxiliary loss; the router's score
function, which cannot be absent, is the softmax of the config class its key
names come from (Qwen2-MoE's). Each is a field (`qk_norm`,
`shared_expert_gate`, `router_bias`, `router_score`) a reader with poolside's
modeling code corrects through `laguna_config(**fields)`.

A program may hold a share of the experts (`experts_held`,
`experts_held_start`: the router still ranks all `num_experts`).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no expert form and none of the window layers, and refuse such a config
(GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register
from galvatron_tpu.ops.rope import YARN_KEYS

LAGUNA_XS2_SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
_PERIOD = ["full_attention", "sliding_attention", "sliding_attention", "sliding_attention"]

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "laguna-xs.2": {
        "source": LAGUNA_XS2_SOURCE,
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
        "num_hidden_layers": 40, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False, "rms_norm_eps": 1e-06,
        "num_experts": 256, "num_experts_per_tok": 8, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False, "gating": True,
        "sliding_window": 512,
        "rope_parameters": {
            "full_attention": {
                "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
                "original_max_position_embeddings": 4096, "beta_slow": 1, "beta_fast": 64,
                "attention_factor": 1.4158883083359672, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
            "original_max_position_embeddings": 4096,
        },
        "layer_types": _PERIOD * 10,
        "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
        "mlp_layer_types": ["dense"] + ["sparse"] * 39,
        "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 64, 64, 64] * 10,
    },
}
# what the published file does not carry (the module's docstring): HF's usual default
INITIALIZER_RANGE = 0.02


def _heads_of(hf_config, layer_type: str) -> int:
    """The one head count of the layers of this type, or a ValueError."""
    heads = {n for n, t in zip(hf_config.num_attention_heads_per_layer, hf_config.layer_types) if t == layer_type}
    if len(heads) != 1:
        raise ValueError("num_attention_heads_per_layer states ONE head count for the %r layers; got %r"
                         % (layer_type, sorted(heads)))
    return heads.pop()


def laguna_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF Laguna config (or anything with its attributes). What the
    program does not model is refused, not dropped. `layer_types` is handed on
    whole: a model cut in depth (`num_layers` overridden) runs the pattern's
    first so many layers."""
    for key, modelled in (("attention_bias", False), ("moe_apply_router_weight_on_input", False)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Laguna-XS.2 has %r)"
                             % (key, getattr(hf_config, key), modelled))
    types, halves = list(hf_config.layer_types), list(hf_config.mlp_layer_types)
    lead = halves.index("sparse") if "sparse" in halves else len(halves)
    if (set(types) - set(_PERIOD) or len({len(types), len(halves), len(hf_config.num_attention_heads_per_layer),
                                          hf_config.num_hidden_layers}) != 1
            or halves != ["dense"] * lead + ["sparse"] * (len(halves) - lead)):
        raise ValueError("layer_types names \"full_attention\" or \"sliding_attention\", mlp_layer_types leading "
                         "\"dense\" layers and then \"sparse\" ones, num_attention_heads_per_layer a count, for "
                         "each of the %d layers; got %r, %r" % (hf_config.num_hidden_layers, types, halves))
    if hf_config.shared_expert_intermediate_size % hf_config.moe_intermediate_size:
        raise ValueError("a shared expert of width %d beside experts of %d is not modelled: the shared expert is "
                         "whole experts' widths" % (hf_config.shared_expert_intermediate_size,
                                                    hf_config.moe_intermediate_size))
    full, sliding = (dict(hf_config.rope_parameters[t]) for t in _PERIOD[:2])
    if sliding.get("rope_type", "default") != "default":
        raise ValueError("the sliding layers' rope_type=%r is not modelled (the published Laguna-XS.2 scales "
                         "its full layers' rope alone)" % (sliding["rope_type"],))
    scaling = None
    if full.get("rope_type", "default") != "default":  # `ops/rope.py` refuses a type it has no form of by name
        scaling = {"rope_type": full["rope_type"], **{k: full[k] for k in YARN_KEYS if k in full}}
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        head_dim=hf_config.head_dim,
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        dense_ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        position_type="rope",
        tie_embeddings=hf_config.tie_word_embeddings,
        qkv_bias=False,
        out_bias=False,
        layer_types=types,
        rope_theta=float(full["rope_theta"]),
        partial_rotary_factor=full.get("partial_rotary_factor", 1.0),
        rope_scaling=scaling,
        sliding_window=hf_config.sliding_window,
        window_num_heads=_heads_of(hf_config, "sliding_attention"),
        window_rope_theta=float(sliding["rope_theta"]),
        window_partial_rotary_factor=sliding.get("partial_rotary_factor", 1.0),
        attn_head_gate=bool(hf_config.gating),
        first_dense_layers=lead,
        num_experts=hf_config.num_experts,
        experts_per_token=hf_config.num_experts_per_tok,
        norm_topk_prob=True,
        router_score="softmax",
        routed_scaling_factor=float(hf_config.moe_routed_scaling_factor),
        num_shared_experts=hf_config.shared_expert_intermediate_size // hf_config.moe_intermediate_size,
    )
    if fields["num_heads"] != _heads_of(hf_config, "full_attention"):
        raise ValueError("num_attention_heads=%d is the full layers' head count; num_attention_heads_per_layer "
                         "gives them %d" % (fields["num_heads"], _heads_of(hf_config, "full_attention")))
    fields.update(overrides)
    return TransformerConfig(**fields)


def laguna_config(model_size: str = "laguna-xs.2", **overrides) -> TransformerConfig:
    return laguna_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="laguna", config_fn=laguna_config, meta_configs=META_CONFIGS,
                     default_size="laguna-xs.2", config_from_hf=laguna_config_from_hf))
