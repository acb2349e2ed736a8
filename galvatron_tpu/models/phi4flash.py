"""Phi-4-mini-flash-reasoning's family (HF `Phi4FlashForCausalLM`, `model_type:
phi4flash`; SambaY, arXiv:2507.06607): a decoder-hybrid-decoder. The
**self-decoder** (layers 0 to L/2 + 1) alternates Mamba-1 selective-scan layers
(even i) with differential attention over a window (odd i < L/2) and ends in
ONE full differential attention layer (i = L/2 + 1); the **cross-decoder**
(the layers after it) has no keys, values or scan of its own: its even layers
are gated memory units on the scan output of the LAST Mamba-1 layer (i = L/2:
the model's memory) and its odd layers differential attention of their own
queries on the keys and values of the ONE full layer.

The block is `models/base.py`'s with the config's switches set: LayerNorm with
a bias, SwiGLU without, no position of any kind, a head tied to the embedding.
**Which mixer a layer runs is a LIST** the depth decides (`layer_types`): the
mixers "mamba1" (`models/parts/mamba.py`; ops/selective_scan.py: the decay a
float a (channel, state), so no matmul form), "sliding_attention" and
"full_attention" in their differential form (`diff_attention`: two softmax maps
a pair of heads and their difference, `lambda_init` a constant of the layer's
published index; `models/parts/attention.diff_attention_mixer`), "gmu" and
"cross_attention" (`models/parts/cross.py`). What a layer PUBLISHES for later
layers, the memory and the keys and values, the stack carries beside the
residual stream (`TransformerConfig.shared`, `models/base.run_layers`).

A cut in depth that is no prefix of the stack names the published layers it
runs (`layer_indices`: the benchmark's six are 0, 1, 16, 17, 18, 19, one
period of each decoder and the pair that joins them); a model of another DEPTH
(`num_layers` alone, a multiple of 4 as HF's constructor wants) is the pattern
of that depth, and any other `num_layers` the published stack's first layers. The published `config.json` has no key for the Mamba-1 sizes,
the biases or the pairing of heads: they are HF `Phi4FlashConfig`'s defaults
and the two papers' conventions, `ASSUMED` below. The preset carries the
PUBLISHED config with its source (ROADMAP D12).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no form of these layers and refuse such a config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import List

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.registry import ModelFamily, register

PHI_4_MINI_FLASH_SOURCE = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "phi-4-mini-flash-reasoning": {
        "source": PHI_4_MINI_FLASH_SOURCE,
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
    },
}
# what the published file has no key for: HF `Phi4FlashConfig`'s defaults, Mamba-1's own
ASSUMED = {"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2, "mamba_dt_rank": "auto",
           "mamba_conv_bias": True, "mamba_proj_bias": False, "attention_bias": True,
           "initializer_range": 0.02}


def layer_types(layers: int) -> List[str]:
    """The mixer of each of a SambaY stack's `layers` layers (`mb_per_layer` 2)."""
    half = layers // 2

    def mixer(i):
        if i % 2 == 0:
            return "mamba1" if i <= half else "gmu"
        return "sliding_attention" if i < half else "full_attention" if i == half + 1 else "cross_attention"

    return [mixer(i) for i in range(layers)]


def phi4flash_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `Phi4FlashConfig` (or anything with its attributes; a key
    the published file lacks is `ASSUMED`'s). What the program does not model
    is refused, not dropped. `layer_indices` (an override) runs those layers
    of the PUBLISHED stack; `num_layers` alone builds the stack of that depth
    where it is a multiple of 4, and else runs the published stack's first so
    many layers (a prefix of the self-decoder: no layer of it reads)."""
    def stated(key):
        return getattr(hf_config, key, ASSUMED.get(key))

    for key, modelled in (("mb_per_layer", 2), ("hidden_act", "silu"), ("mlp_bias", False),
                          ("lm_head_bias", False), ("mamba_conv_bias", True), ("mamba_proj_bias", False),
                          ("embd_pdrop", 0), ("resid_pdrop", 0)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Phi-4-mini-flash-reasoning has %r)"
                             % (key, getattr(hf_config, key), modelled))
    hidden = overrides.get("hidden_size", hf_config.hidden_size)
    cut = overrides.get("layer_indices")
    depth = overrides.get("num_layers", hf_config.num_hidden_layers)
    # a depth HF's constructor takes (whole [scan, attention] pairs in both decoders) is that depth's
    # stack; any other, as in the other families, the first so many layers of the published one
    stack = depth if cut is None and depth % 4 == 0 else hf_config.num_hidden_layers
    rank = stated("mamba_dt_rank")
    fields = dict(
        hidden_size=hf_config.hidden_size,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        num_layers=depth if cut is None else len(cut),
        vocab_size=hf_config.vocab_size,
        ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        norm_type="layernorm", activation="swiglu", causal=True, pre_norm=True, mlp_bias=False,
        layernorm_eps=hf_config.layer_norm_eps,
        init_std=stated("initializer_range"),
        position_type="none",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", True),
        qkv_bias=bool(stated("attention_bias")),
        out_bias=bool(stated("attention_bias")),
        sliding_window=hf_config.sliding_window,
        diff_attention=True,
        layer_types=layer_types(stack),
        mamba_d_state=stated("mamba_d_state"),
        mamba_d_conv=stated("mamba_d_conv"),
        mamba_expand=stated("mamba_expand"),
        mamba_dt_rank=-(-hidden // 16) if rank == "auto" else rank,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def phi4flash_config(model_size: str = "phi-4-mini-flash-reasoning", **overrides) -> TransformerConfig:
    return phi4flash_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="phi4flash", config_fn=phi4flash_config, meta_configs=META_CONFIGS,
                     default_size="phi-4-mini-flash-reasoning", config_from_hf=phi4flash_config_from_hf))
