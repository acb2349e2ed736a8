"""Ouro's family (HF `model_type: ouro`; ByteDance Seed et al., "Scaling Latent Reasoning via Looped Language
Models", arXiv:2510.25741, 2025-10): a LoopLM. ONE stack of pre-norm decoder layers (full causal attention on
as many key heads as query heads, rope on whole heads, a bias-free SwiGLU, RMSNorm) is applied
`total_ut_steps` times over the SAME parameters: h_t = RMSNorm(F(h_{t-1}); final norm) for t = 1 .. T, the
normed state feeding the untied head AND re-entering the stack (`models/base.looped_states`).

What the block adds to `models/base.py`'s, by the config's switches: **sandwich norms** (`post_norm`: each
half's output is normed by a norm of its own before it joins the stream), **the loop** (`loop_steps`), **an
exit gate** (`exit_gate`: a Linear(hidden, 1) on each of the first T - 1 normed states; lambda_t = sigmoid,
p_t = lambda_t prod_{j<t}(1 - lambda_j), p_T the rest) and **the expected loss** over the passes, `sum_t p_t
CE_t - beta H(p)` a position (`exit_entropy_coef` = beta; `models/parts/loop.py`). `early_exit_threshold` is
inference's (stop once the cumulative p reaches it; at 1 every pass runs) and training always runs all T.

The preset carries the PUBLISHED config with its source (ROADMAP D12). What the published file is silent on
is in `ASSUMED`: the sandwich norms, the norm between passes, the gate's form and initialisation, beta, no
QKV bias, the rope's convention and `initializer_range`; neither HF's `modeling_ouro.py` nor weights are in
the repository, so each is the public form as recalled and has a switch in the plain reference
(`benchmarks/references/ouro_lm.py`). A checkpoint conversion waits for those files.

Layouts: one chip, dp with ZeRO-1/2/3, and GSPMD tensor parallelism (dense attention and SwiGLU have their
forms; the loop adds no collective). pp (the last stage's output would re-enter the first: a ring no
schedule here has), `serve` (a cache a pass, early exit), the manual TP path, quantized collectives,
`search`, `profile` and `--autotune` (the cost models price a layer once) refuse such a config (GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

OURO_SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the shape or the objective)
PUBLISHED = {
    "ouro-2.6b": {
        "source": OURO_SOURCE,
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5632,
        "layer_types": ["full_attention"] * 48, "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16, "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
    },
}
# what the published file has no key for: the public form as recalled (the configuration's file lists each
# with its other candidate)
ASSUMED = {"post_norm": "a norm of its own on each half's output (sandwich)", "loop_norm": "the final norm after EVERY pass",
           "exit_gate": "Linear(hidden, 1) + sigmoid a position on the normed state, N(0, initializer_range^2) and bias 0",
           "exit_entropy_coef": 0.1, "attention_bias": False, "rope": "rotate-half (HF Llama's), whole heads",
           "initializer_range": 0.02}


def ouro_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF Ouro config (or anything with its attributes). What the program does not model is refused,
    not dropped."""
    for key, modelled in (("hidden_act", "silu"), ("rope_scaling", None), ("use_sliding_window", False),
                          ("sliding_window", None), ("attention_bias", ASSUMED["attention_bias"])):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Ouro-2.6B has %r)"
                             % (key, getattr(hf_config, key), modelled))
    kinds = set(getattr(hf_config, "layer_types", None) or ["full_attention"])
    if kinds != {"full_attention"}:
        raise ValueError("layer_types %r is not modelled: every layer of the published Ouro-2.6B is "
                         "\"full_attention\"" % sorted(kinds))
    steps = int(getattr(hf_config, "total_ut_steps", 1))
    fields = dict(
        **decoder_fields(hf_config, ASSUMED["initializer_range"]),
        head_dim=getattr(hf_config, "head_dim", None),
        ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        position_type="rope", rope_theta=float(hf_config.rope_theta),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        qkv_bias=False, out_bias=False,
        post_norm=True, loop_steps=steps, exit_gate=steps > 1,
        exit_entropy_coef=ASSUMED["exit_entropy_coef"] if steps > 1 else 0.0,
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def ouro_config(model_size: str = "ouro-2.6b", **overrides) -> TransformerConfig:
    return ouro_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="ouro", config_fn=ouro_config, meta_configs=META_CONFIGS,
                     default_size="ouro-2.6b", config_from_hf=ouro_config_from_hf))
