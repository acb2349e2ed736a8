"""Xing4.0's family (`model_type: xing4_0`, Xing4.0-29B-A4B): DeepSeek-V3's block inside
manifold-constrained hyper-connections.

The block is `models/base.py`'s with the config's switches set, GLM-4.7-Flash's but for its sizes: RMSNorm,
SwiGLU, no biases, an untied head; **latent attention** (MLA: low-rank q and k/v, `latent_qkv_projection`) at
DeepSeek-V3's own head, q and k of `qk_nope_head_dim` 128 dims without positions beside `qk_rope_head_dim` 64
rotated ones and v of 128, zero-padded to ONE attention call at 256 (Kimi-Linear's form); rope under **yarn**
as DeepSeek states it (`rope_scaling.type`, `mscale`, `mscale_all_dim`), mapped here onto `ops/rope.YARN_KEYS`
(cos and sin x m(mscale) / m(mscale_all_dim), m(s) = 0.1 s ln factor + 1) and the softmax's scale
(`attention_multiplier` = (nope + rope)^-1/2 x m(mscale_all_dim)^2); `first_k_dense_replace` leading layers
with a dense MLP, then `n_routed_experts` SwiGLU experts with `num_experts_per_tok` a token beside
`n_shared_experts` shared ones under a **sigmoid router** with a bias that no gradient moves (`noaux_tc`,
`models/base.update_router_bias`), renormalised and scaled by `routed_scaling_factor`.

What no other family has is the residual path (`models/parts/hyper.py`; "mHC", arXiv:2512.24880): `hc_mult` 4
residual streams a token; each half of a layer reads one vector out of them and writes `H_res X + H_post o`
back, H_res a doubly-stochastic 4 x 4 matrix a token and a half, made by `hc_sinkhorn_iters` 20
Sinkhorn-Knopp steps from exp of a logit clipped to `mhc_h_res_clamp_min` / `max`. The embedding starts the
streams as 4 copies and the final norm reads their sum.

`n_group` 1 and `topk_group` 1 make the group-limited choice the plain top-k; another grouping, a
`rope_scaling.type` other than yarn and an `ep_size` other than 1 are refused, not dropped. The published
config has one multi-token-prediction module (`num_nextn_predict_layers` 1): how four streams enter an MTP
block is settled neither by the config nor by the two papers, so `mtp_layers` > 0 beside `hc_mult` > 1 is
REFUSED by name (`xing4_config_from_hf` on the published keys as they are) and the preset builds the model
without the module, as HF's forward of DeepSeek-V3's descendants runs it (`xing4_config`: `mtp_layers` 0
unless asked). A program may hold a share of the experts (`experts_held`, `experts_held_start`: the router still
ranks all `n_routed_experts`). The preset carries the PUBLISHED config with its source (ROADMAP D12).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP path, quantized collectives,
`serve`, `search`, `profile` and `--autotune` have no expert form, no latent-attention form and no form of
the n-stream activation (a pipeline's exchange carries ONE hidden a token, no spec lays the wide array out
under tp / sp / cp, the cost models price a layer's kept activations once, a decoded token has no streams)
and refuse such a config (GLS018).
"""

from __future__ import annotations

import math
from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

XING4_29B_SOURCE = "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the shape or the objective)
PUBLISHED = {
    "xing4.0-29b-a4b": {
        "source": XING4_29B_SOURCE,
        "model_type": "xing4_0", "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "q_lora_rank": 768, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096, "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
    },
}
# what DeepSeek-V3's report states and the config does not carry (arXiv:2412.19437 4.2), and HF's default
ROUTER_BIAS_UPDATE_RATE = 0.001
INITIALIZER_RANGE = 0.02
_DEEPSEEK_YARN_KEYS = ("type", "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
                       "mscale", "mscale_all_dim")


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek's `yarn_get_mscale`: m(s) = 0.1 s ln(factor) + 1 (1 for a factor of 1 or less)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_from_deepseek(scaling, qk_head_dim: int):
    """DeepSeek's `rope_scaling` -> (`ops/rope.py`'s yarn mapping, the softmax's scale): the frequencies'
    numbers as they are, cos and sin x m(mscale) / m(mscale_all_dim) as `attention_factor`, and
    `qk_head_dim`^-1/2 x m(mscale_all_dim)^2 (DeepseekV3Attention's `softmax_scale`; no `mscale_all_dim`: plain).
    None: (None, None)."""
    if scaling is None:
        return None, None
    scaling = dict(scaling)
    unknown = sorted(set(scaling) - set(_DEEPSEEK_YARN_KEYS))
    if scaling.get("type") != "yarn" or unknown:
        raise ValueError("rope_scaling type=%r%s is not modelled (the published Xing4.0-29B-A4B has \"yarn\" with %s)"
                         % (scaling.get("type"), " with %r" % unknown if unknown else "",
                            ", ".join(_DEEPSEEK_YARN_KEYS[1:])))
    factor, all_dim = scaling["factor"], scaling.get("mscale_all_dim", 0)
    mapped = {"rope_type": "yarn", **{k: scaling[k] for k in _DEEPSEEK_YARN_KEYS[1:5]},
              "attention_factor": yarn_mscale(factor, scaling.get("mscale", 1)) / yarn_mscale(factor, all_dim)}
    return mapped, qk_head_dim ** -0.5 * (yarn_mscale(factor, all_dim) ** 2 if all_dim else 1.0)


def xing4_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF config of `model_type` xing4_0 (or anything with its attributes). What the program does not
    model is refused, not dropped; a multi-token-prediction module beside `hc_mult` > 1 is (`parts/hyper.validate`)."""
    for key, modelled in (("n_group", 1), ("topk_group", 1), ("topk_method", "noaux_tc"), ("ep_size", 1),
                          ("moe_layer_freq", 1), ("scoring_func", "sigmoid"), ("hidden_act", "silu")):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published Xing4.0-29B-A4B has %r)"
                             % (key, getattr(hf_config, key), modelled))
    qk = hf_config.qk_nope_head_dim + hf_config.qk_rope_head_dim
    scaling, softmax_scale = yarn_from_deepseek(getattr(hf_config, "rope_scaling", None), qk)
    hc_mult = getattr(hf_config, "hc_mult", 1)
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        # the ONE attention call's width: the flash kernels take heads of whole 128-lane tiles, so q, k (192)
        # and v (128) are padded with zeros to 256
        head_dim=-(-max(qk, hf_config.v_head_dim) // 128) * 128,
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        dense_ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        position_type="rope",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        qkv_bias=getattr(hf_config, "attention_bias", False),
        out_bias=getattr(hf_config, "attention_bias", False),
        rope_theta=float(hf_config.rope_theta),
        rope_scaling=scaling,
        attention_multiplier=softmax_scale,
        q_lora_rank=hf_config.q_lora_rank,
        kv_lora_rank=hf_config.kv_lora_rank,
        qk_nope_head_dim=hf_config.qk_nope_head_dim,
        qk_rope_head_dim=hf_config.qk_rope_head_dim,
        v_head_dim=hf_config.v_head_dim,
        first_dense_layers=hf_config.first_k_dense_replace,
        num_experts=hf_config.n_routed_experts,
        experts_per_token=hf_config.num_experts_per_tok,
        num_shared_experts=hf_config.n_shared_experts,
        norm_topk_prob=hf_config.norm_topk_prob,
        router_score="sigmoid",
        routed_scaling_factor=float(hf_config.routed_scaling_factor),
        router_bias=True,
        router_bias_update_rate=ROUTER_BIAS_UPDATE_RATE,
        mtp_layers=getattr(hf_config, "num_nextn_predict_layers", 0),
        hc_mult=hc_mult,
        hc_sinkhorn_iters=getattr(hf_config, "hc_sinkhorn_iters", 0) if hc_mult > 1 else 0,
        hc_eps=getattr(hf_config, "hc_eps", 1e-6),
        hc_res_clamp=((hf_config.mhc_h_res_clamp_min, hf_config.mhc_h_res_clamp_max)
                      if hc_mult > 1 and hasattr(hf_config, "mhc_h_res_clamp_min") else None),
    )
    fields.update(overrides)
    return TransformerConfig(**fields)


def xing4_config(model_size: str = "xing4.0-29b-a4b", **overrides) -> TransformerConfig:
    """The preset WITHOUT its multi-token-prediction module (`mtp_layers` 0 unless asked, and asked beside
    `hc_mult` > 1 it is refused by name): the module's docstring says why."""
    overrides.setdefault("mtp_layers", 0)
    return xing4_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="xing4", config_fn=xing4_config, meta_configs=META_CONFIGS,
                     default_size="xing4.0-29b-a4b", config_from_hf=xing4_config_from_hf))
