"""GLM-4.7-Flash's family (HF `Glm4MoeLiteForCausalLM`, `model_type:
glm4_moe_lite`): DeepSeek-V3's block at 30B-A3B.

The block is `models/base.py`'s with the config's switches set: RMSNorm,
SwiGLU, no biases, an untied head; **latent attention** (MLA: low-rank q and
k/v, rope on `qk_rope_head_dim` of a head's dims with one rotated key shared
by all heads; `latent_qkv_projection`); `first_k_dense_replace` leading
layers with a dense MLP of `intermediate_size`, then layers of
`n_routed_experts` SwiGLU experts of `moe_intermediate_size` with
`num_experts_per_tok` a token beside `n_shared_experts` shared ones; a
**sigmoid router** chosen by score plus `e_score_correction_bias`
(`topk_method: noaux_tc`), weights renormalised and scaled by
`routed_scaling_factor`, dropless (ops/moe.py), with no auxiliary loss: the
bias takes no gradient and is moved once a step against each expert's load
(`models/base.update_router_bias`, in `runtime/model_api.make_train_step`);
and `num_nextn_predict_layers` multi-token-prediction modules
(`models/base.mtp_logits`). The preset carries the PUBLISHED config with its
source (ROADMAP D12).

`n_group` 1 and `topk_group` 1 make the group-limited choice the plain
top-k; another grouping is refused, not dropped. A program may hold a share
of the experts (`experts_held`, `experts_held_start`: the router still ranks
all `n_routed_experts`).

Layouts: one chip, and dp with ZeRO-1/2/3. tp, pp, cp, sp, the manual TP
path, quantized collectives, `serve`, `search`, `profile` and `--autotune`
have no expert form and no latent-attention form and refuse such a config
(GLS018).
"""

from __future__ import annotations

from types import SimpleNamespace

from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.hf_utils import decoder_fields
from galvatron_tpu.models.registry import ModelFamily, register

GLM_47_FLASH_SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"

# the published config's keys, verbatim (those that say something about the
# shape or the objective)
PUBLISHED = {
    "glm-4.7-flash": {
        "source": GLM_47_FLASH_SOURCE,
        "hidden_size": 2048, "intermediate_size": 10240, "moe_intermediate_size": 1536,
        "num_hidden_layers": 47, "num_attention_heads": 20, "num_key_value_heads": 20,
        "q_lora_rank": 768, "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "partial_rotary_factor": 1,
        "n_routed_experts": 64, "n_shared_experts": 1, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1.8, "num_nextn_predict_layers": 1,
        "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 1000000, "rope_scaling": None,
        "max_position_embeddings": 202752, "vocab_size": 154880,
        "attention_bias": False, "tie_word_embeddings": False,
    },
}
# what DeepSeek-V3's report states and HF's config does not carry
# (arXiv:2412.19437 sections 2.2, 4.2): the bias update speed of its
# pre-training and the weight of the MTP loss in its last 4.8T tokens
ROUTER_BIAS_UPDATE_RATE = 0.001
MTP_LOSS_WEIGHT = 0.3
INITIALIZER_RANGE = 0.02


def glm4_moe_lite_config_from_hf(hf_config, **overrides) -> TransformerConfig:
    """From an HF `Glm4MoeLiteConfig` (or anything with its attributes). What
    the program does not model is refused, not dropped."""
    for key, modelled in (("n_group", 1), ("topk_group", 1), ("rope_scaling", None),
                          ("topk_method", "noaux_tc"), ("partial_rotary_factor", 1)):
        if getattr(hf_config, key, modelled) != modelled:
            raise ValueError("%s=%r is not modelled (the published GLM-4.7-Flash has %r)"
                             % (key, getattr(hf_config, key), modelled))
    fields = dict(
        **decoder_fields(hf_config, INITIALIZER_RANGE),
        ffn_hidden=hf_config.moe_intermediate_size,  # the width of ONE expert
        dense_ffn_hidden=hf_config.intermediate_size,
        max_seq_len=hf_config.max_position_embeddings,
        position_type="rope",
        tie_embeddings=getattr(hf_config, "tie_word_embeddings", False),
        qkv_bias=getattr(hf_config, "attention_bias", False),
        out_bias=getattr(hf_config, "attention_bias", False),
        rope_theta=float(hf_config.rope_theta),
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
        routed_scaling_factor=hf_config.routed_scaling_factor,
        router_bias=True,
        router_bias_update_rate=ROUTER_BIAS_UPDATE_RATE,
        mtp_layers=getattr(hf_config, "num_nextn_predict_layers", 0),
        mtp_loss_weight=MTP_LOSS_WEIGHT,
    )
    fields.update(overrides)
    if fields.get("head_dim") is None:
        fields["head_dim"] = fields["qk_nope_head_dim"] + fields["qk_rope_head_dim"]
    return TransformerConfig(**fields)


def glm4_moe_lite_config(model_size: str = "glm-4.7-flash", **overrides) -> TransformerConfig:
    return glm4_moe_lite_config_from_hf(SimpleNamespace(**PUBLISHED[model_size]), **overrides)


META_CONFIGS = PUBLISHED  # the registry's presets: the published keys, with their source

register(ModelFamily(name="glm4_moe_lite", config_fn=glm4_moe_lite_config, meta_configs=META_CONFIGS,
                     default_size="glm-4.7-flash", config_from_hf=glm4_moe_lite_config_from_hf))
