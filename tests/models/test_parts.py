"""The seam of a layer (models/parts): two tables of one shape, and what an
entry says is what the config validates by, what the stack hands back and what
a layout, a mode or a tool is refused with. Nothing here traces a program."""

import copy
import dataclasses
import inspect
import re

import pytest

from galvatron_tpu import HybridParallelConfig, LayerStrategy
from galvatron_tpu.models import base as M
from galvatron_tpu.models import parts
from galvatron_tpu.models.bert import bert_config
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.glm4_moe_lite import glm4_moe_lite_config
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.granite_hybrid import granite_hybrid_config
from galvatron_tpu.models.kimi_linear import kimi_linear_config
from galvatron_tpu.models.laguna import laguna_config
from galvatron_tpu.models.lfm2_moe import lfm2_moe_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.parts.common import ASKERS, LayerPart
from galvatron_tpu.models.qwen3_next import qwen3_next_config
from galvatron_tpu.models.swin import swin_config
from galvatron_tpu.models.t5 import t5_config
from galvatron_tpu.models.vit import vit_config
from galvatron_tpu.parallel.quant_collectives import wants_quant_comm
from galvatron_tpu.runtime import elastic

LAYERS = 2
DENSE = dict(hidden_size=64, num_heads=4, num_layers=LAYERS, vocab_size=128, max_seq_len=64)
DELTA = dict(linear_num_key_heads=2, linear_num_value_heads=2, linear_key_head_dim=16,
             linear_value_head_dim=16, linear_conv_kernel=4)
MAMBA1 = dict(mamba_d_state=4, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4)
# a tiny config built of each entry (beside dense MLPs under softmax attention, which have every form)
BUILT_OF = {
    ("MIXERS", "attention"): DENSE,
    ("MIXERS", "attention", "latent"): dict(DENSE, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8,
                                             v_head_dim=8),
    ("MIXERS", "linear"): dict(DENSE, full_attention_interval=2, **DELTA),
    ("MIXERS", "kda"): dict(DENSE, layer_types=["kda", "attention"], **DELTA),
    ("MIXERS", "ssm"): dict(DENSE, layer_types=["mamba", "attention"], ssm_num_heads=4, ssm_head_dim=16,
                            ssm_state_dim=8, ssm_conv_kernel=4),
    ("MIXERS", "conv"): dict(DENSE, layer_types=["conv", "attention"], short_conv_kernel=3),
    ("MIXERS", "window"): dict(DENSE, layer_types=["sliding_attention", "full_attention"], sliding_window=8,
                               window_num_heads=8, head_dim=16),
    ("MIXERS", "mamba1"): dict(DENSE, layer_types=["mamba1", "attention"], **MAMBA1),
    ("MIXERS", "gmu"): dict(DENSE, layer_types=["mamba1", "gmu"], **MAMBA1),
    ("MIXERS", "cross"): dict(DENSE, layer_types=["full_attention", "cross_attention"], diff_attention=True),
    ("MIXERS", "eva"): dict(DENSE, mixer="eva", eva_window=32, eva_chunk=8, position_type="rope"),
    ("MLP_HALVES", "dense"): DENSE,
    ("MLP_HALVES", "routed"): dict(DENSE, num_experts=4, experts_per_token=2),
    # the absent half (PR 71), one entry of both tables: a mixer alone, then an MLP alone; it states no phrase
    ("MIXERS", "none"): dict(DENSE, layer_types=["attention", "none"], mlp_types=["none", "dense"]),
    ("MLP_HALVES", "none"): dict(DENSE, layer_types=["attention", "none"], mlp_types=["none", "dense"]),
}


def _hp(world=2, **kw):
    layer = {k: kw.pop(k) for k in ("tp", "cp", "sp", "grad_comm_dtype") if k in kw}
    return HybridParallelConfig(**{"world_size": world, "pp": 1, "global_bsz": 4, **kw,
                                   "layers": [LayerStrategy(**layer) for _ in range(LAYERS)]})


# how each asker is asked: (layout, asker's name, autotune)
ASKED = {
    "serve": lambda: (_hp(1), "serve", None),
    "autotune": lambda: (_hp(1), "train", "observe"),
    "pp": lambda: (_hp(pp=2, chunks=2), "train", None),
    "tp": lambda: (_hp(tp=2), "train", None),
    "vocab_tp": lambda: (_hp(vocab_tp=2), "train", None),
    "tp_comm": lambda: (_hp(tp_comm_mode="overlap"), "train", None),
    "quant": lambda: (_hp(grad_comm_dtype="int8"), "train", None),
    "search": lambda: (None, "search", None),
    "profile": lambda: (None, "profile", None),
}


def test_the_askers_asked_here_are_the_askers():
    assert set(ASKED) == set(ASKERS)
    assert {(t, k) for t, k, *_ in BUILT_OF} == {(t, k) for t in ("MIXERS", "MLP_HALVES") for k in getattr(parts, t)}


@pytest.mark.parametrize("asker", ASKERS)
@pytest.mark.parametrize("entry", sorted(BUILT_OF), ids="-".join)
def test_an_entry_says_what_a_config_built_of_it_is_refused_with(entry, asker):
    """The entry states a phrase or states that it has a form; the one
    function refuses a config built of the entry by that phrase (GLS018) or
    lets it through, and lets the dense config through either way."""
    cfg = TransformerConfig(**BUILT_OF[entry])
    part = getattr(parts, entry[0])[entry[1]]
    assert part in cfg.parts()
    said = part.unsupported(cfg).get(asker)
    hp, name, autotune = ASKED[asker]()
    reason = parts.unsupported_reason(cfg, hp, name, autotune, quant=wants_quant_comm(hp))
    if said is None:
        assert reason is None
        M.refuse_unsupported(cfg, hp, name, autotune)
    else:
        assert said in reason
        with pytest.raises(M.D.DiagnosticError, match="GLS018") as refused:
            M.refuse_unsupported(cfg, hp, name, autotune)
        assert [d.message for d in refused.value.diagnostics] == [reason]
    assert parts.unsupported_reason(TransformerConfig(**DENSE), hp, name, autotune, quant=wants_quant_comm(hp)) is None


def test_a_config_is_told_of_its_own_parts_and_of_no_others():
    granite = TransformerConfig(**BUILT_OF[("MIXERS", "ssm")])
    reason = parts.unsupported_reason(granite, _hp(tp=2))
    assert "state-space layers" in reason and "experts" not in reason and "latent" not in reason
    both = TransformerConfig(**{**BUILT_OF[("MLP_HALVES", "routed")], **BUILT_OF[("MIXERS", "linear")]})
    reason = parts.unsupported_reason(both, _hp(1), "serve")
    assert "no expert form" in reason and "recurrent state of a linear-attention layer" in reason
    assert parts.unsupported_reason(object(), _hp(tp=2)) is None and parts.unsupported_reason(None, None) is None


FAMILIES = {"llama": llama_config, "gpt": gpt_config, "olmoe": olmoe_config, "glm4_moe_lite": glm4_moe_lite_config,
            "qwen3_next": qwen3_next_config, "granite_hybrid": granite_hybrid_config,
            "kimi_linear": kimi_linear_config, "lfm2_moe": lfm2_moe_config, "laguna": laguna_config}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_configs_validate_clauses_are_exactly_its_entries(family, monkeypatch):
    called = []
    for table in ("MIXERS", "MLP_HALVES"):
        for key, part in getattr(parts, table).items():
            def spy(cfg, _was=part.validate, _key=(table, key)):
                called.append(_key)
                return _was(cfg)
            monkeypatch.setitem(getattr(parts, table), key, dataclasses.replace(part, validate=spy))
    cfg = FAMILIES[family]()
    assert sorted(called) == sorted({("MIXERS", m) for m in cfg.mixers()}
                                    | {("MLP_HALVES", h) for h in cfg.mlp_halves()})
    assert len(cfg.parts()) == len(called)
    assert cfg.layer_aux == any(part.counters for part in cfg.parts())


@pytest.mark.parametrize("entry,fields,words", [
    (("MIXERS", "attention"), dict(qk_norm="row"), "qk_norm="),
    (("MIXERS", "attention", "latent"), dict(head_dim=8), "latent attention runs as ONE attention call"),
    (("MIXERS", "linear"), dict(linear_conv_kernel=0), "linear-attention layers"),
    (("MIXERS", "kda"), dict(linear_num_value_heads=4), "equal under \"kda\""),
    (("MIXERS", "ssm"), dict(ssm_groups=3), "ssm_groups that divide the heads"),
    (("MIXERS", "ssm"), dict(ssm_state_dim=0), "state-space layers want"),
    (("MIXERS", "conv"), dict(short_conv_kernel=0), "short-convolution layers want short_conv_kernel"),
    (("MIXERS", "window"), dict(sliding_window=0), "window layers want sliding_window"),
    (("MIXERS", "window"), dict(rope_scaling={"rope_type": "llama3", "factor": 8.0}), "rope_type='llama3' has no form"),
    (("MIXERS", "window"), dict(rope_scaling={"rope_type": "yarn", "factor": 8.0}), "a yarn rope_scaling states"),
    (("MIXERS", "window"), dict(attn_head_gate=True, attn_output_gate=True, num_kv_heads=2), "attn_head_gate"),
], ids=lambda v: "-".join(v) if isinstance(v, tuple) else None)
def test_an_entrys_clause_raises_at_construction_in_its_words(entry, fields, words):
    with pytest.raises(ValueError, match=re.escape(words)):
        TransformerConfig(**{**BUILT_OF[entry], **fields})


def test_the_pattern_is_asked_one_way_however_it_is_stated():
    """`mixers()` of the interval-stated Qwen3-Next config and of the
    list-stated Granite and Kimi configs is what `layer_kinds()` implies, a
    key of `MIXERS` a layer, and never None."""
    for cfg in (qwen3_next_config(), granite_hybrid_config(), kimi_linear_config(), lfm2_moe_config(),
                laguna_config(), llama_config()):
        implied = tuple(kind.rpartition(".")[0] or "attention" for kind in cfg.layer_kinds())
        assert cfg.mixers() == implied and len(implied) == cfg.num_layers and set(implied) <= set(parts.MIXERS)
        assert tuple(kind.rpartition(".")[2] for kind in cfg.layer_kinds()) == cfg.mlp_halves()
        for kind in set(cfg.layer_kinds()):
            layer = cfg.layer_config(kind)
            # a layer's config names its own two halves and no pattern
            mixer, _, half = kind.rpartition(".")
            assert set(layer.mixers()) == {layer.mixer}
            assert (layer.mixer, layer.mlp_half) == (mixer or "attention", half)
    assert set(qwen3_next_config().mixers()) == {"linear", "attention"}
    assert qwen3_next_config().mixers()[:4] == ("linear", "linear", "linear", "attention")
    assert set(granite_hybrid_config().mixers()) == {"ssm", "attention"}
    assert set(kimi_linear_config().mixers()) == {"kda", "attention"}
    assert set(lfm2_moe_config().mixers()) == {"conv", "attention"}
    assert laguna_config().mixers()[:5] == ("attention", "window", "window", "window", "attention")


def test_a_window_layers_config_states_its_own_heads_and_rope_as_the_ordinary_fields():
    """`layer_config("window.*")` hands the part the fields every part reads:
    the window layers' heads, rope base and rotary share in `num_heads`,
    `rope_theta`, `partial_rotary_factor`, and no scaling; the full layers keep
    the model's."""
    cfg = laguna_config()
    window, full = cfg.layer_config("window.routed"), cfg.layer_config("routed")
    assert (window.num_heads, window.rope_theta, window.rotary_dim, window.rope_scaling) == (64, 1e4, 128, None)
    assert (full.num_heads, full.rope_theta, full.rotary_dim) == (48, 5e5, 64) and full.rope_scaling["factor"] == 64
    assert (window.window_num_heads, window.window_rope_theta, window.window_partial_rotary_factor) == (None,) * 3
    assert window.sliding_window == 512 and window.layer_config("window.routed") == window  # settled: asked again, the same
    same = TransformerConfig(**{**BUILT_OF[("MIXERS", "window")], "window_num_heads": None})
    assert same.layer_config("window.dense").num_heads == same.num_heads  # None: the full layers'


def test_every_key_of_the_mixers_table_is_a_word_of_layer_types():
    """The allowed words are read off `MIXERS` (plus HF's "mamba",
    "full_attention" and "sliding_attention"): a part added to the table is
    accepted with no clause added, and the refusal names them all."""
    fields = {**DELTA, "ssm_num_heads": 4, "ssm_head_dim": 16, "ssm_state_dim": 8, "ssm_conv_kernel": 4,
              "short_conv_kernel": 3, "sliding_window": 8, **MAMBA1, "diff_attention": True}
    reads_after = {"gmu": "mamba1", "cross": "attention"}  # a reader stands after a layer that publishes
    eva = dict(eva_window=32, eva_chunk=8, position_type="rope")  # (rope, which differential attention refuses)
    for key in parts.MIXERS:
        pattern = [reads_after[key], key] if key in reads_after else [key, "attention"]
        own = eva if key == "eva" else fields
        assert TransformerConfig(**DENSE, layer_types=pattern, **own).mixers() == tuple(pattern)
    assert TransformerConfig(**DENSE, layer_types=["mamba", "attention"], **fields).mixers() == ("ssm", "attention")
    assert TransformerConfig(**DENSE, layer_types=["sliding_attention", "full_attention"], **fields).mixers() == (
        "window", "attention")
    with pytest.raises(ValueError) as refused:
        TransformerConfig(**DENSE, layer_types=["windowed", "attention"])
    assert all('"%s"' % key in str(refused.value)
               for key in list(parts.MIXERS) + ["mamba", "full_attention", "sliding_attention"])


def test_the_stack_looks_two_tables_up_and_names_no_part():
    named = re.compile(r"cfg\.(routed|num_experts|kv_lora_rank|latent_attention|experts_held|num_shared_experts)"
                       r"|\"(attention|linear|kda|ssm|conv|window|dense|routed)\"")
    for fn in (M.init_layer_params, M.layer_forward, M.decode_layer_forward, M.layer_param_specs, M.run_layers):
        assert not named.search(inspect.getsource(fn)), fn.__name__
    assert M.MIXERS is parts.MIXERS and M.MLP_HALVES is parts.MLP_HALVES
    for name in ("config", "parts", "parts.common", "parts.attention", "parts.linear", "parts.kda", "parts.ssm",
                 "parts.conv", "parts.window", "parts.mlp", "parts.embed_head"):
        module = __import__("galvatron_tpu.models." + name, fromlist=["_"])
        assert "models.base" not in inspect.getsource(module) and "models import base" not in inspect.getsource(module)
    for table in (parts.MIXERS, parts.MLP_HALVES):  # one shape
        assert {type(part) for part in table.values()} == {LayerPart}


# ---------------------------------------------- the elastic digest (GLS201)
PARENT_DIGESTS = {  # `runtime/elastic.model_config_digest` of each preset at PR 44
    "llama": "02cd0464d40680f3ab3c66013c63ff0bc500b1a5b43d9af6a7338ad60fc2e894",
    "gpt": "4b0c1b66b7ccf7cdf1c64df43d4357a18bba8066eb4fda06aac3cfd3ce1c42f9",
    "olmoe": "4f1adbd510236363681ac8a9e4acd11876157b7cfe28edd7cd6726add6e03765",
    "glm4_moe_lite": "9c1924dc35a4c7e253a13c0345171ec4806c9b0b79820eb604c2b33d6f56af01",
    "qwen3_next": "6f25d6dd98ba915150d8973740f5f853c2d08e227e89c7b86d7903e92a15264c",
    "granite_hybrid": "82647523215eb425ccb9ffb27a38a68a9bdf5fef23e2b4ee0f9a0afb8cc90ce5",
    "kimi_linear": "8496d81e3a950a98fbb9bd903551c061efa97fb682a1b6f1b6b1ed90f15c906c",
    "lfm2_moe": "de9ed240051e89d77e8e5b02f0ed783e50ee33e889aa3980ea5f0b3f90a0b4e2",  # as PR 46 added it
    "laguna": "b640e1d156ab41948e3d265bf1ad4e85342f59441181a4135ed58d26fb95824f",  # as PR 49 added it
    # the encoder families, and the two whose config is a dataclass of its own, every field of which is digested
    "bert": "4c98cec753b063daeaaf8da44a24222855ba7c2ec2d4a2fe0221dbaf09a84acd",
    "vit": "88ccd90b3fe6e70442f6dd71d7b08b0f6371acd5f950f9e079856277c18e3319",
    "t5": "1d256036323eb475333a2c577ca525011c3a90d39e0fa99346581dbfe529cc21",
    "swin": "af606b1ab948c5a637c159dc01a2f76b744bc4251cb9b2cc4b27592f8fa72423",
    "duck": "4beee503ea73f941c0a475633ddf7f5866e632b1f04ad3b7c6e49d5f344d5828",
}


class _Duck:  # a config that is no dataclass: its `mixer` at TransformerConfig's default is left out by name
    def __init__(self):
        self.hidden_size, self.mixer, self._hidden = 8, "attention", 1


DIGESTED = {**FAMILIES, "bert": bert_config, "vit": vit_config, "t5": t5_config, "swin": swin_config, "duck": _Duck}


@pytest.mark.parametrize("family", sorted(DIGESTED))
def test_a_checkpoint_in_the_field_keeps_its_digest(family):
    """Every family's preset digests to the parent's hex string: the rule
    that leaves a newer field of `TransformerConfig` out at its dataclass
    default gives what the hand-kept table of defaults gave, and a config
    that is not a `TransformerConfig` (T5's, Swin's) loses no field to it."""
    assert elastic.model_config_digest(DIGESTED[family]()) == PARENT_DIGESTS[family]


def test_the_digest_leaves_out_what_it_derives_and_nothing_else():
    names = {f.name for f in dataclasses.fields(TransformerConfig)}
    assert elastic._DIGEST_ALWAYS <= names and len(elastic._DIGEST_ALWAYS) == 30
    cfg = llama_config()
    for changed in (dict(logits_scaling=2.0), dict(causal=not cfg.causal)):
        assert elastic.model_config_digest(dataclasses.replace(cfg, **changed)) != elastic.model_config_digest(cfg)
    for cfg in (t5_config(), swin_config()):  # a field of theirs at its default is still told from another value
        for f in dataclasses.fields(cfg):
            if isinstance(f.default, int) and f.name not in elastic._DIGEST_EXCLUDE:
                changed = copy.copy(cfg)  # whatever its own validation would say of the value
                setattr(changed, f.name, getattr(cfg, f.name) + 1)
                assert elastic.model_config_digest(changed) != elastic.model_config_digest(cfg), f.name


# ------------------------------- edges no preset reaches, as the parent had them
LATENT = dict(kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=24)
ALL_KDA = dict(DENSE, layer_types=["kda", "kda"], **DELTA)
LEAD_DENSE = dict(BUILT_OF[("MLP_HALVES", "routed")], first_dense_layers=LAYERS)


def test_what_a_config_states_is_held_to_its_entry_whether_or_not_a_layer_runs_it():
    """A model cut in depth to layers that neither attend nor route (Kimi's
    first three, GLM's first) still states latent attention and experts: its
    widths are checked and widened, its layers hand counters back and its
    layouts are refused as the whole model's are."""
    cut = TransformerConfig(**ALL_KDA, **LATENT)
    assert cut.head_dim == 24 and set(cut.mixers()) == {"kda"}  # the digest holds head_dim
    assert "latent attention" in parts.unsupported_reason(cut, _hp(1), "serve")
    with pytest.raises(ValueError, match="latent attention runs as ONE attention call"):
        TransformerConfig(**ALL_KDA, **{**LATENT, "qk_rope_head_dim": 0})
    with pytest.raises(ValueError, match="qk_norm="):
        TransformerConfig(**ALL_KDA, qk_norm="row")
    lead = TransformerConfig(**LEAD_DENSE)
    assert lead.mlp_halves() == ("dense",) * LAYERS and lead.routed_layers == 0
    assert lead.layer_aux and not lead.layer_config("dense").layer_aux
    assert "the experts' kernels" in parts.unsupported_reason(lead, _hp(tp=2))
    listed = TransformerConfig(**DENSE, layer_types=["attention"] * LAYERS)
    assert listed.layer_config("dense").mixer == "attention" and not listed.layer_aux
