"""Model-family registry.

The reference keeps one directory per family under ``galvatron/models/`` with a
uniform 5-file integration surface (SURVEY.md §2.4; e.g.
models/gpt_hf/GPTModel_hybrid_parallel.py:20-79). Here a family is one
``ModelFamily`` record: a config constructor plus optional HF state-dict
conversion hooks, registered where the family's module ends. All families
share the same functional transformer (models/base.py) so "integration"
reduces to configuration.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional


@dataclass(frozen=True)
class ModelFamily:
    name: str
    config_fn: Callable[..., Any]  # (model_size:str, **overrides) -> TransformerConfig
    meta_configs: Dict[str, dict]
    default_size: str
    convert_from_hf: Optional[Callable] = None  # (state_dict, cfg) -> params
    export_to_hf: Optional[Callable] = None  # (params, cfg) -> state_dict
    config_from_hf: Optional[Callable] = None  # (hf_config, **overrides) -> cfg
    # optional family-specific model constructor (cfg, hp, devices=None) ->
    # HybridParallelModel; used by families whose param tree / forward differ
    # from the generic decoder stack (t5, swin)
    build: Optional[Callable] = None
    # which input pipeline the train driver wires up: "lm" (token stream),
    # "seq2seq" (enc+dec token streams), "vision" (pixels/labels)
    data_kind: str = "lm"
    # optional (cfg) -> [{"hidden_size", "seq_len", "layer_num"}, ...] for the
    # search engine's multi-layer-type path (t5 enc/dec, swin per stage —
    # reference layernum_listed, model_profiler.py:71-75)
    layer_configs_fn: Optional[Callable] = None
    # optional (cfg, model_name, args) -> profiler instance overriding the
    # generic ModelProfiler (t5/swin)
    make_profiler: Optional[Callable] = None
    # whether the family's pipeline engine accepts layer-type boundaries that
    # fall mid-stage (swin: patch merges may land inside a stage; enc-dec:
    # the encoder/decoder boundary must align with a stage boundary). The
    # search engine keys its multi-layer-type feasibility filter on this.
    mid_stage_type_boundaries: bool = False
    # whether the family's attention has a sequence dimension that ring-cp /
    # ulysses-sp can shard (swin windowed attention does not —
    # validate_swin_config); False drops cp/sp strategies from the search
    supports_sequence_sharding: bool = True


_REGISTRY: Dict[str, ModelFamily] = {}
# families whose module failed to import, mapped to the import traceback —
# surfaced loudly at get_family() instead of silently vanishing
_BROKEN: Dict[str, str] = {}


def register(family: ModelFamily):
    _REGISTRY[family.name] = family
    return family


def get_family(name: str) -> ModelFamily:
    _ensure_builtin()
    if name in _BROKEN:
        raise ImportError(
            "model family %r failed to import:\n%s" % (name, _BROKEN[name])
        )
    if name not in _REGISTRY:
        # _BROKEN is keyed by MODULE name; a module may register families under
        # other names, so point at any recorded import failures here too
        broken_note = (
            " (modules that failed to import: %s)" % sorted(_BROKEN)
            if _BROKEN else ""
        )
        raise KeyError(
            "unknown model family %r; known: %s%s"
            % (name, sorted(_REGISTRY), broken_note)
        )
    return _REGISTRY[name]


def family_names():
    _ensure_builtin()
    return sorted(_REGISTRY)


def flash_variant(family: ModelFamily) -> ModelFamily:
    """The same family pinned to attn_impl="flash", as `<name>_fa` (reference
    gpt_fa / llama_fa, SURVEY.md §2.4): on TPU the fused-attention choice is
    the pallas flash kernel."""
    def pinned(fn):
        def cfg_fa(*args, **overrides):
            overrides.setdefault("attn_impl", "flash")
            return fn(*args, **overrides)

        return cfg_fa

    return dataclasses.replace(family, name=family.name + "_fa", config_fn=pinned(family.config_fn),
                               config_from_hf=pinned(family.config_from_hf))


# every family registers itself where its module ends; a new one adds its name
_FAMILY_MODULES = ("nemotron_h", "xing4", "ouro", "evabyte", "phi4flash", "laguna", "lfm2_moe", "kimi_linear", "granite_hybrid", "qwen3_next", "glm4_moe_lite", "olmoe", "gpt", "llama",
                   "bert", "vit", "t5", "swin")
_LOADED = False


def _ensure_builtin():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # a broken module is recorded (not swallowed) and re-raised at get_family()
    # so a broken family surfaces at use time instead of vanishing from the registry
    import traceback
    import warnings

    for mod in _FAMILY_MODULES:
        try:
            __import__("galvatron_tpu.models.%s" % mod)
        except Exception:
            # ANY import-time failure (ImportError, NameError, SyntaxError...)
            # must not take down the registry for the healthy families
            tb = traceback.format_exc()
            _BROKEN[mod] = tb
            try:
                warnings.warn(
                    "model family %r failed to import and will raise at use "
                    "time: %s" % (mod, tb.strip().splitlines()[-1])
                )
            except Exception:
                # -W error must not abort registration of the remaining
                # families; the traceback is still surfaced at get_family
                pass
