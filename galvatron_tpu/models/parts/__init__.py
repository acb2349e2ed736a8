"""The parts a layer is built of, in two tables of one shape, and the one
function that says whether a (config, layout, asker) has a form of them.

A layer is `MIXERS[m] + MLP_HALVES[h]`, each half behind a norm of its own; its kind
(`TransformerConfig.layer_kinds`) names the two, "<m>.<h>", softmax attention
going unnamed. Either may be the absent half ("none", `parts/absent.py`: no
leaves, no norm, nothing added to the stream), so a published block of ONE
half is a layer too. Adding a part is adding one module with one entry
(`common.LayerPart`) and its line here: the stack (`models/base.py`), the
config's validation and the refusals read the tables and name no part."""

from __future__ import annotations

from typing import Any, Iterator, Optional, Tuple

from galvatron_tpu.models.parts.absent import ABSENT
from galvatron_tpu.models.parts.attention import ATTENTION
from galvatron_tpu.models.parts.conv import CONV
from galvatron_tpu.models.parts.cross import CROSS
from galvatron_tpu.models.parts.eva import COUNTERS as EVA_COUNTERS, EVA
from galvatron_tpu.models.parts import hyper
from galvatron_tpu.models.parts.kda import KDA
from galvatron_tpu.models.parts.linear import LINEAR
from galvatron_tpu.models.parts import loop
from galvatron_tpu.models.parts.mamba import GMU, MAMBA
from galvatron_tpu.models.parts.mlp import DENSE, ROUTED
from galvatron_tpu.models.parts.ssm import SSM
from galvatron_tpu.models.parts.window import WINDOW

MIXERS = {"attention": ATTENTION, "linear": LINEAR, "ssm": SSM, "kda": KDA, "conv": CONV, "window": WINDOW,
          "mamba1": MAMBA, "gmu": GMU, "cross": CROSS, "eva": EVA, "none": ABSENT}
MLP_HALVES = {"dense": DENSE, "routed": ROUTED, "none": ABSENT}
# counter -> how the stack folds the layers' values into the step's ("mean" | "max" | "sum"), for a counter a part
# hands back under the `step` event's own name: the stack (models/base._fold_aux, lm_loss_fn) reads the table
COUNTERS = {**EVA_COUNTERS, **hyper.COUNTERS}

# how an asker's sentence starts, and what joins the parts' statements in it
_SAYS = {
    "serve": ("serve: the decode engine has ", ", and "),
    "autotune": ("autotune=%s: the re-search would price ", ", and "),
    "pp": ("pp=%d: the pipeline engines ", " and "),
    "tp": ("layer %d: tp=%d cp=%d sp=%d: no tensor-, context- or sequence-parallel form of ", "; nor of "),
    "vocab_tp": ("vocab_tp=%d: tensor parallelism of any layer is unsupported beside ", ", "),
    "tp_comm": ("tp_comm_mode=%r: the manual TP path has no form of ", ", "),
    "quant": ("quantized grad/param collectives run a local loss without ", ", "),
    "search": ("search: the cost models have no row for ", ", "),
    "profile": ("profile: the layer profiler times a dense block under softmax attention, not ", ", "),
}


def _layout_askers(hp, quant: bool) -> Iterator[Tuple[str, Any]]:
    """(asker, the numbers of its sentence) for each thing this layout (a
    `HybridParallelConfig`) asks of a part; `quant`: it asks for quantized collectives."""
    if hp.pp > 1:
        yield "pp", hp.pp
    for i, s in enumerate(hp.layers):
        if s.tp > 1 or s.cp > 1 or s.sp:
            yield "tp", (i, s.tp, s.cp, int(s.sp))
            break
    if hp.vocab_tp > 1:
        yield "vocab_tp", hp.vocab_tp
    if hp.tp_comm_mode != "gspmd":
        yield "tp_comm", hp.tp_comm_mode
    if quant:
        yield "quant", ()


def unsupported_reason(cfg, hp=None, asker: Optional[str] = None, autotune: Optional[str] = None,
                       quant: bool = False) -> Optional[str]:
    """Why this layout, driver mode (`asker` "serve") or tool (`asker`
    "search", "profile") cannot run this config, or None: the statements of
    the config's OWN parts to the first asker any of them has no form for.
    What has none is refused by name, not run wrong or priced as dense: the
    answer is GLS018's message, which `base.refuse_unsupported` raises at
    trace time and `strategy_lint.lint_hp` reports before it. `quant`:
    `quant_collectives.wants_quant_comm(hp)`, which the caller asks."""
    parts = getattr(cfg, "parts", None)
    if not callable(parts):  # T5's, Swin's and duck-typed configs are built of none of them
        return None
    layers_say = [part.unsupported(cfg) for part in parts()]
    # (the loop wraps the stack and hyper-connections both halves of every layer: neither is an entry of the tables)
    streams_say = hyper.unsupported(cfg)
    says = layers_say + [loop.unsupported(cfg), streams_say]
    askers = [(asker, ())] if asker in ("serve", "search", "profile") else []
    if (autotune or "off") != "off":
        askers.append(("autotune", autotune))
    if hp is not None and any(says):
        askers.extend(_layout_askers(hp, quant))
    for name, numbers in askers:
        said = [s[name] for s in says if name in s]
        if said:
            start, glue = _SAYS[name]
            # (a looped or sandwich-norm stack of parts that have every form runs under GSPMD tp too)
            return (start % numbers + glue.join(said) + "; such a config runs on "
                    + ("one chip and under dp with ZeRO-1/2/3" if any(layers_say) or streams_say else loop.RUNS))
    return None
