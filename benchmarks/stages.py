"""A pipelined model's parameter tree as the plain references read it.

Under pp > 1 the program keeps its layers stacked across the stages
(galvatron_tpu/parallel/pipeline.py `stack_params`): `params["stages"]` is a
list of `max(division)` trees, slot j holding every stage's j-th layer along a
leading axis of `pp` (a stage with fewer layers holds zeros in its trailing
slots). The references read `params["layers"]`, one tree a layer in the
model's order. This is the way back, written here and not imported: the
yardstick shares no code with the program. `division` is the layers a stage
(`hp.pp_division`, a tuple of ints): layer `offset[s] + j` is slot j's row s.
It works on arrays and on tracers alike, so a caller that holds abstract
shapes alone (`rehearse.py`) unstacks inside the function it lowers.
"""

from __future__ import annotations

import itertools
from typing import Any, Dict, List, Mapping, Sequence


def offsets(division: Sequence[int]) -> List[int]:
    """The model's index of each stage's first layer."""
    return list(itertools.accumulate(division, initial=0))[:-1]


def per_layer_tree(params: Mapping[str, Any], division: Sequence[int]) -> Dict[str, Any]:
    """`params` with `stages` -> the same tree with `layers`; a tree that
    holds `layers` already (pp = 1) comes back as it is."""
    import jax

    if "stages" not in params:
        return dict(params)
    slots = params["stages"]
    leading = {leaf.shape[0] for slot in slots for leaf in jax.tree.leaves(slot)}
    if len(slots) != max(division) or leading != {len(division)}:
        raise ValueError("%d slots with leading axes %s do not hold a division of %s"
                         % (len(slots), sorted(leading), list(division)))
    layers: List[Any] = [None] * sum(division)
    for stage, first in enumerate(offsets(division)):
        for j in range(division[stage]):
            layers[first + j] = jax.tree.map(lambda leaf, s=stage: leaf[s], slots[j])
    out = {k: v for k, v in params.items() if k != "stages"}
    out["layers"] = layers
    return out
