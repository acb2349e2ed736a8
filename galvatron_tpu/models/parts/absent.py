"""The absent half: a published block that is a token mixer ALONE or an MLP
alone (Nemotron-H's `x + f(norm x)` with ONE f) is a layer whose other half
is this entry, of `MIXERS` and of `MLP_HALVES` alike. It has no leaves, no
norm of its own, no specs and no scope, and the stack adds nothing to the
residual stream for it: `models/base.py` asks the table's entry (`absent`)
and never calls its `forward`."""

from __future__ import annotations

from galvatron_tpu.models.parts.common import LayerPart


def _never(*_, **__):
    raise AssertionError("an absent half is skipped by the stack, not run")


ABSENT = LayerPart(lambda ks, cfg: {}, _never, lambda cfg, axes: {}, (), absent=True)
