"""The layer runs' self time: device time a step in the ops under
`gt.layers.r<k>` that carry no scope nested in it (`gt.mlp`, `gt.attn.*`,
`gt.moe.*`) and are no flash kernel: the norms, the residual adds, the
layouts' constraints, what the scan does with the stacked parameters and
their gradients, relayouts XLA puts between a run's parts. With `flash_ms`'s
share inside the runs and every nested scope it adds up to `layers_fwd_ms` +
`layers_remat_ms` + `layers_bwd_ms`. A fusion is booked by its principal op
(`trace.origins_from_hlo`: its matmul, else its root), so a norm fused into
the matmul after it is that matmul's scope's, not this. In a program that
names none of its runs' parts this is the whole body but the kernels. Device
0, from the trace. None where the program has no scopes."""

import re

from benchmarks import scopes
from benchmarks.layer_metrics import flash_ms
from benchmarks.layer_metrics.mlp_ms import END, both

# a scope of the program other than a layer run's own
NESTED = r"gt\.(?!layers\.r\d)[a-z_]+(?:\.[a-z_]+)?"
FLASH = "|".join(rx.lstrip("^") for rx in flash_ms.KERNELS.values())
# the flash kernels' share that lies inside the layer runs (an MTP module's
# block calls them outside)
FLASH_IN_LAYERS = r"^(?:%s).*%s" % (FLASH, scopes.LAYERS)
REST = r"^(?!%s)(?!.*%s).*%s" % (FLASH, NESTED, scopes.LAYERS)


def read(run):
    return scopes.ms_a_step(run, REST)


def parts(run, phase=None):
    """The layer runs' milliseconds a step by part, of all phases or of one
    (`scopes.LAYERS_FWD`, `LAYERS_REMAT`, `LAYERS_BWD`): {"flash": the kernels
    inside the runs, each nested scope the traced program has by its name,
    "rest": the runs' self time}. The parts of a phase add up to the phase's
    `layers_*_ms`, as long as no op carries two nested scopes."""
    labels = [label for label in run["trace"]["ops_a_step"] if re.search(scopes.LAYERS, label)]
    nested = sorted({name for label in labels for name in re.findall(NESTED, label)})
    patterns = {"flash": FLASH_IN_LAYERS, "rest": REST}
    patterns.update({name: both(scopes.LAYERS, re.escape(name) + END) for name in nested})
    return {part: scopes.ms_a_step(run, rx if phase is None else both(rx, phase))
            for part, rx in patterns.items()}
