"""What a pipeline stage loses to the fill and drain ticks, device 0,
milliseconds a step.

The GPipe schedule (galvatron_tpu/parallel/pipeline.py `pipeline_apply`) is
one `lax.scan` over `chunks + pp - 1` ticks whose body is vmapped over the
stages: every stage runs its layers on every tick, on zeros where its slot
holds no microbatch. A stage does not idle in the bubble, it computes padding,
so `device_idle_pct` does not see it. Of a stage's `chunks + pp - 1` ticks
`pp - 1` hold padding, forward, recomputed and backward alike (autodiff walks
the same scan back). The reading is the device time a step of every op
inside the tick scan's body (`scopes.TICK_BODY`: the layers' matmuls under
`gt.mlp` and `gt.attn.proj`, the flash kernels, the norms and adds that carry
no nested scope, the stage-to-stage collective-permutes) times
`(pp - 1) / (chunks + pp - 1)`. Every tick runs the same program on the same
shapes, so a tick's share of the body's time is one in `chunks + pp - 1`.

None where the mix lays out no pipeline, the traced program has no scopes,
or no op of it lies in such a body (another schedule: 1F1B is no scan)."""

from benchmarks import scopes
from benchmarks.layer_metrics import flash_roofline


def padding_share(cell):
    """(pp - 1) / (chunks + pp - 1) of the cell's mix, or None without a pipeline."""
    lay = flash_roofline.layout(cell)
    if lay["pp"] <= 1:
        return None
    return (lay["pp"] - 1) / (lay["chunks"] + lay["pp"] - 1)


def read(run):
    share = padding_share(run["cell"])
    inside = scopes.ms_a_step(run, scopes.TICK_BODY)
    if share is None or not inside:
        return None
    return inside * share
