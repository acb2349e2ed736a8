"""`linear_state_abs_max` in the Kimi-Linear cell: the largest magnitude in any
head's final state of any KDA layer, the program's counter of that name in the
telemetry `step` event (models/base.py `kda_mixer` hands back the linear
mixer's counters: the state is the same (d_k, d_v) a head, its rows forgetting
apart), mean over the steps of `window_steps`. The Qwen3-Next cell's reader,
whose entry lists its own cell."""

from benchmarks.layer_metrics import linear_state_abs_max


def read(run):
    return linear_state_abs_max.read(run)
