"""The router's bias under the train step (the rule itself: tests/runtime/test_router_bias.py; the tiny
model and its batch: tests/runtime/router_bias_cases.py): a step on one device moves it by the rule alone,
and dp, ZeRO and microbatches move it as one device does, because the counts are the GLOBAL batch's. A file
of its own, and here among the models' tests, so that `--dist loadfile` gives its six compiled steps to a
worker in the middle of a run: as the last long file in path order it was the run's tail (ROADMAP D1)."""

import jax
import numpy as np
import pytest

from galvatron_tpu.models import base as M
from tests.runtime.router_bias_cases import RATE, batch_of, biases, one_step, tiny


@pytest.fixture(scope="module")
def on_one_device():
    return one_step()


def test_the_step_moves_it_by_the_rule_alone(on_one_device):
    params, _, metrics = on_one_device
    assert M.ROUTER_COUNTS not in metrics and "router_bias_abs_max" in metrics
    moved = biases(params)
    assert set(np.unique(np.abs(moved))) <= {0.0, np.float32(RATE)}
    # by the counts of this very batch on the initial weights (the bias was 0)
    cfg = tiny()
    start = M.init_model_params(jax.random.PRNGKey(0), cfg)
    _, parts = M.lm_loss_fn(start, batch_of(), cfg, with_parts=True)
    counts = np.asarray(parts[M.ROUTER_COUNTS])
    np.testing.assert_array_equal(
        moved, np.float32(RATE) * np.sign(counts.mean(axis=1, keepdims=True) - counts))
    assert float(metrics["router_bias_abs_max"]) == 0.0  # the bias this step READ


@pytest.mark.parametrize("world,chunks,dp_type", [(2, 1, "zero2"), (2, 1, "zero3"), (4, 1, "zero2"),
                                                  (1, 2, "ddp"), (2, 2, "zero2")])
def test_dp_and_microbatches_move_it_as_one_device_does(on_one_device, world, chunks, dp_type):
    """The counts are the global batch's: summed over dp inside the routed
    block's region and over the microbatches in the step, before the sign."""
    params, _, _ = on_one_device
    got, _, metrics = one_step(world, chunks, dp_type)
    np.testing.assert_array_equal(biases(got), biases(params))
    assert float(metrics["expert_load_max_over_mean"]) >= 1.0
