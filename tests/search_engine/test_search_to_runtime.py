"""The full reference loop: search (mock profiles) -> strategy JSON ->
runtime executes the searched config (profile -> search -> train,
SURVEY.md intro)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.runtime.dataloader import prepare_batch
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler
from tests.search_engine.test_search_engine import make_engine

pytestmark = [pytest.mark.search_engine, pytest.mark.distributed]


def test_searched_config_trains(tmp_path, devices8):
    eng = make_engine(mem_gb=16.0, layers=4, bsz=8, chunk=2)
    best = eng.parallelism_optimization()
    assert best is not None
    path = eng.save_results(best, str(tmp_path / "searched.json"))

    hp = HybridParallelConfig.from_json(path, world_size=8)
    # NO skips: the search only emits divisions the runtime accepts (equal
    # layers per stage, engine._pp_stage_dict snapping), pp>1 routes to the
    # 1F1B engine which takes heterogeneous per-stage strategies — every
    # searched config must construct and train (round-2 weak item #5)
    cfg = TransformerConfig(
        hidden_size=64, num_heads=4, num_layers=4, vocab_size=128, max_seq_len=64,
        compute_dtype=jnp.float32,
    )
    m = construct_hybrid_parallel_model(cfg, hp, devices8)
    params = m.init_params(jax.random.PRNGKey(0))
    tx, _ = get_optimizer_and_scheduler(OptimizerArgs(lr=1e-3, warmup_steps=1, total_steps=5))
    opt = m.init_opt_state(tx, params)
    step = m.make_train_step(tx)
    tokens = np.random.RandomState(0).randint(0, 128, (hp.global_bsz, 32))
    batch = m.shard_batch(prepare_batch(hp, tokens))
    params, opt, mets = step(params, opt, batch)
    assert np.isfinite(float(mets["loss"]))
