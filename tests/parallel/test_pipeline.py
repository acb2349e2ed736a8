"""Pipeline-parallel correctness (reference pattern: tests/core/test_pp.py —
build a baseline, train both a few steps, compare losses)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from galvatron_tpu.config.strategy import HybridParallelConfig, LayerStrategy
from galvatron_tpu.parallel.pipeline import (
    stack_params,
    unstack_params,
    validate_pipeline_config,
)
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]

from tests.conftest import gpt_traj as _traj  # shared baseline machinery

B, S, V = 8, 32, 128


@pytest.fixture(scope="module")
def cfg(gpt_cfg):
    return gpt_cfg


@pytest.fixture(scope="module")
def params(gpt_params):
    return gpt_params


_EXT = pytest.mark.skipif(
    not __import__("os").environ.get("GALVATRON_EXTENDED_TESTS"),
    reason="extended matrix (set GALVATRON_EXTENDED_TESTS=1); representative "
    "configs stay in the default tier",
)


@pytest.mark.parametrize(
    "pp,tp,chunks,layout",
    [(2, 1, 2, {}), (4, 1, 4, {}),
     # the vocabulary layers split over pp x vocab_tp (mesh.pipeline_vocab_axes): the
     # benchmark cell's layout on four devices, and pp alone under ZeRO-2 beside a dp axis
     (2, 2, 2, dict(world_size=4, vocab_tp=2)),
     (2, 1, 2, dict(world_size=4, vocab_tp=1, default_dp_type="zero2")),
     pytest.param(2, 2, 2, {}, marks=_EXT), pytest.param(2, 1, 1, {}, marks=_EXT)],
    ids=["pp2", "pp4", "pp2tp2-vtp2-4dev", "pp2dp2-zero2-vtp1-4dev", "pp2tp2", "pp2-chunks1"],
)
def test_pipeline_matches_dp(cfg, params, gpt_ref_traj, devices8, pp, tp, chunks, layout):
    ref = gpt_ref_traj(chunks)
    layout = dict(layout)
    world = layout.pop("world_size", 8)
    hp = HybridParallelConfig.uniform(world, 4, pp=pp, tp=tp, global_bsz=B, chunks=chunks, **layout)
    got = _traj(cfg, params, hp, devices8[:world])
    assert max(abs(a - b) for a, b in zip(ref, got)) < 5e-5, (ref, got)


def _loss_and_grads(cfg, hp, devices, params, batch):
    """(model, loss, gradients as the pp = 1 tree has them) of `params` under `hp`."""
    m = construct_hybrid_parallel_model(cfg, hp, devices[: hp.world_size])
    p = jax.tree.map(jnp.copy, params)
    if hp.pp > 1:
        p["stages"] = stack_params(p.pop("layers"), hp)
    p = jax.device_put(p, m.shardings())
    loss, grads = jax.jit(jax.value_and_grad(m.loss_fn))(p, m.shard_batch(batch))
    if hp.pp > 1:
        grads["layers"] = unstack_params(grads.pop("stages"), hp)
    return m, float(loss), grads


def _bert_and_batch():
    from galvatron_tpu.models import base as M
    from galvatron_tpu.models.bert import bert_config

    bert = bert_config("bert-base", hidden_size=64, num_heads=4, num_layers=4,
                       vocab_size=V, max_seq_len=S, compute_dtype=jnp.float32)
    rng = np.random.RandomState(0)
    mask = np.ones((B, S), np.float32)
    mask[:, -8:] = 0.0
    batch = dict(
        tokens=jnp.asarray(rng.randint(0, V, (B, S))),
        positions=jnp.broadcast_to(jnp.arange(S), (B, S)),
        token_type_ids=jnp.asarray(rng.randint(0, 2, (B, S))),
        labels=jnp.asarray(rng.randint(0, V, (B, S))),
        attn_mask=jnp.asarray(mask), loss_mask=jnp.asarray(mask),
    )
    return bert, M.init_model_params(jax.random.PRNGKey(0), bert), batch


# name -> (model, the scan pipeline's layout): what `pipeline_vocab_axes` splits
# and which leaves of the vocabulary it reaches
VOCAB_OVER_PP = {
    "pp2tp2-vtp2-untied": ("gpt-untied", dict(world_size=4, pp=2, tp=2, vocab_tp=2)),
    "pp2dp2-zero2-vtp1-untied": ("gpt-untied", dict(world_size=4, pp=2, vocab_tp=1, default_dp_type="zero2")),
    "pp2tp2dp2-vtp2-tied": ("gpt", dict(world_size=8, pp=2, tp=2, vocab_tp=2)),
    "pp2tp2-vtp2-mlm-bias": ("bert", dict(world_size=4, pp=2, tp=2, vocab_tp=2)),
    "pp2tp2-vtp2-megatron-sp": ("gpt-untied", dict(world_size=4, pp=2, tp=2, vocab_tp=2, sequence_parallel=True)),
}


@pytest.mark.parametrize("name", sorted(VOCAB_OVER_PP))
def test_vocab_layers_over_pp_match_single_stage(cfg, params, devices8, name):
    """The scan pipeline stores and computes the table, the head and an MLM
    head's bias split over ('pp',) + vocab_tp: the loss and EVERY leaf's
    gradient are the pp = 1 model's, leaf by leaf on the unstacked tree."""
    from tests.conftest import gpt_batch

    family, layout = VOCAB_OVER_PP[name]
    if family == "bert":
        model_cfg, p, batch = _bert_and_batch()
    else:
        model_cfg, p, batch = cfg, dict(params), gpt_batch(0)
        if family == "gpt-untied":
            model_cfg = dataclasses.replace(cfg, tie_embeddings=False)
            p["lm_head"] = {"kernel": 0.02 * jax.random.normal(jax.random.PRNGKey(7), (cfg.hidden_size, V))}
    layout = dict(layout)
    world = layout.pop("world_size")
    _, ref_loss, ref = _loss_and_grads(
        model_cfg, HybridParallelConfig.uniform(world, 4, global_bsz=B), devices8, p, batch)
    hp = HybridParallelConfig.uniform(world, 4, global_bsz=B, chunks=2, **layout)
    m, loss, got = _loss_and_grads(model_cfg, hp, devices8, p, batch)
    assert "pp" in m.param_specs["embed"]["wte"][0]
    assert abs(loss - ref_loss) < 5e-5, (loss, ref_loss)
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6, err_msg=jax.tree_util.keystr(path))
    if family == "bert":
        assert float(jnp.abs(got["head"]["bias"]).max()) > 0


@pytest.mark.parametrize("pipeline_type", ["gpipe", "pipedream_flush"])
@pytest.mark.parametrize("layout,vocab_dim", [
    (dict(world_size=8, tp=2, vocab_tp=2), ("pp", "m1")),
    (dict(world_size=4, vocab_tp=1, default_dp_type="zero2"), "pp"),
    (dict(world_size=4, tp=2, vocab_tp=2, vocab_sp=1), None),
], ids=["pp2tp2dp2-vtp2", "pp2dp2-zero2-vtp1", "pp2tp2-vocab-sp"])
def test_both_engines_store_the_vocabulary_over_pp_from_one_function(cfg, devices8, pipeline_type, layout, vocab_dim):
    """`parallel/pipeline.vocab_param_specs` lays the vocabulary out for both
    engines: `embed.wte`, an untied `lm_head.kernel` and the table the lookup
    reads (`table_spec`) carry pp, then the vocabulary's tp axes. Under
    vocab-SP the vocabulary is dense: the scan keeps it as pp = 1 has it, the
    1F1B engine, which gathers its copy once a step, stores it over pp alone."""
    from galvatron_tpu.parallel import pipeline, pipeline_1f1b

    assert not hasattr(pipeline_1f1b, "vocab_param_specs")
    layout = dict(layout)
    world = layout.pop("world_size")
    hp = HybridParallelConfig.uniform(world, 4, pp=2, global_bsz=B, chunks=2, pipeline_type=pipeline_type, **layout)
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    m = construct_hybrid_parallel_model(untied, hp, devices8[:world])
    specs = pipeline.vocab_param_specs(untied, hp)
    if vocab_dim is None:
        vocab_dim = "pp" if pipeline_type == "pipedream_flush" else None
    assert m.param_specs["embed"]["wte"] == specs["embed"]["wte"] == P(vocab_dim, None)
    assert m.param_specs["lm_head"]["kernel"] == specs["lm_head"]["kernel"] == P(None, vocab_dim)
    assert m.table_spec() == m.state_specs()["embed"]["wte"] == P(vocab_dim, None)


def test_stack_unstack_roundtrip(cfg, params):
    hp = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=B, chunks=2)
    stacked = stack_params(params["layers"], hp)
    back = unstack_params(stacked, hp)
    for a, b in zip(jax.tree.leaves(params["layers"]), jax.tree.leaves(back)):
        assert (a == b).all()


def test_pipeline_validation():
    hp = HybridParallelConfig(
        world_size=8, pp=2,
        layers=[LayerStrategy(tp=2), LayerStrategy(tp=2), LayerStrategy(tp=1), LayerStrategy(tp=1)],
        global_bsz=8, chunks=2,
    )
    with pytest.raises(ValueError, match="same strategy"):
        validate_pipeline_config(hp)
    hp2 = HybridParallelConfig.uniform(8, 4, pp=2, cp=2, global_bsz=8)
    with pytest.raises(ValueError, match="cp>1"):
        validate_pipeline_config(hp2)


def test_pipelined_bert_mlm_matches_single_stage(devices8):
    """pp=2 BERT (mlm head, token types, padding mask) must reproduce the
    pp=1 loss (review finding: pipeline previously served lm heads only)."""
    cfg, _, batch = _bert_and_batch()

    hp1 = HybridParallelConfig.uniform(8, 4, global_bsz=8)
    m1 = construct_hybrid_parallel_model(cfg, hp1, devices8)
    p1 = m1.init_params(jax.random.PRNGKey(0))
    ref = float(jax.jit(m1.loss_fn)(p1, m1.shard_batch(batch)))

    hp2 = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8, chunks=2)
    m2 = construct_hybrid_parallel_model(cfg, hp2, devices8)
    p2 = m2.init_params(jax.random.PRNGKey(0))
    got = float(jax.jit(m2.loss_fn)(p2, m2.shard_batch(batch)))
    assert abs(got - ref) < 1e-4, (got, ref)


def test_pipelined_vit_classification(devices8):
    """pp=2 ViT trains: patch embedding feeds the scan pipeline and the
    classification head pools last-stage outputs."""
    import numpy as np
    import optax

    from galvatron_tpu.config.strategy import HybridParallelConfig
    from galvatron_tpu.models.vit import vit_config
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    cfg = vit_config("vit-base", hidden_size=64, num_heads=4, num_layers=4,
                     ffn_hidden=128, image_size=32, patch_size=8, num_classes=10,
                     compute_dtype=jnp.float32)
    hp = HybridParallelConfig.uniform(8, 4, pp=2, global_bsz=8, chunks=2)
    m = construct_hybrid_parallel_model(cfg, hp, devices8)
    params = m.init_params(jax.random.PRNGKey(0))
    tx = optax.adam(3e-3)
    opt = m.init_opt_state(tx, params)
    step = m.make_train_step(tx)
    rng = np.random.RandomState(0)
    batch = m.shard_batch(dict(
        pixels=jnp.asarray(rng.randn(8, 32, 32, 3).astype(np.float32)),
        labels=jnp.asarray(rng.randint(0, 10, (8,))),
    ))
    losses = []
    for _ in range(6):
        params, opt, mets = step(params, opt, batch)
        losses.append(float(mets["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses
