"""ZeRO-2's compute copy (runtime/model_api.compute_params): the float32
parameters are stored split over dp, and forward, recomputation and backward
read a copy in the compute dtype that the step gathers once. The token table
that `vocab_parallel_lookup` reads is stored split too, and read as it lies.

Held here, on four virtual CPU devices at tiny widths: the copy changes no
value (against `ddp`, whose state is whole on every replica); which leaves
take it, for every family in the zoo, against the family's own traced loss;
that a layout with nothing to copy lowers to the text it lowered to before
there was a copy; that a checkpoint crosses between the two layouts of the
state."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models.bert import bert_config
from galvatron_tpu.models.gpt import gpt_config
from galvatron_tpu.models.llama import llama_config
from galvatron_tpu.models.olmoe import olmoe_config
from galvatron_tpu.models.parts.embed_head import table_split_axes
from galvatron_tpu.models.swin import construct_swin_model, swin_config
from galvatron_tpu.models.t5 import construct_t5_model, t5_config
from galvatron_tpu.models.vit import vit_config
from galvatron_tpu.parallel.mesh import vocab_axes
from galvatron_tpu.runtime import checkpoint as ckpt
from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model
from galvatron_tpu.runtime.optimizer import OptimizerArgs, get_optimizer_and_scheduler

pytestmark = [pytest.mark.parallel, pytest.mark.distributed]

B, S, V = 8, 32, 256
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def devices4(devices8):
    return devices8[:4]


def tiny_llama(dtype=BF16, **kw):
    return llama_config("llama-0.3b", num_layers=2, hidden_size=64, num_heads=4, ffn_hidden=128,
                        vocab_size=V, max_seq_len=S, compute_dtype=dtype, **kw)


def layout(dp_type, *, tp=2, pp=1, chunks=1, vocab_tp=None, layers=2, world=4, **kw):
    return HybridParallelConfig.uniform(
        world, layers, tp=tp, pp=pp, chunks=chunks, vocab_tp=tp if vocab_tp is None else vocab_tp,
        default_dp_type=dp_type, global_bsz=B, mixed_precision="bf16", **kw)


def lm_batch(seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (B, S), 0, V)
    return dict(tokens=tokens, positions=jnp.broadcast_to(jnp.arange(S), (B, S)),
                labels=jnp.roll(tokens, -1, 1))


TABLE = "['embed']['wte']"


def adam():
    return get_optimizer_and_scheduler(OptimizerArgs(lr=3e-3, warmup_steps=1, total_steps=20))[0]


def leaf_paths(tree):
    return {jax.tree_util.keystr(p): x for p, x in jax.tree_util.tree_leaves_with_path(tree)}


def train(cfg, hp, devices, steps=3, model=None, batch=None):
    """`steps` Adam steps from one seed: the model, the losses, the state."""
    m = model or construct_hybrid_parallel_model(cfg, hp, devices)
    params = m.init_params(jax.random.PRNGKey(0))
    tx = adam()
    opt = m.init_opt_state(tx, params)
    step = m.make_train_step(tx)
    batch = m.shard_batch(batch or lm_batch())
    losses = []
    for _ in range(steps):
        params, opt, mets = step(params, opt, batch)
        losses.append(float(mets["loss"]))
    assert step._cache_size() == 1  # the state goes out in the layout it came in
    return m, losses, params


# ------------------------------------------------------------- no value changes
def first_gradient(m):
    """The first step's gradient as the step takes it: of the loss on the
    copy the step reads, before `to_accum` widens it."""
    params = m.init_params(jax.random.PRNGKey(0))
    grad = jax.jit(lambda p, b: jax.grad(m.loss_fn)(m.compute_params(p), b))
    return leaf_paths(grad(params, m.shard_batch(lm_batch())))


@pytest.mark.parametrize("dp_type,kw,dp_summed_first", [
    ("zero2", dict(), "['wi']['kernel']"), ("zero2", dict(sequence_parallel=False), None),
    ("zero2", dict(chunks=2), None), ("zero2", dict(tp=1, pp=2, chunks=2), None),
    ("zero3", dict(tp=1), None)],
    ids=["tp2dp2", "tp2dp2_no_megatron_sp", "tp2dp2_chunks2", "gpipe_pp2dp2_chunks2", "dp4_zero3"])
def test_zero_trains_as_ddp_does(dp_type, kw, dp_summed_first, devices4):
    """bf16 compute, three steps, one seed: ZeRO with the copy against ddp.
    Tolerance: tests/models/test_parallel_correctness.py's. GPipe (pp > 1)
    takes no copy: its scan sums a stage's gradient over the microbatches in
    the dtype the stage reads, float32 only while the cast is inside it.
    Under `zero3` the layers' leaves are split over dp as ZeRO-3 has them
    and the vocabulary layers' (ZeRO-2 there without `embed_sdp`) are copied.

    `dp_summed_first` (PR 55): under Megatron-SP, at this size, XLA:CPU
    contracts the gated up kernel's weight gradient over the sequence AS IT
    LIES, split over tp, so the matmul leaves a partial product over tp AND
    dp, and both sums round to bf16. ddp sums over tp inside the scan's body
    and over dp after the scan; ZeRO-2, whose scanned cotangent is asked for
    in ZeRO's shards (models/base.run_layers), sums over dp first, in the
    body. (a + b) + (c + d) against (a + c) + (b + d), each sum rounded:
    the same precision, and not the same bits. Every other leaf's partial
    product is over dp alone, and without Megatron-SP this one's too (the
    case beside it: equal to the bit, so the whole state within the bound).
    That leaf's first gradient is held within one step of bf16 at its own
    scale instead, since Adam's normalisation turns a last bit of a small
    gradient into 4e-3 on the parameter in two updates, and the third step
    carries that to every leaf; every other leaf's first gradient is ddp's to
    the bit, and the loss keeps its bound over the three steps."""
    cfg = tiny_llama()
    m, losses, params = train(cfg, layout(dp_type, **kw), devices4)
    ddp, want_losses, want = train(cfg, layout("ddp", **kw), devices4)
    assert losses[-1] < losses[0]
    assert max(abs(a - b) for a, b in zip(losses, want_losses)) < 5e-5, (losses, want_losses)
    if dp_summed_first is None:
        worst = max(float(jnp.max(jnp.abs(np.asarray(a) - np.asarray(b))))
                    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)))
        # (ZeRO-3's table, split over dp on the vocabulary, ends 1.6e-4 from ddp's
        # with or without the copy: another partitioning of its scatter-add)
        assert worst < (5e-4 if dp_type == "zero3" else 5e-5), worst
    else:
        got, ref = first_gradient(m), first_gradient(ddp)
        reordered = [p for p in got if dp_summed_first in p]
        assert len(reordered) == cfg.num_layers
        for p, g in got.items():
            a, b = (np.asarray(t.astype(jnp.float32)) for t in (g, ref[p]))
            if p not in reordered:
                np.testing.assert_array_equal(a, b, err_msg=p)
                continue
            assert g.dtype == BF16 and (a != b).any(), p  # (the day they are equal, take the bound above)
            one_step = 2.0 ** (np.floor(np.log2(np.abs(b).max())) - 7)  # bf16: 8 bits of significand
            assert np.abs(a - b).max() <= one_step, (p, np.abs(a - b).max(), one_step)

    # the state: every leaf float32, in `state_specs`; a copied leaf, the
    # looked-up table of a split state, and no other, leaves `param_specs`
    # for a split over dp
    copied, specs, state = (leaf_paths(t) for t in (m.copied_leaves(), m.param_specs, m.state_specs()))
    dp = set(vocab_axes(m.hp).dp)
    assert any(copied.values()) == (m.hp.pp == 1) and dp
    split = {p for p, c in copied.items() if c}
    if m.hp.vocab_tp > 1 and m.hp.pp == 1:
        split.add(TABLE)
    for path, leaf in leaf_paths(params).items():
        assert leaf.dtype == jnp.float32, path
        assert leaf.sharding.is_equivalent_to(
            jax.sharding.NamedSharding(m.mesh, state[path]), leaf.ndim), (path, leaf.sharding)
        assert (state[path] != specs[path]) == (path in split), path
        if path in split:
            assert dp & {a for e in state[path] if e is not None
                         for a in (e if isinstance(e, tuple) else (e,))}, (path, state[path])


def test_eval_loss_reads_the_copy(devices4):
    """Outside the step the loss meets the state as it is stored: the eval
    loss makes the step's copy and gives the step's loss."""
    cfg = tiny_llama()
    m = construct_hybrid_parallel_model(cfg, layout("zero2"), devices4)
    params = m.init_params(jax.random.PRNGKey(0))
    batch = m.shard_batch(lm_batch())
    tx = adam()
    _, _, mets = m.make_train_step(tx, donate=False)(params, m.init_opt_state(tx, params), batch)
    assert abs(float(jax.jit(m.eval_loss)(params, batch)) - float(mets["loss"])) < 5e-5
    ddp = construct_hybrid_parallel_model(cfg, layout("ddp"), devices4)
    assert ddp.eval_loss is ddp.loss_fn


# ------------------------------------------------ the table, by the state's case
def gpt_tied():
    return gpt_config("gpt-0.3b", num_layers=2, hidden_size=64, num_heads=4, vocab_size=V,
                      max_seq_len=S, compute_dtype=BF16)


def custom_loss(p, b):
    return jnp.sum(p["embed"]["wte"]) * 0.0


# name -> (config, layout, devices, a custom loss or None, whether the table is stored split)
TABLE_CASES = {
    "zero2_looked_up_untied": (tiny_llama, dict(dp_type="zero2"), 4, None, True),
    "zero2_chunks2": (tiny_llama, dict(dp_type="zero2", chunks=2), 4, None, True),
    "zero2_dp4_of_two_axes": (tiny_llama, dict(dp_type="zero2", world=8), 8, None, True),
    "zero3": (tiny_llama, dict(dp_type="zero3"), 4, None, False),  # the same spec, from `param_specs`
    "embed_sdp": (tiny_llama, dict(dp_type="zero2", embed_sdp=1), 4, None, False),  # `param_specs` has it so
    "tied": (gpt_tied, dict(dp_type="zero2"), 4, None, False),
    "whole_vocabulary": (tiny_llama, dict(dp_type="zero2", vocab_tp=1), 4, None, False),  # copied instead
    "gpipe_pp2": (tiny_llama, dict(dp_type="zero2", pp=2, chunks=2, world=8), 8, None, False),
    "1f1b_pp2": (tiny_llama, dict(dp_type="zero2", pp=2, chunks=2, world=8,
                                  pipeline_type="pipedream_flush"), 8, None, False),
    "ddp": (tiny_llama, dict(dp_type="ddp"), 4, None, False),
    "dp1": (tiny_llama, dict(dp_type="zero2", tp=4), 4, None, False),
    "ulysses": (tiny_llama, dict(dp_type="zero2", sp=1, vocab_sp=1), 4, None, False),
    "quantized_sync": (tiny_llama, dict(dp_type="zero2", grad_comm_dtype="int8"), 4, None, False),
    "manual_tp": (tiny_llama, dict(dp_type="zero2", tp_comm_mode="shard_map"), 4, None, False),
    "custom_loss": (tiny_llama, dict(dp_type="zero2"), 4, custom_loss, False),
}


@pytest.mark.parametrize("name", list(TABLE_CASES))
def test_the_table_is_stored_split_where_the_step_looks_it_up_so(name, devices8):
    """`state_specs`' third case: a looked-up, untied table under ZeRO axes
    that split it further lies as `grad_accum_specs` has it, uncopied, and the
    model's own loss is handed that spec; everywhere else it lies as
    `param_specs` has it and the lookup is the whole-table form."""
    make_cfg, kw, n, loss, split = TABLE_CASES[name]
    kw = dict(kw)
    m = construct_hybrid_parallel_model(make_cfg(), layout(kw.pop("dp_type"), **kw), devices8[:n], loss_fn=loss)
    vax = vocab_axes(m.hp)
    state, placed, accum = (t["embed"]["wte"] for t in (m.state_specs(), m.param_specs, m.grad_accum_specs()))
    # a table read whole as `wte.astype(dtype)[tokens]` is a copied leaf like any other
    copied = name in ("whole_vocabulary", "ulysses")
    assert m.table_spec() == state
    assert m.copied_leaves()["embed"]["wte"] == copied
    assert (state != placed) == (split or copied), (state, placed)
    if split:
        assert state == accum == jax.sharding.PartitionSpec(vax.tp[0], vax.dp[0] if len(vax.dp) == 1 else vax.dp)
        assert table_split_axes(state, vax) == tuple(vax.dp)
        # the opt state's moments lie where the table does
        tx = adam()
        mu = m.opt_state_shardings(tx, m.abstract_params())
        assert any(s.spec == state for s in jax.tree.leaves(mu) if len(s.spec) == 2)
    elif name not in ("embed_sdp", "zero3") and not copied:
        assert table_split_axes(state, vax) == ()


def parents_form(cfg, hp, devices):
    """The model as the parent of PR 52 built it: the table stored whole over
    dp as `param_specs` lays it out, its float32 gradient summed over dp and
    the updated table gathered after the update."""
    m = construct_hybrid_parallel_model(cfg, hp, devices)
    m.table_as_stored, m._stored = False, None
    assert m.table_spec() == m.param_specs["embed"]["wte"]
    return m


def repeated_ids_batch():
    """Ids repeated over a whole row and half a row, of both tp halves."""
    batch = lm_batch()
    batch["tokens"] = batch["tokens"].at[0].set(7).at[1, : S // 2].set(V - 3)
    batch["labels"] = jnp.roll(batch["tokens"], -1, 1)
    return batch


@pytest.mark.parametrize("kw", [dict(), dict(chunks=2), dict(sequence_parallel=False)],
                         ids=["the_cells_flags", "chunks2", "no_megatron_sp"])
def test_the_split_table_trains_as_the_parents_form_does(kw, devices4):
    """A tiny Qwen (qkv bias, untied head) under the four-chip cell's flags
    (tp 2 x dp 2, ZeRO-2, the vocabulary split, full recomputation), three
    Adam steps: the first loss to the bit (the same rows), the rest within
    1e-6 (the same float32 addends in another order), every leaf within
    float32 rounding, and the table comes back split as it went in (what the
    compiled step holds: tests/ops/test_tpu_compile_steps.py)."""
    cfg, hp = tiny_llama(qkv_bias=True), layout("zero2", checkpoint=1, **kw)
    batch = repeated_ids_batch()
    _, want_losses, want = train(cfg, hp, devices4, model=parents_form(cfg, hp, devices4), batch=batch)
    m, losses, params = train(cfg, hp, devices4, batch=batch)
    assert losses[0] == want_losses[0], (losses, want_losses)
    assert max(abs(a - b) for a, b in zip(losses, want_losses)) < 1e-6, (losses, want_losses)
    for (path, a), b in zip(leaf_paths(params).items(), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-6, err_msg=path)
    table = params["embed"]["wte"]
    assert table.dtype == jnp.float32 and table.sharding.spec == m.table_spec() != m.param_specs["embed"]["wte"]


def test_a_checkpoint_crosses_between_the_split_table_and_the_whole_one(tmp_path, devices4):
    """A checkpoint holds logical arrays: one written with the table split
    over dp restores into the parent's layout (whole over dp) and back."""
    cfg, hp = tiny_llama(), layout("zero2")
    split = construct_hybrid_parallel_model(cfg, hp, devices4)
    whole = parents_form(cfg, hp, devices4)
    params = split.init_params(jax.random.PRNGKey(3))
    want = np.asarray(params["embed"]["wte"])
    ckpt.save_checkpoint(str(tmp_path / "a"), 1, params, None, hp=hp)
    as_whole, _, _ = ckpt.load_checkpoint(str(tmp_path / "a"), 1, target=whole, tx=None)
    ckpt.save_checkpoint(str(tmp_path / "b"), 1, as_whole, None, hp=hp)
    back, _, _ = ckpt.load_checkpoint(str(tmp_path / "b"), 1, target=split, tx=None)
    for got, m in ((as_whole, whole), (back, split)):
        table = got["embed"]["wte"]
        assert table.sharding.is_equivalent_to(m.shardings()["embed"]["wte"], 2)
        np.testing.assert_array_equal(np.asarray(table), want)
    assert as_whole["embed"]["wte"].sharding.spec != back["embed"]["wte"].sharding.spec


# ------------------------------------------- nothing to copy: the parent's step
# sha256 of `make_train_step(adam).lower(...).as_text()` (StableHLO) for
# layouts in which no leaf is copied: the step is to stay that text. The
# parent is PR 30's step (the head and its loss under their written backward,
# models/parts/embed_head._head_matmul and _token_nll; before it, from the commit before
# the compute copy, 9c3c713, to PR 29, the text was one other; PR 52 stores
# the looked-up table split over dp under ZeRO-2 whatever the compute dtype,
# so `tp2dp2_zero2_fp32` is PR 52's step; PR 54 splits the scan pipeline's
# table and head over pp, so `gpipe_pp2dp2_zero2` is PR 54's; PR 55 asks for a
# scanned run's stacked cotangent in ZeRO's layout wherever the state may be
# split, a copy or none, so `tp2dp2_zero2_fp32` is PR 55's; the other five
# are PR 30's, the manual TP path's among them: its regions sum a leaf's
# gradient themselves and `_zero_splits_state` keeps ZeRO off it). A PR that
# changes the step on purpose prints the new digests with
# `pytest -k lowers_to -s` and replaces these.
PARENT_STEP_SHA256 = {
    "one_chip": "333bcf1c3f20e1196bff40153aefb0add16e16644225a23e4ed80dd85ad1b4d6",
    "dp4_ddp": "70995ecd487ae2884bbf396bf49771cba3a7ac9bbcefc2b5870fa7933dd6a908",
    "tp2dp2_ddp_chunks2": "5b6cd9e54b8928e4dd99a65f9ba070bf576b707a8f9da29745c241ae8be70815",
    "tp4_zero2_dp1": "3c537facf5b745a4add1697b6547b8e81cd6332accea54f6bac0b3945fd14a09",
    # PR 52: the table split; PR 55: the scanned gradient in ZeRO's layout
    "tp2dp2_zero2_fp32": "1c3ae4c9bb5451857c2cc110b427187a9ac9a747e47a01287e13438ccc4c68e9",
    "gpipe_pp2dp2_zero2": "633ee69f9744f8b3d766bc364c9281162bda216a03446958eb3090ef311c6693",  # PR 54: the table over pp
    "tp2dp2_zero2_manual_tp": "e72629bd031daad6af1e4a377f78d497457a3631b97ed8e6ae58b28847749727",
}


def uncopied_layout(name):
    """(cfg, hp, device count) of a layout in which nothing is copied."""
    if name == "one_chip":
        return tiny_llama(), layout("zero2", tp=1, world=1), 1
    if name == "dp4_ddp":
        return tiny_llama(), layout("ddp", tp=1), 4
    if name == "tp2dp2_ddp_chunks2":
        return tiny_llama(), layout("ddp", chunks=2), 4
    if name == "tp4_zero2_dp1":
        return tiny_llama(), layout("zero2", tp=4), 4
    if name == "tp2dp2_zero2_fp32":
        return tiny_llama(jnp.float32), layout("zero2"), 4
    if name == "gpipe_pp2dp2_zero2":
        return tiny_llama(), layout("zero2", tp=1, pp=2, chunks=2), 4
    if name == "tp2dp2_zero2_manual_tp":
        return tiny_llama(), layout("zero2", tp_comm_mode="shard_map"), 4
    raise KeyError(name)


@pytest.mark.parametrize("name", list(PARENT_STEP_SHA256))
def test_a_layout_with_nothing_to_copy_lowers_to_the_parents_step(name, devices8):
    """dp = 1, ddp, float32 compute, GPipe and the manual TP path
    give no leaf a copy, and the step lowers to the text it lowered to
    before: what the one-chip cells of the benchmark run."""
    cfg, hp, n = uncopied_layout(name)
    m = construct_hybrid_parallel_model(cfg, hp, devices8[:n])
    assert not any(jax.tree.leaves(m.copied_leaves()))
    params = m.abstract_params()
    stored = jax.tree.map(lambda s: s.spec, m.shardings())
    if name == "tp2dp2_zero2_fp32":
        # float32 compute copies nothing, but the looked-up table is stored
        # split over dp all the same (PR 52) and the scanned layers' gradient
        # is summed into ZeRO's shards (PR 55: this layout's step is no
        # longer the parent's, and its digest above is PR 55's)
        assert stored["embed"].pop("wte") == m.grad_accum_specs()["embed"]["wte"] != m.param_specs["embed"]["wte"]
        assert stored == {**m.param_specs, "embed": {k: v for k, v in m.param_specs["embed"].items() if k != "wte"}}
    else:
        assert stored == m.param_specs
    assert m.compute_params(params) is params
    tx = adam()

    def sds(tree, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s), tree, shardings)

    batch = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=jax.sharding.NamedSharding(
        m.mesh, m._batch_spec_for(v))) for k, v in lm_batch().items()}
    text = m.make_train_step(tx).lower(
        sds(params, m.shardings()),
        sds(jax.eval_shape(tx.init, params), m.opt_state_shardings(tx, params)), batch).as_text()
    digest = hashlib.sha256(text.encode()).hexdigest()
    print("%s: %s" % (name, digest))
    assert digest == PARENT_STEP_SHA256[name]


# ------------------------------------------------- a checkpoint between layouts
def test_a_checkpoint_crosses_between_zero2_and_ddp(tmp_path, devices4):
    """Saved under zero2 (float32 shards over dp), restored under ddp (whole
    on every replica) and back: the same global float32 arrays."""
    cfg = tiny_llama()
    zero2, _, params = train(cfg, layout("zero2"), devices4, steps=1)
    ddp = construct_hybrid_parallel_model(cfg, layout("ddp"), devices4)
    want = jax.tree.map(np.asarray, params)

    ckpt.save_checkpoint(str(tmp_path / "a"), 1, params, None, hp=zero2.hp)
    as_ddp, _, _ = ckpt.load_checkpoint(str(tmp_path / "a"), 1, target=ddp, tx=None)
    for leaf, sh in zip(jax.tree.leaves(as_ddp), jax.tree.leaves(ddp.shardings())):
        assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
    ckpt.save_checkpoint(str(tmp_path / "b"), 1, as_ddp, None, hp=ddp.hp)
    back, _, _ = ckpt.load_checkpoint(str(tmp_path / "b"), 1, target=zero2, tx=None)
    for got, a, b, sh in zip(jax.tree.leaves(back), jax.tree.leaves(as_ddp), jax.tree.leaves(want),
                             jax.tree.leaves(zero2.shardings())):
        assert got.sharding.is_equivalent_to(sh, got.ndim)
        np.testing.assert_array_equal(np.asarray(got), b)
        np.testing.assert_array_equal(np.asarray(a), b)


# ---------------------------------------------- which leaves: every family's rule
# primitives that hand a leaf on as it is (a view, a slice of a stacked leaf,
# a constraint): the value that comes out is still the leaf's
VIEWS = {"reshape", "transpose", "squeeze", "slice", "dynamic_slice", "broadcast_in_dim",
         "expand_dims", "copy", "copy_p", "sharding_constraint", "rev", "optimization_barrier"}
JAXPRS = (jcore.Jaxpr, jcore.ClosedJaxpr)


def reads(jaxpr, tracked, wide, uses):
    """Walk `jaxpr` and every jaxpr nested in it. `tracked` maps a variable
    that holds parameter leaves (one, or the layers' stacked into one) to
    their paths; `wide` collects the leaves a `convert_element_type` widens,
    `uses` counts the places that read a leaf. A loop's constant and an
    operand of a manual region count once more: the loop sums the leaf's
    cotangents over its iterations, the region's boundary over the devices,
    in the dtype the leaf has there, as those of two uses are summed."""
    def count(paths, n=1):
        for path in paths:
            uses[path] = uses.get(path, 0) + n

    for eqn in jaxpr.eqns:
        held = {i: tracked[v] for i, v in enumerate(eqn.invars)
                if not isinstance(v, jcore.Literal) and v in tracked}
        if not held:
            continue
        name = eqn.primitive.name
        if name in VIEWS:
            if 0 in held:
                tracked[eqn.outvars[0]] = held[0]
            continue
        if name == "custom_vjp_call" and len(eqn.invars) == 1 and all(
                e.primitive.name in VIEWS for e in eqn.params["call_jaxpr"].eqns):
            # an identity that says where the cotangent lies (`spec.constrain_grad_as`
            # on a scanned run's stacked leaf): the leaf goes on as it is, read
            # at no more places and no wider
            tracked[eqn.outvars[0]] = held[0]
            continue
        if name == "concatenate":  # `jnp.stack` of a run's layers
            tracked[eqn.outvars[0]] = frozenset().union(*held.values())
            continue
        inner = [v for v in eqn.params.values() if isinstance(v, JAXPRS)]
        inner += [b for v in eqn.params.values() if isinstance(v, (tuple, list))
                  for b in v if isinstance(b, JAXPRS)]
        if not inner:
            count(p for paths in held.values() for p in paths)
            if name == "convert_element_type" and (
                    jnp.dtype(eqn.params["new_dtype"]).itemsize > eqn.invars[0].aval.dtype.itemsize):
                wide.update(held[0])
            continue
        consts = {"scan": eqn.params.get("num_consts", 0),
                  "while": eqn.params.get("cond_nconsts", 0) + eqn.params.get("body_nconsts", 0),
                  "shard_map": len(eqn.invars)}
        count(p for i, paths in held.items() if i < consts.get(name, 0) for p in paths)
        for sub in inner:
            sub = sub.jaxpr if isinstance(sub, jcore.ClosedJaxpr) else sub
            skip = len(eqn.invars) - len(sub.invars)  # cond's index, while's cond consts
            assert skip == 0 or (skip > 0 and name in ("cond", "while")), (name, skip)
            reads(sub, {sub.invars[i - skip]: p for i, p in held.items() if i >= skip}, wide, uses)
    return wide, uses


def traced_reads(model, params, batch):
    closed = jax.make_jaxpr(model.loss_fn)(params, batch)
    paths = list(leaf_paths(params))
    tracked = {v: frozenset([p]) for v, p in zip(closed.jaxpr.invars, paths)}
    return reads(closed.jaxpr, tracked, set(), {})


def lm(cfg, hp, devices):
    return construct_hybrid_parallel_model(cfg, hp, devices), lm_batch()


def family(name, devices):
    """(model, batch) of a family at tiny widths under dp with ZeRO-2."""
    if name == "gpt_vocab_tp2":  # tied table, looked up under vocab_tp: stored twice over
        return lm(gpt_config("gpt-0.3b", num_layers=2, hidden_size=64, num_heads=4, vocab_size=V,
                             max_seq_len=S, compute_dtype=BF16), layout("zero2"), devices)
    if name == "gpt":  # tied table, whole: `wte.astype(dtype)` twice, so stored
        return lm(gpt_config("gpt-0.3b", num_layers=2, hidden_size=64, num_heads=4, vocab_size=V,
                             max_seq_len=S, compute_dtype=BF16), layout("zero2", vocab_tp=1), devices)
    if name == "llama_qwen":  # qkv bias, untied head, RMSNorm
        return lm(tiny_llama(qkv_bias=True), layout("zero2"), devices)
    if name == "llama_whole_table":  # untied, not split: the one copied table
        return lm(tiny_llama(), layout("zero2", vocab_tp=1), devices)
    if name == "bert":
        cfg = bert_config("bert-base", num_layers=2, hidden_size=64, num_heads=4, ffn_hidden=128,
                          vocab_size=V, max_seq_len=S, compute_dtype=BF16)
        return lm(cfg, layout("zero2", vocab_tp=1), devices)
    if name == "vit":
        cfg = vit_config("vit-base", hidden_size=64, num_heads=4, num_layers=2, ffn_hidden=128,
                         image_size=32, patch_size=8, num_classes=10, compute_dtype=BF16)
        m = construct_hybrid_parallel_model(cfg, layout("zero2", vocab_tp=1), devices)
        return m, dict(pixels=jnp.zeros((B, 32, 32, 3)), labels=jnp.zeros((B,), jnp.int32))
    if name == "olmoe":  # experts have no tp form: dp 4
        cfg = olmoe_config("olmoe-1b-7b", hidden_size=64, num_heads=4, num_kv_heads=4, head_dim=16,
                           ffn_hidden=32, num_layers=2, vocab_size=V, max_seq_len=S,
                           num_experts=8, experts_per_token=2, compute_dtype=BF16)
        return lm(cfg, layout("zero2", tp=1), devices)
    if name == "t5":
        cfg = t5_config("t5-base", hidden_size=64, num_heads=4, head_dim=16, ffn_hidden=128,
                        num_enc_layers=2, num_dec_layers=2, vocab_size=V, compute_dtype=BF16)
        m = construct_t5_model(cfg, layout("zero2", layers=4))
        tok = jnp.zeros((B, 16), jnp.int32)
        return m, dict(tokens=tok, dec_tokens=tok, labels=tok)
    if name == "swin":
        cfg = swin_config("swin-tiny", embed_dim=16, depths=(2, 2), num_heads=(2, 4), image_size=32,
                          patch_size=4, window=4, mlp_ratio=2.0, num_classes=10, compute_dtype=BF16)
        m = construct_swin_model(cfg, layout("zero2", layers=4, vocab_tp=1))
        return m, dict(pixels=jnp.zeros((B, 32, 32, 3)), labels=jnp.zeros((B,), jnp.int32))
    raise KeyError(name)


FAMILIES = ["gpt", "gpt_vocab_tp2", "llama_qwen", "llama_whole_table", "bert", "vit", "olmoe", "t5", "swin"]


@pytest.mark.parametrize("name", FAMILIES)
def test_the_leaf_rule_holds_for_the_family(name, devices4):
    """`parallel/spec.cast_first_tree` against the family's own loss, traced
    with EVERY leaf handed over in bf16. The leaves the rule keeps off the
    copy must be exactly those the loss widens again (read in float32: norm
    scales and biases, the router, relative-position tables), reads at more
    than one place (a tied table: the cotangents of its uses are summed
    wide), or hands to a manual region (the experts' kernels, the table
    `vocab_parallel_lookup` gathers from: the region's boundary sums their
    cotangents over dp in the stored dtype). So no copied leaf is ever
    widened back to float32, and none has its gradient summed narrower."""
    m, batch = family(name, devices4)
    assert m.mesh.devices.size == 4
    cast_first, copied = leaf_paths(m.cast_first), leaf_paths(m.copied_leaves())
    assert any(copied.values()) and all(cast_first[p] for p, c in copied.items() if c)

    narrow = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, BF16), m.abstract_params())
    wide, uses = traced_reads(m, narrow, batch)
    assert set(uses) == set(cast_first), set(cast_first) - set(uses)  # every leaf was followed
    stored = {p for p, c in cast_first.items() if not c}
    found = wide | {p for p, n in uses.items() if n > 1}
    assert found == stored, (sorted(found - stored), sorted(stored - found))
    assert any("scale" in p for p in wide)
    assert (TABLE in stored) == (name in ("gpt", "gpt_vocab_tp2", "llama_qwen", "bert", "t5")), name
    if name == "olmoe":
        assert any("router" in p for p in wide)
        assert all(uses[p] > 1 for p in uses if "['wi']" in p or "['wo_mlp']" in p)
    if name in ("t5", "swin"):
        assert any("rel_bias" in p for p in wide)

    # and with the step's own copy in place the loss widens no copied leaf
    as_read = jax.eval_shape(m.compute_params, m.abstract_params())
    assert {leaf.dtype for p, leaf in leaf_paths(as_read).items() if copied[p]} == {jnp.dtype(BF16)}
    wide, uses = traced_reads(m, as_read, batch)
    assert not {p for p in wide if copied[p]}, wide
    assert {uses[p] for p, c in copied.items() if c} == {1}
