"""The stack: a model is (config, params-pytree, pure functions) built of the
parts in `models/parts`.

This is the TPU-native analogue of the reference's model-integration layer
(`<M>Model_tensor_parallel.py` + `<M>Model_sequential.py`, e.g.
galvatron/models/gpt_hf/GPTModel_tensor_parallel.py:84-132 and
GPTModel_sequential.py:201-248). Where the reference rewrites HF modules into
Megatron ParallelAttention/ParallelMLP with per-layer NCCL groups, here the
per-layer parallel strategy enters only through PartitionSpecs
(parallel/spec.py) and sharding constraints at layer boundaries.

A layer is a token mixer and an MLP half, each behind its norm, two entries of the
tables `MIXERS` and `MLP_HALVES` (models/parts; a layer of ONE half has the
absent entry for the other, which gets no norm and is not run): this module initialises,
runs and lays out the layers, the embedding before them and the head and the
losses after them (the multi-token-prediction module among those), and names
no part. The config is `models/config.TransformerConfig`."""

from __future__ import annotations

import contextlib
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from galvatron_tpu.analysis import diagnostics as D
from galvatron_tpu.config.strategy import (
    HybridParallelConfig,
    LayerRun,
    LayerStrategy,
    layer_runs,
    model_layer_kinds,
)
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts import COUNTERS, MIXERS, MLP_HALVES, hyper, loop, unsupported_reason
from galvatron_tpu.models.parts.common import LayerPart, Params, _dense, _dense_init, _norm, _norm_params
from galvatron_tpu.models.parts.embed_head import (embed_patches, embed_tokens, head_logits, model_head,
                                                   next_tokens_cross_entropy, softmax_nll, token_cross_entropies,
                                                   vocab_parallel_cross_entropy)
from galvatron_tpu.models.parts.mlp import ROUTER_BIAS, grad_as_stored
from galvatron_tpu.obs import forms, tracing
from galvatron_tpu.ops.kernels import KernelSharding
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import LayerAxes, layer_axes, vocab_axes


def refuse_unsupported(cfg, hp=None, asker=None, autotune=None) -> None:
    """Raise GLS018 where a part of `cfg` has no form under this layout, mode or tool (`unsupported_reason`)."""
    from galvatron_tpu.parallel.quant_collectives import wants_quant_comm

    reason = unsupported_reason(cfg, hp, asker, autotune, quant=wants_quant_comm(hp))
    if reason is not None:
        raise D.DiagnosticError([D.make("GLS018", reason)])


def _halves(cfg: TransformerConfig) -> Tuple[Tuple[str, LayerPart], ...]:
    """(the norm's name, the table's entry) of each half ONE layer's config has, the mixer's first: both, or
    of a layer of one half (the other `absent`) the one."""
    return tuple((norm, half) for norm, half in (("ln1", MIXERS[cfg.mixer]), ("ln2", MLP_HALVES[cfg.mlp_half]))
                 if not half.absent)


# ===================================================================== init
def init_layer_params(rng: jax.Array, cfg: TransformerConfig, index: int = 0) -> Params:
    """One layer's tree: its token mixer's leaves (`MIXERS`) and its MLP
    half's (`MLP_HALVES`), and a norm for each of the two that is there
    (`_halves`). `index`: the layer's PUBLISHED index,
    for the leaves a part sets from the layer's place in the stack
    (`LayerPart.place`: differential attention's `lambda_init`; no other)."""
    ks = jax.random.split(rng, 5)
    p: Params = {norm: _norm_params(cfg) for norm, _ in _halves(cfg)}
    if cfg.post_norm:  # HF's `input_layernorm_2`, `post_attention_layernorm_2` (names assumed)
        p.update({norm + "_post": _norm_params(cfg) for norm, _ in _halves(cfg)})
    p.update(MIXERS[cfg.mixer].init(ks, cfg))
    p.update(MLP_HALVES[cfg.mlp_half].init(ks, cfg))
    p.update(hyper.init_layer(jax.random.fold_in(rng, 5), cfg))  # (nothing for one residual stream)
    return MIXERS[cfg.mixer].place(p, cfg, index)


def init_model_params(rng: jax.Array, cfg: TransformerConfig) -> Params:
    n = cfg.num_layers
    h = cfg.hidden_size
    ks = jax.random.split(rng, n + 6)
    if cfg.input_type == "patches":
        patch_dim = cfg.patch_size * cfg.patch_size * cfg.num_channels
        embed: Params = {
            "patch": {
                "kernel": _dense_init(ks[0], (patch_dim, h), cfg.init_std, cfg.param_dtype),
                "bias": jnp.zeros((h,), cfg.param_dtype),
            },
            "wpe": _dense_init(ks[1], (cfg.max_seq_len, h), cfg.init_std, cfg.param_dtype),
        }
        if cfg.use_cls_token:
            embed["cls_token"] = jnp.zeros((h,), cfg.param_dtype)
    else:
        embed = {"wte": _dense_init(ks[0], (cfg.vocab_size, h), cfg.init_std, cfg.param_dtype)}
        if cfg.position_type == "learned":
            embed["wpe"] = _dense_init(ks[1], (cfg.max_seq_len, h), cfg.init_std, cfg.param_dtype)
        if cfg.type_vocab_size:
            embed["tte"] = _dense_init(ks[n + 3], (cfg.type_vocab_size, h), cfg.init_std, cfg.param_dtype)
    if cfg.embed_norm:
        embed["norm"] = _norm_params(cfg)
    params: Params = {
        "embed": embed,
        "layers": [init_layer_params(ks[2 + i], cfg.layer_config(kind), index)
                   for i, (kind, index) in enumerate(zip(cfg.layer_kinds(), cfg.published_indices()))],
    }
    if cfg.mtp_layers:
        # HF's names: enorm, hnorm, eh_proj, the block, shared_head.norm; the
        # embedding and the head are the model's own
        km = jax.random.split(jax.random.fold_in(rng, n), 2)
        params["mtp"] = {
            "enorm": _norm_params(cfg), "hnorm": _norm_params(cfg),
            "eh_proj": {"kernel": _dense_init(km[0], (2 * h, h), cfg.init_std, cfg.param_dtype)},
            "block": init_layer_params(km[1], cfg.layer_config(cfg.layer_kinds()[-1])),
            "norm": _norm_params(cfg),
        }
    # post-LN models (BERT) normalise inside each block; no final norm
    if cfg.pre_norm:
        params["final_norm"] = _norm_params(cfg)
    if cfg.exit_gate:
        params["exit_gate"] = loop.init_exit_gate(jax.random.fold_in(rng, n + 1), cfg)
    if cfg.head_type == "classification":
        params["head"] = {
            "kernel": _dense_init(ks[n + 4], (h, cfg.num_classes), cfg.init_std, cfg.param_dtype),
            "bias": jnp.zeros((cfg.num_classes,), cfg.param_dtype),
        }
    elif cfg.head_type == "mlm":
        params["head"] = {
            "transform": {
                "kernel": _dense_init(ks[n + 5], (h, h), cfg.init_std, cfg.param_dtype),
                "bias": jnp.zeros((h,), cfg.param_dtype),
            },
            "norm": _norm_params(cfg),
            "bias": jnp.zeros((cfg.vocab_size,), cfg.param_dtype),
        }
    if cfg.head_type in ("lm", "mlm") and not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": _dense_init(ks[n + 2], (h, cfg.pred_heads * cfg.vocab_size), cfg.init_std, cfg.param_dtype)
        }
    return params


# ============================================================== layer forward
def layer_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    *,
    mesh: Optional[Mesh] = None,
    axes: Optional[LayerAxes] = None,
    attn_bias: Optional[jax.Array] = None,
    return_kv: bool = False,
    attn_sharding: Optional[KernelSharding] = None,
    shared: Optional[Dict[str, jax.Array]] = None,
    publish: Tuple[str, ...] = (),
):
    """One transformer block on (B, S_local, H) activations: x + Mixer(norm
    x), then + MLP(norm x), the two halves `MIXERS[cfg.mixer]`'s and
    `MLP_HALVES[cfg.mlp_half]`'s; a layer of ONE half (`_halves`) is the one
    of the two that is there. With ``cfg.post_norm`` (sandwich norms) a
    half's OUTPUT is normed by a norm of its own (`ln1_post`, `ln2_post`,
    under `gt.norm.post`) before it joins the stream: x + norm(Mixer(norm x)).
    With ``cfg.hc_mult`` = n > 1 (hyper-connections, `parts/hyper.py`) x is the
    n residual streams side by side, (B, S_local, n H): a half reads ONE vector
    out of them, `Mixer(norm(read(x)))`, and writes `H_res x + H_post o` back,
    its coefficients a token from x itself (all under `gt.hc`).

    Under GSPMD the parallel form is implied by weight shardings plus the
    activation constraints here and in the mixer.

    ``return_kv`` additionally returns this layer's post-rope (k, v)
    projections — the serving prefill's cache-write side outputs
    (serve/engine.py). Unsupported under ring context parallelism, whose
    blockwise k/v never materialise per-layer.

    ``attn_sharding`` is the kernels' layout for callers that run this body
    with ``mesh=None`` under their own mapping (the GPipe stage vmap); with a
    mesh and axes it is derived here.

    A config with ``layer_aux`` (a part of it hands back counters) returns
    ``(x, aux)``: the block's output, and its router's auxiliary terms
    (ops/moe.py) and its mixer's counters in one dict.

    A layer's input is the residual stream and, for a few mixers, named
    tensors EARLIER layers published (`TransformerConfig.shared`): ``shared``
    is handed to a mixer that reads (`LayerPart.reads`), and a mixer asked to
    ``publish`` names hands them back, so that the layer returns one value
    more, ``{name: tensor}``, after the others. Neither is given to any
    other part, whose call is what it always was."""
    if return_kv:
        refuse_unsupported(cfg, asker="serve")
    if mesh is not None and axes is not None:
        attn_sharding = KernelSharding.for_layer(mesh, axes)
    kv_out, aux, published = None, {}, {}
    col_errs = []  # hyper-connections' counter, a half
    for norm, half in _halves(cfg):
        residual = x
        if cfg.hc_mult > 1:
            with jax.named_scope(tracing.HC):
                mix, col_err = hyper.coefficients(p[hyper.LEAVES[norm]], x, cfg)
                x = hyper.read(mix, x)
            col_errs.append(col_err)
        y = _norm(x, p[norm], cfg) if cfg.pre_norm else x
        how = {"shared": shared} if half.reads else {}
        if publish and half.publishes:
            how["publish"] = publish
        o, kv, said, *handed = half.forward(p, y, positions, cfg, mesh=mesh, axes=axes, attn_bias=attn_bias,
                                            attn_sharding=attn_sharding, return_kv=return_kv, **how)
        for names in handed:
            published.update(names)
        kv_out = kv_out or kv
        aux = {**(said or {}), **aux}  # the MLP half's terms before the mixer's
        if mesh is not None and axes is not None:
            o = S.constrain(o, mesh, S.act_spec(axes))
        if cfg.post_norm:
            with jax.named_scope(tracing.NORM_POST):
                o = _norm(o, p[norm + "_post"], cfg)
        if cfg.residual_multiplier != 1.0:
            o = o * cfg.residual_multiplier
        if cfg.hc_mult > 1:
            with jax.named_scope(tracing.HC):
                x = hyper.write(mix, residual, o)
        else:
            x = residual + o
        if not cfg.pre_norm:
            x = _norm(x, p[norm], cfg)
    if return_kv:
        return x, kv_out
    if col_errs:
        aux = {**aux, **hyper.counters(col_errs)}
    out = (x, aux) if cfg.layer_aux else (x,)
    if publish:
        out += (published,)
    return out if len(out) > 1 else x


def decode_layer_forward(
    p: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    *,
    k_cache: jax.Array,
    v_cache: jax.Array,
    write_index: jax.Array,
    mesh: Optional[Mesh] = None,
    axes: Optional[LayerAxes] = None,
    attn_bias: Optional[jax.Array] = None,
):
    """One transformer block for single-token decode over a preallocated KV
    cache. ``x``: (B, 1, H) — one new token per cache slot; ``k_cache`` /
    ``v_cache``: (B, S_cache, nkv, hd); ``write_index``: (B,) int32, the new
    token's position per slot. The mixer's `decode` appends this token's k/v
    at ``write_index`` and attends against the updated cache
    (parts/attention.attention_decode). Every other op mirrors
    ``layer_forward`` exactly, so incremental decode reproduces the
    full-forward logits within float tolerance
    (tests/serve/test_decode_parity.py)."""
    refuse_unsupported(cfg, asker="serve")
    sharded = mesh is not None and axes is not None

    residual = x
    y = _norm(x, p["ln1"], cfg) if cfg.pre_norm else x
    o, k_cache, v_cache = MIXERS[cfg.mixer].decode(
        p, y, positions, cfg, k_cache=k_cache, v_cache=v_cache, write_index=write_index,
        mesh=mesh, axes=axes, attn_bias=attn_bias)
    if sharded:
        o = S.constrain(o, mesh, P(S._ax(axes.batch_axes), None, None))
    x = residual + o
    if not cfg.pre_norm:
        x = _norm(x, p["ln1"], cfg)

    residual = x
    y = _norm(x, p["ln2"], cfg) if cfg.pre_norm else x
    out, _, _ = MLP_HALVES[cfg.mlp_half].forward(p, y, positions, cfg)
    if sharded:
        out = S.constrain(out, mesh, P(S._ax(axes.batch_axes), None, None))
    x = residual + out
    if not cfg.pre_norm:
        x = _norm(x, p["ln2"], cfg)
    return x, k_cache, v_cache


# ------------------------------------------------- scan-over-layer-runs
def _layer_fwd_fn(cfg, hp, mesh, axes, attn_bias, strategy):
    """The per-layer forward for one run: the GSPMD `layer_forward` by
    default; under ``tp_comm_mode in (shard_map, overlap)`` the manual
    shard_map path (parallel/tp_shard_map.py) for layers that actually have
    TP collectives — refusing loudly (GLS012) on configs it cannot express.
    tp=1 layers have no TP collectives and compile to the identical GSPMD
    program either way (the linter warns that the knob is inert)."""
    from galvatron_tpu.parallel import tp_shard_map as T

    if T.wants_manual_tp(hp, axes):
        # refusal is per-run at trace time; the train driver's lint_hp pass
        # reports the same GLS012 before any tracing
        T.assert_manual_tp_supported(cfg, hp, strategy)
        return partial(T.manual_layer_forward, cfg=cfg, mesh=mesh, axes=axes,
                       hp=hp, attn_bias=attn_bias, mode=hp.tp_comm_mode)
    return partial(layer_forward, cfg=cfg, mesh=mesh, axes=axes,
                   attn_bias=attn_bias)


def _remat(fn, policy: str):
    """jax.checkpoint with the configured saveable policy. "full" (and the
    caller-filtered "none") is jax.checkpoint's default — save nothing,
    rematerialise everything; the other names select the matching
    jax.checkpoint_policies member."""
    if policy in ("full", "none"):
        return jax.checkpoint(fn)
    from jax import checkpoint_policies as _policies

    return jax.checkpoint(fn, policy=getattr(_policies, policy))


def stack_layer_run(layer_params: List[Params]) -> Params:
    """Stack a run's per-layer param trees along a new leading layer axis.

    `jnp.stack` (expand_dims per layer + one concatenate along the NEW,
    never-sharded axis) and not the cheaper concatenate-then-reshape trick:
    reshape-splitting a dim that is tp-sharded (the row-parallel `wo` /
    `wo_mlp` kernels, P(tp, ...)) MISCOMPILED in the GSPMD partitioner
    inside a scan (WA004; seen on jax 0.4.37 XLA:CPU, not ruled out on the
    installed jax) — silently wrong layer outputs, not an error. The
    per-layer expand_dims are pure layout equations; XLA
    compile time stays governed by the per-RUN body, which is what the
    trace-cost test asserts (tests/models/test_scan_layers.py)."""
    if len(layer_params) == 1:
        return jax.tree.map(lambda t: t[None], layer_params[0])
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layer_params)


def stacked_layer_param_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    """layer_param_specs with an unsharded leading layer axis, matching
    stack_layer_run's layout (every layer of the run shares `axes`, so the
    per-layer spec is prefix-extended verbatim)."""
    return jax.tree.map(
        lambda sp: P(None, *sp), layer_param_specs(cfg, axes),
        is_leaf=lambda t: isinstance(t, P),
    )


def stacked_layer_grad_specs(cfg: TransformerConfig, axes: LayerAxes, stacked: Params, mesh: Mesh) -> Params:
    """The specs of a run's stacked COTANGENT where ZeRO may split the state:
    each layer's leaf as `spec.zero_split_spec` lays its gradient out over the
    run's ZeRO axes (what runtime/model_api.grad_accum_specs gives the same
    leaf, by the same two functions), behind the unsharded layer axis.
    `stacked`: the run's stacked leaves, for their shapes. A leaf ZeRO does
    not split further (ddp, dp = 1, a ZeRO-3 leaf, one no dim of which
    divides) gets `stacked_layer_param_specs`' spec back, equal to it."""
    zax, mesh_shape = S.zero_axes(axes), dict(mesh.shape)
    return jax.tree.map(
        lambda sp, t: P(None, *S.zero_split_spec(sp, t.shape[1:], zax, mesh_shape)),
        layer_param_specs(cfg, axes), stacked, is_leaf=lambda t: isinstance(t, P),
    )


def _at(tree: Params, path: Tuple[str, ...], fn) -> Params:
    """`tree` with `fn` applied to the leaf at `path`; the rest shared."""
    return {**tree, path[0]: _at(tree[path[0]], path[1:], fn) if path[1:] else fn(tree[path[0]])}


def _gated_grads_as_stored(layers: List[Params], runs, scanned, cfg: TransformerConfig,
                           mesh: Optional[Mesh]) -> List[Params]:
    """The layers with their gated kernels (`LayerPart.gated_kernels`: a dense
    SwiGLU half's up kernel) read through `parts/mlp.grad_as_stored`, where the
    stack observes that it pays: on a TPU (read off the mesh), in a model SOME
    run of which holds such a kernel and is not scanned. That layer's gradient
    reaches the update straight from the backward's matmul, in the matmul's
    tiling, and the compiler then moves the STATE of every leaf of that shape
    into it and back, the scanned layers' too (Granite's one attention layer
    among nine scanned ones: 60 copies). Where every such run is scanned (the
    Qwen cells) the compiler lays the scan's gradient buffer out after the
    state by itself, and the program stays as it is. All of a model's gated
    kernels or none; each is said to `obs/forms` (`GATED_KERNEL_GRADS`), by layer
    and path, as it is traced."""
    kinds = cfg.layer_kinds()

    def gated(run):  # a run is of one kind
        lcfg = cfg.layer_config(kinds[run.start])
        return MLP_HALVES[lcfg.mlp_half].gated_kernels(lcfg)

    if mesh is None or mesh.devices.flat[0].platform != "tpu" or not any(
            gated(run) for run in runs if not scanned(run)):
        return layers
    layers = list(layers)
    for run in runs:
        held_in = None if scanned(run) else cfg.compute_dtype
        for path in gated(run):
            for i in run.layer_indices:
                layers[i] = _at(layers[i], path, partial(grad_as_stored, held_in=held_in))
                forms.took(forms.GATED_KERNEL_GRADS, "as_stored", key=(i,) + path)
    return layers


def run_layers(
    params: Params,
    x: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
    attn_bias: Optional[jax.Array] = None,
    scan: Optional[bool] = None,
    collect_kv: bool = False,
    zero_splits_state: bool = False,
):
    """The encoder stack with per-layer sharding constraints and remat.

    Layers are partitioned into maximal same-strategy runs
    (config/strategy.layer_runs); each run of length >= 2 executes as ONE
    `jax.lax.scan` over weight-stacked params, so trace size and XLA compile
    time are proportional to the number of DISTINCT strategies, not to
    depth. Strategy boundaries and length-1 runs fall back to the unrolled
    per-layer path; `scan=False` (or `hp.scan_layers=False`, the
    `--no_scan_layers` escape hatch) unrolls everything, reproducing the
    pre-scan trace exactly.

    ``collect_kv=True`` (the serving prefill, serve/engine.py) additionally
    returns one post-rope (k, v) pair per layer, in layer order — scan runs
    emit them as stacked side outputs of the SAME scan, so prefill keeps the
    depth-constant trace. The collecting path is GSPMD-only and forward-only
    (no manual-TP shard_map body, no remat): serve lints away the layouts
    that would need either.

    ``zero_splits_state``: the caller's answer to
    `runtime/model_api.HybridParallelModel._zero_splits_state`, the ONE
    predicate for "ZeRO's dp axes may split a leaf of the state further than
    `param_specs` does" (not under pp > 1, the manual TP path, the quantized
    sync, the 1F1B engines or a custom loss, whose code sums a leaf's
    gradient itself in `param_specs`' layout; whoever calls without a model
    has no split state). Where it holds, a scanned run reads its stacked
    leaves through `spec.constrain_grad_as`: the forward's constraint as
    ever, the cotangent's to `stacked_layer_grad_specs`, so that the scan's
    body ends a leaf's sum over dp in the shards the step accumulates
    (a reduce-scatter a layer) and not whole on every chip (an all-reduce of
    which `to_accum` keeps a slice). Every leaf of the run alike, a routed
    block's experts' kernels too (`spec.cast_first_tree`'s `routed` leaves):
    `ops/moe.moe_ffn`'s manual region has summed their cotangents over dp at
    its boundary, whole, and the constraint is then the slice `to_accum`
    would take after the scan, taken a layer earlier.

    ``hp.narrow_scan_grads`` (set by the launch where the state and the
    float32 stacks would leave the device too little for the rest of the
    step, `runtime/model_api.scan_stacks_are_tight`; no flag): a scanned run casts the leaves it reads through a cast
    (`spec.cast_first_tree`) to the compute dtype BEFORE stacking them. The
    forward reads the same values; the scan's backward stacks the cotangents
    as the matmuls yield them, in the compute dtype (what an unrolled layer
    hands the update), where it otherwise stacks them widened: half the bytes
    of the one array of the step that is as large as the float32 parameters.

    A config with ``layer_aux`` returns ``(x, auxs)``, the layers' auxiliary
    terms (the routers', the linear mixers' counters) a layer or a scanned
    run, for `_fold_aux`. Any other config carries nothing and traces what
    it did.

    **What layers publish** (`TransformerConfig.shared`: a Mamba-1 layer's
    memory, a full differential layer's keys and values) is carried beside
    ``x`` in a dict that is empty for every config none of whose mixers
    reads, which therefore traces the step it did. A layer that publishes a
    name a later layer reads is a run of its own (`config/strategy.
    model_layer_kinds` keys it apart from a plain layer of its kind), never scanned (its
    tensors are outputs of ONE layer, and of its `jax.checkpoint` where it is
    rematerialised: saved, not recomputed by each reader); a scanned run of
    readers closes over the dict, a constant of the scan whose cotangent the
    scan sums over its layers."""
    use_hp = hp is not None and mesh is not None
    refuse_unsupported(cfg, hp)  # GLS018, for whoever comes here past construct_hybrid_parallel_model
    layers = params["layers"]
    if scan is None:
        scan = hp.scan_layers if hp is not None else True
    kvs: List[Tuple[jax.Array, jax.Array]] = []
    auxs: List[Dict[str, jax.Array]] = []  # routed: a layer's, or a scanned run's stacked

    kinds = cfg.layer_kinds()
    halves = [MIXERS[m] for m in cfg.mixers()] + [MLP_HALVES[h] for h in cfg.mlp_halves()]
    absent = sum(half.absent for half in halves)
    if absent:  # a model with layers of ONE half
        forms.took(forms.HALVES, "%d of %d" % (len(halves) - absent, len(halves)), key="halves")
    shares = cfg.shared()  # a layer: (the names it hands on, the names it reads)
    shared: Dict[str, jax.Array] = {}  # the latest of each name published so far
    if use_hp:
        runs = layer_runs(hp, model_layer_kinds(cfg))
    else:
        # no strategy info: one homogeneous run a kind of layer
        keys = model_layer_kinds(cfg) or kinds  # (a layer that publishes: a run of its own)
        starts = [i for i in range(len(layers)) if i == 0 or keys[i] != keys[i - 1]]
        runs = [LayerRun(start=a, stop=b, strategy=LayerStrategy())
                for a, b in zip(starts, starts[1:] + [len(layers)])]

    def scanned(run):
        return scan and run.length >= 2  # (a layer that hands a tensor on is a run of ONE: `model_layer_kinds`)

    def read_by(i):  # the call's share of `shared`, for a layer that reads
        return {"shared": {name: shared[name] for name in shares[i][1]}} if shares[i][1] else {}

    layers = _gated_grads_as_stored(layers, runs, scanned, cfg, mesh if use_hp else None)

    def unrolled(x, indices):
        for i in indices:
            lp = layers[i]
            lcfg = cfg.layer_config(kinds[i])
            axes = layer_axes(hp, i) if use_hp else None
            if use_hp:
                x = S.constrain(x, mesh, S.act_spec(axes))
            if collect_kv:
                x, kv = layer_forward(
                    lp, x, positions, lcfg, mesh=mesh, axes=axes,
                    attn_bias=attn_bias, return_kv=True,
                )
                kvs.append(kv)
                continue
            fwd = _layer_fwd_fn(lcfg, hp if use_hp else None, mesh, axes,
                                attn_bias, hp.layers[i] if use_hp else None)
            # the per-layer serialized policy decides (checkpoint=1 layers
            # default to "full"); the global --remat_policy flag was folded
            # in at construction (config/strategy precedence rule)
            publish = shares[i][0]
            if publish:  # static, so bound before the layer is wrapped for recomputation
                fwd = partial(fwd, publish=publish)
            if use_hp:
                pol = hp.layers[i].effective_remat_policy
                if pol != "none":
                    fwd = _remat(fwd, pol)
            x = fwd(lp, x, positions, **read_by(i))
            if publish:
                *x, handed = x
                x = x[0] if len(x) == 1 else tuple(x)
                shared.update(handed)
                if cfg.layer_aux:  # the step's counter: what outlives its layer, in MiB
                    auxs.append({"published_mib": jnp.float32(
                        sum(t.size * t.dtype.itemsize for t in handed.values()) / 2 ** 20)})
            if lcfg.layer_aux:
                x, aux = x
                auxs.append(aux)
        return x

    def one_run(x, run, k):
        if not scanned(run):
            return unrolled(x, run.layer_indices)
        lcfg = cfg.layer_config(kinds[run.start])  # a run is of one kind
        axes = layer_axes(hp, run.start) if use_hp else None
        members = [layers[i] for i in run.layer_indices]
        if use_hp and hp.narrow_scan_grads:
            # a layer at a time, before the stack: the cotangent is then sliced and widened a layer at a time
            # too, and no float32 stack stands beside the state (the body's own cast of such a leaf is the identity)
            cast_first = S.cast_first_tree(layer_param_specs(lcfg, axes), table_stored=False)
            members = [jax.tree.map(lambda first, t: t.astype(lcfg.compute_dtype) if first else t, cast_first, lp)
                       for lp in members]
            forms.took(forms.SCAN_GRADS, "compute_dtype", key=(k, "narrow"))
        stacked = stack_layer_run(members)
        if use_hp:
            read_as = stacked_layer_param_specs(lcfg, axes)
            # the gradient where ZeRO keeps it, or (no split state) where the
            # forward reads the leaf: the plain constraint
            summed_as = stacked_layer_grad_specs(lcfg, axes, stacked, mesh) if zero_splits_state else read_as

            def constrained(path, t, sp, grad_sp):
                if sp != grad_sp:
                    # (what the compiler made of the request is the compiled step's to say: obs/compiled.dp_grad_sums_mb)
                    forms.took(forms.SCAN_GRADS, "zero_layout", key=(k, jax.tree_util.keystr(path)))
                return S.constrain_grad_as(t, mesh, sp, grad_sp)

            stacked = jax.tree_util.tree_map_with_path(constrained, stacked, read_as, summed_as)
        if collect_kv:
            body = partial(layer_forward, cfg=lcfg, mesh=mesh, axes=axes,
                           attn_bias=attn_bias, return_kv=True)

            def step_kv(carry, lp, _body=body, _axes=axes):
                if use_hp:
                    carry = S.constrain(carry, mesh, S.act_spec(_axes))
                out, kv = _body(lp, carry, positions)
                return out, kv

            x, kv_stacked = jax.lax.scan(step_kv, x, stacked)
            for j in range(run.length):
                kvs.append(jax.tree.map(lambda t, _j=j: t[_j], kv_stacked))
            return x
        body = _layer_fwd_fn(lcfg, hp if use_hp else None, mesh, axes,
                             attn_bias, run.strategy if use_hp else None)
        if use_hp:
            # a run is maximal over (axes, effective policy, stage, kind) —
            # config/strategy.layer_runs splits on differing remat_policy
            # exactly like the checkpoint flag, so one policy wraps the
            # whole scanned body
            run_pol = run.strategy.effective_remat_policy
            if run_pol != "none":
                body = _remat(body, run_pol)

        reads = read_by(run.start)  # a run is of one kind: every layer of it reads the same

        def step(carry, lp, _body=body, _axes=axes):
            if use_hp:
                carry = S.constrain(carry, mesh, S.act_spec(_axes))
            out = _body(lp, carry, positions, **reads)
            return out if lcfg.layer_aux else (out, None)

        x, run_aux = jax.lax.scan(step, x, stacked)
        if lcfg.layer_aux:
            auxs.append(run_aux)
        return x

    for k, run in enumerate(runs):
        # one scope a run, scanned or unrolled; k is the `layer_run` event's
        with jax.named_scope(tracing.layers_scope(k)):
            x = one_run(x, run, k)
    if collect_kv:
        return x, kvs
    if cfg.layer_aux:
        return x, auxs
    return x


def _fold_aux(auxs: List[Dict[str, jax.Array]]) -> Dict[str, jax.Array]:
    """The layers' auxiliary terms as one, each over the layers that have
    it: a loss and the linear mixers' gate the mean, the load, the bias and
    the state's magnitude the worst layer's, the rows held their sum, and
    `counts` a row a routed block, in the blocks' order
    (`router_bias_leaves`'). An entry is a layer's values or a scanned run's,
    stacked along the layer axis."""
    def total(name, reduce):
        return reduce(jnp.stack([reduce(jnp.atleast_1d(a[name])) for a in auxs if name in a]))

    def mean(name):
        layers = sum(jnp.atleast_1d(a[name]).shape[0] for a in auxs if name in a)
        return total(name, jnp.sum) / layers

    fold = {
        "load_balance": mean, "router_z": mean, "decay_mean": mean,
        "load_max_over_mean": lambda n: total(n, jnp.max),
        "bias_abs_max": lambda n: total(n, jnp.max),
        "state_abs_max": lambda n: total(n, jnp.max),
        "ssm_state_abs_max": lambda n: total(n, jnp.max),
        "selscan_state_abs_max": lambda n: total(n, jnp.max),
        "published_mib": lambda n: total(n, jnp.sum),
        "rows_held": lambda n: total(n, jnp.sum),
        "window_fallbacks": lambda n: total(n, jnp.sum),
        "counts": lambda n: jnp.concatenate([jnp.atleast_2d(a[n]) for a in auxs if n in a]),
    }
    folds = {"mean": mean, "max": lambda n: total(n, jnp.max), "sum": lambda n: total(n, jnp.sum)}
    fold.update({name: folds[how] for name, how in COUNTERS.items()})  # the parts' own (parts/__init__.COUNTERS)
    return {name: fold[name](name) for name in dict.fromkeys(n for a in auxs for n in a)}


def padding_attn_bias(attn_mask: jax.Array) -> jax.Array:
    """(B, S) 1/0 key-validity mask -> additive (B, 1, 1, S) bias."""
    return (1.0 - attn_mask.astype(jnp.float32))[:, None, None, :] * -1e9


def model_forward(params, tokens, positions, cfg, hp=None, mesh=None, **inputs) -> jax.Array:
    """Full forward to logits (single pipeline stage; pipelined execution lives
    in parallel/pipeline.py). `table_spec`, here and in the loss functions
    below: the spec the token table is STORED in where that is not
    `param_specs`' (runtime/model_api.state_specs), which chooses the form of
    a vocabulary-split table's lookup (embed_head.vocab_parallel_lookup);
    `zero_splits_state`: `run_layers`'."""
    return _forward(params, tokens, positions, cfg, hp, mesh, **inputs)[0]


def _forward(
    params: Params,
    tokens: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    hp: Optional[HybridParallelConfig] = None,
    mesh: Optional[Mesh] = None,
    token_type_ids: Optional[jax.Array] = None,
    attn_mask: Optional[jax.Array] = None,
    table_spec: Optional[P] = None,
    zero_splits_state: bool = False,
):
    """-> (logits, the last layer's output before the final norm (of n
    hyper-connected streams their sum), the routed
    blocks' auxiliary terms as `run_layers` lists them or None). A looped
    stack (`cfg.loop_steps` > 1): (the LAST pass's logits, every pass's normed
    state (T, B, S, H), None)."""
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    if positions is None and cfg.input_type != "patches":
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    with jax.named_scope(tracing.EMBED):
        if cfg.input_type == "patches":
            x = embed_patches(params["embed"], tokens, cfg)
        else:
            x = embed_tokens(params["embed"], tokens, positions, cfg, mesh, vax,
                             token_type_ids=token_type_ids, table_spec=table_spec)
    if use_hp:
        x = S.constrain(x, mesh, S.act_spec(vax))
    if cfg.hc_mult > 1:  # hyper-connections: the layers carry n residual streams side by side
        with jax.named_scope(tracing.HC):
            embedded, x = x, hyper.widen(x, cfg.hc_mult)
    bias = padding_attn_bias(attn_mask) if attn_mask is not None else None
    if cfg.loop_steps > 1:
        states = looped_states(params, x, positions, cfg, hp, mesh, bias, zero_splits_state)
        with jax.named_scope(tracing.HEAD_LOSS):  # the LAST pass's: what a caller of the forward alone reads
            logits = head_logits(params, states[-1], cfg)
            if use_hp:
                logits = S.constrain(logits, mesh, S.logits_spec(vax))
        return logits, states, None
    x = run_layers(params, x, positions, cfg, hp, mesh, attn_bias=bias, zero_splits_state=zero_splits_state)
    x, auxs = x if cfg.layer_aux else (x, None)
    if cfg.hc_mult > 1:  # the streams' sum is what the final norm reads; their gain through the stack a counter
        with jax.named_scope(tracing.HC):
            x = hyper.contract(x, cfg.hc_mult)
            auxs = auxs + [{hyper.GAIN: hyper.stream_gain(embedded, x, cfg.hc_mult)}]
    if use_hp:
        x = S.constrain(x, mesh, S.act_spec(vax))
    # the head is the first half of gt.head_loss; the loss functions below
    # put their cross entropy under the same name
    with jax.named_scope(tracing.HEAD_LOSS):
        logits = model_head(params, x, cfg)
        if use_hp and cfg.head_type in ("lm", "mlm"):
            logits = S.constrain(logits, mesh, S.logits_spec(vax))
    return logits, x, auxs


def over_passes(one_pass, x: jax.Array, steps: int, scan: bool) -> jax.Array:
    """`one_pass` applied `steps` times from `x` on -> its results stacked, (steps, ...): ONE traced body under
    `jax.lax.scan` where the layers are scanned, else as many Python calls (`--no_scan_layers` unrolls both)."""
    if scan:
        return jax.lax.scan(lambda h, _: (one_pass(h),) * 2, x, None, length=steps)[1]
    states = []
    for _ in range(steps):
        x = one_pass(x)
        states.append(x)
    return jnp.stack(states)


def looped_states(params: Params, x: jax.Array, positions: jax.Array, cfg: TransformerConfig,
                  hp: Optional[HybridParallelConfig], mesh: Optional[Mesh], attn_bias: Optional[jax.Array],
                  zero_splits_state: bool) -> jax.Array:
    """A looped stack (`cfg.loop_steps` = T > 1): h_t = final_norm(run_layers(h_{t-1})) for t = 1 .. T over the
    SAME `params["layers"]` and the same final norm, from the embedding's rows on; the normed state feeds the
    head and re-enters the stack -> (T, B, S, H).

    One traced body: a `lax.scan` over t whose body is `run_layers` (its runs traced, stacked and lowered once,
    so trace, lowering and the compiled program stay those of a model of `num_layers` layers) and the norm.
    The layers' leaves are constants of that scan: its transpose holds each leaf's gradient as ONE sum over
    the passes in the dtype the leaf is read in (float32 for the stored parameters), and a pass's own
    cotangents (a scanned run's stacked ones among them) are transient beside it. `--checkpoint 1` keeps a
    layer APPLICATION's input, `num_layers` x T of them. The scanned loop is entered under the first run's name
    and, inside it, `gt.loop`: the transforms wrap the OUTERMOST name alone of those that stand outside the
    outermost scan, and the trace's readers tell a run's backward by the wrapper on its name (`obs/tracing.LOOP`)."""
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    scan = hp.scan_layers if hp is not None else True

    def one_pass(h):
        h = run_layers(params, h, positions, cfg, hp, mesh, attn_bias=attn_bias, zero_splits_state=zero_splits_state)
        if use_hp:
            h = S.constrain(h, mesh, S.act_spec(vax))
        return _norm(h, params["final_norm"], cfg)

    outermost = jax.named_scope(tracing.layers_scope(0)) if scan else contextlib.nullcontext()
    with outermost, jax.named_scope(tracing.LOOP):
        return over_passes(one_pass, x, cfg.loop_steps, scan)


def mtp_logits(params: Params, hidden: jax.Array, batch, cfg: TransformerConfig,
               hp: Optional[HybridParallelConfig] = None, mesh: Optional[Mesh] = None,
               table_spec: Optional[P] = None):
    """The multi-token-prediction module (DeepSeek-V3's, depth 1; arXiv:2412.19437
    2.2): for position i of a sequence t,

        m_i = [RMSNorm_h(hidden_i) ; RMSNorm_e(Emb(t_{i+1}))] Weh,  m'_i = Block(m_i)

    and the logits of t_{i+2} are the model's own head on RMSNorm(m'_i).
    `hidden` is the last layer's output before the final norm, `Emb` the
    model's own table, and t_{i+1} is `batch["labels"]`. The block is one
    more layer of the last layer's kind, in its layout and under its
    recomputation policy. -> (logits, the block's auxiliary terms or None)."""
    mp, dtype = params["mtp"], cfg.compute_dtype
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    last = cfg.num_layers - 1
    lcfg = cfg.layer_config(cfg.layer_kinds()[last])
    axes = layer_axes(hp, last) if use_hp else None
    positions = batch["positions"]
    with jax.named_scope(tracing.MTP):
        e = embed_tokens(params["embed"], batch["labels"], positions, cfg, mesh, vax,
                         table_spec=table_spec)
        m = jnp.concatenate([_norm(hidden, mp["hnorm"], cfg), _norm(e, mp["enorm"], cfg)], axis=-1)
        m = _dense(m, mp["eh_proj"], dtype)
        if use_hp:
            m = S.constrain(m, mesh, S.act_spec(axes))
        attn_bias = padding_attn_bias(batch["attn_mask"]) if "attn_mask" in batch else None
        block = partial(layer_forward, cfg=lcfg, mesh=mesh, axes=axes, attn_bias=attn_bias)
        policy = hp.layers[last].effective_remat_policy if use_hp else "none"
        if policy != "none":
            block = _remat(block, policy)
        m = block(mp["block"], m, positions)
        m, aux = m if lcfg.layer_aux else (m, None)
        if use_hp:
            m = S.constrain(m, mesh, S.act_spec(vax))
    with jax.named_scope(tracing.HEAD_LOSS):
        logits = head_logits(params, _norm(m, mp["norm"], cfg), cfg)
        if use_hp:
            logits = S.constrain(logits, mesh, S.logits_spec(vax))
    return logits, aux


EXPERT_LOAD = "expert_load_max_over_mean"  # the fullest expert's tokens over the mean
ROUTER_COUNTS = "router_counts"  # (routed blocks, E): the step's; no metric
# how the microbatch loop folds a part that is not a loss term (those are
# weighted as the loss is)
PART_FOLDS = {EXPERT_LOAD: jnp.maximum, "router_bias_abs_max": jnp.maximum,
              ROUTER_COUNTS: jnp.add, "expert_rows_held": jnp.add, "expert_window_fallbacks": jnp.add,
              "linear_state_abs_max": jnp.maximum, "ssm_state_abs_max": jnp.maximum,
              "selscan_state_abs_max": jnp.maximum, "published_mib": jnp.add}
# (a part's counter folded as a "mean", `parts.COUNTERS`, is weighted as a loss term is; as a "max", the worst microbatch's)
PART_FOLDS.update({name: jnp.maximum for name, how in COUNTERS.items() if how == "max"})


def lm_loss_fn(params, batch, cfg, hp=None, mesh=None, with_parts: bool = False,
               table_spec: Optional[P] = None, zero_splits_state: bool = False):
    """batch: dict(tokens, positions, labels, loss_mask?, token_type_ids?,
    attn_mask?). Serves lm and mlm heads (token-level CE).

    A routed-experts config's loss is the cross entropy plus
    `router_aux_loss_coef` x load balancing plus `router_z_loss_coef` x router
    z-loss (each the mean over the routed blocks, over all tokens; a sigmoid
    router has neither), and with a multi-token-prediction module plus
    `mtp_loss_weight` x the cross entropy of the token after next (labels
    shifted by one more; a sequence's last position has none). `with_parts`
    returns `(loss, parts)`: the terms, the worst block's expert load and
    the other counters of the `step` event (`telemetry.EXPERT_STEP_FIELDS`,
    `LINEAR_STEP_FIELDS` for linear-attention layers, `SSM_STEP_FIELDS` for
    state-space layers, `SHARED_STEP_FIELDS` for Mamba-1 layers and what layers publish,
    and the counters the parts' own table names, `parts.COUNTERS`),
    and for a router with a bias the blocks' assignment counts
    (`ROUTER_COUNTS`), which the train step moves the bias by."""
    logits, hidden, auxs = _forward(
        params, batch["tokens"], batch["positions"], cfg, hp, mesh,
        token_type_ids=batch.get("token_type_ids"), attn_mask=batch.get("attn_mask"),
        table_spec=table_spec, zero_splits_state=zero_splits_state,
    )
    labels, mask = batch["labels"], batch.get("loss_mask")
    if cfg.loop_steps > 1:
        loss, parts = looped_loss(params, hidden, logits, labels, mask, cfg, hp, mesh)
        return (loss, parts) if with_parts else loss
    with jax.named_scope(tracing.HEAD_LOSS):
        if cfg.pred_heads > 1:  # a head of several predictions a position: the mean of the heads' means
            loss = next_tokens_cross_entropy(logits, labels, mask, cfg.pred_heads)
        else:
            loss = vocab_parallel_cross_entropy(logits, labels, mask)
    parts = {"loss_ce": loss}
    if cfg.mtp_layers:
        logits2, aux = mtp_logits(params, hidden, batch, cfg, hp, mesh, table_spec)
        auxs = auxs + [aux] if aux is not None else auxs
        # the label of position i + 1, where it is one; none at a sequence's end
        ahead = jnp.ones(labels.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)
        ahead = jnp.roll(ahead, -1, axis=1).at[:, -1].set(0.0)
        with jax.named_scope(tracing.HEAD_LOSS):
            parts["loss_mtp"] = vocab_parallel_cross_entropy(
                logits2, jnp.roll(labels, -1, axis=1), ahead)
        loss = loss + cfg.mtp_loss_weight * parts["loss_mtp"]
    if not auxs:
        return (loss, parts) if with_parts else loss
    aux = _fold_aux(auxs)
    if "load_balance" in aux:
        parts["loss_load_balance"] = aux["load_balance"]
        parts["loss_router_z"] = aux["router_z"]
        loss = (loss + cfg.router_aux_loss_coef * aux["load_balance"]
                + cfg.router_z_loss_coef * aux["router_z"])
    if "decay_mean" in aux:  # the linear mixers' counters (telemetry.LINEAR_STEP_FIELDS)
        parts["linear_decay_mean"] = aux["decay_mean"]
        parts["linear_state_abs_max"] = aux["state_abs_max"]
    if "ssm_state_abs_max" in aux:  # the state-space mixers' counter (telemetry.SSM_STEP_FIELDS)
        parts["ssm_state_abs_max"] = aux["ssm_state_abs_max"]
    for name in ("selscan_state_abs_max", "published_mib", *COUNTERS):  # telemetry.SHARED_STEP_FIELDS, the parts' own
        if name in aux:
            parts[name] = aux[name]
    if "load_max_over_mean" in aux:  # a router (linear layers over dense MLPs have none)
        parts[EXPERT_LOAD] = aux["load_max_over_mean"]
    if "rows_held" in aux:
        even = (cfg.routed_layers * labels.size * cfg.experts_per_token
                * cfg.held_experts[1] / cfg.num_experts)
        parts["expert_rows_held"] = aux["rows_held"]
        parts["expert_rows_held_over_even"] = aux["rows_held"] / even
        parts["expert_window_fallbacks"] = aux["window_fallbacks"]
    if "counts" in aux:
        parts["router_bias_abs_max"] = aux["bias_abs_max"]
        parts[ROUTER_COUNTS] = aux["counts"]
    return (loss, parts) if with_parts else loss


def looped_loss(params: Params, states: jax.Array, last_logits: jax.Array, labels: jax.Array,
                mask: Optional[jax.Array], cfg: TransformerConfig, hp: Optional[HybridParallelConfig],
                mesh: Optional[Mesh]) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """A looped stack's objective from every pass's normed state (T, B, S, H): the T heads and cross entropies
    one after another through `head_logits` and `token_cross_entropies` (a pass's logits at a time; the last
    pass's are `_forward`'s), each a cross entropy a POSITION in float32, weighted by the exit gate's
    distribution over the passes, summed, less `exit_entropy_coef` x its entropy (`parts/loop.expected_loss`;
    no gate: the last pass's cross entropy). -> (loss, its parts: `loop.PARTS` beside `loss_ce`)."""
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    nll = []
    with jax.named_scope(tracing.HEAD_LOSS):
        for t in range(cfg.loop_steps):
            logits = last_logits
            if t < cfg.loop_steps - 1:
                logits = head_logits(params, states[t], cfg)
                if use_hp:
                    logits = S.constrain(logits, mesh, S.logits_spec(vax))
            nll.append(token_cross_entropies(logits, labels))
    with jax.named_scope(tracing.EXIT):
        p = loop.exit_distribution(params.get("exit_gate"), states)
        return loop.expected_loss(p, jnp.stack(nll), mask, cfg.exit_entropy_coef)


def router_bias_leaves(params: Params) -> List[Dict[str, jax.Array]]:
    """The `router` dicts that hold a bias, in the order of `ROUTER_COUNTS`'
    rows: the stack's routed layers, then the MTP module's block."""
    blocks = list(params["layers"]) + ([params["mtp"]["block"]] if "mtp" in params else [])
    return [b["router"] for b in blocks if ROUTER_BIAS in b.get("router", {})]


def update_router_bias(params: Params, counts: jax.Array, rate: float) -> Params:
    """`b_e += rate x sign(mean(c) - c_e)` a routed block, `c` the block's row
    of `counts`: the auxiliary-loss-free balancing rule (arXiv:2412.19437
    2.1.2). An expert with more than its share of the batch's assignments is
    ranked lower next step, one with fewer higher. -> params, new dicts on
    the way to each bias and every other leaf as it was."""
    rows = iter(counts)

    def block(b):
        if ROUTER_BIAS not in b.get("router", {}):
            return b
        c = next(rows)
        bias = b["router"][ROUTER_BIAS]
        moved = bias + rate * jnp.sign(jnp.mean(c) - c).astype(bias.dtype)
        return {**b, "router": {**b["router"], ROUTER_BIAS: moved}}

    out = {**params, "layers": [block(b) for b in params["layers"]]}
    if "mtp" in params:
        out["mtp"] = {**params["mtp"], "block": block(params["mtp"]["block"])}
    return out


def classification_loss_fn(params, batch, cfg, hp=None, mesh=None, table_spec: Optional[P] = None,
                           zero_splits_state: bool = False):
    """batch: dict(pixels | tokens, labels). Mean softmax CE over classes
    (reference vit/swin `Cls_` heads)."""
    inputs = batch.get("pixels", batch.get("tokens"))
    logits = model_forward(params, inputs, batch.get("positions"), cfg, hp, mesh,
                           attn_mask=batch.get("attn_mask"), table_spec=table_spec,
                           zero_splits_state=zero_splits_state)
    with jax.named_scope(tracing.HEAD_LOSS):
        return softmax_nll(logits, batch["labels"])


# ============================================================== param specs
def layer_param_specs(cfg: TransformerConfig, axes: LayerAxes) -> Params:
    """PartitionSpec tree matching init_layer_params output: the norms whole,
    the halves as their table entries lay them out."""
    r1 = S.replicated_1d_spec(axes)
    norm = {"scale": r1} if cfg.norm_type == "rmsnorm" else {"scale": r1, "bias": r1}
    sp: Params = {name: dict(norm) for name, _ in _halves(cfg)}
    if cfg.post_norm:
        sp.update({name + "_post": dict(norm) for name, _ in _halves(cfg)})
    sp.update(MIXERS[cfg.mixer].specs(cfg, axes))
    sp.update(MLP_HALVES[cfg.mlp_half].specs(cfg, axes))
    sp.update(hyper.layer_specs(cfg))
    return sp


def model_param_specs(cfg: TransformerConfig, hp: HybridParallelConfig) -> Params:
    vax = vocab_axes(hp)
    r1 = S.replicated_1d_spec(vax)
    norm_spec = {"scale": r1} if cfg.norm_type == "rmsnorm" else {"scale": r1, "bias": r1}
    if cfg.input_type == "patches":
        embed: Params = {"patch": {"kernel": P(None, None), "bias": r1}, "wpe": P(None, None)}
        if cfg.use_cls_token:
            embed["cls_token"] = r1
    else:
        embed = {"wte": S.vocab_embed_spec(vax)}
        if cfg.position_type == "learned":
            embed["wpe"] = P(None, None)
        if cfg.type_vocab_size:
            embed["tte"] = P(None, None)
    if cfg.embed_norm:
        embed["norm"] = dict(norm_spec)
    specs: Params = {
        "embed": embed,
        "layers": [layer_param_specs(cfg.layer_config(kind), layer_axes(hp, i))
                   for i, kind in enumerate(cfg.layer_kinds())],
    }
    if cfg.mtp_layers:
        specs["mtp"] = {
            "enorm": dict(norm_spec), "hnorm": dict(norm_spec),
            "eh_proj": {"kernel": P(S._ax(vax.dp) if vax.zero3 else None, None)},
            "block": specs["layers"][-1], "norm": dict(norm_spec),
        }
    if cfg.pre_norm:
        specs["final_norm"] = dict(norm_spec)
    if cfg.exit_gate:
        specs["exit_gate"] = loop.exit_gate_specs()
    vocab_col = P(None, None) if vax.ulysses else P(None, S._ax(vax.tp))
    if cfg.head_type == "classification":
        specs["head"] = {"kernel": P(None, None), "bias": P(None)}
    elif cfg.head_type == "mlm":
        specs["head"] = {
            "transform": {"kernel": P(None, None), "bias": r1},
            "norm": dict(norm_spec),
            "bias": P(None) if vax.ulysses else P(S._ax(vax.tp)),
        }
    if cfg.head_type in ("lm", "mlm") and not cfg.tie_embeddings:
        # lm head is column-parallel over the vocab dim (vocab-parallel
        # logits); vocab-dense under vocab-SP, matching logits_spec
        specs["lm_head"] = {"kernel": vocab_col}
    return specs
