"""Analytic model-FLOPs accounting and the peak-FLOPs registry behind MFU.

Model FLOPs (not hardware FLOPs): the arithmetic the model semantically
requires — matmul-dominated terms of attention (including the causal 0.5
factor), the MLP, and the embed/head projection — independent of remat
replay or compiler fusions, per the PaLM appendix-B convention. MFU is then
``model_flops / step_time / peak_flops`` on the device kind's peak dense
matmul throughput.

Two validation hooks keep the analytic numbers honest:

- :func:`xla_flops` reads ``cost_analysis()`` off a lowered/compiled XLA
  program where the backend reports flops (XLA:CPU does), and
  tests/obs/test_flops.py pins the analytic forward count against it on a
  tiny model;
- every consumer (RuntimeProfiler.summary, the per-step telemetry, ``cli
  report``) reports model-FLOPs/s alongside MFU, so a wrong peak entry
  shifts MFU but never the throughput trend.

No jax at module scope: ``cli report`` reads a finished run's log through
this module on a machine with no accelerator stack; jax is touched only
inside :func:`xla_flops`, which receives an already-built jax object.

``benchmarks/flops.py`` is a second copy of these counts BY DESIGN: the
yardstick shares no code with the program it measures, so a change here
cannot move a cell's ``mfu``. Do not merge the two.
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Optional, Sequence

# Peak dense matmul throughput per chip, FLOP/s, by device_kind prefix
# (jax Device.device_kind), bf16. A kind the table does not hold has no peak
# and a run on it reports no MFU: there is no row for a CPU.
PEAK_FLOPS_BY_KIND: Dict[str, float] = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
    "TPU7x": 2307e12,
}


def peak_flops_for(device_kind: Optional[str]) -> Optional[float]:
    """Peak FLOP/s for a device kind (longest-prefix match, case-insensitive);
    None for a kind the table does not hold."""
    if not device_kind:
        return None
    kind = device_kind.lower()
    best: Optional[float] = None
    best_len = -1
    for prefix, peak in PEAK_FLOPS_BY_KIND.items():
        if kind.startswith(prefix.lower()) and len(prefix) > best_len:
            best, best_len = peak, len(prefix)
    return best


# ------------------------------------------------------------ analytic FLOPs
# A token mixer's forward FLOPs a token, (projections, core): a function a key
# of `models/parts.MIXERS` (`MIXER_FWD_FLOPS` below; this module imports no jax).
def attention_fwd_flops_a_token(*, hidden: int, num_heads: int, head_dim: int, num_kv_heads: int,
                                seq_len: int, causal: bool = True, gated: bool = False,
                                latent: Optional[Mapping[str, int]] = None, head_gate: bool = False,
                                diff: bool = False):
    """Softmax attention: q, fused kv (GQA-scaled) and out projections (q
    twice as wide beside an output gate, or a (hidden, heads) gate a head
    beside it; latent attention's five by their
    shapes, or four where q has no low rank), and scores (q k^T) + weighted
    sum (p v), each 2 S q_dim, half of it under a causal mask; latent
    attention's scores at its q/k width (nope + rope) and its sum at v's,
    whatever width the one attention call pads them to. `diff`: differential
    attention as its mathematics needs it, two score maps of head_dim a pair
    of heads (as many as ordinary attention's) and two `p v` products at the
    pair's 2 x head_dim (twice ordinary attention's): 1.5 x the ordinary core,
    whatever heads an implementation pads."""
    q_dim = num_heads * head_dim
    if latent:
        ql, kvl = latent["q_lora_rank"], latent["kv_lora_rank"]
        nope, rope, vd = (latent["qk_nope_head_dim"], latent["qk_rope_head_dim"],
                          latent["v_head_dim"])
        q_proj = (2.0 * hidden * ql + 2.0 * ql * num_heads * (nope + rope) if ql
                  else 2.0 * hidden * num_heads * (nope + rope))
        proj = (q_proj + 2.0 * hidden * (kvl + rope) + 2.0 * kvl * num_heads * (nope + vd)
                + 2.0 * num_heads * vd * hidden)
        return proj, 2.0 * seq_len * num_heads * ((nope + rope) + vd) * (0.5 if causal else 1.0)
    proj = (2.0 * hidden * q_dim * (2 if gated else 1) + (2.0 * hidden * num_heads if head_gate else 0.0)
            + 2.0 * hidden * (2 * num_kv_heads * head_dim) + 2.0 * q_dim * hidden)
    return proj, (3.0 if diff else 2.0) * (2.0 * seq_len * q_dim) * (0.5 if causal else 1.0)


def window_fwd_flops_a_token(*, hidden: int, num_heads: int, head_dim: int, num_kv_heads: int,
                             window: int, head_gate: bool, seq_len: int, diff: bool = False):
    """Softmax attention over a window: the attention row's projections at
    the window layer's heads, and scores + weighted sum over the keys a query
    SEES, the exact band: query i sees min(i + 1, window) keys, a mean of
    (W S - W (W - 1) / 2) / S over a sequence (W = min(window, S))."""
    proj, _ = attention_fwd_flops_a_token(hidden=hidden, num_heads=num_heads, head_dim=head_dim,
                                          num_kv_heads=num_kv_heads, seq_len=seq_len, head_gate=head_gate)
    w = min(window, seq_len)
    keys = (w * seq_len - w * (w - 1) / 2.0) / seq_len
    return proj, (3.0 if diff else 2.0) * 2.0 * keys * num_heads * head_dim


def linear_fwd_flops_a_token(*, hidden: int, num_key_heads: int, num_value_heads: int,
                             key_head_dim: int, value_head_dim: int):
    """A gated-DeltaNet mixer: hidden -> [q | k | v | z] and [b | a], value ->
    hidden; and the core as the RECURRENCE needs it, three (d_k, d_v) products
    a value head a token (S^T k, k u^T, S^T q: 6 d_k d_v), whatever chunk an
    implementation cuts the sequence into and at any sequence length. The
    convolution's taps are no matmul."""
    key_dim, value_dim = num_key_heads * key_head_dim, num_value_heads * value_head_dim
    proj = (2.0 * hidden * (2 * key_dim + 2 * value_dim) + 2.0 * hidden * (2 * num_value_heads)
            + 2.0 * value_dim * hidden)
    return proj, 6.0 * num_value_heads * key_head_dim * value_head_dim


def kda_fwd_flops_a_token(*, hidden: int, num_key_heads: int, num_value_heads: int,
                          key_head_dim: int, value_head_dim: int):
    """A Kimi-Delta-Attention mixer: hidden -> [q | k | v], the gate's and the
    output gate's low-rank pairs (hidden -> d_v -> key dims, hidden -> d_v ->
    value dims), hidden -> beta a head, value -> hidden; and the core as the
    RECURRENCE needs it, the linear mixer's three (d_k, d_v) products a head a
    token: a gate that is a vector scales the state's rows, which is no
    matmul."""
    key_dim, value_dim = num_key_heads * key_head_dim, num_value_heads * value_head_dim
    proj = (2.0 * hidden * (2 * key_dim + value_dim)
            + 2.0 * hidden * value_head_dim + 2.0 * value_head_dim * key_dim
            + 2.0 * hidden * value_head_dim + 2.0 * value_head_dim * value_dim
            + 2.0 * hidden * num_value_heads + 2.0 * value_dim * hidden)
    return proj, 6.0 * num_value_heads * key_head_dim * value_head_dim


def ssm_fwd_flops_a_token(*, hidden: int, num_heads: int, head_dim: int, state_dim: int, groups: int = 1):
    """A Mamba-2 mixer: hidden -> [z | x | B | C | dt] (2 x inner + 2 x groups x state
    + heads: B and C a group), inner -> hidden; and the scan as the RECURRENCE needs it, two
    (d_head, d_state) products a head a token (dt x B^T into the state, h C
    out of it: 4 d_head d_state), whatever chunk an implementation cuts the
    sequence into and at any sequence length. The convolution's taps, the
    decay and the D skip are no matmul."""
    inner = num_heads * head_dim
    proj = 2.0 * hidden * (2 * inner + 2 * groups * state_dim + num_heads) + 2.0 * inner * hidden
    return proj, 4.0 * num_heads * head_dim * state_dim


def conv_fwd_flops_a_token(*, hidden: int):
    """A gated short-convolution mixer: hidden -> [B | C | u] (3 x hidden),
    hidden -> hidden; the two gates and the taps are no matmul, so the core
    counts nothing."""
    return 2.0 * hidden * (3 * hidden) + 2.0 * hidden * hidden, 0.0


def mamba1_fwd_flops_a_token(*, hidden: int, expand: int, d_state: int, dt_rank: int):
    """A Mamba-1 mixer: hidden -> [x | z] (2 x inner, inner = expand x
    hidden), inner -> [dt_r | B | C] (dt_rank + 2 x d_state), dt_rank -> inner,
    inner -> hidden; and the selective scan as the RECURRENCE needs it, a
    multiply-add into the state and one out of it a (channel, state) a token
    (`4 inner d_state`), whatever chunk an implementation cuts the sequence
    into. The convolution's taps, the decay and the D skip are no matmul."""
    inner = expand * hidden
    proj = 2.0 * hidden * (2 * inner) + 2.0 * inner * (dt_rank + 2 * d_state) + 2.0 * dt_rank * inner \
        + 2.0 * inner * hidden
    return proj, 4.0 * inner * d_state


def gmu_fwd_flops_a_token(*, hidden: int, expand: int):
    """A gated memory unit: hidden -> inner and inner -> hidden; the gate on
    another layer's memory is no matmul, so the core counts nothing."""
    inner = expand * hidden
    return 2.0 * hidden * inner + 2.0 * inner * hidden, 0.0


def cross_fwd_flops_a_token(*, hidden: int, num_heads: int, head_dim: int, seq_len: int):
    """A cross layer: q's and the output's projections alone (K and V are
    another layer's), and a differential core over the causal half."""
    q_dim = num_heads * head_dim
    return 2.0 * hidden * q_dim + 2.0 * q_dim * hidden, 3.0 * (2.0 * seq_len * q_dim) * 0.5


def eva_pairs(seq_len: int, window: int, chunk: int) -> int:
    """The (query, key) pairs a head of EVA attention scores over one sequence, EXACTLY: query t meets the
    `(t mod W) + 1` keys of its own window up to itself and the `(t // W) C` pooled keys of every earlier
    window (C = W / chunk); the sum over t, with `full` whole windows and a last one of `rest` positions."""
    full, rest = divmod(seq_len, window)
    own = full * (window * (window + 1) // 2) + rest * (rest + 1) // 2
    return own + (window // chunk) * (window * (full * (full - 1) // 2) + rest * full)


def eva_fwd_flops_a_token(*, hidden: int, num_heads: int, head_dim: int, window: int, chunk: int, seq_len: int):
    """An EVA attention mixer: q, k, v and out projections (as many key heads as query heads); and the
    core as its mathematics needs it: scores and weighted sum over the pairs a query MEETS (`eva_pairs`,
    exact: its window's keys up to itself and the pooled keys of earlier windows), and the pooling's two
    weighted sums of a chunk's keys and values (2 head_dim multiply-adds a position a head). The chunk's
    weights `<phi, k>` are a dot product a position on the vector unit, no matmul."""
    q_dim = num_heads * head_dim
    pairs_a_token = eva_pairs(seq_len, window, chunk) / float(seq_len)
    return 2.0 * hidden * q_dim * 4, 2.0 * (2.0 * pairs_a_token * q_dim) + 2.0 * (2.0 * q_dim)


def absent_fwd_flops_a_token(*, hidden: int):
    """The absent mixer of a layer that is an MLP alone: nothing."""
    return 0.0, 0.0


def hyper_fwd_flops_a_token(*, hidden: int, streams: int) -> float:
    """Hyper-connections around ONE layer (models/parts/hyper.py: `streams` = n residual streams, two halves):
    a half's coefficients `x~ Phi` 2 n hidden (n^2 + 2n), its read 2 n hidden, its write 2 n^2 hidden + 2 n
    hidden. The Sinkhorn steps and the sigmoids are no matmul."""
    n = streams
    return 2.0 * (2.0 * n * hidden * (n * n + 2 * n) + 2.0 * n * hidden + 2.0 * n * n * hidden + 2.0 * n * hidden)


# the row of each `MIXERS` key, and the config fields its keyword arguments read
# ("seq_len": no field, the sequence length the count is asked at)
_DELTA_DIMS = {k: "linear_" + k for k in ("num_key_heads", "num_value_heads", "key_head_dim", "value_head_dim")}
MIXER_FWD_FLOPS = {
    "attention": (attention_fwd_flops_a_token, {}),
    "linear": (linear_fwd_flops_a_token, _DELTA_DIMS),
    "kda": (kda_fwd_flops_a_token, _DELTA_DIMS),
    "ssm": (ssm_fwd_flops_a_token, {k: "ssm_" + k for k in ("num_heads", "head_dim", "state_dim", "groups")}),
    "none": (absent_fwd_flops_a_token, {}),
    "conv": (conv_fwd_flops_a_token, {}),
    "window": (window_fwd_flops_a_token, {
        "num_heads": "num_heads", "head_dim": "head_dim", "num_kv_heads": "num_kv_heads",
        "window": "sliding_window", "head_gate": "attn_head_gate", "seq_len": "seq_len",
        "diff": "diff_attention"}),
    "mamba1": (mamba1_fwd_flops_a_token, {k: "mamba_" + k for k in ("expand", "d_state", "dt_rank")}),
    "gmu": (gmu_fwd_flops_a_token, {"expand": "mamba_expand"}),
    "cross": (cross_fwd_flops_a_token, {"num_heads": "num_heads", "head_dim": "head_dim", "seq_len": "seq_len"}),
    "eva": (eva_fwd_flops_a_token, {"num_heads": "num_heads", "head_dim": "head_dim", "window": "eva_window",
                                    "chunk": "eva_chunk", "seq_len": "seq_len"}),
}


def layer_fwd_flops(
    *,
    hidden: int,
    num_heads: int,
    seq_len: int,
    ffn_hidden: Optional[int] = None,
    head_dim: Optional[int] = None,
    num_kv_heads: Optional[int] = None,
    causal: bool = True,
    swiglu: bool = False,
    tokens: Optional[float] = None,
    num_experts: int = 0,
    experts_per_token: int = 0,
    experts_held: int = 0,
    num_shared_experts: int = 0,
    latent: Optional[Mapping[str, int]] = None,
    attn_gate: bool = False,
    shared_gate: bool = False,
    mixer: str = "attention",
    mixer_dims: Optional[Mapping[str, int]] = None,
    head_gate: bool = False,
    diff: bool = False,
    mlp_half: Optional[str] = None,
    shared_ffn: Optional[int] = None,
) -> float:
    """Forward model FLOPs of ONE transformer block over `tokens` tokens
    (default: one sequence). Matmul terms only (2 FLOPs per MAC); norms and
    elementwise activations are O(tokens*hidden) noise next to these. A
    routed block (`num_experts` > 0, `ffn_hidden` the width of one expert)
    counts the experts a token is SENT to, not the experts held, plus the
    router's matmul and the shared experts; where only `experts_held` of the
    experts are held here, the even share of a token's experts that falls to
    them (experts_per_token x held / num_experts: a constant, whatever the
    routing). `latent` (q_lora_rank, kv_lora_rank, qk_nope_head_dim,
    qk_rope_head_dim, v_head_dim): latent attention's five projections by
    their shapes in place of q, k/v and out; `attn_gate`: q projected beside
    an output gate. `mixer` with `mixer_dims`: the layer's token mixer is
    that row of `MIXER_FWD_FLOPS` on those sizes, in place of attention.
    `shared_gate`: the shared expert's (hidden, 1) gate; `head_gate`: the
    attention output's (hidden, heads) gate; `diff`: differential attention's
    core (1.5 x the ordinary one). `mlp_half` "none": a layer that is a mixer
    alone, whose MLP half counts nothing; `shared_ffn`: the shared expert's
    width where it is not `num_shared_experts` x the experts'."""
    tokens = float(seq_len if tokens is None else tokens)
    ffn = ffn_hidden or 4 * hidden
    if mixer != "attention":
        proj, attn = MIXER_FWD_FLOPS[mixer][0](hidden=hidden, **mixer_dims)
    else:
        proj, attn = attention_fwd_flops_a_token(
            hidden=hidden, num_heads=num_heads, head_dim=head_dim or hidden // num_heads,
            num_kv_heads=num_kv_heads or num_heads, seq_len=seq_len, causal=causal,
            gated=attn_gate, latent=latent, head_gate=head_gate, diff=diff)
    # MLP: swiglu projects to 2*ffn (gate+up) then back; gelu/relu ffn both ways
    mlp = (2.0 * hidden * (2 * ffn) + 2.0 * ffn * hidden) if swiglu \
        else (2.0 * hidden * ffn + 2.0 * ffn * hidden)
    if mlp_half == "none":
        mlp = 0.0
    elif num_experts:
        sent = experts_per_token * (experts_held or num_experts) / num_experts
        shared = num_shared_experts * mlp if shared_ffn is None else mlp * shared_ffn / ffn
        mlp = sent * mlp + shared + 2.0 * hidden * num_experts
        if shared_gate:
            mlp += 2.0 * hidden
    return tokens * (proj + attn + mlp)


def layer_fwd_flops_from_config(cfg: Any, tokens: Optional[float] = None,
                                seq_len: Optional[int] = None) -> Optional[float]:
    """Duck-typed entry for TransformerConfig-shaped configs; None when the
    config lacks the transformer fields (custom families). A looped stack
    (`loop_steps` = T > 1) applies every layer T times a step: T x the layer."""
    hidden = getattr(cfg, "hidden_size", None)
    heads = getattr(cfg, "num_heads", None)
    seq = seq_len or getattr(cfg, "max_seq_len", None)
    if not hidden or not heads or not seq:
        return None
    latent = None
    if getattr(cfg, "kv_lora_rank", 0):
        latent = {k: getattr(cfg, k) for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")}
    mixer = getattr(cfg, "mixer", "attention")
    streams = getattr(cfg, "hc_mult", 1)  # > 1: hyper-connections around both halves, over the same tokens
    around = (float(seq if tokens is None else tokens) * hyper_fwd_flops_a_token(hidden=hidden, streams=streams)
              if streams > 1 else 0.0)
    return around + getattr(cfg, "loop_steps", 1) * layer_fwd_flops(
        hidden=hidden,
        num_heads=heads,
        seq_len=seq,
        ffn_hidden=getattr(cfg, "ffn_hidden", None),
        head_dim=getattr(cfg, "head_dim", None),
        num_kv_heads=getattr(cfg, "num_kv_heads", None),
        causal=bool(getattr(cfg, "causal", True)),
        swiglu=getattr(cfg, "activation", "gelu") == "swiglu",
        tokens=tokens,
        num_experts=getattr(cfg, "num_experts", 0),
        experts_per_token=getattr(cfg, "experts_per_token", 0),
        experts_held=getattr(cfg, "experts_held", 0),
        num_shared_experts=getattr(cfg, "num_shared_experts", 0),
        latent=latent,
        attn_gate=bool(getattr(cfg, "attn_output_gate", False)),
        shared_gate=bool(getattr(cfg, "shared_expert_gate", False)),
        mixer=mixer,
        mixer_dims={k: seq if field == "seq_len" else getattr(cfg, field)
                    for k, field in MIXER_FWD_FLOPS[mixer][1].items()},
        head_gate=bool(getattr(cfg, "attn_head_gate", False)),
        diff=bool(getattr(cfg, "diff_attention", False)),
        mlp_half=getattr(cfg, "mlp", None),
        shared_ffn=getattr(cfg, "shared_expert_ffn", None) if getattr(cfg, "num_shared_experts", 0) else None,
    )


def head_fwd_flops_from_config(cfg: Any, tokens: Optional[float] = None) -> float:
    """Embed/head projection FLOPs over `tokens` tokens: the vocab matmul for
    lm/mlm heads (embedding lookups are gathers, ~0 FLOPs), the class
    projection for classification heads. A looped stack (`loop_steps` = T > 1)
    runs the head on every pass's state: T x the vocab matmul (the lookup once)."""
    hidden = getattr(cfg, "hidden_size", 0) or 0
    tokens = float(tokens if tokens is not None else getattr(cfg, "max_seq_len", 0) or 0)
    head_type = getattr(cfg, "head_type", "lm")
    if head_type in ("lm", "mlm"):
        # (a head of several predictions a position is one matmul on that many times the columns)
        vocab = (getattr(cfg, "vocab_size", 0) or 0) * getattr(cfg, "pred_heads", 1)
        extra = 2.0 * hidden * hidden if head_type == "mlm" else 0.0  # transform dense
        return getattr(cfg, "loop_steps", 1) * tokens * (2.0 * hidden * vocab + extra)
    if head_type == "classification":
        classes = getattr(cfg, "num_classes", 0) or 0
        # one pooled vector per sample; callers pass tokens=batch*seq, the
        # per-sample projection is seq-fold smaller — negligible, price ~0
        return 2.0 * hidden * classes
    return 0.0


def model_fwd_flops(cfg: Any, batch_size: int = 1) -> Optional[float]:
    """Whole-model forward FLOPs for one batch; None for configs the
    analytic model cannot describe."""
    seq = getattr(cfg, "max_seq_len", None)
    layers = getattr(cfg, "num_layers", None)
    if not seq or not layers:
        return None
    tokens = float(batch_size) * seq
    per_kind = layer_kind_fwd_flops(cfg, tokens)
    if per_kind is None:
        return None
    kinds = _layer_kinds(cfg)
    return (sum(per_kind[k] * kinds.count(k) for k in sorted(per_kind))
            + _after_layers_fwd_flops(cfg, tokens, per_kind))


def _after_layers_fwd_flops(cfg: Any, tokens: float, per_kind: Mapping[str, float]) -> float:
    """The head, and with a multi-token-prediction module its (2h, h)
    projection, its block (one more layer of the last layer's kind) and the
    head a second time."""
    head = head_fwd_flops_from_config(cfg, tokens=tokens)
    if not getattr(cfg, "mtp_layers", 0):
        return head
    hidden = cfg.hidden_size
    return 2 * head + tokens * 2.0 * (2 * hidden) * hidden + per_kind[_layer_kinds(cfg)[-1]]


def _layer_kinds(cfg: Any) -> Sequence[str]:
    kinds = getattr(cfg, "layer_kinds", None)
    return tuple(kinds()) if callable(kinds) else ("dense",) * cfg.num_layers


def layer_kind_fwd_flops(cfg: Any, tokens: float) -> Optional[Dict[str, float]]:
    """Forward FLOPs of one layer of each kind the model has (a model of one
    kind: {"dense": ...} or {"routed": ...}), over `tokens` tokens."""
    out = {}
    for kind in set(_layer_kinds(cfg)):
        layer_cfg = cfg.layer_config(kind) if hasattr(cfg, "layer_config") else cfg
        out[kind] = layer_fwd_flops_from_config(layer_cfg, tokens=tokens)
        if out[kind] is None:
            return None
    return out


# backward ~= 2x forward (dL/dx and dL/dW each re-run every matmul)
BWD_FWD_RATIO = 2.0


def train_step_flops(cfg: Any, global_bsz: int) -> Optional[float]:
    """Model FLOPs of one optimizer step at `global_bsz`: forward + backward
    (3x forward). Remat replay is deliberately NOT counted — MFU measures
    useful arithmetic, recompute is overhead it should expose."""
    fwd = model_fwd_flops(cfg, batch_size=global_bsz)
    if fwd is None:
        return None
    return fwd * (1.0 + BWD_FWD_RATIO)


def run_fwd_flops(cfg: Any, hp: Any) -> Optional[List[float]]:
    """Per-LayerRun forward FLOPs for one global batch (config/strategy
    layer_runs partitioning); None when the model is not analytically
    describable. The head/embed share is appended as a final pseudo-run so
    shares over the step sum to 1."""
    from galvatron_tpu.config.strategy import layer_runs, model_layer_kinds

    tokens = float(hp.global_bsz) * (getattr(cfg, "max_seq_len", 0) or 0)
    per_kind = layer_kind_fwd_flops(cfg, tokens) if tokens else None
    if per_kind is None:
        return None
    kinds = _layer_kinds(cfg)
    out = [per_kind[kinds[run.start]] * run.length
           for run in layer_runs(hp, model_layer_kinds(cfg))]
    out.append(_after_layers_fwd_flops(cfg, tokens, per_kind))
    return out


# -------------------------------------------------------------- inference
def decode_step_flops(cfg: Any, batch_size: int = 1,
                      context_len: Optional[int] = None) -> Optional[float]:
    """Model FLOPs of ONE decode tick: `batch_size` slots each emit one
    token against a KV cache of `context_len` entries. Forward-only — no 3x
    train multiplier — and the attention term prices query-length 1 against
    the CACHE length (causal=False: the cache rows ARE the visible past, so
    no 0.5 triangular discount), which is what layer_fwd_flops computes when
    tokens=batch and seq_len=context. None for non-transformer configs."""
    layers = getattr(cfg, "num_layers", None)
    ctx = context_len or getattr(cfg, "max_seq_len", None)
    if not layers or not ctx:
        return None
    per_layer = layer_fwd_flops_from_config(
        cfg, tokens=float(batch_size), seq_len=int(ctx))
    if per_layer is None:
        return None
    # decode attention is not causal-masked: every cached position is live
    # (layer_fwd_flops_from_config honours cfg.causal, so undo the 0.5)
    if bool(getattr(cfg, "causal", True)):
        hd = getattr(cfg, "head_dim", None) or cfg.hidden_size // cfg.num_heads
        q_dim = cfg.num_heads * hd
        per_layer += float(batch_size) * (2.0 * (2.0 * ctx * q_dim)) * 0.5
    return layers * per_layer + head_fwd_flops_from_config(
        cfg, tokens=float(batch_size))


def model_bytes_per_decode_token(cfg: Any, *, context_len: Optional[int] = None,
                                 dtype_bytes: int = 2,
                                 batch_size: int = 1) -> Optional[float]:
    """HBM bytes one decode tick must stream per generated token: the full
    weight read (amortised over the batch — weights are read once per STEP,
    not per token) plus the token's own KV-cache read at `context_len`.
    This is the bandwidth-roofline denominator serving throughput divides
    by (search/cost_model.ServeTimeCostModel prices the same quantity from
    profiled tables); None for non-transformer configs."""
    hidden = getattr(cfg, "hidden_size", None)
    layers = getattr(cfg, "num_layers", None)
    heads = getattr(cfg, "num_heads", None)
    if not hidden or not layers or not heads:
        return None
    ctx = context_len or getattr(cfg, "max_seq_len", 0) or 0
    ffn = getattr(cfg, "ffn_hidden", None) or 4 * hidden
    hd = getattr(cfg, "head_dim", None) or hidden // heads
    nkv = getattr(cfg, "num_kv_heads", None) or heads
    swiglu = getattr(cfg, "activation", "gelu") == "swiglu"
    # per-layer weight elements: q + kv (GQA) + out projections and the MLP
    q_dim = heads * hd
    proj = hidden * q_dim + hidden * (2 * nkv * hd) + q_dim * hidden
    mlp = hidden * (2 * ffn) + ffn * hidden if swiglu else 2 * hidden * ffn
    weight_bytes = layers * (proj + mlp) * float(dtype_bytes)
    vocab = getattr(cfg, "vocab_size", 0) or 0
    weight_bytes += hidden * vocab * float(dtype_bytes)  # head matmul read
    kv_bytes = layers * 2.0 * ctx * nkv * hd * float(dtype_bytes)
    return weight_bytes / max(int(batch_size), 1) + kv_bytes


# ------------------------------------------------------------------ ratios
def mfu(flops_per_step: Optional[float], step_ms: Optional[float],
        peak_flops: Optional[float]) -> Optional[float]:
    """Model-FLOPs utilization; None when any input is unknown/degenerate."""
    if not flops_per_step or not step_ms or not peak_flops or step_ms <= 0:
        return None
    return flops_per_step / (step_ms / 1e3) / peak_flops


def flops_per_s(flops_per_step: Optional[float], step_ms: Optional[float]) -> Optional[float]:
    if not flops_per_step or not step_ms or step_ms <= 0:
        return None
    return flops_per_step / (step_ms / 1e3)


def xla_flops(lowered_or_compiled: Any) -> Optional[float]:
    """Total flops XLA's cost analysis reports for a lowered/compiled
    program; None when the backend does not report (TPU plugins vary) or the
    API shape differs. The validation hook for the analytic numbers.

    Caveat (pinned by tests/obs/test_flops.py): HloCostAnalysis counts a
    while/scan BODY once, not per trip — under scan-over-layer-runs the
    reported number covers one layer per run, so it under-reports a deep
    scanned model by roughly the run length. Compare against unrolled
    programs (or per-run bodies), and treat the recorded
    ``xla_flops_per_step`` as a lower bound."""
    try:
        analysis = lowered_or_compiled.cost_analysis()
    except Exception:
        return None
    # jax has returned both a dict and a per-device list of dicts here
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    val = analysis.get("flops")
    try:
        val = float(val)
    except (TypeError, ValueError):
        return None
    # XLA reports -1/0 when it cannot count
    return val if val > 0 else None
