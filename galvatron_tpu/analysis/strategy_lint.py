"""Static strategy-JSON linter (``GLS***`` diagnostics).

Validates a searched/hand-written hybrid-parallel strategy against a model
config and world size with *no device or tracing work*: a bad config is
refused in milliseconds on the host instead of minutes later as an opaque XLA
compile error or an OOM on real TPUs.

Check layers (each gated on the previous one succeeding):

1. raw-dict schema (shared with ``HybridParallelConfig.from_json``):
   unknown/typo'd keys with did-you-mean hints, missing required keys, array
   length mismatches, out-of-range flags — GLS001/GLS005/GLS006.
2. structural (shared with ``HybridParallelConfig.validate``): device-grid and
   batch divisibility — GLS002/GLS003/GLS004.
3. pipeline-engine consistency (``pipeline_engine_diagnostics``): gpipe
   stage-uniformity, ring-cp stage-uniformity under 1F1B — GLS010/GLS011.
4. model-aware divisibility (needs a model config): heads vs tp, sequence vs
   cp/sp shard degrees, vocab vs vocab-tp — GLS007/GLS008/GLS009.
5. cost-model-backed warnings: per-stage memory estimated through the search
   engine's own ``MemoryCostModel`` (profiled activation tables when
   available, an analytic Megatron-style estimate otherwise) vs the HBM
   budget — GLS101; adjacent-layer resharding — GLS102; runnable-but-odd
   configs — GLS103.

Entry points: `lint_strategy_dict`, `lint_strategy_file`, `lint_hp` (for an
already-constructed config — the train driver and search engine hook).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from galvatron_tpu.analysis import diagnostics as D
from galvatron_tpu.config.strategy import (
    HybridParallelConfig,
    schema_diagnostics,
)
from galvatron_tpu.utils.jsonio import read_json_config

# ------------------------------------------------------- model-aware checks


def _model_aware_diagnostics(hp: HybridParallelConfig, model_cfg: Any) -> List[D.Diagnostic]:
    """GLS007/GLS008/GLS009: divisibility of the model's head/sequence/vocab
    dimensions by the per-layer shard degrees. `model_cfg` is duck-typed
    (TransformerConfig or anything exposing the same fields); checks whose
    field is absent (e.g. swin configs have no flat ``num_heads``) are
    skipped rather than guessed."""
    out: List[D.Diagnostic] = []
    num_heads = getattr(model_cfg, "num_heads", None)
    num_kv = getattr(model_cfg, "num_kv_heads", None) or num_heads
    seq_len = getattr(model_cfg, "max_seq_len", None)
    vocab = getattr(model_cfg, "vocab_size", None)
    for i, s in enumerate(hp.layers):
        if num_heads is not None and s.tp > 1:
            # megatron-tp shards the head dim; ulysses all-to-all also
            # re-buckets by head — both need heads % tp == 0
            if num_heads % s.tp != 0:
                out.append(D.make(
                    "GLS007", "layer %d: num_heads=%d not divisible by tp=%d"
                    % (i, num_heads, s.tp), layer=i,
                ))
            elif num_kv is not None and num_kv % s.tp != 0 and s.tp % num_kv != 0:
                out.append(D.make(
                    "GLS007", "layer %d: num_kv_heads=%d neither divides nor "
                    "is divided by tp=%d; GQA heads will pad/replicate "
                    "unevenly" % (i, num_kv, s.tp), layer=i,
                    severity=D.WARNING,
                ))
        if seq_len is not None:
            if s.cp > 1 and seq_len % (2 * s.cp) != 0:
                # the zigzag ring layout splits each rank's shard in two
                # (ops/ring_attention.py asserts seq_len % (2*cp) == 0)
                out.append(D.make(
                    "GLS008", "layer %d: seq_len=%d not divisible by 2*cp=%d "
                    "(ring attention's zigzag layout needs two blocks per "
                    "rank)" % (i, seq_len, 2 * s.cp), layer=i,
                ))
            shard = s.seq_shard_degree * (
                s.tp if (not s.sp and hp.sequence_parallel) else 1
            )
            if shard > 1 and seq_len % shard != 0:
                out.append(D.make(
                    "GLS008", "layer %d: seq_len=%d not divisible by its "
                    "sequence shard degree %d (cp=%d, %s)"
                    % (i, seq_len, shard, s.cp,
                       "ulysses tp=%d" % s.tp if s.sp else "megatron-sp tp=%d" % s.tp),
                    layer=i,
                ))
    if vocab is not None and hp.vocab_tp > 1 and vocab % hp.vocab_tp != 0:
        out.append(D.make(
            "GLS009", "vocab_size=%d not divisible by vocab_tp=%d; pad the "
            "vocab (e.g. to %d) or lower vtp"
            % (vocab, hp.vocab_tp,
               (vocab + hp.vocab_tp - 1) // hp.vocab_tp * hp.vocab_tp),
            key="vtp",
        ))
    if seq_len is not None and hp.vocab_cp > 1 and seq_len % hp.vocab_cp != 0:
        out.append(D.make(
            "GLS008", "seq_len=%d not divisible by vocab_cp=%d (embed/head "
            "sequence sharding)" % (seq_len, hp.vocab_cp), key="vcp",
        ))
    return out


def _comm_quant_diagnostics(
    hp: HybridParallelConfig, model_cfg: Any,
    anomaly_guard: Optional[bool] = None,
) -> List[D.Diagnostic]:
    """GLS013/GLS103 for the comm-precision axis
    (parallel/quant_collectives.py). The quantized grad-sync path refuses —
    with the same reason string the trace-time assert raises — layouts it
    cannot express: non-pure-dp layers, vocab parallelism, zero2 grad
    accumulators, fp8 without runtime support, and composition with the
    anomaly guard (whose spike/rollback contract expects the bitwise GSPMD
    loss — `anomaly_guard` is driver state, so the check only fires when
    the caller passes it). Runnable-but-inert knobs warn GLS103: quantized
    comm with no dp group, param_comm_dtype with no ZeRO-3 leaf, and
    tp_comm_quant with nothing routed through the manual TP rings."""
    from galvatron_tpu.parallel import quant_collectives as QC

    out: List[D.Diagnostic] = []
    asks = any(
        s.grad_comm_dtype != "none" or s.param_comm_dtype != "none"
        for s in hp.layers
    )
    if asks:
        try:
            inert = all(hp.dp(i) <= 1 for i in range(hp.num_layers))
        except Exception:
            inert = False  # broken grids already reported by GLS002
        if inert:
            out.append(D.make(
                "GLS103", "grad/param comm dtypes are set but every layer "
                "has dp=1: there is no gradient sync to quantize",
                key="grad_comm_dtype",
            ))
        else:
            reason = QC.quant_comm_reason(model_cfg, hp,
                                          anomaly_guard=anomaly_guard)
            if reason is not None:
                out.append(D.make(
                    "GLS013", "quantized collectives: %s" % reason,
                    key="grad_comm_dtype",
                ))
        if any(s.param_comm_dtype != "none" and not s.fsdp for s in hp.layers):
            out.append(D.make(
                "GLS103", "param_comm_dtype set on a non-ZeRO-3 layer is "
                "inert: only fsdp=1 layers all-gather parameters",
                key="param_comm_dtype",
            ))
    if hp.tp_comm_quant != "none":
        # the gspmd combination is refused at construction (GLS013 in
        # structural_diagnostics); here the runnable-but-odd rest
        if hp.tp_comm_quant == "fp8_e4m3" and not QC.fp8_supported() \
                and hp.tp_comm_mode != "gspmd":
            out.append(D.make(
                "GLS013", "tp_comm_quant='fp8_e4m3' needs "
                "jax.numpy.float8_e4m3fn, which this jax does not provide",
                key="tp_comm_quant",
            ))
        elif hp.tp_comm_mode != "gspmd" and (
                all(s.tp <= 1 for s in hp.layers) or hp.pp > 1):
            out.append(D.make(
                "GLS103", "tp_comm_quant=%r is inert: no layer routes "
                "through the manual TP rings (%s)" % (
                    hp.tp_comm_quant,
                    "pp>1 keeps the GSPMD path" if hp.pp > 1
                    else "every layer has tp=1"),
                key="tp_comm_quant",
            ))
    return out


def _tp_comm_mode_diagnostics(hp: HybridParallelConfig, model_cfg: Any) -> List[D.Diagnostic]:
    """GLS012: the manual shard_map TP path (tp_comm_mode != gspmd) refuses
    configs it cannot express — report the refusal here, before any tracing,
    with the same reason run_layers would raise with. Deduplicated by
    reason; pp>1 is inert (GLS103), not refused, since the pipeline engines
    keep the GSPMD path."""
    out: List[D.Diagnostic] = []
    if hp.tp_comm_mode == "gspmd" or hp.pp > 1:
        return out
    from galvatron_tpu.parallel.tp_shard_map import manual_tp_reason

    seen = set()
    for i, s in enumerate(hp.layers):
        if s.tp <= 1:
            continue
        reason = manual_tp_reason(model_cfg, hp, s)
        if reason and reason not in seen:
            seen.add(reason)
            out.append(D.make(
                "GLS012", "layer %d: tp_comm_mode=%r refused: %s"
                % (i, hp.tp_comm_mode, reason), layer=i, key="tp_comm_mode",
            ))
    return out


# ----------------------------------------------------- cost-model warnings


def _analytic_parameter_mb(model_cfg: Any) -> Optional[float]:
    """fp32 MB of one transformer layer's parameters, from the model config
    alone (used when no profiled memory table is supplied)."""
    h = getattr(model_cfg, "hidden_size", None)
    nh = getattr(model_cfg, "num_heads", None)
    if h is None or nh is None:
        return None
    nkv = getattr(model_cfg, "num_kv_heads", None) or nh
    ffn = getattr(model_cfg, "ffn_hidden", None) or 4 * h
    attn = h * h * (2.0 + 2.0 * nkv / nh)  # q,o full; k,v scaled by GQA
    mlp_mats = 3 if getattr(model_cfg, "activation", "gelu") == "swiglu" else 2
    mlp = mlp_mats * h * ffn
    return (attn + mlp) * 4.0 / 2**20


def _analytic_activation_dict(model_cfg: Any, max_tp: int) -> Optional[Dict[Any, float]]:
    """Megatron-style per-sample live-activation MB per layer, keyed by tp
    degree (+ 'checkpoint' = the layer input only). bf16 residual stream:
    ~34*s*h bytes of intermediates + 5*a*s^2 of attention scores."""
    h = getattr(model_cfg, "hidden_size", None)
    nh = getattr(model_cfg, "num_heads", None)
    s = getattr(model_cfg, "max_seq_len", None)
    if h is None or nh is None or s is None:
        return None
    base = (34.0 * s * h + 5.0 * nh * s * s) / 2**20
    d: Dict[Any, float] = {"checkpoint": 2.0 * s * h / 2**20}
    t = 1
    while t <= max_tp:
        d[t] = base / t
        t *= 2
    return d


def estimate_stage_memory_mb(
    hp: HybridParallelConfig,
    model_cfg: Any = None,
    memory_profile: Optional[dict] = None,
) -> Optional[List[float]]:
    """Per-pipeline-stage estimated device memory (MB), priced through the
    search engine's MemoryCostModel so the linter and the search agree on
    what fits. `memory_profile` is the profiler's memory JSON
    (``layertype_0`` schema); without it, analytic tables derived from the
    model config are used. Returns None when neither source has enough
    information."""
    from galvatron_tpu.search.cost_model import MemoryCostModel
    from galvatron_tpu.search.cost_model_args import (
        ModelArgs,
        ParallelArgs,
        ProfileModelArgs,
        TrainArgs,
    )

    per_stage = hp.per_stage_devices
    if memory_profile is not None and "layertype_0" in memory_profile:
        lt = memory_profile["layertype_0"]
        param_mb = float(lt["parameter_size"])
        act_dict = dict(lt["tp_activation_per_bsz_dict"])
    else:
        param_mb = _analytic_parameter_mb(model_cfg) if model_cfg is not None else None
        act_dict = (
            _analytic_activation_dict(model_cfg, per_stage)
            if model_cfg is not None else None
        )
    if param_mb is None or not act_dict:
        return None
    seq_len = getattr(model_cfg, "max_seq_len", 2048) if model_cfg is not None else 2048
    hidden = getattr(model_cfg, "hidden_size", 1024) if model_cfg is not None else 1024
    ma = ModelArgs(parameter_size=param_mb, seq_length=seq_len,
                   hidden_size=hidden, layer_num=hp.num_layers)
    ta = TrainArgs(mixed_precision=hp.mixed_precision == "bf16")
    pa = ParallelArgs(
        use_zero2_for_dp=hp.default_dp_type == "zero2",
        sequence_parallel=hp.sequence_parallel,
        chunks=hp.chunks,
        pipeline_type=hp.pipeline_type,
        disable_vtp=True,  # embed/head priced analytically below
    )
    stage_mb = [0.0] * hp.pp
    for i, s in enumerate(hp.layers):
        info: Dict[str, int] = {}
        if s.sp:
            info["sp"] = 1
        if s.cp > 1:
            info["cp"] = s.cp
        if s.fsdp:
            info["fsdp"] = 1
        if s.checkpoint:
            info["cpt"] = 1
            if s.remat_policy != "full":
                info["rp"] = s.remat_policy
        strategy = [hp.pp, s.tp, hp.dp(i), info]
        cost = MemoryCostModel(
            strategy, global_batch_size=hp.global_bsz,
            mbsz=max(1, hp.global_bsz // max(1, hp.chunks)),
            min_tp=1, max_tp=per_stage, model_args=ma, train_args=ta,
            parallel_args=pa,
            profile_model_args=ProfileModelArgs(tp_activation_per_bsz_dict=act_dict),
        ).get_memory_cost()
        stage_mb[hp.stage_of_layer[i]] += cost["enc_total"]
    # embed/head states: vocab-parallel table(s), Adam fp32 states (~4x),
    # sharded over vocab_tp (and over pp for the 1F1B storage layout)
    vocab = getattr(model_cfg, "vocab_size", None) if model_cfg is not None else None
    if vocab is not None:
        tables = 1 if getattr(model_cfg, "tie_embeddings", True) else 2
        vmb = tables * vocab * hidden * 4.0 * 4.0 / 2**20 / hp.vocab_tp
        if hp.pp == 1:
            stage_mb[0] += vmb
        elif hp.pipeline_type == "pipedream_flush":
            for st in range(hp.pp):
                stage_mb[st] += vmb / hp.pp
        else:
            stage_mb[0] += vmb / tables
            stage_mb[-1] += vmb / tables
    return stage_mb


def _warning_diagnostics(
    hp: HybridParallelConfig,
    model_cfg: Any = None,
    memory_budget_gb: Optional[float] = None,
    memory_profile: Optional[dict] = None,
) -> List[D.Diagnostic]:
    out: List[D.Diagnostic] = []
    # GLS102: adjacent layers whose activations live on different mesh axes
    # force a resharding collective between them on every microbatch
    for i in range(1, hp.num_layers):
        a, b = hp.layers[i - 1], hp.layers[i]
        if hp.stage_of_layer[i - 1] != hp.stage_of_layer[i]:
            continue  # stage boundary: the p2p transfer reshards anyway
        moves = []
        if a.tp != b.tp or a.sp != b.sp:
            moves.append("tp%s%d->tp%s%d" % ("/sp" if a.sp else "", a.tp,
                                             "/sp" if b.sp else "", b.tp))
        if a.cp != b.cp:
            moves.append("cp%d->cp%d" % (a.cp, b.cp))
        if a.tp == b.tp and a.tp > 1 and a.tp_consec != b.tp_consec:
            moves.append("tp placement consec%d->consec%d" % (a.tp_consec, b.tp_consec))
        if moves:
            out.append(D.make(
                "GLS102", "layers %d->%d reshard activations within a stage "
                "(%s): an allgather/all-to-all per microbatch; consider "
                "aligning the run of layers" % (i - 1, i, ", ".join(moves)),
                layer=i,
            ))
    # GLS103: runnable but almost certainly not what was meant
    if hp.pp == 1 and hp.pipeline_type == "pipedream_flush":
        out.append(D.make(
            "GLS103", "pipeline_type='pipedream_flush' with pp=1 runs the "
            "plain single-stage path; the flag is inert", key="pipeline_type",
        ))
    for i, s in enumerate(hp.layers):
        if s.sp and s.tp == 1:
            out.append(D.make(
                "GLS103", "layer %d: use_sp=1 with tp=1 is a no-op (ulysses "
                "repurposes the tp axis)" % i, layer=i,
            ))
            break
    if hp.tp_comm_mode != "gspmd":
        if all(s.tp <= 1 for s in hp.layers):
            out.append(D.make(
                "GLS103", "tp_comm_mode=%r with tp=1 on every layer is "
                "inert: there are no TP collectives to make visible or "
                "overlap" % hp.tp_comm_mode, key="tp_comm_mode",
            ))
        elif hp.pp > 1:
            out.append(D.make(
                "GLS103", "tp_comm_mode=%r with pp=%d is inert: the "
                "pipeline engines drive layer_forward directly and keep "
                "the GSPMD TP path" % (hp.tp_comm_mode, hp.pp),
                key="tp_comm_mode",
            ))
    # remat precedence rule (config/strategy.py): the per-layer serialized
    # remat_policy is authoritative at runtime; a non-default global flag
    # that disagrees with any layer was shadowed, not applied
    if hp.remat_policy != "full" and any(
            s.remat_policy != hp.remat_policy for s in hp.layers):
        out.append(D.make(
            "GLS103", "global remat_policy=%r is shadowed by serialized "
            "per-layer policies (%d of %d layers differ): the per-layer "
            "field is authoritative; drop the flag or edit the JSON"
            % (hp.remat_policy,
               sum(1 for s in hp.layers if s.remat_policy != hp.remat_policy),
               hp.num_layers),
            key="remat_policy",
        ))
    # GLS101: estimated memory vs budget
    if memory_budget_gb:
        stage_mb = estimate_stage_memory_mb(hp, model_cfg, memory_profile)
        if stage_mb is not None:
            budget_mb = memory_budget_gb * 1024.0
            for st, mb in enumerate(stage_mb):
                if mb > budget_mb:
                    out.append(D.make(
                        "GLS101", "stage %d estimated %.2f GB exceeds the "
                        "%.1f GB budget (%s estimate via MemoryCostModel)"
                        % (st, mb / 1024.0, memory_budget_gb,
                           "profiled" if memory_profile else "analytic"),
                    ))
    return out


def serve_kv_mb_per_device(
    hp: HybridParallelConfig,
    model_cfg: Any,
    max_concurrency: int,
    page_size: int,
    dtype_bytes: int = 2,
) -> Optional[float]:
    """Per-device MB the decode KV cache pins: `max_concurrency` slots, each
    holding a full-context (k, v) pair per layer, sharded the way
    serve/kv_cache.layer_kv_spec shards it (slots over dp, kv heads over tp
    when divisible). The serve search and the GLS014 budget check price KV
    through this one function so they agree on what fits."""
    nh = getattr(model_cfg, "num_heads", None)
    hd = getattr(model_cfg, "head_dim", None)
    seq = getattr(model_cfg, "max_seq_len", None)
    if nh is None or seq is None:
        return None
    nkv = getattr(model_cfg, "num_kv_heads", None) or nh
    hd = hd or getattr(model_cfg, "hidden_size") // nh
    page = max(int(page_size), 1)
    max_ctx = -(-seq // page) * page  # bucket-quantised full context
    total = 0.0
    for i, s in enumerate(hp.layers):
        slots_per_dev = max_concurrency / max(hp.dp(i), 1)
        heads_per_dev = nkv / s.tp if (s.tp > 1 and nkv % s.tp == 0) else nkv
        total += 2.0 * slots_per_dev * max_ctx * heads_per_dev * hd * dtype_bytes
    return total / 2**20


def _serve_diagnostics(
    hp: HybridParallelConfig,
    model_cfg: Any,
    memory_budget_gb: Optional[float],
) -> List[D.Diagnostic]:
    """GLS014: layouts and budgets a decode engine cannot realise
    (serve/kv_cache.py raises the same refusals at construction; the lint
    fires them pre-trace with the layer named). Latency-bound infeasibility
    is the search engine's half of GLS014 — it needs the time cost models."""
    out: List[D.Diagnostic] = []
    if hp.pp > 1:
        out.append(D.make(
            "GLS014", "pp=%d: the decode engine drives single-token steps "
            "over one stage; pipeline parallelism is unsupported in serve "
            "mode" % hp.pp, key="pp_deg",
        ))
    for i, s in enumerate(hp.layers):
        if s.cp > 1:
            out.append(D.make(
                "GLS014", "layer %d: cp=%d — ring context parallelism never "
                "materialises the full per-layer k/v, so a decode cache "
                "cannot be filled; serve layouts require cp=1" % (i, s.cp),
                layer=i,
            ))
            break
    for i, s in enumerate(hp.layers):
        if s.sp:
            out.append(D.make(
                "GLS014", "layer %d: use_sp=1 (Ulysses) repurposes the tp "
                "axes for sequence all-to-alls a length-1 decode query "
                "cannot use; serve layouts require sp=0" % i, layer=i,
            ))
            break
    conc = hp.serve_max_concurrency
    if conc > 0 and model_cfg is not None and memory_budget_gb:
        kv_mb = serve_kv_mb_per_device(
            hp, model_cfg, conc, hp.serve_page_size or 16)
        layer_mb = _analytic_parameter_mb(model_cfg)
        if kv_mb is not None and layer_mb is not None:
            # bf16 inference weights, sharded over tp (and dp when fsdp)
            param_mb = sum(
                layer_mb / 2.0 / s.tp / (hp.dp(i) if s.fsdp else 1)
                for i, s in enumerate(hp.layers)
            )
            budget_mb = memory_budget_gb * 1024.0
            if kv_mb + param_mb > budget_mb:
                out.append(D.make(
                    "GLS014", "KV cache for %d concurrent slots needs %.1f MB"
                    "/device on top of %.1f MB of weights — over the %.1f GB "
                    "budget; lower concurrency, context, or raise tp/dp"
                    % (conc, kv_mb, param_mb, memory_budget_gb),
                    key="serve_max_concurrency",
                ))
    return out


# ------------------------------------------------------------- entry points


def lint_hp(
    hp: HybridParallelConfig,
    model_cfg: Any = None,
    memory_budget_gb: Optional[float] = None,
    memory_profile: Optional[dict] = None,
    file: Optional[str] = None,
    anomaly_guard: Optional[bool] = None,
    mode: Optional[str] = None,
    sdc_check: Optional[str] = None,
    sdc_interval: Optional[int] = None,
    autotune: Optional[str] = None,
    autotune_margin: Optional[float] = None,
    elastic_strategy: Optional[str] = None,
) -> D.DiagnosticReport:
    """Lint an already-constructed config (the train-driver / search-engine
    hook): engine-consistency + model-aware checks + cost warnings. The
    construction itself already enforced schema + structure.
    ``anomaly_guard`` is driver state (not part of the strategy): the train
    driver passes it so the quantized-comm x guard refusal (GLS013) fires
    pre-trace; file-level lints leave it None and skip that check.
    ``mode`` is likewise driver state: "serve" turns on the GLS014
    serve-feasibility layer (cli/serve and the serve-objective search),
    "train" warns GLS103 on inert serve knobs; None (file-level lint
    without --serve) runs neither. ``sdc_check``/``sdc_interval`` are the
    silent-corruption sentinel flags: voting on a layout with no per-device
    replica (runtime/sdc.vote_reason) silently downgrades at runtime, and
    an interval with the sentinel off is inert — both warned GLS103 here so
    the operator learns it before a multi-day run does.
    ``autotune``/``autotune_margin``/``elastic_strategy`` are the online-
    autotuner flags: `apply` composed with a pinned --elastic_strategy is
    refused outright (GLS017 — every swap the tuner performs would be undone
    by the next migration resolving back to the pinned JSON), and knobs that
    silently degrade or disable the tuner warn GLS103."""
    report = D.DiagnosticReport()
    report.extend(hp.structural_diagnostics())
    report.extend(hp.pipeline_engine_diagnostics())
    if model_cfg is not None:
        report.extend(_model_aware_diagnostics(hp, model_cfg))
    report.extend(_tp_comm_mode_diagnostics(hp, model_cfg))
    from galvatron_tpu.models.parts import unsupported_reason
    from galvatron_tpu.parallel.quant_collectives import wants_quant_comm

    # GLS018, pre-trace: what construct_hybrid_parallel_model raises at trace time
    reason = unsupported_reason(model_cfg, hp, mode, autotune, quant=wants_quant_comm(hp))
    if reason is not None:
        report.add(D.make("GLS018", reason))
    report.extend(_comm_quant_diagnostics(hp, model_cfg, anomaly_guard))
    report.extend(_warning_diagnostics(hp, model_cfg, memory_budget_gb, memory_profile))
    if mode == "serve":
        report.extend(_serve_diagnostics(hp, model_cfg, memory_budget_gb))
    elif mode == "train" and (hp.serve_max_concurrency or hp.serve_page_size):
        report.add(D.make(
            "GLS103", "serve_max_concurrency/serve_page_size are inert in "
            "train mode: only the serve engine allocates a KV cache",
            key="serve_max_concurrency",
        ))
    if mode == "train" and (hp.serve_p99_ttft_ms or hp.serve_max_pending):
        report.add(D.make(
            "GLS103", "serve_p99_ttft_ms/serve_max_pending are inert in "
            "train mode: admission control and overload shedding live in "
            "the serve batcher, not the training loop",
            key="serve_p99_ttft_ms",
        ))
    if sdc_check == "vote":
        from galvatron_tpu.runtime.sdc import vote_reason

        reason = vote_reason(hp)
        if reason is not None:
            report.add(D.make(
                "GLS103", "sdc_check=vote downgrades to digest on this "
                "layout (%s): cross-replica voting needs a full per-device "
                "parameter replica" % reason,
                key="sdc_check",
            ))
    if sdc_interval and (sdc_check or "off") == "off":
        report.add(D.make(
            "GLS103", "sdc_interval is inert with sdc_check off: there is "
            "no integrity digest to emit",
            key="sdc_interval",
        ))
    autotune_mode = autotune or "off"
    if autotune_mode == "apply" and elastic_strategy:
        report.add(D.make(
            "GLS017", "--autotune apply with a pinned --elastic_strategy: "
            "any strategy the autotuner swaps to would be reverted by the "
            "next migration resolving back to the pinned JSON; drop one of "
            "the two (observe mode composes fine)",
            key="autotune",
        ))
    if autotune_mode != "off":
        if not hp.scan_layers:
            report.add(D.make(
                "GLS103", "autotune with scan_layers off: every hot-swap "
                "recompiles a program whose build time grows with layer "
                "count, inflating the swap cost the amortization check "
                "must recover",
                key="autotune",
            ))
        if hp.pp > 1:
            report.add(D.make(
                "GLS103", "autotune with pp=%d: the pipeline engines bypass "
                "the per-LayerRun path, so the calibrator falls back to "
                "whole-step scaling and the measured tables are coarser"
                % hp.pp,
                key="autotune",
            ))
    if autotune_margin is not None and autotune_mode == "off":
        report.add(D.make(
            "GLS103", "autotune_margin is inert with autotune off: there "
            "is no re-search decision to apply the hysteresis to",
            key="autotune_margin",
        ))
    if file:
        report.diagnostics = [
            D.Diagnostic(**{**d.__dict__, "file": d.file or file})
            for d in report.diagnostics
        ]
    return report


def lint_strategy_dict(
    cfg_dict: dict,
    world_size: int,
    model_cfg: Any = None,
    memory_budget_gb: Optional[float] = None,
    memory_profile: Optional[dict] = None,
    file: Optional[str] = None,
    mode: Optional[str] = None,
    **overrides,
) -> D.DiagnosticReport:
    """Lint a raw strategy dict (the on-disk JSON schema) bottom-up. Stops
    after the schema layer if the dict cannot construct at all."""
    report = D.DiagnosticReport()
    schema = schema_diagnostics(cfg_dict)
    report.extend(schema)
    if any(d.severity == D.ERROR for d in schema):
        return _with_file(report, file)
    try:
        hp = HybridParallelConfig.from_json(cfg_dict, world_size=world_size, **overrides)
    except D.DiagnosticError as e:
        report.extend(e.diagnostics)
        return _with_file(report, file)
    except (KeyError, ValueError, TypeError) as e:
        report.add(D.make("GLS005", "config failed to construct: %s" % e))
        return _with_file(report, file)
    report.extend(lint_hp(
        hp, model_cfg=model_cfg, memory_budget_gb=memory_budget_gb,
        memory_profile=memory_profile, mode=mode,
    ).diagnostics)
    return _with_file(report, file)


def lint_strategy_file(
    path: str,
    world_size: int,
    model_cfg: Any = None,
    memory_budget_gb: Optional[float] = None,
    memory_profile: Optional[dict] = None,
    mode: Optional[str] = None,
    **overrides,
) -> D.DiagnosticReport:
    return lint_strategy_dict(
        read_json_config(path), world_size, model_cfg=model_cfg,
        memory_budget_gb=memory_budget_gb, memory_profile=memory_profile,
        file=path, mode=mode, **overrides,
    )


def _with_file(report: D.DiagnosticReport, file: Optional[str]) -> D.DiagnosticReport:
    if file:
        report.diagnostics = [
            D.Diagnostic(**{**d.__dict__, "file": d.file or file})
            for d in report.diagnostics
        ]
    return report
