"""Prefill/decode inference engine with continuous batching.

Execution model
---------------
- **Prefill** (one request, compute-bound): the prompt runs through the SAME
  `models/base.run_layers` scan path the trainer uses, with `collect_kv=True`
  turning each layer's post-rope (k, v) into scan side outputs; the block is
  written into the request's cache slot and the first token is sampled from
  the last valid position. TTFT is dominated by this step.
- **Decode** (all active slots, bandwidth-bound): one jitted step embeds the
  last sampled token per slot at position `lengths`, runs
  `models/base.decode_layer_forward` per layer against the cached K/V
  (causality + slot-length masking folded into one additive
  `kv_cache.length_bias`), appends the new k/v in place, and samples.
- **Buckets**: context lengths are quantised to `page_size` pages; each
  (kind, page-count) pair gets ONE executable, AOT-compiled through an
  in-process memo of live executables (re-launches are served by the
  persistent compile cache, utils/compile_cache.py).
- **Continuous batching**: slot-based admission in strict arrival (FIFO)
  order; a slot frees the moment its request hits `max_new_tokens`, and the
  next pending request is admitted at the following scheduler tick, so batch
  occupancy refills without draining.
"""

from __future__ import annotations

import dataclasses
import hashlib
import random
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.models import base as M
from galvatron_tpu.models.config import TransformerConfig
from galvatron_tpu.models.parts.embed_head import embed_tokens, lm_logits
from galvatron_tpu.obs import telemetry as T
from galvatron_tpu.serve.kv_cache import (
    KVCacheConfig,
    bucket_pages,
    init_kv_cache,
    kv_cache_specs,
    length_bias,
    request_fits,
    write_prompt_kv,
)
from galvatron_tpu.parallel import spec as S
from galvatron_tpu.parallel.mesh import layer_axes, vocab_axes


def _cache_constrainer(cfg, hp, mesh, max_slots=None):
    """Pin the returned cache pytree to its canonical strategy-derived
    layout. Without this, GSPMD propagates whatever sharding the last update
    op preferred into the jit output, and the SECOND call of the memoized
    AOT executable rejects its own previous output ("input sharding does not
    match the sharding the computation was compiled with")."""
    if hp is None or mesh is None:
        return lambda c: c
    specs = kv_cache_specs(hp, mesh, cfg, max_slots)

    def constrain(c):
        return {
            "k": [S.constrain(x, mesh, sp) for x, sp in zip(c["k"], specs["k"])],
            "v": [S.constrain(x, mesh, sp) for x, sp in zip(c["v"], specs["v"])],
            "lengths": S.constrain(c["lengths"], mesh, specs["lengths"]),
        }

    return constrain

# ------------------------------------------------------------- AOT executables
# In-process memo of live compiled executables, keyed on (mesh device ids,
# HLO digest) — the cli/train.py `_STEP_EXECUTABLES` discipline.
_SERVE_EXECUTABLES: "OrderedDict[Tuple, Any]" = OrderedDict()
_SERVE_EXECUTABLES_MAX = 32


def _exec_key(mesh: Optional[Mesh], lowered) -> Optional[Tuple]:
    try:
        dev_ids = (
            tuple(int(d.id) for d in mesh.devices.flat)
            if mesh is not None else ("nomesh",)
        )
        return (dev_ids, hashlib.sha256(lowered.as_text().encode()).hexdigest())
    except Exception:
        return None


def _aot_executable(jitted, mesh, *args):
    """AOT-compile `jitted` for these args through the memo; returns a
    callable — `jitted` itself (plain-jit fallback) when lowering or AOT
    compilation is unsupported. Lower/compile happens at most once per
    (mesh, HLO) — callers hold on to the result and reuse it every tick."""
    try:
        lowered = jitted.lower(*args)
        key = _exec_key(mesh, lowered)
    except Exception:
        return jitted
    if key is not None and key in _SERVE_EXECUTABLES:
        _SERVE_EXECUTABLES.move_to_end(key)
        return _SERVE_EXECUTABLES[key]
    try:
        compiled = lowered.compile()
    except ValueError:
        return jitted
    if key is not None:
        _SERVE_EXECUTABLES[key] = compiled
        while len(_SERVE_EXECUTABLES) > _SERVE_EXECUTABLES_MAX:
            _SERVE_EXECUTABLES.popitem(last=False)
    return compiled


# ------------------------------------------------------------------- sampling
def sample_token(logits: jax.Array, rng: jax.Array, temperature: float) -> jax.Array:
    """Greedy (temperature <= 0) or temperature sampling over (..., V)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits / temperature, axis=-1).astype(jnp.int32)


# ------------------------------------------------------------ step factories
def make_prefill_step(
    cfg: TransformerConfig,
    hp: Optional[HybridParallelConfig],
    mesh: Optional[Mesh],
    kv_cfg: KVCacheConfig,
    pages: int,
    temperature: float = 0.0,
) -> Callable:
    """Build the prefill function for one `pages` bucket:
    (params, cache, tokens (1, ctx_b), prompt_len, slot, rng)
      -> (cache', first_token (1,), last_logits (1, V)).
    Padding past prompt_len is masked in attention and in the sampled
    position; its garbage K/V lands in the cache but stays behind the
    length mask until decode overwrites it."""
    ctx_b = pages * kv_cfg.page_size
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    constrain_cache = _cache_constrainer(cfg, hp, mesh, kv_cfg.max_slots)

    def prefill_bucket(params, cache, tokens, prompt_len, slot, rng):
        positions = jnp.broadcast_to(jnp.arange(ctx_b), (1, ctx_b))
        valid = (jnp.arange(ctx_b) < prompt_len)[None, :]
        bias = M.padding_attn_bias(valid)
        x = embed_tokens(params["embed"], tokens, positions, cfg, mesh, vax)
        x, kvs = M.run_layers(
            params, x, positions, cfg,
            hp if use_hp else None, mesh if use_hp else None,
            attn_bias=bias, collect_kv=True,
        )
        h_last = jax.lax.dynamic_slice(
            x, (0, prompt_len - 1, 0), (1, 1, x.shape[-1])
        )
        logits = lm_logits(params, h_last, cfg)[:, 0]
        token = sample_token(logits, rng, temperature)
        cache = constrain_cache(write_prompt_kv(cache, kvs, slot, prompt_len))
        return cache, token, logits

    return jax.jit(prefill_bucket, donate_argnums=(1,))


def make_decode_step(
    cfg: TransformerConfig,
    hp: Optional[HybridParallelConfig],
    mesh: Optional[Mesh],
    kv_cfg: KVCacheConfig,
    pages: int,
    temperature: float = 0.0,
) -> Callable:
    """Build the single-token decode function for one `pages` bucket:
    (params, cache, tokens (slots,), active (slots,) bool, rng)
      -> (cache', next_tokens (slots,), logits (slots, V)).
    All slots step together; inactive slots compute (and write masked
    garbage k/v at their frozen length) but neither advance `lengths` nor
    change their token — their columns are overwritten at re-admission."""
    ctx_b = pages * kv_cfg.page_size
    use_hp = hp is not None and mesh is not None
    vax = vocab_axes(hp) if use_hp else None
    constrain_cache = _cache_constrainer(cfg, hp, mesh, kv_cfg.max_slots)

    def decode(params, cache, tokens, active, rng):
        lengths = cache["lengths"]
        positions = lengths[:, None]
        x = embed_tokens(params["embed"], tokens[:, None], positions, cfg, mesh, vax)
        bias = length_bias(lengths, ctx_b)
        k_list, v_list = list(cache["k"]), list(cache["v"])
        for li in range(cfg.num_layers):
            axes = layer_axes(hp, li) if use_hp else None
            k_c = jax.lax.slice_in_dim(k_list[li], 0, ctx_b, axis=1)
            v_c = jax.lax.slice_in_dim(v_list[li], 0, ctx_b, axis=1)
            x, k_c, v_c = M.decode_layer_forward(
                params["layers"][li], x, positions, cfg,
                k_cache=k_c, v_cache=v_c, write_index=lengths,
                mesh=mesh if use_hp else None, axes=axes, attn_bias=bias,
            )
            k_list[li] = jax.lax.dynamic_update_slice(k_list[li], k_c, (0, 0, 0, 0))
            v_list[li] = jax.lax.dynamic_update_slice(v_list[li], v_c, (0, 0, 0, 0))
        logits = lm_logits(params, x, cfg)[:, 0]
        next_tok = sample_token(logits, rng, temperature)
        next_tok = jnp.where(active, next_tok, tokens)
        lengths = lengths + active.astype(jnp.int32)
        return (
            constrain_cache({"k": k_list, "v": v_list, "lengths": lengths}),
            next_tok,
            logits,
        )

    return jax.jit(decode, donate_argnums=(1,))


# -------------------------------------------------------------------- engine
class ServeEngine:
    """Owns the cache + per-bucket executables; host-level prefill/decode API
    returning numpy. The scheduler (ContinuousBatcher) drives it."""

    def __init__(
        self,
        cfg: TransformerConfig,
        params: Any,
        kv_cfg: KVCacheConfig,
        hp: Optional[HybridParallelConfig] = None,
        mesh: Optional[Mesh] = None,
        temperature: float = 0.0,
        rng_seed: int = 0,
    ):
        if cfg.head_type != "lm":
            raise ValueError("serving requires a causal LM head, got head_type=%r" % cfg.head_type)
        self.cfg, self.params, self.kv_cfg = cfg, params, kv_cfg
        self.hp, self.mesh = hp, mesh
        self.temperature = temperature
        self.cache = init_kv_cache(cfg, kv_cfg, hp, mesh)
        self._rng = jax.random.PRNGKey(rng_seed)
        self._prefill_fns: Dict[int, Callable] = {}
        self._decode_fns: Dict[int, Callable] = {}
        self._execs: Dict[Tuple[str, int], Callable] = {}

    def _next_rng(self) -> jax.Array:
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _prefill_fn(self, pages: int) -> Callable:
        if pages not in self._prefill_fns:
            self._prefill_fns[pages] = make_prefill_step(
                self.cfg, self.hp, self.mesh, self.kv_cfg, pages, self.temperature
            )
        return self._prefill_fns[pages]

    def _decode_fn(self, pages: int) -> Callable:
        if pages not in self._decode_fns:
            self._decode_fns[pages] = make_decode_step(
                self.cfg, self.hp, self.mesh, self.kv_cfg, pages, self.temperature
            )
        return self._decode_fns[pages]

    def _call(self, kind: str, pages: int, jitted: Callable, *args):
        ekey = (kind, pages)
        fn = self._execs.get(ekey)
        if fn is None:
            fn = _aot_executable(jitted, self.mesh, *args)
            self._execs[ekey] = fn
        return fn(*args)

    def prefill(self, prompt: Sequence[int], slot: int) -> Tuple[int, np.ndarray]:
        """Run one prompt into cache row `slot`; returns (first_token, logits)."""
        plen = len(prompt)
        pages = bucket_pages(plen, self.kv_cfg.page_size, self.kv_cfg.max_pages)
        ctx_b = pages * self.kv_cfg.page_size
        tokens = np.zeros((1, ctx_b), np.int32)
        tokens[0, :plen] = np.asarray(prompt, np.int32)
        self.cache, tok, logits = self._call(
            "prefill", pages, self._prefill_fn(pages),
            self.params, self.cache, jnp.asarray(tokens),
            jnp.int32(plen), jnp.int32(slot), self._next_rng(),
        )
        return int(jax.device_get(tok)[0]), np.asarray(jax.device_get(logits))[0]

    def decode_step(
        self, tokens: np.ndarray, active: np.ndarray, pages: int
    ) -> Tuple[np.ndarray, np.ndarray]:
        """One decode tick over every slot; returns (next_tokens, logits)."""
        self.cache, next_tok, logits = self._call(
            "decode", pages, self._decode_fn(pages),
            self.params, self.cache,
            jnp.asarray(tokens, jnp.int32), jnp.asarray(active, bool),
            self._next_rng(),
        )
        return np.asarray(jax.device_get(next_tok)), np.asarray(jax.device_get(logits))


# ---------------------------------------------------------------- load model
@dataclasses.dataclass
class Request:
    rid: int
    arrival_s: float
    prompt: List[int]
    max_new_tokens: int
    deadline_s: Optional[float] = None  # absolute TTFT deadline (batcher clock)
    # runtime bookkeeping (filled by the batcher)
    slot: Optional[int] = None
    output: List[int] = dataclasses.field(default_factory=list)
    prefill_start_t: Optional[float] = None
    first_token_t: Optional[float] = None
    done_t: Optional[float] = None
    # terminal disposition: "pending" while live, then exactly one of
    # "completed" | "shed" (retryable, never started or abandoned mid-decode)
    # | "failed" (non-retryable, e.g. oversize for the cache geometry).
    status: str = "pending"
    finish_reason: Optional[str] = None
    retryable: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def journal(self) -> List[int]:
        """The request's full token history — prompt plus every sampled
        token. Pure token sequences are replayable by construction: the
        exact cache state of an in-flight request is reproduced by greedy
        re-prefill of ``journal[:-1]`` (see ContinuousBatcher.migrate_to)."""
        return list(self.prompt) + list(self.output)

    def ttft_ms(self) -> Optional[float]:
        if self.first_token_t is None:
            return None
        return (self.first_token_t - self.arrival_s) * 1000.0

    def tpot_ms(self) -> Optional[float]:
        if self.done_t is None or self.first_token_t is None or len(self.output) < 2:
            return None
        return (self.done_t - self.first_token_t) * 1000.0 / (len(self.output) - 1)


def synthetic_requests(
    n: int,
    *,
    vocab_size: int,
    seed: int = 0,
    rate_rps: float = 0.0,
    prompt_len_range: Tuple[int, int] = (4, 16),
    max_new_tokens: int = 8,
) -> List[Request]:
    """Poisson arrivals (`rate_rps` > 0; 0 = a t=0 backlog) with uniform
    prompt lengths — the synthetic open-loop load for cli/serve."""
    rnd = random.Random(seed)
    t = 0.0
    out = []
    for rid in range(n):
        if rate_rps > 0:
            t += rnd.expovariate(rate_rps)
        plen = rnd.randint(*prompt_len_range)
        prompt = [rnd.randrange(vocab_size) for _ in range(plen)]
        out.append(Request(rid=rid, arrival_s=t, prompt=prompt, max_new_tokens=max_new_tokens))
    return out


def replay_requests(path: str, *, vocab_size: int, seed: int = 0) -> List[Request]:
    """Replay a trace: JSONL of {"arrival_s", "prompt_len", "max_new_tokens"}
    (prompt token ids synthesised deterministically from `seed`)."""
    import json

    rnd = random.Random(seed)
    out = []
    with open(path) as f:
        for rid, line in enumerate(ln for ln in f if ln.strip()):
            rec = json.loads(line)
            plen = int(rec["prompt_len"])
            out.append(Request(
                rid=rid,
                arrival_s=float(rec.get("arrival_s", 0.0)),
                prompt=[rnd.randrange(vocab_size) for _ in range(plen)],
                max_new_tokens=int(rec.get("max_new_tokens", 8)),
            ))
    return out


# ----------------------------------------------------------------- scheduler
class ContinuousBatcher:
    """Slot-based continuous batching over a ServeEngine (or any object with
    the same prefill/decode_step surface — scheduler tests use a fake).

    Invariants (tests/serve/test_scheduler.py):
    - admission is strict FIFO in arrival order — a later request never
      occupies a slot while an earlier arrived one waits;
    - no slot leak: every admitted request frees its slot at completion, and
      a slot is never doubly occupied — including under exceptions in
      prefill or decode;
    - bucket routing: each decode tick runs in the smallest page bucket
      covering every active slot's next write position;
    - no request ever raises out of the batcher: oversize prompts, blown
      deadlines, and predicted-TTFT overload are structured rejections
      (`Request.status`/`finish_reason`/`retryable`) collected in
      ``self.shed``, not exceptions.

    Admission control: ``p99_ttft_ms`` arms a cheap predicted-TTFT model —
    time already waited plus queue position times the learned median prefill
    and decode-tick costs — that sheds (retryable) any pending request which
    cannot meet the bound. ``max_pending`` bounds the arrived-but-unadmitted
    queue; overflow sheds from the tail (newest arrivals). Both engage only
    after ``min_shed_samples`` prefills AND ticks have been observed, so
    compile warmup never sheds.

    Resilience: an optional ``watchdog`` (runtime/health.Watchdog) is armed
    around every prefill and decode tick with learned deadlines; an optional
    ``control`` callback is polled once per scheduler iteration and may
    return a drain-reason string (e.g. ``"SIGTERM"``, ``"watchdog"``) to
    stop admission and wind down, or trigger a live migration itself via
    ``migrate_to`` and return None (the cli/serve resilience hook).
    """

    def __init__(
        self,
        engine,
        kv_cfg: KVCacheConfig,
        clock: Optional[Callable[[], float]] = None,
        p99_ttft_ms: float = 0.0,
        max_pending: int = 0,
        request_timeout_s: float = 0.0,
        min_shed_samples: int = 3,
        watchdog=None,
        control: Optional[Callable[["ContinuousBatcher"], Optional[str]]] = None,
    ):
        self.engine = engine
        self.kv_cfg = kv_cfg
        self._clock = clock if clock is not None else time.monotonic
        self._t0: Optional[float] = None
        self.p99_ttft_ms = float(p99_ttft_ms)
        self.max_pending = int(max_pending)
        self.request_timeout_s = float(request_timeout_s)
        self.min_shed_samples = int(min_shed_samples)
        self.watchdog = watchdog
        self.control = control
        # host-side per-slot state (device lengths are never read back)
        self.slot_req: List[Optional[Request]] = [None] * kv_cfg.max_slots
        self.slot_len = np.zeros((kv_cfg.max_slots,), np.int64)
        self.slot_tok = np.zeros((kv_cfg.max_slots,), np.int32)
        self.decode_steps = 0
        self.completed: List[Request] = []
        self.shed: List[Request] = []
        self.migrations = 0
        self.drain_reason: Optional[str] = None
        # learned cost medians feeding the predicted-TTFT shed model
        self._prefill_ms: deque = deque(maxlen=64)
        self._tick_ms: deque = deque(maxlen=64)

    def now(self) -> float:
        if self._t0 is None:
            self._t0 = self._clock()
        return self._clock() - self._t0

    def _free_slot(self) -> Optional[int]:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def occupancy(self) -> int:
        return sum(1 for r in self.slot_req if r is not None)

    # ------------------------------------------------- rejection + shedding
    def _reject(self, req: Request, reason: str, retryable: bool,
                **extra) -> None:
        """Terminal structured rejection: mark the request, collect it, and
        emit a `serve_shed` event. Never touches slot state — callers free
        any slot the request held BEFORE rejecting."""
        req.status = "shed" if retryable else "failed"
        req.finish_reason = reason
        req.retryable = retryable
        req.done_t = self.now()
        req.slot = None
        self.shed.append(req)
        T.emit(
            "serve_shed", id=req.rid, reason=reason,
            retryable=int(retryable), prompt_len=req.prompt_len,
            output_len=len(req.output) or None,
            waited_ms=max(0.0, (self.now() - req.arrival_s) * 1000.0),
            **extra,
        )

    @staticmethod
    def _median(xs) -> float:
        if not xs:
            return 0.0
        s = sorted(xs)
        return float(s[len(s) // 2])

    def predicted_ttft_ms(self, req: Request, queue_pos: int) -> float:
        """Cheap TTFT forecast: time already waited + one prefill for this
        request + (queue depth ahead) × (median prefill + median tick) —
        every request ahead costs its own prefill and roughly one decode
        tick before a slot frees."""
        waited = max(0.0, (self.now() - req.arrival_s) * 1000.0)
        mp = self._median(self._prefill_ms)
        mt = self._median(self._tick_ms)
        return waited + mp + queue_pos * (mp + mt)

    def _shed_scan(self, pending: deque) -> None:
        """Drop pending requests that cannot be served: blown per-request
        deadlines, predicted-TTFT overload, and pending-queue overflow.
        Rebuilds the deque preserving FIFO order of the survivors."""
        if not pending:
            return
        now = self.now()
        learned = (len(self._prefill_ms) >= self.min_shed_samples
                   and len(self._tick_ms) >= self.min_shed_samples)
        keep: List[Request] = []
        arrived_kept = 0
        for req in pending:
            if req.arrival_s > now:
                keep.append(req)
                continue
            deadline = req.deadline_s
            if deadline is None and self.request_timeout_s > 0:
                deadline = req.arrival_s + self.request_timeout_s
            if deadline is not None and now > deadline:
                self._reject(req, "deadline", retryable=True)
                continue
            if self.p99_ttft_ms > 0 and learned:
                pred = self.predicted_ttft_ms(req, arrived_kept)
                if pred > self.p99_ttft_ms:
                    self._reject(req, "predicted_ttft", retryable=True,
                                 predicted_ttft_ms=pred,
                                 queue_depth=arrived_kept)
                    continue
            if self.max_pending > 0 and arrived_kept >= self.max_pending:
                self._reject(req, "queue_full", retryable=True,
                             queue_depth=arrived_kept)
                continue
            arrived_kept += 1
            keep.append(req)
        if len(keep) != len(pending):
            pending.clear()
            pending.extend(keep)

    def _admit(self, pending: deque) -> None:
        while pending:
            req = pending[0]
            if req.arrival_s > self.now():
                break
            slot = self._free_slot()
            if slot is None:
                break
            pending.popleft()
            if not request_fits(self.kv_cfg, req.prompt_len, req.max_new_tokens):
                # structured per-request refusal: the slot was never
                # occupied, the loop continues with the next arrival
                self._reject(req, "oversize", retryable=False)
                continue
            req.slot = slot
            req.prefill_start_t = self.now()
            if self.watchdog is not None:
                self.watchdog.arm(self.decode_steps, phase="prefill",
                                  inflight=self.occupancy())
            try:
                tok, _ = self.engine.prefill(req.prompt, slot)
            except Exception as e:
                # slot never assigned (slot_req[slot] still None): contain
                # the failure to this request and keep serving
                if self.watchdog is not None:
                    self.watchdog.progress()
                self._reject(req, "prefill_error", retryable=True,
                             error=repr(e)[:200])
                continue
            prefill_ms = (self.now() - req.prefill_start_t) * 1000.0
            self._prefill_ms.append(prefill_ms)
            if self.watchdog is not None:
                self.watchdog.observe_step_time(prefill_ms)
                self.watchdog.progress()
            req.first_token_t = self.now()
            req.output.append(tok)
            self.slot_req[slot] = req
            self.slot_len[slot] = req.prompt_len
            self.slot_tok[slot] = tok
            self._maybe_finish(slot)

    def _maybe_finish(self, slot: int) -> None:
        req = self.slot_req[slot]
        if req is not None and len(req.output) >= req.max_new_tokens:
            req.done_t = self.now()
            req.status = "completed"
            req.finish_reason = "completed"
            self.completed.append(req)
            self.slot_req[slot] = None
            T.emit(
                "serve_request", id=req.rid, arrival_t=req.arrival_s,
                prefill_start_t=req.prefill_start_t,
                first_token_t=req.first_token_t, done_t=req.done_t,
                prompt_len=req.prompt_len, output_len=len(req.output),
                ttft_ms=req.ttft_ms(), tpot_ms=req.tpot_ms(),
            )

    def decode_pages(self) -> int:
        """Smallest bucket whose context covers every active slot's write
        position (= its current length)."""
        active_lens = [int(self.slot_len[i]) for i, r in enumerate(self.slot_req) if r is not None]
        return bucket_pages(max(active_lens), self.kv_cfg.page_size, self.kv_cfg.max_pages)

    def _abandon_active(self, reason: str) -> int:
        """Free every occupied slot, rejecting its request as retryable —
        the containment path for engine-wide decode failures and hard
        drains. Returns how many were abandoned."""
        n = 0
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            self.slot_req[slot] = None
            self.slot_len[slot] = 0
            self.slot_tok[slot] = 0
            self._reject(req, reason, retryable=True)
            n += 1
        return n

    def _decode_tick(self) -> None:
        active = np.array([r is not None for r in self.slot_req], bool)
        pages = self.decode_pages()
        t_start = self.now()
        if self.watchdog is not None:
            self.watchdog.arm(self.decode_steps, phase="decode",
                              inflight=int(active.sum()))
        try:
            next_tok, _ = self.engine.decode_step(self.slot_tok, active, pages)
        except Exception:
            # an engine-wide failure, not a per-request one: free every
            # slot (no leak), park the requests as retryable, and let the
            # driver decide (migrate / exit) on the re-raised error
            if self.watchdog is not None:
                self.watchdog.progress()
            self._abandon_active("decode_error")
            raise
        step_ms = (self.now() - t_start) * 1000.0
        self._tick_ms.append(step_ms)
        if self.watchdog is not None:
            self.watchdog.observe_step_time(step_ms)
            self.watchdog.progress()
        self.decode_steps += 1
        n_active = int(active.sum())
        tokens = 0
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            tok = int(next_tok[slot])
            req.output.append(tok)
            self.slot_tok[slot] = tok
            self.slot_len[slot] += 1
            tokens += 1
            self._maybe_finish(slot)
        T.emit(
            "decode_batch", step=self.decode_steps,
            occupancy=n_active / self.kv_cfg.max_slots,
            slots=self.kv_cfg.max_slots, step_ms=step_ms, bucket_pages=pages,
            tokens=tokens,
        )

    # --------------------------------------------------------------- drain
    def drain(self, reason: str, pending: Optional[deque] = None,
              finish_active: bool = True) -> Dict[str, int]:
        """Graceful wind-down: stop admitting (every pending request sheds
        retryable), complete in-flight decodes where possible (bounded by
        the tokens they still owe), mark anything left retryable, and emit
        one `serve_drain` event. Idempotent per run()."""
        if self.watchdog is not None:
            self.watchdog.disarm()
        pending_shed = 0
        if pending:
            while pending:
                self._reject(pending.popleft(), "drain", retryable=True)
                pending_shed += 1
        active_before = self.occupancy()
        completed_before = len(self.completed)
        if finish_active and active_before:
            budget = sum(
                r.max_new_tokens - len(r.output)
                for r in self.slot_req if r is not None
            ) + active_before
            try:
                while self.occupancy() and budget > 0:
                    self._decode_tick()
                    budget -= 1
            except Exception:
                pass  # _decode_tick already freed slots + parked retryable
        active_shed = self._abandon_active("drain")
        self.drain_reason = reason
        T.emit(
            "serve_drain", reason=reason,
            completed=len(self.completed),
            active_completed=len(self.completed) - completed_before,
            active_shed=active_shed, pending_shed=pending_shed,
            shed=len(self.shed),
        )
        return {
            "reason": reason, "pending_shed": pending_shed,
            "active_shed": active_shed,
            "active_completed": len(self.completed) - completed_before,
        }

    # ----------------------------------------------------------- migration
    def migrate_to(self, engine, kv_cfg: Optional[KVCacheConfig] = None) -> Dict[str, int]:
        """Swap in a new engine (typically rebuilt on a degraded mesh with a
        re-searched strategy) and re-prefill every in-flight request from
        its token journal into the new KV cache.

        Replay math: after k sampled tokens the old cache holds the K/V of
        ``prompt + output[:-1]`` (the last sampled token has not been
        embedded yet — it is the pending `slot_tok`). Greedy prefill of that
        prefix therefore reproduces the exact cache state AND re-samples
        ``output[-1]``; the re-sampled token is discarded and `slot_tok` is
        restored, so the greedy continuation is identical to an
        uninterrupted run. Requests that no longer fit the new cache
        geometry shed retryable instead of raising."""
        if self.watchdog is not None:
            self.watchdog.disarm()
        old_slots = [(r, int(self.slot_len[i]), int(self.slot_tok[i]))
                     for i, r in enumerate(self.slot_req) if r is not None]
        self.engine = engine
        if kv_cfg is not None:
            self.kv_cfg = kv_cfg
        self.slot_req = [None] * self.kv_cfg.max_slots
        self.slot_len = np.zeros((self.kv_cfg.max_slots,), np.int64)
        self.slot_tok = np.zeros((self.kv_cfg.max_slots,), np.int32)
        replayed = shed = 0
        for req, _, last_tok in old_slots:
            replay = req.journal[:-1]
            slot = self._free_slot()
            remaining = req.max_new_tokens - len(req.output) + 1
            if slot is None or not request_fits(self.kv_cfg, len(replay), remaining):
                self._reject(req, "migrate_infeasible", retryable=True)
                shed += 1
                continue
            try:
                self.engine.prefill(replay, slot)  # re-sampled token == last_tok (greedy); discarded
            except Exception as e:
                self._reject(req, "migrate_prefill_error", retryable=True,
                             error=repr(e)[:200])
                shed += 1
                continue
            req.slot = slot
            self.slot_req[slot] = req
            self.slot_len[slot] = len(replay)
            self.slot_tok[slot] = last_tok
            replayed += 1
        self.migrations += 1
        return {"replayed": replayed, "shed": shed}

    def run(self, requests: Sequence[Request]) -> List[Request]:
        """Drive the load to completion; returns the completed requests in
        completion order. Shed/failed requests land in ``self.shed``."""
        pending = deque(sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        self.now()  # start the clock
        try:
            while pending or any(r is not None for r in self.slot_req):
                if self.control is not None:
                    verdict = self.control(self)
                    if verdict:
                        self.drain(str(verdict), pending)
                        break
                self._shed_scan(pending)
                self._admit(pending)
                if any(r is not None for r in self.slot_req):
                    self._decode_tick()
                elif pending:
                    # idle: wait out the arrival gap (real clock) / spin (fake)
                    gap = pending[0].arrival_s - self.now()
                    if gap > 0 and self._clock is time.monotonic:
                        time.sleep(min(gap, 0.05))
        finally:
            if self.watchdog is not None:
                self.watchdog.disarm()
        return self.completed


# -------------------------------------------------------------------- report
def percentile(values: Sequence[float], q: float) -> float:
    if not values:
        return float("nan")
    xs = sorted(values)
    idx = min(len(xs) - 1, max(0, int(round(q / 100.0 * (len(xs) - 1)))))
    return xs[idx]


def summarize(
    completed: Sequence[Request], wall_s: float, world_size: int = 1,
    shed: Sequence[Request] = (),
) -> Dict[str, Any]:
    """TTFT/TPOT percentiles + throughput for a finished load, plus the shed
    ledger (count, retryable count, per-reason breakdown) when given."""
    ttfts = [r.ttft_ms() for r in completed if r.ttft_ms() is not None]
    tpots = [r.tpot_ms() for r in completed if r.tpot_ms() is not None]
    out_tokens = sum(len(r.output) for r in completed)
    by_reason: Dict[str, int] = {}
    for r in shed:
        by_reason[r.finish_reason or "unknown"] = by_reason.get(r.finish_reason or "unknown", 0) + 1
    return {
        "shed": len(shed),
        "shed_retryable": sum(1 for r in shed if r.retryable),
        "shed_by_reason": by_reason,
        "requests": len(completed),
        "output_tokens": out_tokens,
        "wall_s": wall_s,
        "tokens_per_s": out_tokens / wall_s if wall_s > 0 else float("nan"),
        "tokens_per_s_per_chip": (
            out_tokens / wall_s / world_size if wall_s > 0 else float("nan")
        ),
        "ttft_ms": {
            "p50": percentile(ttfts, 50), "p90": percentile(ttfts, 90),
            "p99": percentile(ttfts, 99),
        },
        "tpot_ms": {
            "p50": percentile(tpots, 50), "p90": percentile(tpots, 90),
            "p99": percentile(tpots, 99),
        },
    }
