"""Distributed checkpoint save / resume with torn-write detection.

TPU-native counterpart of the reference's distributed checkpoint system
(models/llama_hf/LlamaModel_checkpoint.py:148-220: per-FSDP-module
FULL_STATE_DICT save, one file per tp-rank per layer under ``iter_N/`` plus
per-rank optimizer state and scheduler JSON). Here sharded arrays are written
through orbax/tensorstore — each host writes exactly its addressable shards,
and restore re-shards to the current mesh layout. What is written is a leaf's
logical array: a leaf's physical tiling on the device is the compiler's and no
part of a checkpoint (runtime/model_api.state_specs).

The reference *asserts the parallel strategy is unchanged on resume* (no
cross-strategy re-sharding, hybrid_parallel_config.py:112-124). We keep the
same guard by default (`strict_strategy=True`) but — because restore targets
are (spec, mesh)-typed abstract arrays and tensorstore reads any slice —
resume under a *different* searched strategy also works when the guard is
relaxed, which the reference cannot do.

Layout under ``<dir>/``:
    hybrid_parallel_config.json      strategy fingerprint (assert-equal on resume)
    meta.json                        model family/size, world size
    <iteration>/                     orbax composite: params, opt_state, train_meta
    manifests/<iteration>.json       post-save integrity manifest (below)

Integrity manifest
------------------
A preempted or killed process can leave a torn ``<iteration>/`` directory
that poisons the next resume. The manifest is the commit record: it is
written atomically (tmp file + ``os.replace``) *after* the orbax save
completes, so a step directory without a matching manifest is by definition
torn. Each manifest records, per item (``params`` / ``opt_state`` /
``train_meta``):

    ``digest``       sha256 over every leaf's (path, dtype, shape, bytes),
                     in deterministic flatten order (None when some shards
                     are not host-addressable, i.e. multi-host meshes)
    ``spec_digest``  sha256 over (path, dtype, shape) only
    ``num_leaves``   leaf count

plus the step metadata (iteration, save unix time). ``load_checkpoint``
verifies the manifest: a missing manifest or a value-digest mismatch marks
the step torn, and — when no explicit iteration was requested — restore
falls back to the latest *intact* step instead of crashing. A
``spec_digest`` mismatch (caller restores under different dtypes/shapes,
e.g. a precision change) skips value verification with a warning rather
than failing. Checkpoint directories written before this discipline (no
``manifests/`` dir) are accepted as-is for back-compat.

Provenance
----------
The manifest additionally carries a ``provenance`` block (built by
runtime/elastic.build_provenance): the serialized strategy JSON the
checkpoint was written under, mesh shape / device count, a model-config
digest, the optimizer identity/hyperparam digest, and chunks/global_bsz.
Provenance is what makes a checkpoint *strategy-portable*: on resume the
driver can detect that the live mesh no longer matches the saved one and
re-plan (runtime/elastic.py) instead of failing the strategy assert, and
``load_checkpoint(..., target=)`` can restore the on-disk global arrays
directly into a DIFFERENT ``HybridParallelModel``'s shardings — including
across pipeline-layout changes (the stacked ``stages`` tree is re-laid-out
leaf-exactly through pipeline.stack/unstack). Incompatibilities refuse with
structured GLS2xx diagnostics (analysis/diagnostics.py) rather than
garbling state.

Retention: `keep_latest_k` on save (the driver's ``--keep_latest_k``)
garbage-collects the oldest step dirs and their manifests. GC never deletes
a step another thread is currently restoring (``_RESTORING``), nor the
newest intact step (the only guaranteed-resumable state), and tolerates
stray non-step directories.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np
import orbax.checkpoint as ocp

from galvatron_tpu.config.strategy import HybridParallelConfig
from galvatron_tpu.obs import telemetry
from galvatron_tpu.utils.jsonio import read_json_config, write_json_config

MANIFEST_DIRNAME = "manifests"

# test-only seam (tests/runtime/fault_injection.py): called after the orbax
# write completes but before the manifest commit — the torn-save window a
# preemption kill actually hits
_before_manifest_write = None

# steps currently being restored (load_checkpoint registers them for the
# duration of the orbax read): gc_checkpoints must never delete one out from
# under an in-flight restore, e.g. a background save's GC racing the
# rollback path's fallback to an older intact step
_RESTORING: set = set()


def _manager(ckpt_dir: str, create: bool = False) -> ocp.CheckpointManager:
    options = ocp.CheckpointManagerOptions(create=create, enable_async_checkpointing=False)
    return ocp.CheckpointManager(os.path.abspath(ckpt_dir), options=options)


# ----------------------------------------------------------------- manifests
def _manifest_path(ckpt_dir: str, iteration: int) -> str:
    return os.path.join(ckpt_dir, MANIFEST_DIRNAME, "%d.json" % iteration)


def _tree_digests(tree: Any) -> Dict[str, Any]:
    """Per-item integrity record: value digest (None when shards are not
    addressable), structure-only digest, leaf count, and the
    sharding-layout-invariant integrity fold (runtime/sdc.py). The sha256
    covers the exact host bytes in tree order — torn/partial writes; the
    fold survives any relayout, so `cli lint --ckpt --deep` (GLS214) and a
    cross-strategy resume can both check the VALUES independently of how
    the restoring run shards them."""
    from galvatron_tpu.runtime import sdc

    value = hashlib.sha256()
    spec = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    addressable = True
    fold = 0
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path).encode()
        try:
            arr = np.asarray(jax.device_get(leaf))
        except Exception:
            addressable = False
            arr = None
        if arr is not None:
            spec.update(key + str(arr.dtype).encode() + str(arr.shape).encode())
            value.update(key + str(arr.dtype).encode() + str(arr.shape).encode())
            value.update(arr.tobytes())
            fold = (fold + sdc.host_tree_fold(arr)) & 0xFFFFFFFF
        else:
            spec.update(key)
            addressable = False
    return {
        "digest": value.hexdigest() if addressable else None,
        "spec_digest": spec.hexdigest(),
        "num_leaves": len(leaves),
        "fold": fold if addressable else None,
    }


def _meta_digest(meta: Dict[str, Any]) -> Dict[str, Any]:
    blob = json.dumps(meta, sort_keys=True).encode()
    d = hashlib.sha256(blob).hexdigest()
    return {"digest": d, "spec_digest": d, "num_leaves": 1}


def _write_manifest(ckpt_dir: str, iteration: int, items: Dict[str, Dict[str, Any]],
                    provenance: Optional[Dict[str, Any]] = None) -> None:
    path = _manifest_path(ckpt_dir, iteration)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    payload = {"format": 1, "iteration": iteration, "saved_at": time.time(), "items": items}
    if provenance is not None:
        payload["provenance"] = provenance
    tmp = path + ".tmp.%d" % os.getpid()
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # atomic commit: manifest exists => save completed


def _read_manifest_raising(ckpt_dir: str, iteration: int) -> Optional[Dict[str, Any]]:
    """Like read_manifest, but lets transient OSErrors propagate so a caller
    can put a retry policy around the read (resilience.with_retry); only a
    missing file returns None here."""
    path = _manifest_path(ckpt_dir, iteration)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def read_manifest(ckpt_dir: str, iteration: int) -> Optional[Dict[str, Any]]:
    try:
        return _read_manifest_raising(ckpt_dir, iteration)
    except (OSError, ValueError):
        return None  # a torn manifest marks the step torn too


def read_provenance(ckpt_dir: str, iteration: Optional[int] = None):
    """(iteration, provenance dict) from the requested (or newest intact)
    step's manifest; (None, None) when no manifest carries provenance —
    a pre-elastic checkpoint, or no checkpoint at all."""
    if iteration is not None:
        m = read_manifest(ckpt_dir, iteration)
        prov = (m or {}).get("provenance")
        return (iteration, prov) if prov else (None, None)
    for step in reversed(intact_iterations(ckpt_dir)):
        m = read_manifest(ckpt_dir, step)
        if m and m.get("provenance"):
            return step, m["provenance"]
    return None, None


def _has_manifest_discipline(ckpt_dir: str) -> bool:
    """False for checkpoint dirs written before the manifest era — those are
    accepted as-is (back-compat); once any manifest exists, a manifest-less
    step means a torn save."""
    return os.path.isdir(os.path.join(ckpt_dir, MANIFEST_DIRNAME))


# ---------------------------------------------------------------------- save
def save_checkpoint(
    ckpt_dir: str,
    iteration: int,
    params: Any,
    opt_state: Any = None,
    hp: Optional[HybridParallelConfig] = None,
    train_meta: Optional[Dict[str, Any]] = None,
    keep_latest_k: Optional[int] = None,
    provenance: Optional[Dict[str, Any]] = None,
) -> None:
    """Write params (+ optimizer state + scalar train metadata) at `iteration`,
    commit the integrity manifest (carrying `provenance` when given — see
    runtime/elastic.build_provenance), then GC to the newest `keep_latest_k`."""
    t0 = time.perf_counter()
    os.makedirs(ckpt_dir, exist_ok=True)
    if hp is not None:
        write_json_config(hp.to_json_dict(), os.path.join(ckpt_dir, "hybrid_parallel_config.json"))
    items = {"params": ocp.args.StandardSave(params)}
    digests = {"params": _tree_digests(params)}
    if opt_state is not None:
        items["opt_state"] = ocp.args.StandardSave(opt_state)
        digests["opt_state"] = _tree_digests(opt_state)
    if train_meta:
        items["train_meta"] = ocp.args.JsonSave(train_meta)
        digests["train_meta"] = _meta_digest(train_meta)
    with _manager(ckpt_dir, create=True) as mgr:
        if iteration in set(mgr.all_steps()):
            # re-save of an existing step (e.g. retraining over a torn step
            # after a rollback): replace it wholesale — its manifest, if any,
            # is invalidated by the overwrite either way
            mgr.delete(iteration)
            try:
                os.remove(_manifest_path(ckpt_dir, iteration))
            except OSError:
                pass
        mgr.save(iteration, args=ocp.args.Composite(**items))
        mgr.wait_until_finished()
    if _before_manifest_write is not None:
        _before_manifest_write(iteration)
    if jax.process_index() == 0:
        _write_manifest(ckpt_dir, iteration, digests, provenance=provenance)
    telemetry.emit(
        "checkpoint_save", iteration=iteration, path=ckpt_dir,
        duration_ms=(time.perf_counter() - t0) * 1e3,
        emergency=True if (train_meta and train_meta.get("emergency")) else None,
    )
    if keep_latest_k:
        gc_checkpoints(ckpt_dir, keep_latest_k)


def gc_checkpoints(ckpt_dir: str, keep_latest_k: int,
                   protect: Any = ()) -> List[int]:
    """Delete all but the newest `keep_latest_k` steps (and their manifests).
    Returns the deleted iterations.

    Safety rules (the GC/resume race): a step currently being restored
    (`_RESTORING`, registered by load_checkpoint) or listed in `protect` is
    never deleted, and neither is the newest INTACT step — with torn newer
    steps on disk, blindly keeping the newest K by number could delete the
    only state a fallback restore can still use. Stray non-step directories
    and already-missing steps are tolerated, not raised on."""
    if keep_latest_k <= 0 or jax.process_index() != 0:
        return []
    keep = set(protect) | set(_RESTORING)
    intact = intact_iterations(ckpt_dir)
    if intact:
        keep.add(max(intact))
    deleted = []
    with _manager(ckpt_dir) as mgr:
        steps = sorted(mgr.all_steps())
        doomed = steps[:-keep_latest_k] if keep_latest_k < len(steps) else []
        for step in doomed:
            if step in keep:
                continue
            try:
                mgr.delete(step)
            except (OSError, ValueError) as e:
                # a concurrently-removed or stray step is not worth failing
                # a SAVE over; leave it for the next GC pass
                telemetry.runtime_log(
                    "checkpoint gc: could not delete step %d: %s" % (step, e))
                continue
            deleted.append(step)
    for step in deleted:
        try:
            os.remove(_manifest_path(ckpt_dir, step))
        except OSError:
            pass
    if deleted:
        telemetry.emit("checkpoint_gc", deleted=deleted, path=ckpt_dir)
    return deleted


# ------------------------------------------------------------------- listing
def latest_iteration(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    with _manager(ckpt_dir) as mgr:
        return mgr.latest_step()


def intact_iterations(ckpt_dir: str) -> List[int]:
    """Saved steps whose manifest committed (all steps for pre-manifest
    dirs), ascending. Steps present on disk but missing from this list are
    torn."""
    if not os.path.isdir(ckpt_dir):
        return []
    with _manager(ckpt_dir) as mgr:
        steps = sorted(mgr.all_steps())
    if not _has_manifest_discipline(ckpt_dir):
        return steps
    return [s for s in steps if read_manifest(ckpt_dir, s) is not None]


def _abstract_like(tree, shardings):
    return jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree,
        shardings,
    )


# --------------------------------------------- cross-strategy param layouts
def _same_param_layout(a: HybridParallelConfig, b: HybridParallelConfig) -> bool:
    """True when both strategies produce the same params TREE (sharding may
    still differ — that is just a device_put): the tree only depends on
    whether layers are stacked into pipeline stages and how."""
    if (a.pp > 1) != (b.pp > 1):
        return False
    return a.pp <= 1 or (a.pp == b.pp and list(a.pp_division) == list(b.pp_division))


def _abstract_canonical_params(cfg):
    """Abstract canonical (un-stacked, per-layer) param tree for the generic
    transformer family."""
    from galvatron_tpu.models import base as M

    rng = jax.random.PRNGKey(0)
    return jax.eval_shape(lambda: M.init_model_params(rng, cfg))


def _abstract_saved_params(cfg, saved_hp: HybridParallelConfig):
    """Abstract params tree AS SAVED under `saved_hp`: canonical for pp=1,
    stacked `stages` (leading pp dim per slot) for pp>1. Every layer of the
    generic tree shares one shape, so the stacked slots are derivable
    without building the saved model (whose mesh may need devices that no
    longer exist — the whole point of elastic resume)."""
    canonical = _abstract_canonical_params(cfg)
    if saved_hp.pp <= 1:
        return canonical
    from galvatron_tpu.parallel.pipeline import layers_per_stage

    out = dict(canonical)
    layers = out.pop("layers")
    slot = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((saved_hp.pp,) + l.shape, l.dtype), layers[0]
    )
    out["stages"] = [slot for _ in range(layers_per_stage(saved_hp))]
    return out


def _relayout_tree(tree, saved_hp: HybridParallelConfig, target_hp: HybridParallelConfig):
    """Re-layout any pytree holding a params-shaped subtree (params itself,
    adam mu/nu, ...) from `saved_hp`'s pipeline layout to `target_hp`'s:
    stacked ``stages`` unstack to the canonical layer list and restack for
    the target division. Pure data movement — leaf values are bit-exact."""
    from galvatron_tpu.parallel.pipeline import stack_params, unstack_params

    def walk(t):
        if isinstance(t, dict) and ("stages" in t or "layers" in t):
            t = dict(t)
            if "stages" in t:
                layers = unstack_params(t.pop("stages"), saved_hp)
            else:
                layers = list(t.pop("layers"))
            if target_hp.pp > 1:
                t["stages"] = stack_params(layers, target_hp)
            else:
                t["layers"] = layers
            return t
        if isinstance(t, dict):
            return {k: walk(v) for k, v in t.items()}
        if isinstance(t, tuple) and hasattr(t, "_fields"):
            return type(t)(*(walk(x) for x in t))
        if isinstance(t, (list, tuple)):
            return type(t)(walk(x) for x in t)
        return t

    return walk(tree)


def _read_saved_strategy(ckpt_dir: str, iteration: Optional[int],
                         fallback_world: int) -> Optional[HybridParallelConfig]:
    """The strategy the checkpoint was written under: provenance first (it
    records the true world size), the legacy hybrid_parallel_config.json
    otherwise."""
    _, prov = read_provenance(ckpt_dir, iteration)
    if prov and prov.get("strategy"):
        return HybridParallelConfig.from_json(
            dict(prov["strategy"]), world_size=int(prov.get("world_size", fallback_world))
        )
    cfg_path = os.path.join(ckpt_dir, "hybrid_parallel_config.json")
    if os.path.exists(cfg_path):
        return HybridParallelConfig.from_json(cfg_path, world_size=fallback_world)
    return None


# ---------------------------------------------------------------------- load
def _verify_items(manifest: Dict[str, Any], restored: Dict[str, Any]) -> Optional[str]:
    """None when every restored item matches its manifest record; otherwise a
    reason string. A spec mismatch (different dtypes/shapes requested by the
    restore target) downgrades to a warning — the bytes legitimately differ."""
    for name, rec in manifest.get("items", {}).items():
        if name not in restored:
            continue  # caller did not request this item
        got = (
            _meta_digest(restored[name])
            if name == "train_meta"
            else _tree_digests(restored[name])
        )
        if rec.get("num_leaves") != got["num_leaves"]:
            return "item %r: leaf count %s != manifest %s" % (
                name, got["num_leaves"], rec.get("num_leaves"))
        if rec.get("spec_digest") != got["spec_digest"]:
            telemetry.runtime_log(
                "checkpoint: item %r restored under a different dtype/shape "
                "spec; skipping value verification" % name
            )
            continue
        if rec.get("digest") is None or got["digest"] is None:
            continue  # shards not fully addressable at save or restore time
        if rec["digest"] != got["digest"]:
            return "item %r: content digest mismatch" % name
    return None


def load_checkpoint(
    ckpt_dir: str,
    iteration: Optional[int] = None,
    *,
    params_target: Any = None,
    params_shardings: Any = None,
    opt_state_target: Any = None,
    opt_state_shardings: Any = None,
    hp: Optional[HybridParallelConfig] = None,
    strict_strategy: bool = True,
    verify_integrity: bool = True,
    target: Any = None,
    tx: Any = None,
    saved_strategy: Optional[HybridParallelConfig] = None,
    retry_policy: Any = None,
    counters: Any = None,
    sdc_check: bool = False,
):
    """Restore (params, opt_state, train_meta) re-sharded to the current mesh.

    `*_target` are example pytrees (real or ShapeDtypeStruct) giving
    shapes/dtypes; `*_shardings` optional matching NamedShardings. With
    `strict_strategy` the saved strategy must equal `hp` (reference
    hybrid_parallel_config.py:112-124 resume assert).

    `target` (a runtime.model_api.HybridParallelModel, duck-typed) selects
    the STRATEGY-PORTABLE path: the on-disk global arrays are restored
    directly into `target`'s shardings, even when the checkpoint was written
    under a different strategy (`saved_strategy`; read from the manifest
    provenance / legacy strategy JSON when omitted). A pipeline-layout
    change (pp on/off, different division) restores the saved tree
    structure host-side, re-lays it out leaf-exactly, and places it onto
    the target mesh. `tx` (the optax transformation) supplies the optimizer
    tree to restore opt_state into; a structurally incompatible saved
    opt_state refuses with a GLS202 DiagnosticError instead of garbling
    state. Families with custom param trees (t5/swin) support same-layout
    `target` restores only (GLS206 otherwise).

    `retry_policy`/`counters` (resilience.RetryPolicy/ResilienceCounters)
    put exponential backoff around the manifest reads and the orbax
    restore, mirroring the retries saves have always had.

    With `verify_integrity` (default), each candidate step must have a
    committed manifest whose digests match the restored bytes. When
    `iteration` is None the newest step is tried first and torn steps are
    skipped (the skipped steps are reported under
    ``meta["torn_iterations"]``); an explicitly requested `iteration` that
    fails verification raises instead — the caller asked for that exact
    state."""
    from galvatron_tpu.analysis import diagnostics as D

    t0 = time.perf_counter()

    if hp is not None:
        cfg_path = os.path.join(ckpt_dir, "hybrid_parallel_config.json")
        if os.path.exists(cfg_path):
            saved = HybridParallelConfig.from_json(cfg_path, world_size=hp.world_size)
            if strict_strategy:
                hp.assert_equal(saved)

    # ------------------------------------------ strategy-portable target path
    cross = False
    target_abs_params = None
    if target is not None:
        target_hp = target.hp
        if saved_strategy is None:
            saved_strategy = _read_saved_strategy(ckpt_dir, iteration, target_hp.world_size)
        cross = saved_strategy is not None and not _same_param_layout(saved_strategy, target_hp)
        target_abs_params = target.abstract_params()
        if cross and target.init_fn is not None:
            raise D.DiagnosticError([D.make(
                "GLS206", "cross-pipeline-layout restore (pp %s -> pp %s) is "
                "only supported for the generic transformer tree; this "
                "family builds its own params" % (saved_strategy.pp, target_hp.pp),
            )])
        if cross:
            # restore the SAVED tree structure host-side (unsharded); the
            # re-layout + device_put onto the target mesh happens below
            params_target = _abstract_saved_params(target.cfg, saved_strategy)
            params_shardings = None
            opt_state_target = jax.eval_shape(tx.init, params_target) if tx is not None else None
            opt_state_shardings = None
        else:
            params_target = target_abs_params
            params_shardings = target.shardings()
            opt_state_target = jax.eval_shape(tx.init, params_target) if tx is not None else None
            opt_state_shardings = (
                target.opt_state_shardings(tx, params_target) if tx is not None else None
            )
    if params_target is None:
        raise TypeError("load_checkpoint needs params_target or target=")

    def abstract(tree, sh):
        if sh is None:
            return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        return _abstract_like(tree, sh)

    def read_manifest_retrying(step):
        def fn():
            return _read_manifest_raising(ckpt_dir, step)

        try:
            if retry_policy is not None:
                from galvatron_tpu.runtime import resilience as rsl

                return rsl.with_retry(fn, retry_policy, counters,
                                      description="manifest read")
            return fn()
        except (OSError, ValueError):
            return None

    with _manager(ckpt_dir) as mgr:
        explicit = iteration is not None
        if explicit:
            candidates = [iteration]
        else:
            candidates = sorted(mgr.all_steps(), reverse=True)
            if not candidates:
                raise FileNotFoundError("no checkpoint found under %s" % ckpt_dir)
        check = verify_integrity and _has_manifest_discipline(ckpt_dir)
        torn: Dict[int, str] = {}
        out = None
        for step in candidates:
            manifest = read_manifest_retrying(step) if check else None
            if check and manifest is None:
                reason = "missing/unreadable manifest (torn save)"
                if explicit:
                    raise RuntimeError(
                        "checkpoint %s step %d: %s" % (ckpt_dir, step, reason))
                torn[step] = reason
                continue
            # refuse an optimizer-tree mismatch BEFORE the orbax restore can
            # garble state: the manifest records the saved leaf count
            if manifest and opt_state_target is not None:
                rec = manifest.get("items", {}).get("opt_state")
                want = len(jax.tree.leaves(opt_state_target))
                if rec and rec.get("num_leaves") is not None and rec["num_leaves"] != want:
                    raise D.DiagnosticError([D.make(
                        "GLS202", "saved opt_state has %s leaves but the "
                        "requested optimizer expects %d — resume with the "
                        "optimizer the checkpoint was written with, or "
                        "restore params-only (opt_state_target=None)"
                        % (rec["num_leaves"], want),
                    )])
            # only request items actually present: an h2g-converted checkpoint
            # is params-only (tools/convert_checkpoint.py) — the optimizer then
            # starts fresh, matching the reference's HF-init path
            # (parallel.py:79-89)
            try:
                present = set(dict(mgr.item_metadata(step).items()))
            except Exception:
                present = {"params", "opt_state", "train_meta"}
            items = {"params": ocp.args.StandardRestore(abstract(params_target, params_shardings))}
            if opt_state_target is not None and "opt_state" in present:
                items["opt_state"] = ocp.args.StandardRestore(
                    abstract(opt_state_target, opt_state_shardings)
                )
            if "train_meta" in present:
                items["train_meta"] = ocp.args.JsonRestore()

            def do_restore(step=step, items=items):
                return mgr.restore(step, args=ocp.args.Composite(**items))

            _RESTORING.add(step)
            try:
                if retry_policy is not None:
                    from galvatron_tpu.runtime import resilience as rsl

                    out = rsl.with_retry(do_restore, retry_policy, counters,
                                         description="orbax restore")
                else:
                    out = do_restore()
            except D.DiagnosticError:
                raise
            except (ValueError, TypeError, KeyError) as e:
                if target is not None:
                    # a tree-structure mismatch against a known-intact step is
                    # an optimizer/model incompatibility, not a torn save
                    raise D.DiagnosticError([D.make(
                        "GLS202", "restore into the target tree failed "
                        "structurally (%s: %s) — the checkpoint's optimizer "
                        "or model tree differs from the target's"
                        % (type(e).__name__, e),
                    )])
                if explicit:
                    raise
                torn[step] = "restore failed: %s: %s" % (type(e).__name__, e)
                continue
            except Exception as e:
                if explicit:
                    raise
                torn[step] = "restore failed: %s: %s" % (type(e).__name__, e)
                continue
            finally:
                _RESTORING.discard(step)
            reason = _verify_items(manifest, dict(out.items())) if manifest else None
            if reason is not None:
                if explicit:
                    raise RuntimeError(
                        "checkpoint %s step %d failed integrity verification: %s"
                        % (ckpt_dir, step, reason)
                    )
                torn[step] = reason
                out = None
                continue
            iteration = step
            break
        if out is None:
            raise FileNotFoundError(
                "no intact checkpoint under %s (torn steps skipped: %s)"
                % (ckpt_dir, {k: v for k, v in sorted(torn.items())})
            )
    if torn:
        telemetry.runtime_log(
            "checkpoint: fell back to intact step %d; skipped torn steps %s"
            % (iteration, sorted(torn))
        )
    params = out["params"]
    opt_state = out.get("opt_state")
    params_fold = opt_fold = None
    if sdc_check and target is not None and cross:
        # the layout-invariant fold of the AS-RESTORED state, asserted
        # unchanged across the relayout + placement below (GLS016): the
        # manifest sha256 cannot make this check — it is bound to the saved
        # strategy's exact byte layout
        from galvatron_tpu.runtime import sdc

        params_fold = sdc.host_tree_fold(params)
        if opt_state is not None and tx is not None:
            opt_fold = sdc.host_tree_fold(opt_state)
    if target is not None and cross:
        # integrity was verified on the AS-SAVED tree above; now re-lay-out
        # (leaf-exact host-side data movement) and place onto the target mesh
        params = _relayout_tree(params, saved_strategy, target.hp)
        params = jax.device_put(params, target.shardings())
        if opt_state is not None and tx is not None:
            opt_state = _relayout_tree(opt_state, saved_strategy, target.hp)
            target_abs_opt = jax.eval_shape(tx.init, target_abs_params)
            got = [(jax.tree_util.keystr(p), tuple(l.shape)) for p, l in
                   jax.tree_util.tree_flatten_with_path(opt_state)[0]]
            want = [(jax.tree_util.keystr(p), tuple(l.shape)) for p, l in
                    jax.tree_util.tree_flatten_with_path(target_abs_opt)[0]]
            if got != want:
                diffs = [(g, w) for g, w in zip(got, want) if g != w][:3]
                raise D.DiagnosticError([D.make(
                    "GLS202", "re-laid-out opt_state does not match the "
                    "target optimizer tree (%d vs %d leaves; first diffs: "
                    "%s)" % (len(got), len(want), diffs),
                )])
            opt_state = jax.device_put(
                opt_state, target.opt_state_shardings(tx, target_abs_params))
        if params_fold is not None:
            from galvatron_tpu.runtime import sdc

            sdc.assert_digest_continuity(
                params_fold, params, "load_checkpoint(cross, params)",
                iteration=iteration)
            if opt_fold is not None and opt_state is not None:
                sdc.assert_digest_continuity(
                    opt_fold, opt_state, "load_checkpoint(cross, opt_state)",
                    iteration=iteration)
    meta = out.get("train_meta") or {}
    meta.setdefault("iteration", iteration)
    if torn:
        meta["torn_iterations"] = sorted(torn)
    telemetry.emit(
        "checkpoint_restore", iteration=int(meta["iteration"]), path=ckpt_dir,
        duration_ms=(time.perf_counter() - t0) * 1e3,
        torn_skipped=len(torn) or None,
        cross_strategy=True if (target is not None and cross) else None,
    )
    return params, opt_state, meta
