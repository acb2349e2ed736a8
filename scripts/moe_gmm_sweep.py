#!/usr/bin/env python3
"""Chip measurement behind `ops/moe.gmm_tiling`: the three megablox kernels of
a routed block's two grouped matmuls, each ALONE, at the seven routed cells'
shapes and at every candidate tiling. Not a benchmark cell: run by hand
through the chip tool,

    chiprun --timeout 3000 -- python3 scripts/moe_gmm_sweep.py [cell ...]

and read `chiprun_out/moe_gmm_sweep.json` (written anew after every cell;
`--out NAME` writes `chiprun_out/NAME` instead). `python3
scripts/moe_gmm_sweep.py --summary` reads that file, here, with no chip: for
each kernel the parent's tiling, the best measured and the one
`ops/moe.gmm_tiling` picks today, in ms a call.

A cell's shapes are its routed block's (`CELLS`): tokens x k assignments over
all the experts, the held share's WINDOW of them where the block builds one
(`ops/moe.window_rows`'s formula at the candidate row tile), hidden, the up
projection's width and the experts' own. The groups are drawn as a step's
counters show them (`expert_rows_held` near the even share,
`expert_load_max_over_mean` about 2): every expert's share of the assignments
is `exp(0.5 z)`, z normal ("uneven"), and as a multinomial draws them at equal
shares ("even", for the parent's tiling and the best alone).

The kernels of a matmul of (K, N) kernels, as `ops/moe.grouped_matmul` and
`grouped_matmul_bwd` call them:

    gmm     rows (M, K) x kernels (G, K, N) -> (M, N)        tiles (tm, tk | K, tn | N)
    gmm_t   cotangent (M, N) x kernels^T    -> (M, K)        tiles (tm, tk | N, tn | K)
    tgmm    rows^T (K, M) x cotangent (M, N) -> (G, K, N)    tiles (tm, tk | K, tn | N)

Times: the kernel's own device time out of a profiler trace (the custom call
`gmm` / `tgmm`, what `benchmarks/layer_metrics/moe_held_gmm_roofline.py`
reads), median of `REPEAT` runs, and the median wall time of the fenced call
beside it (the group metadata's small ops and a dispatch with it). The floor
is `benchmarks/flops.least_time_s` of the rows the groups really hold, each
operand once. A tiling the compiler refuses is kept with its error. Refuses to
run where jax finds no TPU.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

OUT = os.path.join(ROOT, "chiprun_out", "moe_gmm_sweep.json")
REPEAT = 5
PARENT = (512, 1024, 1024)  # the one tiling of every call before PR 69
# the routed block of each cell: assignments = tokens x k over `experts`, of which `held` sit here
CELLS = {
    "laguna-c1-s8k": dict(tokens=8192, k=8, experts=256, held=32, hidden=2048, width=1024, ffn=512),
    "qwen3next-c1-s8k": dict(tokens=8192, k=10, experts=512, held=32, hidden=2048, width=1024, ffn=512),
    "kimilin-c1-s8k": dict(tokens=8192, k=8, experts=256, held=8, hidden=2304, width=2048, ffn=1024),
    "glm47f-c1-s8k": dict(tokens=8192, k=4, experts=64, held=8, hidden=2048, width=3072, ffn=1536),
    "xing4-c1-s4k": dict(tokens=4096, k=4, experts=64, held=8, hidden=3584, width=2048, ffn=1024),
    "lfm2moe-c1-s8k": dict(tokens=16384, k=4, experts=32, held=8, hidden=2048, width=3584, ffn=1792),
    "olmoe-c1-s4k": dict(tokens=8192, k=8, experts=64, held=64, hidden=2048, width=2048, ffn=1024),
    # two matrices an expert and no gate: the up projection is as wide as the expert, 1856 = 14.5 x 128, which no
    # multiple of 128 divides; hidden 2688 = 21 x 128. And the same block with the kernels padded to 1920 = 15 x 128
    # columns of zeros (PR 71: the rule's rival for a dim that is no multiple of 128)
    "nemo3n-c1-s8k": dict(tokens=8192, k=6, experts=128, held=8, hidden=2688, width=1856, ffn=1856),
    "nemo3n-c1-s8k-pad1920": dict(tokens=8192, k=6, experts=128, held=8, hidden=2688, width=1920, ffn=1920),
}
KERNELS = ("gmm", "gmm_t", "tgmm")
VMEM_TRY = 24 << 20  # a tiling whose blocks alone (`moe.gmm_blocks_bytes`) are over this is not sent to the compiler


def even_rows(cell) -> float:
    return cell["tokens"] * cell["k"] / cell["experts"]


def dim_tiles(dim: int):
    """Candidate tiles of a K or N: the multiples of 128 that divide it, from
    768 up to 2304 (a narrower dim: itself; one up to 2688: itself too), the
    largest three. (512-wide tiles of a wider dim lost to 1024 at OLMoE's
    shapes: PERF.md, PR 27.) Of a dim that no multiple of 128 divides (1856):
    the least multiple of 128 that covers it in as many tiles as 1024-wide ones
    would, the rest of whose last tile megablox masks, and the dim itself as ONE
    block (a block as wide as its array need be no multiple of 128)."""
    if dim % 128:
        return sorted({math.ceil(dim / math.ceil(dim / 1024) / 128) * 128, dim})
    fits = [t for t in range(128, dim + 1, 128)
            if dim % t == 0 and (t >= 768 or t == dim) and (t <= 2304 or t == dim <= 2688)]
    return sorted(fits)[-3:]


def row_tiles(cell):
    return (128, 256, 512) if even_rows(cell) <= 512 else (256, 512)


def candidates(kernel: str, kdim: int, ndim: int, cell):
    """The tilings tried for a kernel of a (K, N) matmul, the parent's first."""
    from galvatron_tpu.ops import moe

    over_k, over_n = dict(moe.matmul_calls(kdim, ndim))[kernel]
    out = [PARENT]
    for tm in row_tiles(cell):
        for tk in dim_tiles(over_k):
            for tn in dim_tiles(over_n):
                tiling = (tm, tk, tn)
                if tiling not in out and moe.gmm_blocks_bytes(kernel, tiling) <= VMEM_TRY:
                    out.append(tiling)
    return out


def draw_counts(cell, how: str, seed: int):
    """Assignments an expert, all the experts: the held experts' (the first
    `held`) add up to the even share, the others' to the rest."""
    import numpy as np

    rng = np.random.default_rng(seed)
    total, experts, held = cell["tokens"] * cell["k"], cell["experts"], cell["held"]

    def dealt(count, groups):
        share = np.exp(0.5 * rng.standard_normal(groups)) if how == "uneven" else np.ones(groups)
        share = np.minimum(share / share.sum(), cell["tokens"] / max(count, 1))  # an expert gets a token once
        return rng.multinomial(count, share / share.sum())

    here = total * held // experts
    counts = np.concatenate([dealt(here, held), dealt(total - here, experts - held) if experts > held else []])
    return counts.astype(np.int32)


def device_times(trace_dir: str):
    """ms of every `gmm` / `tgmm` custom call of the trace's first device, in the order they ran."""
    from benchmarks import trace

    path = trace.find_xplane(trace_dir)
    if path is None:
        return []
    devices = trace.load(path)["devices"]
    ops = devices[min(devices)]["ops"] if devices else []
    ours = sorted((start, ns) for name, start, ns in ops if re.match(r"^t?gmm", name))
    if not ours:
        print("no gmm / tgmm event among", sorted({name for name, _, _ in ops})[:12], flush=True)
    return [ns / 1e6 for _, ns in ours]


def measure(calls):
    """[(row, jitted fn, args)] -> the rows with `wall_ms` and `device_ms`
    (or `error`). All compile first; then one trace holds every call's runs."""
    import jax

    ready = []
    for row, fn, args in calls:
        try:
            jax.block_until_ready(fn(*args))
            ready.append((row, fn, args))
        except Exception as e:  # a tiling the compiler refuses
            row["error"] = "%s: %s" % (type(e).__name__, " ".join(str(e).split())[:240])
            print(row, flush=True)
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the device's line is all that is read
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for row, fn, args in ready:
            walls = []
            for _ in range(REPEAT):
                t = time.perf_counter()
                jax.block_until_ready(fn(*args))
                walls.append(time.perf_counter() - t)
            row["wall_ms"] = statistics.median(walls) * 1e3
        jax.profiler.stop_trace()
        took = device_times(trace_dir)
    if len(took) == REPEAT * len(ready):
        for i, (row, _, _) in enumerate(ready):
            row["device_ms"] = statistics.median(took[i * REPEAT:(i + 1) * REPEAT])
    else:
        print("trace holds %d kernel events for %d runs: wall times alone" % (len(took), REPEAT * len(ready)),
              flush=True)


def sweep_cell(name: str, cell) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental.pallas.ops.tpu.megablox import ops

    megablox = ops.backend  # the module of `gmm` and `tgmm` (the package's `gmm` is the function)

    from benchmarks import flops
    from galvatron_tpu.ops import moe

    peak = json.load(open(os.path.join(ROOT, "benchmarks", "peaks.json")))[jax.devices()[0].device_kind]
    total, experts, held = cell["tokens"] * cell["k"], cell["experts"], cell["held"]
    hidden, width, ffn = cell["hidden"], cell["width"], cell["ffn"]
    key = jax.random.PRNGKey(0)
    out = {"shapes": dict(cell, even_rows_a_group=even_rows(cell)), "windows": {}, "kernels": []}

    def placed(counts, tm):
        """The rows the kernels see at this row tile and the rows an expert within them."""
        if held == experts:
            return total, jnp.asarray(counts)
        length = (math.ceil(moe.WINDOW_OVER_EVEN * total * held / experts / tm) + 1) * tm
        _, sizes, fits = moe._place_window(jnp.asarray(counts), (0, held), length, tm)
        assert bool(fits), (name, tm, length)
        return length, sizes

    @functools.lru_cache(maxsize=4)  # a kernel's calls share the operands of a length
    def operands(kdim, ndim, rows):
        return (jax.random.normal(key, (rows, kdim), jnp.bfloat16), jax.random.normal(key, (rows, ndim), jnp.bfloat16),
                jax.random.normal(key, (held, kdim, ndim), jnp.bfloat16) * 0.02)

    def call_of(kernel, kdim, ndim, tiling, rows, sizes):
        x, g, w = operands(kdim, ndim, rows)
        offset = None if held == experts else jnp.int32(0)
        if kernel == "gmm":
            return jax.jit(lambda x, w, s: megablox.gmm(x, w, s, jnp.bfloat16, tiling, offset)), (x, w, sizes)
        if kernel == "gmm_t":
            return (jax.jit(lambda g, w, s: megablox.gmm(g, w, s, jnp.bfloat16, tiling, offset, transpose_rhs=True)),
                    (g, w, sizes))
        return (jax.jit(lambda x, g, s: megablox.tgmm(x.swapaxes(0, 1), g, s, jnp.bfloat16, tiling, offset, held)),
                (x, g, sizes))

    draws = {how: draw_counts(cell, how, seed) for seed, how in enumerate(("uneven", "even"))}
    for how, counts in draws.items():
        here = counts[:held]
        out["windows"][how] = {"rows_held": int(here.sum()), "load_max_over_mean": float(counts.max() / counts.mean()),
                               "held_max_over_mean": float(here.max() / here.mean()),
                               "rows_at": {str(tm): int(placed(counts, tm)[0]) for tm in row_tiles(cell) + (512,)}}
    for site, (kdim, ndim) in (("in", (hidden, width)), ("out", (ffn, hidden))):
        for kernel in KERNELS:
            entry = {"site": site, "kernel": kernel, "K": kdim, "N": ndim, "rows": []}
            sent = int(draws["uneven"][:held].sum())
            cost = {"flops": 2.0 * sent * kdim * ndim,
                    "bytes": 2.0 * (held * kdim * ndim + sent * kdim + sent * ndim)}
            entry["floor_ms"], entry["bound"] = flops.least_time_s(cost, peak)
            entry["floor_ms"] *= 1e3
            calls = []
            for tiling in candidates(kernel, kdim, ndim, cell):
                rows, sizes = placed(draws["uneven"], tiling[0])
                row = {"tiling": list(tiling), "groups": "uneven", "M": rows}
                entry["rows"].append(row)
                calls.append((row, *call_of(kernel, kdim, ndim, tiling, rows, sizes)))
            measure(calls)
            timed = [r for r in entry["rows"] if "error" not in r]
            best = min(timed, key=lambda r: r.get("device_ms", r["wall_ms"]))
            again = []
            for tiling in {tuple(best["tiling"]), PARENT}:
                rows, sizes = placed(draws["even"], tiling[0])
                row = {"tiling": list(tiling), "groups": "even", "M": rows}
                entry["rows"].append(row)
                again.append((row, *call_of(kernel, kdim, ndim, tiling, rows, sizes)))
            measure(again)
            out["kernels"].append(entry)
            print(name, site, kernel, "floor %.3f" % entry["floor_ms"], "parent",
                  json.dumps(entry["rows"][0]), "best", json.dumps(best), flush=True)
    return out


def summary() -> int:
    from galvatron_tpu.ops import moe

    results = json.load(open(OUT))
    for name, cell in results["cells"].items():
        even = cell["shapes"]["even_rows_a_group"]
        print("%s (even rows a group %g)" % (name, even))
        sums = {"parent": 0.0, "best": 0.0, "rule": 0.0}
        for entry in cell["kernels"]:
            rows = [r for r in entry["rows"] if r["groups"] == "uneven" and "error" not in r]
            ms = lambda r: r.get("device_ms", r["wall_ms"])  # noqa: E731
            parent, best = rows[0], min(rows, key=ms)
            dims = dict(moe.matmul_calls(entry["K"], entry["N"]))[entry["kernel"]]
            picked = list(moe.gmm_tiling(entry["kernel"], *dims, even))
            rule = next((r for r in rows if r["tiling"] == picked), None)
            for label, r in (("parent", parent), ("best", best), ("rule", rule)):
                sums[label] += ms(r) if r else float("nan")
            print("  %-3s %-5s K %4d N %4d floor %.3f  parent %.3f  best %s %.3f  rule %s %s" % (
                entry["site"], entry["kernel"], entry["K"], entry["N"], entry["floor_ms"], ms(parent),
                best["tiling"], ms(best), picked, "%.3f" % ms(rule) if rule else "not timed"))
        print("  a block's six kinds of call: parent %(parent).3f  best %(best).3f  rule %(rule).3f" % sums)
    return 0


def main(argv) -> int:
    if "--summary" in argv:
        return summary()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("moe_gmm_sweep needs a TPU; found %s" % jax.devices()[0].platform, file=sys.stderr)
        return 2
    names = [a for a in argv if a in CELLS] or list(CELLS)
    out = os.path.join(os.path.dirname(OUT), argv[argv.index("--out") + 1]) if "--out" in argv else OUT
    os.makedirs(os.path.dirname(out), exist_ok=True)
    results = {"device": jax.devices()[0].device_kind, "repeat": REPEAT, "cells": {}}
    for name in names:
        results["cells"][name] = sweep_cell(name, CELLS[name])
        with open(out, "w") as f:
            json.dump(results, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
