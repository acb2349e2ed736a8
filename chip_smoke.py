#!/usr/bin/env python3
"""The quickest proof that the trainer still starts on the chip.

    python3 chip_smoke.py             # one chip (what the driver runs)
    python3 chip_smoke.py --chips 4   # four chips: one-chip run vs two hybrid layouts

One process drives `galvatron_tpu.cli.train` — the code `python -m
galvatron_tpu.cli train` runs — at LLaMA-7B width (hidden 4096, ffn 11008,
32 heads x 128, vocab 32000, seq 2048, bf16 compute, fp32 parameters and
Adam) with depth cut 32 -> 2 and random weights from --seed, and checks what
comes out. It needs a TPU whose kind is in obs/flops.py's peak table and
exits non-zero, before any work, on anything else. Every stdout line is one
JSON object; the last is the verdict, the earlier ones are smoke observations
(one run, no repeats — not benchmark numbers). The trainer's own log goes to
stderr.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import sys
import types

# Expected loss of the seed's untrained weights: the final norm hands the
# lm_head unit-RMS rows and the head is N(0, init_std^2), so the logits are
# N(0, hidden * init_std^2) and E[CE] = ln V + sigma^2 / 2 (11.19, not ln V)
FIRST_LOSS = math.log(32000) + 4096 * 0.02 ** 2 / 2
FIRST_LOSS_TOL = 0.1
# forward loss, bf16 compute: flash kernel vs XLA attention on the same
# weights and batch differ by accumulation order only
XLA_REF_TOL = 2e-2
# per-step loss of a sharded layout vs the one-chip run (same seed, same
# batches): bf16 partial sums are reduced in another order under tp/pp, and
# the difference feeds back through the Adam steps
LAYOUT_TOL = 5e-2
TRAIN_ITERS = 8
BATCH, SEQ = 2, 2048  # sized from the compile-only rehearsal: 14.59 of 15.75 GiB


def say(**obj):
    print(json.dumps(obj), flush=True)


def fail(why):
    say(ok=False, error=why)
    sys.exit(1)


class StepCompileCounter:
    """Counts backend compilations of the jitted train step, by name, off
    jax's own monitoring events: a second one means some call saw other
    shapes or shardings than the step was built for."""

    STEP_NAMES = ("jit(plain_step)", "jit(train_step)")

    def __init__(self):
        self.count = 0

    def __call__(self, event, duration, fun_name=None, **_):
        if (event == "/jax/core/compile/backend_compile_duration"
                and fun_name in self.STEP_NAMES):
            self.count += 1


def train_argv(seed, batch, extra=()):
    return [
        "--model_type", "llama", "--model_size", "llama-7b",
        "--set_layernum_manually", "1", "--num_layers", "2",
        "--mixed_precision", "bf16",
        "--global_train_batch_size", str(batch),
        "--train_iters", str(TRAIN_ITERS), "--log_interval", "1",
        "--seed", str(seed), *extra,
    ]


def run_train(argv, sample_devices=None):
    """One `cli.train.train` run. Returns (args, summary, compiled step,
    compilations of the step, per-device bytes_in_use sampled while the state
    was live)."""
    import jax

    from galvatron_tpu.cli import train as T
    from galvatron_tpu.cli.arguments import initialize_galvatron

    args = initialize_galvatron(mode="train_dist", argv=argv)
    live = {}
    if sample_devices is not None:
        def on_step(it):
            if it == TRAIN_ITERS - 1:
                live.update({d.id: d.memory_stats()["bytes_in_use"]
                             for d in sample_devices})

        # the driver's per-step observation seam; the step itself is untouched
        args.fault_hooks = types.SimpleNamespace(
            on_step=on_step, wrap_step_fn=None, wrap_data_iter=None)
    counter = StepCompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    before = set(T._STEP_EXECUTABLES)
    try:
        with contextlib.redirect_stdout(sys.stderr):
            summary = T.train(args)
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    new = [k for k in T._STEP_EXECUTABLES if k not in before]
    if len(new) != 1:
        fail("expected one new step executable, found %d" % len(new))
    gc.collect()
    return args, summary, T._STEP_EXECUTABLES[new[0]], counter.count, live


def checked_run(name, argv, batch, seq, sample_devices=None):
    """run_train, its observation line, and the checks every run must pass:
    finite losses from the expected first value, the flash kernel in the
    compiled step, exactly one compilation of the step."""
    args, summary, step, n_compiled, live = run_train(argv, sample_devices)
    hlo = step.as_text()
    losses = summary["losses"]
    say(phase=name, smoke_observation=True,
        losses=[round(x, 5) for x in losses],
        trace_s=round(summary["trace_ms"] / 1e3, 2),
        compile_s=round(summary["compile_ms"] / 1e3, 2),
        persistent_cache_hit=summary["compile_cache_hit"],
        step_ms_after_warmup=round(summary["wall_ms_per_iter"], 2),
        step_ms_p50=round(summary["p50_iter_ms"], 2),
        tokens_per_s=round(batch * seq / (summary["wall_ms_per_iter"] / 1e3), 1),
        model_flops_utilization=round(summary["mfu"], 4),
        compiled_step_memory_gib=round(summary["compiled_step_memory_mb"] / 1024, 2),
        step_programs_compiled=n_compiled,
        tpu_custom_calls=hlo.count("tpu_custom_call"))
    if len(losses) != TRAIN_ITERS or not all(math.isfinite(x) for x in losses):
        fail("%s: losses not finite or missing: %r" % (name, losses))
    if abs(losses[0] - FIRST_LOSS) > FIRST_LOSS_TOL:
        fail("%s: first loss %.4f is not within %.2f of ln(32000) + sigma^2/2 = %.4f"
             % (name, losses[0], FIRST_LOSS_TOL, FIRST_LOSS))
    if "tpu_custom_call" not in hlo:
        fail("%s: the compiled step holds no tpu_custom_call: the flash kernel "
             "is not in it" % name)
    if n_compiled != 1:
        fail("%s: the train step was compiled %d times, expected exactly 1"
             % (name, n_compiled))
    return args, losses, step, hlo, live


def xla_reference_loss(args):
    """Forward loss of the first batch on the seed's initial weights with
    attn_impl='xla': the plain reference for the kernel path."""
    import jax

    from galvatron_tpu.cli.arguments import hp_config_from_args, model_config_from_args
    from galvatron_tpu.cli.train import build_data_iterator
    from galvatron_tpu.runtime.model_api import construct_hybrid_parallel_model

    fam, cfg = model_config_from_args(args)
    hp = hp_config_from_args(args, cfg.num_layers, args.world_size or len(jax.devices()))
    model = construct_hybrid_parallel_model(dataclasses.replace(cfg, attn_impl="xla"), hp)
    params = model.init_params(jax.random.PRNGKey(args.seed))
    batch = model.shard_batch(next(build_data_iterator(args, fam, cfg, hp)))
    return float(jax.jit(model.eval_loss)(params, batch))


def one_chip(seed):
    import jax

    dev = jax.devices()[0]
    batch = BATCH
    try:
        args, losses, *_ = checked_run("one_chip", train_argv(seed, batch), batch, SEQ)
    except jax.errors.JaxRuntimeError as e:
        if "RESOURCE_EXHAUSTED" not in str(e):
            raise
        # the rehearsal counted the step program only (14.59 of 15.75 GiB)
        batch = 1
        say(cuts={"global_batch": "2 -> 1", "why": str(e).splitlines()[0][:200]})
        gc.collect()
        args, losses, *_ = checked_run("one_chip", train_argv(seed, batch), batch, SEQ)
    ref = xla_reference_loss(args)
    diff = abs(losses[0] - ref)
    peak = dev.memory_stats()["peak_bytes_in_use"]
    say(phase="checks", smoke_observation=True, first_loss=round(losses[0], 5),
        xla_attention_forward_loss=round(ref, 5), abs_diff=round(diff, 5),
        tolerance=XLA_REF_TOL, peak_hbm_gib=round(peak / 2**30, 2))
    if not diff <= XLA_REF_TOL:
        fail("first loss differs from the XLA-attention forward by %.4g > %.4g"
             % (diff, XLA_REF_TOL))
    if not peak > 0:
        fail("memory_stats()['peak_bytes_in_use'] is %r" % peak)


LAYOUTS = (
    # name, extra flags, collectives the layout implies in the compiled step
    ("one_of_four", ("--world_size", "1"), ()),
    ("tp2_dp2_zero2", ("--global_tp_deg", "2", "--default_dp_type", "zero2",
                       "--vocab_tp", "2"),
     ("all-reduce", "all-gather", "reduce-scatter")),
    ("pp2_tp2", ("--pp_deg", "2", "--global_tp_deg", "2", "--chunks", "2"),
     ("collective-permute",)),
)


def four_chips(seed):
    import jax

    devices = jax.devices()
    base = None
    for name, extra, collectives in LAYOUTS:
        sharded = name != "one_of_four"
        _, losses, step, hlo, live = checked_run(
            name, train_argv(seed, BATCH, extra), BATCH, SEQ,
            sample_devices=devices if sharded else None)
        if not sharded:
            base = losses
            continue
        diffs = [abs(a - b) for a, b in zip(losses, base)]
        param_shardings = jax.tree.leaves(step.input_shardings[0][0])
        short = [s for s in param_shardings if len(s.device_set) != len(devices)]
        found = {c: hlo.count(c) for c in collectives}
        say(phase=name + "_checks", smoke_observation=True,
            max_abs_loss_diff_vs_one_chip=round(max(diffs), 5), tolerance=LAYOUT_TOL,
            param_leaves=len(param_shardings), leaves_not_on_all_chips=len(short),
            bytes_in_use_gib={str(k): round(v / 2**30, 2) for k, v in live.items()},
            collectives=found)
        if not max(diffs) <= LAYOUT_TOL:
            fail("%s: per-step losses differ from the one-chip run by %.4g > %.4g"
                 % (name, max(diffs), LAYOUT_TOL))
        if short:
            fail("%s: %d parameter leaves do not span all chips" % (name, len(short)))
        if len(live) != len(devices) or not all(v > 0 for v in live.values()):
            fail("%s: not every chip holds state: %r" % (name, live))
        missing = [c for c, n in found.items() if n == 0]
        if missing:
            fail("%s: compiled step lacks %s" % (name, missing))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=1234)
    opts = ap.parse_args()

    import flax
    import jax
    import jaxlib
    import optax

    from galvatron_tpu.obs.flops import PEAK_FLOPS_BY_KIND

    devices = jax.devices()
    dev = devices[0]
    known = [k for k in PEAK_FLOPS_BY_KIND
             if k != "cpu" and dev.device_kind.lower().startswith(k.lower())]
    if dev.platform != "tpu" or not known:
        print("chip_smoke needs a TPU listed in obs/flops.py; found platform=%r kind=%r"
              % (dev.platform, dev.device_kind), file=sys.stderr)
        sys.exit(1)
    if len(devices) != opts.chips:
        print("chip_smoke --chips %d needs exactly that many devices, found %d"
              % (opts.chips, len(devices)), file=sys.stderr)
        sys.exit(1)
    say(versions={"python": sys.version.split()[0], "jax": jax.__version__,
                  "jaxlib": jaxlib.__version__, "flax": flax.__version__,
                  "optax": optax.__version__})
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    say(device=device, peak_flops_row=max(known, key=len))
    say(published={"model": "llama-7b", "hidden": 4096, "ffn": 11008, "heads": 32,
                   "head_dim": 128, "vocab": 32000, "seq": SEQ},
        cuts={"num_layers": "32 -> 2", "global_batch": BATCH,
              "weights": "random, seed %d" % opts.seed, "data": "synthetic tokens",
              "train_iters": TRAIN_ITERS})
    (one_chip if opts.chips == 1 else four_chips)(opts.seed)
    say(ok=True, device=device)


if __name__ == "__main__":
    main()
