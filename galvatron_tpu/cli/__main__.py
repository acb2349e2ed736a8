"""``python -m galvatron_tpu.cli <subcommand> [flags]``.

Subcommands replace the reference's per-model shell scripts
(models/*/scripts/train_dist.sh etc.):

    train              run training (GLOBAL flags or --galvatron_config_path)
    search             run the strategy search (CPU only; --objective serve
                       adds the latency-aware serving objective)
    serve              run the prefill/decode inference engine under a
                       (searched) strategy: restores a train-layout
                       checkpoint into the serve layout, drives a synthetic
                       or replayed load through the continuous batcher,
                       reports TTFT/TPOT percentiles and tokens/s
    profile            profile model computation/memory
    profile-hardware   profile ICI/DCN collective bandwidths
    lint               static analysis: validate strategy JSONs / scan code
                       for jax-API drift and jit hazards / audit checkpoint
                       dirs offline (--ckpt) / trace-lint the train step's
                       jaxpr (--trace: GSPMD miscompile classes, collective
                       audit) / jax-workaround inventory (--compat)
                       (CPU only, never compiles; exits 1 on errors)
    report             analyze a telemetry JSONL written by `train
                       --telemetry`: steady-state step time, MFU, lifecycle
                       timeline, predicted-vs-measured divergence table
                       (offline; exits 1 on schema violations)
"""

import sys

from galvatron_tpu.obs import launch


def main():
    if len(sys.argv) < 2 or sys.argv[1] in ("-h", "--help"):
        print(__doc__)
        return 0
    cmd, argv = sys.argv[1], sys.argv[2:]
    if cmd == "train":
        from galvatron_tpu.cli.train import main as run
    elif cmd == "search":
        from galvatron_tpu.cli.search import main as run
    elif cmd == "serve":
        from galvatron_tpu.cli.serve import main as run
    elif cmd == "profile":
        from galvatron_tpu.cli.profile import main_model as run
    elif cmd == "profile-hardware":
        from galvatron_tpu.cli.profile import main_hardware as run
    elif cmd == "lint":
        from galvatron_tpu.cli.lint import main as run
    elif cmd == "report":
        from galvatron_tpu.obs.report import main as run
    else:
        print("unknown subcommand %r\n%s" % (cmd, __doc__))
        return 2
    # no subcommand carries the import record's monitoring into its work
    # (cli/train.py has closed it itself)
    launch.IMPORTS.done()
    run(argv)
    return 0


if __name__ == "__main__":
    sys.exit(main())
