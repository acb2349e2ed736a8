"""Importing `galvatron_tpu.runtime.checkpoint`, inclusive of everything
it pulls (`orbax.checkpoint` and under it `google.cloud.logging`): what a run
that neither loads nor saves a checkpoint pays for nothing.
`launch_imports.checkpoint_s` of the trainer's summary."""


def read(run):
    imports = run["summary"].get("launch_imports")
    return None if not imports else imports.get("checkpoint_s")
