"""Command-line entry points: train / search / profile / profile-hardware.

The analogue of the reference's per-model ``train_dist.py`` / ``search_dist.py``
/ ``profiler.py`` entry scripts plus ``initialize_galvatron`` (reference
core/arguments.py:8-30). One set of drivers serves every registered model
family (``--model_type``), so there is no per-model script duplication.

The package's first statement starts the record of the program's import
(obs/launch.py): `cli/train.py` closes it after its last import, and
`cli/__main__.main` after importing any other subcommand.
"""

from galvatron_tpu.obs import launch

launch.IMPORTS.install()

from galvatron_tpu.cli.arguments import initialize_galvatron  # noqa: E402,F401
