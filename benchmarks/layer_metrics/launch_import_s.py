"""Importing the program, read from inside it: from the first statement of
`galvatron_tpu/cli/__init__.py` to the last module-level import of
`cli/train.py`, `launch_imports.total_s` of the trainer's summary
(`galvatron_tpu/obs/launch.py`). The harness times the same import from
outside as `setup_parts_s.import_program_s`, which also holds what runs
before the CLI package is entered (`galvatron_tpu/__init__.py`) and after
`cli/train.py`'s last import (its own body)."""


def read(run):
    imports = run["summary"].get("launch_imports")
    return None if not imports else imports.get("total_s")
