"""Importing jax, importing the program and starting the chip
(`jax.devices()`), added up: what `python -m galvatron_tpu.cli train` pays one
after the other. The benchmark starts the chip on a thread beside the
imports, so its `setup_s` holds the longer of the two and not this sum."""


def read(run):
    parts, chip = run.get("setup_parts_s") or {}, run.get("chip_start_s")
    if chip is None or "import_jax_s" not in parts or "import_program_s" not in parts:
        return None
    return parts["import_jax_s"] + parts["import_program_s"] + chip
