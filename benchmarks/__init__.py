"""The benchmark of galvatron_tpu on the chip: see benchmarks/README.md."""
