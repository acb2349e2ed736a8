"""One module a per-layer metric, named as BENCHMARK.json names the metric,
each with one `read(run) -> float or None`. See benchmarks/README.md."""
