"""The benchmark of BENCHMARK.json: see benchmarks/README.md."""
