"""The repo's benchmark: see benchmarks/README.md and BENCHMARK.json."""
