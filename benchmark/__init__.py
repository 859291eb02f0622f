"""The benchmark: BENCHMARK.json at the repo root names the cells; everything
that measures them lives in this directory and in tests/benchmark."""
