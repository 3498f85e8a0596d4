"""The chip benchmark: one data-driven harness (`bench.run`) over the
cells that `BENCHMARK.json` declares."""
