"""The benchmark: one command, driven by ``BENCHMARK.json`` at the root."""
