"""The chip benchmark: one cell per run, found by the names in
BENCHMARK.json (see perf/harness.py)."""
