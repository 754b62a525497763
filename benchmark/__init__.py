"""The benchmark: BENCHMARK.json's harness and yardstick (see run.py)."""
