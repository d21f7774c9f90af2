"""The harness: run.py's parts. None of them knows a cell by name; what
belongs to one configuration, traffic mix or metric is a file found by the
name BENCHMARK.json gives it (see benchmark/README.md)."""
