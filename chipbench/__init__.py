"""Chip benchmark of the paged serving engine (see BENCHMARK.json and
PERF.md at the checkout root; ``python3 chipbench/run.py --help``)."""
