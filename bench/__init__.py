"""The benchmark: one harness, driven by ``BENCHMARK.json`` and the data
files beside it.  ``python bench/run.py --help`` runs one cell."""
