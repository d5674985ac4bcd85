"""The benchmark: cells, traffic, metrics and references, driven by
``BENCHMARK.json``."""
