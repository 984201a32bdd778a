"""The on-chip benchmark: one harness (``chipbench.run``) driven by data.

Each configuration (``configs/``), traffic mix (``traffic/``) and
per-layer metric reader (``layer_metrics/``) is a file of its own that the
harness finds by the name ``BENCHMARK.json`` gives it.
"""
