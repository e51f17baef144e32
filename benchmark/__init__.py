"""The benchmark: ``BENCHMARK.json`` names the cells; ``run.py`` runs one.

Configurations, traffic mixes, references and per-layer metric readers are
files of their own, found by name (``harness.Cell``); see ``PERF.md``."""
