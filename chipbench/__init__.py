"""On-chip benchmark of this repository: cells, traffic, metrics, checks.

``BENCHMARK.json`` at the repository root names every cell. Everything that
belongs to one configuration, traffic mix or per-layer metric lives in a file
of its own here, found by the name the JSON gives it (see ``spec``).
"""
