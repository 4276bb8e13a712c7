"""Chip benchmark of this repository: cells, traffic, metrics and checks.

Driven by ``BENCHMARK.json`` at the repository root and by data files found
by name under this directory; see ``run.py`` for the command line.
"""
