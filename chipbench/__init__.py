"""Chip benchmark of the tuner: one cell, one run, one JSON result line.

See ``run.py`` for the command and ``BENCHMARK.json`` at the repository
root for the cells, metrics and bounds.
"""
