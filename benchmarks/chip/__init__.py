"""Chip benchmark: one command, one cell, one run, driven by data.

``BENCHMARK.json`` (at the root of the checkout) names the cells. Each
cell pairs a configuration (``configs/<name>.json``) with a traffic mix
(``traffic/<name>.json``); each per-layer metric has a reader of its own
(``metrics/<name>.py``). Adding any of them needs new files only.
"""
