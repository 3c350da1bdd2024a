"""Chip benchmark: every cell of ``BENCHMARK.json`` run on a TPU.

``run.py`` is the entry point; everything a cell needs is found by name:
``configs/<config>.json``, ``traffic/<mix>.json`` and
``metrics/<metric>.py``.
"""
