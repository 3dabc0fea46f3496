"""Chip benchmark for the serving stack: open-loop traffic through
``ServeEngine`` on a TPU, with end-to-end metrics, per-layer metrics read
from a profiler trace, and a float32 reference that decides ``correct``.

Everything one cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.py``, ``refs/<family>.py``
and ``work/<family>.py``.  ``run.py`` is the entry point.
"""
