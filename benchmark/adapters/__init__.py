"""Adapters: what the judge (``correctness.py``) judges, one module an
architecture. ``README.md`` has the seam's contract."""
