"""Tier-1 guard of the benchmark's data files: the cases of
``benchmark/tests/test_names_resolve.py``, re-exported. Every name that a
file under ``benchmark/`` or ``BENCHMARK.json`` uses for code (a
configuration's adapter and counts module, a layer metric's reader kind and
arguments, a cell's traffic file, a metric's ``workloads``) resolves, on the
CPU, without a compile (about 5 s). A PR that adds a configuration, a cell or
a metric as files and entries is held to this by the driver's own command."""

from benchmark.tests.test_names_resolve import *  # noqa: F401,F403
