"""``benchmark/tests/test_ragged_metrics.py`` (no judge, no server),
re-exported so that tier-1 holds PR 53's three per-layer entries in
``BENCHMARK.json`` and what their data files read from a reduced trace."""

from benchmark.tests.test_ragged_metrics import (  # noqa: F401
    test_each_call_sites_kernel_is_read_under_its_own_name,
    test_the_entries_are_appended_and_resolve)
