"""The cheap cases of ``benchmark/tests/test_ouro.py`` (no judge, no server),
re-exported so that tier-1 holds this configuration's entries in
``BENCHMARK.json``, its file's published keys and its counts module."""

from benchmark.tests.test_ouro import (  # noqa: F401
    test_the_configuration_carries_the_published_keys_unchanged,
    test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand,
    test_the_real_files_names_resolve_and_only_add,
    test_the_rehearsal_files_names_resolve)
