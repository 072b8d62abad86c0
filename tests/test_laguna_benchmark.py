"""The cheap cases of ``benchmark/tests/test_laguna.py`` (no judge, no
server), re-exported so that tier-1 holds this configuration's entries in
``BENCHMARK.json``, its file's published keys, its counts module and what
its metric files read."""

from benchmark.tests.test_laguna import (  # noqa: F401
    test_every_new_metric_file_reads_its_own_call_site_and_counter,
    test_the_configuration_carries_the_published_keys_unchanged,
    test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand,
    test_the_real_files_names_resolve_and_only_add,
    test_the_rehearsal_files_names_resolve)
