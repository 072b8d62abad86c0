"""The cheap cases of ``benchmark/tests/test_ouro.py`` (no judge, no server),
re-exported so that tier-1 holds this configuration's entries in
``BENCHMARK.json``, its file's published keys and its counts module."""

import json

from benchmark.tests import test_ouro
from benchmark.tests.test_ouro import (  # noqa: F401
    test_the_configuration_carries_the_published_keys_unchanged,
    test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand,
    test_the_rehearsal_files_names_resolve)


def test_the_real_files_names_resolve_and_only_add(monkeypatch):
    """The benchmark's own case pins ouro's four metrics as the LAST four of
    ``per_layer``, which was true of the file PR 52 left and is a
    ``benchmark`` PR's to restate (no other kind may edit a file under
    ``benchmark/``). Here it reads the file up to those four, and what later
    PRs appended (PR 53: the three ragged latent kernel metrics) is held to
    come after them, in one piece."""
    raw = (test_ouro.REPO / "BENCHMARK.json").read_text()
    bench = json.loads(raw)
    names = [m["name"] for m in bench["per_layer"]]
    end = names.index(test_ouro.NEW_METRICS[-1]) + 1
    since = names[end:]
    assert since == ["mla_ragged_attention_us",
                     "gdla_full_ragged_attention_us",
                     "gdla_window_ragged_attention_us"]
    as_left = {**bench, "per_layer": bench["per_layer"][:end]}
    loads = json.loads
    monkeypatch.setattr(json, "loads", lambda text, *a, **kw: (
        as_left if text == raw else loads(text, *a, **kw)))
    test_ouro.test_the_real_files_names_resolve_and_only_add()
