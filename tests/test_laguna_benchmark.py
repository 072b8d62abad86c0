"""The cheap cases of ``benchmark/tests/test_laguna.py`` (no judge, no
server), re-exported so that tier-1 holds this configuration's entries in
``BENCHMARK.json``, its file's published keys, its counts module and what
its metric files read."""

import json

from benchmark.tests import test_laguna
from benchmark.tests.test_laguna import (  # noqa: F401
    test_every_new_metric_file_reads_its_own_call_site_and_counter,
    test_the_configuration_carries_the_published_keys_unchanged,
    test_the_counts_answer_the_roles_and_agree_with_a_count_by_hand,
    test_the_rehearsal_files_names_resolve)


def test_the_real_files_names_resolve_and_only_add(monkeypatch):
    """The benchmark's own case pins laguna's cell as the LAST of the two
    gauge shares' ``workloads`` lists, which was true of the file PR 54 left
    and is a ``benchmark`` PR's to restate. Here every metric's list is read
    up to laguna's cell, and what a later PR appended behind it (PR 58: its
    cell, to the accepted expert metrics and ``moe_layers_share``) is held
    to be exactly that."""
    raw = (test_laguna.REPO / "BENCHMARK.json").read_text()
    bench = json.loads(raw)
    later = {}

    def as_laguna_left(metric: dict) -> dict:
        listed = metric.get("workloads", [])
        if test_laguna.REAL_CELL not in listed:
            return metric
        end = listed.index(test_laguna.REAL_CELL) + 1
        if listed[end:]:
            later[metric["name"]] = listed[end:]
        return {**metric, "workloads": listed[:end]}

    as_left = {**bench,
               "per_layer": [as_laguna_left(m) for m in bench["per_layer"]]}
    assert later == {name: ["glm-5-int8.longctx-closed-32"] for name in (
        "moe_experts_us", "moe_kernel_time_share",
        "moe_experts_touched_share", "moe_assignments_local_share",
        "moe_decode_experts_touched_share", "routed_experts_roofline",
        "moe_compact_share", "moe_item_rows_per_touched_expert",
        "moe_layers_share")}
    loads = json.loads
    monkeypatch.setattr(json, "loads", lambda text, *a, **kw: (
        as_left if text == raw else loads(text, *a, **kw)))
    test_laguna.test_the_real_files_names_resolve_and_only_add()
