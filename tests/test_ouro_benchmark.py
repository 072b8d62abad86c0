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
    """The benchmark's own case pins ouro's configuration, its cell and its
    four metrics as the LAST entries of ``configs``, ``workloads`` and
    ``per_layer``, which was true of the file PR 52 left and is a
    ``benchmark`` PR's to restate (no other kind may edit a file under
    ``benchmark/``). Here it reads each list up to ouro's own entry, and what
    later PRs appended (PR 53: the three ragged latent kernel metrics; PR 54:
    laguna's configuration, cell, eight metrics and the window layers' pages
    a program; PR 58: the configuration of attention over a chosen set, its
    cell and its twelve metrics) is held to come after it, in one piece and in the order it
    was added. The same for a metric's ``workloads`` list that ouro's cell
    was appended to and a later cell after it."""
    raw = (test_ouro.REPO / "BENCHMARK.json").read_text()
    bench = json.loads(raw)

    def cut(key: str, last: str) -> tuple[list, list]:
        """(``bench[key]`` up to the entry named ``last``, the names of the
        entries appended since)."""
        names = [e["name"] for e in bench[key]]
        end = names.index(last) + 1
        return bench[key][:end], names[end:]

    configs, since_configs = cut("configs", test_ouro.REAL)
    cells, since_cells = cut("workloads", test_ouro.REAL_CELL)
    metrics, since = cut("per_layer", test_ouro.NEW_METRICS[-1])
    assert since_configs == ["laguna-s-2.1-int8", "glm-5-int8"]
    assert since_cells == ["laguna-s-2.1-int8.longtail-closed-64",
                           "glm-5-int8.longctx-closed-32"]
    assert since == ["mla_ragged_attention_us",
                     "gdla_full_ragged_attention_us",
                     "gdla_window_ragged_attention_us",
                     "gqa_full_decode_attention_us",
                     "gqa_window_decode_attention_us",
                     "gqa_full_decode_attention_roofline",
                     "gqa_window_decode_attention_roofline",
                     "gqa_kernels_time_share",
                     "gqa_full_ragged_attention_us",
                     "gqa_window_ragged_attention_us",
                     "gqa_window_moe_step_roofline",
                     "attn_window_pages_per_program",
                     "ragged_paged_attention_us",      # PR 55: ouro's cell too
                     # PR 58: none of them lists ouro's cell
                     "dsa_index_scores_us", "dsa_topk_us",
                     "dsa_sparse_decode_attention_us",
                     "dsa_ragged_attention_us", "dsa_kernels_time_share",
                     "dsa_selected_share", "dsa_binding_share",
                     "dsa_decode_keys_scored_per_call",
                     "dsa_decode_keys_selected_per_call",
                     "dsa_index_scores_roofline",
                     "dsa_sparse_decode_attention_roofline",
                     "dsa_moe_step_roofline",
                     "dsa_select_us"]                  # PR 59: glm's alone
    later = {}

    def as_ouro_left(metric: dict) -> dict:
        listed = metric.get("workloads", [])
        if test_ouro.REAL_CELL not in listed:
            return metric
        end = listed.index(test_ouro.REAL_CELL) + 1
        if listed[end:]:
            later[metric["name"]] = listed[end:]
        return {**metric, "workloads": listed[:end]}

    metrics = [as_ouro_left(m) for m in metrics]
    assert later == {
        "kv_layers_share": ["laguna-s-2.1-int8.longtail-closed-64"]}
    as_left = {**bench, "configs": configs, "workloads": cells,
               "per_layer": metrics}
    loads = json.loads
    monkeypatch.setattr(json, "loads", lambda text, *a, **kw: (
        as_left if text == raw else loads(text, *a, **kw)))
    test_ouro.test_the_real_files_names_resolve_and_only_add()
