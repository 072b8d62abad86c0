"""Who writes a slot's device rows (ISSUE 36).

What the programs only read (page table, sampling and termination rows, the
active mask) the host owns and uploads whole with a dispatch; what the device
advances (last token, key, length, finished) only the step programs write,
a flipping row inside ``mixed_step``. So serving compiles and dispatches the
step programs and nothing else: no op-by-op program at an arrival, a flip or
a finish, and ``restore_row`` alone at a resume. This file is a process of
its own under ``--dist loadfile``: no earlier test has cached the tiny
programs an op-by-op patch would compile."""

import logging
import re
import threading

import jax
import numpy as np
import pytest

from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.ops.sampling import host_key, host_split
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

MODELS = ["tiny-llama", "tiny-falcon-h1", "tiny-sdar", "tiny-kimi-share4"]
STEP_PROGRAMS = {"mixed_step", "paged_decode_chunk"}


def _cfg(model, **over):
    base = dict(model=model, max_seq_len=128, max_batch=2, decode_chunk=4,
                prefix_cache_pages=80, prefix_page_size=16,
                prefill_budget_tokens=32)
    if "kimi" in model:
        base["quantization"] = "int8"
    if "sdar" in model:
        base["decode_chunk"] = 10
    base.update(over)
    return EngineConfig(**base)


def _counter(name) -> float:
    for _labels, value in default_registry.counter(name).samples():
        return value
    return 0.0


class _Compiles(logging.Handler):
    """The names of the programs JAX compiles while this is attached."""

    def __init__(self):
        super().__init__()
        self.names: list[str] = []

    def emit(self, record):
        m = re.match(r"Compiling (?:jit\()?(\w+)\)? with global shapes",
                     record.getMessage())
        if m:
            self.names.append(m.group(1))

    def paused(self, fn):
        """``fn``, with what it compiles left out."""
        def call(*args, **kwargs):
            n = len(self.names)
            try:
                return fn(*args, **kwargs)
            finally:
                del self.names[n:]
        return call

    def __enter__(self):
        self._was = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", self._was)


def _serve(eng, prompt, sampling, timeout=240):
    """One request to its end on a running engine: (tokens, finish)."""
    done = threading.Event()
    out = {"tokens": [], "finish": None}

    def emit(ev):
        if ev.token_id >= 0:
            out["tokens"].append(ev.token_id)
        if ev.finished is not None:
            out["finish"] = ev.finished
            done.set()

    eng.submit(prompt, sampling, emit)
    assert done.wait(timeout), eng.stats()
    return out["tokens"], out["finish"]


def _manual(cfg):
    eng = ContinuousBatchingEngine(cfg, seed=0)
    eng.start = lambda: None    # no thread: the test makes the loop's passes
    return eng


def _prompts(seed=5):
    rng = np.random.default_rng(seed)
    # 70 tokens: three chunks under the budget of 32, then the flip
    return [rng.integers(3, 200, n).tolist() for n in (70, 41, 23)]


@pytest.mark.parametrize("model", MODELS)
def test_serving_compiles_only_the_step_programs(model, retrace):
    """Admission, chunks, the flip, decode, a finish and the slot's next
    owner: from the engine's build on, nothing is compiled but
    ``mixed_step`` (a width each) and ``paged_decode_chunk``; no loose
    program is counted, and the host-owned rows go up with dispatches."""
    eng = ContinuousBatchingEngine(_cfg(model, max_batch=1), seed=0)
    loose = _counter("llm_loose_row_programs_total")
    uploads = _counter("llm_control_rows_uploads_total")
    try:
        with _Compiles() as compiled:
            first, second, third = _prompts()
            toks, fin = _serve(eng, first, SamplingParams(
                max_tokens=9, temperature=0.8, top_k=5, seed=11))
            assert fin == "length" and len(toks) == 9
            # the slot's next owners, other rows: greedy, another limit
            toks, fin = _serve(eng, second, SamplingParams(max_tokens=14))
            assert fin == "length" and len(toks) == 14
            _serve(eng, third, SamplingParams(max_tokens=5, temperature=0.5))
    finally:
        eng.shutdown()
    assert compiled.names, "no compile was logged: a wrong pattern?"
    assert set(compiled.names) <= STEP_PROGRAMS, compiled.names
    assert _counter("llm_loose_row_programs_total") == loose
    assert _counter("llm_control_rows_uploads_total") >= uploads + 3


def test_a_resume_takes_one_program(retrace):
    """A preempted row comes back through ``restore_row``, one program, with
    its stream bit for bit (a greedy row and a seeded sampled one): beside
    the step programs the scheduler compiles that and nothing else."""
    first, second, _ = _prompts(9)
    samplings = [SamplingParams(max_tokens=24),
                 SamplingParams(max_tokens=24, temperature=0.9, top_k=8,
                                seed=77)]

    def run(preempt_at, compiled=None):
        eng = _manual(_cfg("tiny-llama"))
        if compiled is not None:
            # the pool's movers (pages to the host and back) are the page
            # pool's own programs: not counted among a slot's rows
            for name in ("save_chain_to_host", "restore_chain_from_host"):
                setattr(eng.pool, name,
                        compiled.paused(getattr(eng.pool, name)))
        out = {0: [], 1: []}
        fins = {}

        def emit_for(i):
            def emit(ev):
                if ev.token_id >= 0:
                    out[i].append(ev.token_id)
                if ev.finished is not None:
                    fins[i] = ev.finished
            return emit

        try:
            for i, (p, s) in enumerate(zip((first, second), samplings)):
                eng.submit(p, s, emit_for(i))
            for n in range(400):
                if len(fins) == 2:
                    break
                if n == preempt_at:
                    slot = next(i for i, st in enumerate(eng.slots)
                                if st is not None and st.phase == "decode"
                                and st.sampling.temperature > 0)
                    eng._preempt_slot(slot, eng.slots[slot])
                eng._loop_pass()
        finally:
            eng.shutdown()
        assert fins == {0: "length", 1: "length"}, (fins, eng.stats())
        return out

    want = run(preempt_at=None)
    loose = _counter("llm_loose_row_programs_total")
    with _Compiles() as compiled:
        got = run(preempt_at=8, compiled=compiled)
    assert got == want
    assert _counter("llm_loose_row_programs_total") == loose + 1
    assert set(compiled.names) - STEP_PROGRAMS == {"restore_row"}, \
        compiled.names


def _first_owner(model):
    """A sampled request that ends by a stop id: (prompt, sampling)."""
    prompt = _prompts(21)[0]
    free = SamplingParams(max_tokens=12, temperature=0.8, top_k=5, seed=31)
    eng = ContinuousBatchingEngine(_cfg(model, max_batch=1), seed=0)
    try:
        toks, _ = _serve(eng, prompt, free)
    finally:
        eng.shutdown()
    # the first token that does not occur earlier in the stream, from the
    # fourth on: the same request then stops there
    stop = next(t for i, t in enumerate(toks) if i >= 3 and t not in toks[:i])
    return prompt, SamplingParams(
        max_tokens=12, temperature=0.8, top_k=5, seed=31,
        stop_token_ids=[stop]), toks[: toks.index(stop) + 1]


@pytest.mark.parametrize("model", MODELS)
def test_a_slots_second_owner_starts_clean(model):
    """The first owner ends by a stop id, sampled with a seed; the second
    takes the slot greedy, with other stop ids and a longer limit, and gets
    the tokens a fresh engine gives it: ``finished``, the stop ids, the
    limit, the key and (a block model) ``gen_start`` and the opened block
    do not leak. And the seeded request reads the same in another slot with
    neighbours."""
    prompt, sampled, want_first = _first_owner(model)
    other = _prompts(22)[1]
    greedy = SamplingParams(max_tokens=20, stop_token_ids=[7, 8, 9])

    fresh = ContinuousBatchingEngine(_cfg(model, max_batch=1), seed=0)
    try:
        want_second = _serve(fresh, other, greedy)
    finally:
        fresh.shutdown()

    eng = ContinuousBatchingEngine(_cfg(model, max_batch=1), seed=0)
    try:
        toks, fin = _serve(eng, prompt, sampled)
        assert (toks, fin) == (want_first, "stop")
        assert _serve(eng, other, greedy) == want_second
        # and the slot's third owner is the first again, seed and all
        assert _serve(eng, prompt, sampled) == (want_first, "stop")
    finally:
        eng.shutdown()

    # another slot, neighbours beside it: the seed's stream is its own
    eng = ContinuousBatchingEngine(_cfg(model, max_batch=3), seed=0)
    try:
        done = threading.Event()
        left = [2]

        def neighbour(ev):
            if ev.finished is not None:
                left[0] -= 1
                if not left[0]:
                    done.set()

        for p in _prompts(23)[1:]:
            eng.submit(p, SamplingParams(max_tokens=30, temperature=0.7,
                                         seed=5), neighbour)
        assert _serve(eng, prompt, sampled) == (want_first, "stop")
        assert done.wait(240), eng.stats()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1, 2**31, 2147480001,
                                  2**32 + 5, -3])
def test_host_keys_are_jax_randoms(seed):
    """A key made on the host is ``jax.random.PRNGKey``'s, and the host's
    split is ``jax.random.split``'s, word for word: a seed reproduces the
    tokens it produced when the scheduler split on the device."""
    key = host_key(seed)
    np.testing.assert_array_equal(key, np.asarray(jax.random.PRNGKey(seed)))
    want = jax.random.PRNGKey(seed)
    for _ in range(3):
        want, want_sub = jax.random.split(want)
        key, sub = host_split(key)
        np.testing.assert_array_equal(key, np.asarray(want))
        np.testing.assert_array_equal(sub, np.asarray(want_sub))


def test_arrival_host_ms_mean_reads_round_records():
    """``benchmark/layer_metrics/arrival_host_ms_mean.json`` over recorded
    round records: the mean, over the rounds that carried a prompt chunk
    (``mixed`` with rows decoding beside it, ``prefill`` with none), of the
    admission pass and the dispatch; decode rounds are left out, and no
    such round is nothing to read."""
    import json
    from pathlib import Path

    from benchmark import layer_readers

    spec = json.loads((Path(__file__).resolve().parents[1] / "benchmark" /
                       "layer_metrics" / "arrival_host_ms_mean.json"
                       ).read_text())
    reader = layer_readers.resolve(spec.pop("kind"))
    spec.pop("what")
    canned = [
        {"kind": "prefill", "admit_ms": 2.0, "dispatch_ms": 10.0,
         "host_emit_ms": 50.0},
        {"kind": "decode", "admit_ms": 0.0, "dispatch_ms": 1.0},
        {"kind": "mixed", "admit_ms": 4.0, "dispatch_ms": 20.0},
        {"kind": "mixed", "admit_ms": 0.0, "dispatch_ms": 6.0},
    ]
    assert reader({"rounds": canned}, **spec) == pytest.approx(14.0)
    assert reader({"rounds": canned[1:2]}, **spec) is None

    eng = ContinuousBatchingEngine(_cfg("tiny-llama"), seed=0)
    try:
        _serve(eng, _prompts()[0], SamplingParams(max_tokens=6))
        records = list(eng.round_timings)
    finally:
        eng.shutdown()
    arrivals = [r for r in records if r["kind"] in ("mixed", "prefill")]
    assert len(arrivals) == 3 and len(records) > 3      # 70 tokens under 32
    assert reader({"rounds": records}, **spec) == pytest.approx(
        sum(r["admit_ms"] + r["dispatch_ms"] for r in arrivals) / 3)
