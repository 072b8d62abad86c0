"""The scheduler's phase clock (runtime/scheduler.py: _PhaseClock).

The loop is TILED by phases: every record's ``phases`` sum to its
``pass_ms``, consecutive records sum to the thread's time, the four stage
fields are sums over the phases they cover, and the ``starved`` flag (nothing
launched is undrained: the device waits for the host) rises at a drain that
empties the ring and falls at the next launch. The emit of such a drain is
HELD and runs behind that launch (``_close_round``), so ``emit`` is never
starved where something follows. With a profiler running the same phases are
``sched.*`` spans on a line of the trace's ``/host:CPU`` plane."""

import time

import numpy as np
import pytest

from cyberfabric_core_tpu.modkit.metrics import default_registry
from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import (PHASES,
                                                    ContinuousBatchingEngine,
                                                    _PhaseClock)

MODELS = {
    "tiny-llama": dict(decode_chunk=4),
    # a block model: a decode step yields a block
    "tiny-sdar": dict(decode_chunk=10, prefill_budget_tokens=32),
    # a latent page, one chip's share of the experts
    "tiny-kimi-share4": dict(decode_chunk=4, prefill_budget_tokens=32,
                             quantization="int8"),
}
STAGES = {"admit_ms": ("admit",),
          "dispatch_ms": ("capacity", "plan", "upload", "launch"),
          "sync_wait_ms": ("drain",),
          "host_emit_ms": ("commit", "emit")}


def _manual(model="tiny-llama", **over):
    base = dict(model=model, max_seq_len=128, max_batch=4,
                prefix_cache_pages=80, prefix_page_size=16,
                **MODELS.get(model, {}))
    base.update(over)
    eng = ContinuousBatchingEngine(EngineConfig(**base), seed=0)
    eng.start = lambda: None    # no thread: the test makes the loop's passes
    return eng


def _submit(eng, n, max_tokens=12, prompt=20, done=None):
    done = [] if done is None else done
    rng = np.random.default_rng(5)
    for i in range(n):
        eng.submit(rng.integers(3, 200, prompt + 3 * i).tolist(),
                   SamplingParams(max_tokens=max_tokens),
                   lambda ev: done.append(1) if ev.finished else None)
    return done


def _run(eng, done, n, limit=400):
    for _ in range(limit):
        eng._loop_pass()
        if len(done) >= n:
            return
    raise AssertionError(f"not finished in {limit} passes: {eng.stats()}")


@pytest.fixture(scope="module", params=sorted(MODELS))
def served(request):
    """Three requests served by hand-made passes, the third arriving while
    the first two decode (it waits for the ring, whose last chunk's drain
    leaves nothing in flight); the records, and the thread's time from the
    clock's reset to the last record."""
    eng = _manual(request.param)
    done = _submit(eng, 2)
    t0 = eng._clock.take()[2]             # the pass starts here
    while not eng._ring:
        eng._loop_pass()
    _submit(eng, 1, prompt=26, done=done)
    _run(eng, done, 3)
    records = list(eng.round_timings)
    elapsed_ms = (eng.last_round_at - t0) * 1000.0
    eng.shutdown()
    assert len(records) >= 4
    return records, elapsed_ms


def test_phases_tile_every_pass(served):
    records, _ = served
    for r in records:
        assert set(r["phases"]) <= set(PHASES)
        walls = sum(v[0] for v in r["phases"].values())
        assert walls == pytest.approx(r["pass_ms"], rel=0.01, abs=0.002)


def test_passes_sum_to_the_threads_time(served):
    records, elapsed_ms = served
    assert sum(r["pass_ms"] for r in records) == pytest.approx(elapsed_ms,
                                                                rel=0.02)


def test_stage_fields_are_their_phases_sums(served):
    records, _ = served
    for r in records:
        for field, phases in STAGES.items():
            want = sum(r["phases"][p][0] for p in phases if p in r["phases"])
            assert r[field] == pytest.approx(want, abs=0.002), (field, r)


def test_cpu_and_starved_lie_inside_wall(served):
    records, _ = served
    for r in records:
        for phase, (wall, cpu, starved) in r["phases"].items():
            assert 0.0 <= cpu <= wall and 0.0 <= starved <= wall, (phase, r)
    # a round ran in every record: it drained and committed; where its
    # drain left nothing in flight its emit is in the NEXT record, behind
    # that pass's launch and in front of its drain
    assert all({"drain", "commit"} <= set(r["phases"]) for r in records)
    held = [(r, after) for r, after in zip(records, records[1:])
            if r["depth"] == 0]
    assert held
    for r, after in held:
        assert _starved_of(r, "emit") == 0.0
        order = list(after["phases"])
        assert (order.index("launch") < order.index("emit")
                < order.index("drain")), order
        assert after["phases"]["emit"][2] == 0.0 < after["phases"]["emit"][0]


def test_clock_switches_tile_and_starve():
    """The clock alone, under a scripted sequence of switches."""
    clock = _PhaseClock("scripted")
    assert clock.starved and clock.phase == "wait"    # nothing launched yet
    t0 = clock.take()[2]
    clock.to("service")
    clock.to("launch")
    time.sleep(0.002)
    clock.to("launch", starved=False)     # the launch returned
    time.sleep(0.002)
    clock.to("drain")
    time.sleep(0.002)
    clock.to("commit", starved=False)     # a chunk is still in flight
    time.sleep(0.002)
    clock.to("drain")
    clock.to("commit", starved=True)      # that drain emptied the ring
    time.sleep(0.002)
    clock.to("emit")
    phases, pass_ms, t1, _ = clock.take()
    clock.close()
    assert list(phases) == ["wait", "service", "launch", "drain", "commit",
                            "emit"]
    assert sum(v[0] for v in phases.values()) == pytest.approx(pass_ms,
                                                               rel=0.01)
    assert pass_ms == pytest.approx((t1 - t0) * 1000.0, rel=0.01)
    assert phases["service"][2] == phases["service"][0]   # still starved
    assert 2.0 <= phases["launch"][2] <= phases["launch"][0] - 2.0
    assert phases["drain"][2] == 0.0
    assert 2.0 <= phases["commit"][2] <= phases["commit"][0] - 2.0
    assert phases["emit"][2] == phases["emit"][0]
    assert all(cpu <= wall for wall, cpu, _ in phases.values())


def _starved_of(record, *phases):
    return sum(record["phases"][p][2] for p in phases
               if p in record["phases"])


def test_starved_follows_the_ring():
    """With a ring two deep a steady decode round drains with chunks still
    in flight and is never starved; the drain that empties the ring (an
    arrival waits for it) raises the flag, and the arrival's launch drops
    it. That drain's emit waits for the launch: no emit is starved."""
    eng = _manual(decode_lookahead=2)
    try:
        done = _submit(eng, 1, max_tokens=60)
        assert eng._clock.starved             # nothing launched yet
        eng._loop_pass()                      # admit + the mixed step
        first = eng.round_timings[-1]
        assert first["kind"] == "prefill" and first["depth"] == 2
        # chunks were chained off the mixed step: its drain left them in
        # flight, so nothing after the launch's return is starved
        assert not eng._clock.starved
        assert _starved_of(first, "service", "admit", "capacity", "plan",
                           "upload") > 0.0
        assert 0.0 < first["phases"]["launch"][2] <= first["phases"]["launch"][0]
        assert _starved_of(first, "drain", "commit") == 0.0
        for _ in range(3):                    # steady state: ring topped up
            eng._loop_pass()
            steady = eng.round_timings[-1]
            assert steady["kind"] == "decode" and steady["depth"] >= 1
            assert _starved_of(steady, *PHASES) == 0.0
            assert not eng._clock.starved
        # an arrival: the ring is no longer topped up and drains down
        _submit(eng, 1, max_tokens=4, done=done)
        while eng._ring:
            eng._loop_pass()
        assert eng._held is not None and eng._clock.starved
        n_records = len(eng.round_timings)    # the held round's comes later
        eng._loop_pass()                      # the arrival's mixed step
        assert eng._held is None and len(eng.round_timings) == n_records + 2
        last, arrival = list(eng.round_timings)[-2:]
        assert last["kind"] == "decode" and last["depth"] == 0
        assert last["phases"]["drain"][2] == 0.0
        assert last["phases"]["commit"][2] == last["phases"]["commit"][0] > 0
        assert _starved_of(last, "emit") == 0.0   # held: none ran starved
        assert arrival["kind"] == "mixed"
        assert arrival["phases"]["admit"][2] == arrival["phases"]["admit"][0]
        assert arrival["phases"]["upload"][2] > 0.0
        assert arrival["phases"]["drain"][2] == 0.0
        assert arrival["phases"]["emit"][0] > 0.0 == _starved_of(arrival,
                                                                 "emit")
        _run(eng, done, 2)
    finally:
        eng.shutdown()


def test_no_lookahead_starves_between_every_two_rounds():
    """Without a ring every drain empties it: the host's commit, and the
    next round up to its launch, are the device's wait. The emit is not: it
    runs behind that launch."""
    eng = _manual(decode_lookahead=0)
    try:
        done = _submit(eng, 2, max_tokens=16)
        before = _starved_seconds("tiny-llama")
        _run(eng, done, 2)
        records = [r for r in eng.round_timings if r["kind"] == "decode"]
        assert len(records) >= 3
        for r in records[1:]:
            for phase in ("commit", "service", "admit", "capacity"):
                assert r["phases"][phase][2] == r["phases"][phase][0]
            assert r["phases"]["drain"][2] == 0.0
        # /metrics carries the same seconds, by model and phase
        after = _starved_seconds("tiny-llama")
        grew = {p: after.get(p, 0.0) - before.get(p, 0.0) for p in after}
        assert grew["commit"] > 0.0 and grew["admit"] > 0.0
        assert grew.get("drain", 0.0) == 0.0 == grew.get("emit", 0.0)
        assert all(r["phases"]["emit"][0] > 0.0 == r["phases"]["emit"][2]
                   for r in records[1:])
        recorded = sum(v[2] for r in eng.round_timings
                       for v in r["phases"].values()) / 1000.0
        assert sum(grew.values()) >= 0.95 * recorded
    finally:
        eng.shutdown()


def _starved_seconds(model):
    counter = default_registry.counter("llm_device_starved_seconds_total")
    return {labels["phase"]: value for labels, value in counter.samples()
            if labels.get("model") == model}


def test_phases_are_spans_on_the_profilers_host_plane(tmp_path):
    """With jax.profiler running, the phases of a few rounds are ``sched.*``
    events on ONE line of the trace's /host:CPU plane, and they tile that
    thread's time: each starts where the one before ended."""
    import jax
    from jax.profiler import ProfileData

    eng = _manual(decode_lookahead=0)
    try:
        done = _submit(eng, 2, max_tokens=24)
        for _ in range(3):                 # compile outside the trace
            eng._loop_pass()
        jax.profiler.start_trace(str(tmp_path))
        try:
            for _ in range(5):
                eng._loop_pass()
        finally:
            jax.profiler.stop_trace()
        _run(eng, done, 2)
    finally:
        eng.shutdown()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    host = next(p for p in ProfileData.from_file(str(path)).planes
                if p.name == "/host:CPU")
    lines = []
    for line in host.lines:
        spans = sorted((int(e.start_ns), int(e.start_ns + e.duration_ns),
                        e.name) for e in line.events
                       if e.name.startswith("sched."))
        # (an idle engine another test of this process left running has a
        # line too: service, admit, wait, ten times a second)
        if any(name.startswith("sched.drain") for _, _, name in spans):
            lines.append(spans)
    assert len(lines) == 1, "the rounds' spans lie on one thread's line"
    spans = lines[0]
    names = {name for _, _, name in spans}
    assert {n.split(".")[1] for n in names} <= set(PHASES)
    assert "sched.drain" in names and "sched.commit.starved" in names
    assert "sched.emit" in names      # behind a launch, every one of them
    assert not {"sched.drain.starved", "sched.emit.starved"} & names
    covered = sum(e - s for s, e, _ in spans)
    assert covered >= 0.98 * (spans[-1][1] - spans[0][0])
    # a switch, not a nest: no span starts inside another
    assert all(b[0] >= a[1] - 1 for a, b in zip(spans, spans[1:]))


# ------------------------------------------------ the emit behind the launch
#
# A drain that leaves nothing in flight holds its emit; the pass that follows
# launches what comes next and flushes it right behind that launch, before
# its own drain. One rule for every model: K/V pages, recurrent state, blocks.

HELD = {"tiny-llama": dict(decode_chunk=4),
        "tiny-falcon-h1": dict(decode_chunk=4),
        "tiny-sdar": dict(decode_chunk=10)}
SERIES = ("llm_drains_ring_empty_total", "llm_emits_deferred_total")


def _held_engine(model, **over):
    return _manual(model, prefill_budget_tokens=32, **HELD[model], **over)


def _count(name) -> float:
    return sum(v for _, v in default_registry.counter(name).samples())


def _switches(eng) -> list:
    """Every switch of the engine's clock from here on, in order."""
    seen, to = [], eng._clock.to

    def spy(phase, starved=None):
        seen.append(phase)
        return to(phase, starved)
    eng._clock.to = spy
    return seen


def _ask(eng, prompt, max_tokens, got):
    rng = np.random.default_rng(prompt)
    eng.submit(rng.integers(3, 200, prompt).tolist(),
               SamplingParams(max_tokens=max_tokens),
               lambda ev: got.append(ev.token_id))


@pytest.mark.parametrize("model", sorted(HELD))
def test_an_arrivals_step_is_launched_before_the_held_emit(model):
    """An arrival waits while the ring drains. The drain of its last chunk
    commits and HOLDS the emit; the next pass admits, launches the arrival's
    mixed step and only then emits that chunk's tokens, under the step."""
    eng = _held_engine(model, decode_lookahead=2)
    try:
        got, new = [], []
        _ask(eng, 32, 60, got)
        while len(eng._ring) < 2:
            eng._loop_pass()
        _ask(eng, 32, 8, new)
        while len(eng._ring) > 1:
            eng._loop_pass()
        before = [_count(n) for n in SERIES]
        tokens, records = len(got), len(eng.round_timings)
        eng._loop_pass()                      # drains the ring's last chunk
        assert not eng._ring and eng._held is not None
        assert len(got) == tokens, "the held tokens went out early"
        assert len(eng.round_timings) == records     # nor is its record
        assert [_count(n) - b for n, b in zip(SERIES, before)] == [1, 0]
        seen = _switches(eng)
        eng._loop_pass()                      # the arrival's mixed step
        assert eng._held is None and len(got) > tokens
        # the flip's first token too (a block model samples none there)
        assert bool(new) == (model != "tiny-sdar")
        assert (seen.index("admit") < seen.index("launch")
                < seen.index("emit") < seen.index("drain")), seen
        assert [_count(n) - b for n, b in zip(SERIES, before)] == [1, 1]
        # the step drained the prefill queue: chunks were chained off it,
        # so its own emit ran at once (two records closed in this pass)
        last, arrival = list(eng.round_timings)[records:]
        assert last["kind"] == "decode" and last["depth"] == 0
        assert last["phases"]["commit"][2] == last["phases"]["commit"][0]
        assert _starved_of(last, "emit", "drain") == 0.0
        assert arrival["kind"] == "mixed" and arrival["depth"] == 2
        order = list(arrival["phases"])
        assert order.index("launch") < order.index("emit"), order
        assert arrival["phases"]["emit"][2] == 0.0
        assert arrival["phases"]["emit"][0] > 0.0
    finally:
        eng.shutdown()


@pytest.mark.parametrize("model", sorted(HELD))
def test_a_prompts_next_chunk_is_launched_before_the_held_emit(model):
    """Where the next chunk's step cannot be launched ahead of this one's
    drain (a block model's rows advance by what the forward committed; for
    the others the test says so in the engine's place) nothing is chained
    between two chunks of one prompt, so the step's drain leaves the device
    with nothing: the next chunk's step is launched first, and the running
    row's token, the ``prefill_chunk`` event and the record follow it."""
    eng = _held_engine(model, decode_lookahead=2)
    if model != "tiny-sdar":
        eng._chains_mixed = lambda step: False
    try:
        got, new = [], []
        _ask(eng, 32, 60, got)
        while len(eng._ring) < 2:
            eng._loop_pass()
        _ask(eng, 96, 8, new)                 # three chunks of 32
        while not eng._prefill_slots:
            eng._loop_pass()
        state = eng.slots[eng._prefill_slots[0]]
        assert state.prefill_pos == 32 and state.prefill_chunks == 1
        assert eng._held is not None and not eng._ring    # chunk 1, held
        before = [_count(n) for n in SERIES]
        tokens, records = len(got), len(eng.round_timings)
        seen = _switches(eng)
        eng._loop_pass()                      # chunk 2
        assert state.prefill_pos == 64        # the commit's, never held
        assert (seen.index("launch") < seen.index("emit")
                < seen.index("drain")), seen
        assert len(got) == tokens + (0 if model == "tiny-sdar" else 1)
        first, = list(eng.round_timings)[records:]      # chunk 1's record
        assert first["kind"] == "mixed" and first["chunk_tokens"] == 32
        assert first["depth"] == 0 and _starved_of(first, "emit") == 0.0
        assert eng._held is not None          # chunk 2's, held in its turn
        assert [_count(n) - b for n, b in zip(SERIES, before)] == [1, 1]
        eng._loop_pass()                      # the last chunk: chains
        second, third = list(eng.round_timings)[records + 1:]
        assert second["phases"]["emit"][2] == 0.0 < second["phases"]["emit"][0]
        assert third["depth"] == 2
        assert bool(new) == (model != "tiny-sdar"), "the flip's first token"
        _run(eng, new, 8)
    finally:
        eng.shutdown()


STEPS = ("llm_mixed_steps_total", "llm_mixed_steps_chained_total")


@pytest.mark.parametrize("model", ["tiny-llama", "tiny-falcon-h1"])
def test_a_prompts_next_chunk_is_launched_before_the_drain(model):
    """Between two chunks of one prompt the host knows the next step before
    this one's tokens are read: it is launched off this step's device
    outputs ahead of the drain, the drain leaves it in flight, and the emit
    runs at once under it. Nothing is held and the device is never
    starved from the prompt's first chunk to the chunks chained off its
    last."""
    eng = _held_engine(model, decode_lookahead=2)
    try:
        got, new = [], []
        _ask(eng, 32, 60, got)
        while len(eng._ring) < 2:
            eng._loop_pass()
        _ask(eng, 96, 8, new)                 # three chunks of 32
        before = [_count(n) for n in SERIES + STEPS]
        records = len(eng.round_timings)
        while not eng._prefill_slots:
            eng._loop_pass()
        # the pass that admitted it launched chunk 1, chunk 2 behind it,
        # and drained chunk 1
        state = eng.slots[eng._prefill_slots[0]]
        assert state.prefill_pos == 32 and state.prefill_chunks == 1
        assert eng._mixed is not None and eng._mixed.chained
        assert eng._held is None and not eng._ring
        first = eng.round_timings[-1]
        assert first["kind"] == "mixed" and first["chunk_tokens"] == 32
        assert first["depth"] == 1 and not first["chained"]
        assert _starved_of(first, "emit") == 0.0
        tokens = len(got)
        seen = _switches(eng)
        eng._loop_pass()                      # launches chunk 3, drains 2
        assert state.prefill_pos == 64 and eng._mixed.finals
        assert (seen.index("launch") < seen.index("drain")
                < seen.index("emit")), seen
        assert len(got) == tokens + 1
        second = eng.round_timings[-1]
        assert second["chained"] and second["depth"] == 1
        assert sum(v[2] for v in second["phases"].values()) == 0.0
        eng._loop_pass()                      # chunk 3: decode chunks chain
        third = eng.round_timings[-1]
        assert third["chained"] and third["depth"] == 2
        assert eng._mixed is None and len(eng._ring) == 2
        assert new, "the flip's first token"
        # three steps, two of them chained; the one drain that left nothing
        # in flight was the ring's last chunk ahead of the arrival
        assert [_count(n) - b for n, b in zip(SERIES + STEPS, before)] \
            == [1, 1, 3, 2]
        assert len(eng.round_timings) - records >= 4
        _run(eng, new, 8)
    finally:
        eng.shutdown()
