"""KV eviction/restore for preempted requests (scheduler preempt-to-host).

Pool pressure no longer sheds a mid-flight request: its chain pages round-trip
through host memory and decoding resumes bit-exact (greedy output must equal
the undisturbed run)."""

import threading

import numpy as np
import pytest

from cyberfabric_core_tpu.runtime import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine


def _cfg():
    return EngineConfig(model="tiny-llama", max_seq_len=128, max_batch=2,
                        decode_chunk=4,
                        prefix_cache_pages=64, prefix_page_size=8)


def _collect(sched, prompt, max_tokens=16):
    done = threading.Event()
    out = {"tokens": [], "finish": None}

    def emit(ev):
        if ev.token_id >= 0:
            out["tokens"].append(ev.token_id)
        if ev.finished is not None:
            out["finish"] = ev.finished
            done.set()

    sched.submit(prompt, SamplingParams(max_tokens=max_tokens, temperature=0.0),
                 emit)
    assert done.wait(120), sched.stats()
    return out


def test_preempted_request_resumes_bit_exact():
    prompt = np.random.default_rng(0).integers(3, 900, 20).tolist()

    # undisturbed reference run
    ref_sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        ref = _collect(ref_sched, prompt)
    finally:
        ref_sched.shutdown()
    assert len(ref["tokens"]) == 16

    # run with an injected pool-pressure fault mid-stream
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        pool = sched.pool
        orig_extend = pool.extend_chain
        fired = {"n": 0}
        first_tok = threading.Event()

        def flaky_extend(chain, needed):
            # after the stream starts, fail extensions until a preemption
            # actually lands (the 2·k lookahead horizon absorbs optimistic
            # failures gracefully; only a mandatory-chunk failure preempts)
            if first_tok.is_set() and sched.preemptions == 0:
                fired["n"] += 1
                raise MemoryError("injected pool pressure")
            return orig_extend(chain, needed)

        pool.extend_chain = flaky_extend

        done = threading.Event()
        out = {"tokens": [], "finish": None}

        def emit(ev):
            if ev.token_id >= 0:
                out["tokens"].append(ev.token_id)
                first_tok.set()
            if ev.finished is not None:
                out["finish"] = ev.finished
                done.set()

        sched.submit(prompt, SamplingParams(max_tokens=16, temperature=0.0), emit)
        assert done.wait(120), (out, sched.stats())
        assert fired["n"] >= 1, "fault never fired"
        st = sched.stats()
        assert st["preemptions"] == 1
        assert out["finish"] in ("stop", "length")
        # bit-exact continuation: host round-trip lost nothing
        assert out["tokens"] == ref["tokens"]
    finally:
        sched.shutdown()


def test_suspended_request_outranks_new_admissions():
    """A resumed request takes the freed slot before queued new work."""
    sched = ContinuousBatchingEngine(
        EngineConfig(model="tiny-llama", max_seq_len=128, max_batch=1,
                     decode_chunk=4,
                     prefix_cache_pages=64, prefix_page_size=8), seed=0)
    try:
        pool = sched.pool
        orig_extend = pool.extend_chain
        started = threading.Event()

        def flaky_extend(chain, needed):
            # persist until the preemption lands (optimistic-horizon failures
            # are absorbed without preempting)
            if started.is_set() and sched.preemptions == 0:
                raise MemoryError("injected")
            return orig_extend(chain, needed)

        pool.extend_chain = flaky_extend

        events: list[tuple[str, int]] = []
        lock = threading.Lock()
        done = {"a": threading.Event(), "b": threading.Event()}

        def mk(name):
            def emit(ev):
                with lock:
                    if ev.token_id >= 0:
                        events.append((name, ev.token_id))
                        started.set()
                    if ev.finished is not None:
                        done[name].set()
            return emit

        rng = np.random.default_rng(1)
        sched.submit(rng.integers(3, 900, 12).tolist(),
                     SamplingParams(max_tokens=12, temperature=0.0), mk("a"))
        # b queues behind a (1 slot); a gets preempted mid-flight, must still
        # finish BEFORE b starts emitting
        sched.submit(rng.integers(3, 900, 12).tolist(),
                     SamplingParams(max_tokens=4, temperature=0.0), mk("b"))
        assert done["a"].wait(120) and done["b"].wait(120), sched.stats()
        first_b = next(i for i, (n, _) in enumerate(events) if n == "b")
        last_a = max(i for i, (n, _) in enumerate(events) if n == "a")
        assert last_a < first_b, "preempted request did not retain priority"
    finally:
        sched.shutdown()


def test_unserviceable_suspended_request_terminal_sheds_not_hangs():
    """A preempted request even the IDLE pool can't re-hold must finish with
    'length' instead of retrying forever (review finding: infinite resume loop
    left the client stream — and everyone queued behind it — hanging)."""
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        def always_fail(chain, needed):
            raise MemoryError("no pages, ever")

        sched.pool.extend_chain = always_fail
        prompt = [5] * 20
        out = _collect(sched, prompt, max_tokens=16)  # must terminate
        assert out["finish"] == "length"
        assert sched.stats()["preemptions"] >= 1
    finally:
        sched.shutdown()


def test_scheduler_failure_fails_suspended_requests_too():
    sched = ContinuousBatchingEngine(_cfg(), seed=0)
    try:
        from cyberfabric_core_tpu.runtime.scheduler import _SlotState, _Suspended

        errors = []
        rec = _Suspended(
            state=_SlotState(request_id="r", emit=lambda ev: errors.append(ev),
                             sampling=SamplingParams(max_tokens=4),
                             stops=frozenset()),
            host_kv=(np.zeros((1, 1, 8, 1, 4)), np.zeros((1, 1, 8, 1, 4))),
            length=8, last_token=5, slot_key=np.zeros((2,), np.uint32))
        sched._suspended.append(rec)
        sched.start()
        sched._decode_round = lambda: (_ for _ in ()).throw(RuntimeError("boom"))
        # force a round: submit something
        sched.submit([5, 6, 7], SamplingParams(max_tokens=2), lambda ev: None)
        import time

        deadline = time.monotonic() + 30
        while not errors and time.monotonic() < deadline:
            time.sleep(0.1)
        assert errors and errors[0].finished == "error"
    finally:
        sched.shutdown()


def test_infeasible_suspended_request_sheds_even_under_load():
    """Feasibility-based terminal shed (round-2 advisory): a suspended request
    whose page need exceeds the ENTIRE pool must shed immediately — under
    sustained load `active` never empties, so idleness-gated shedding would
    hang its client stream forever while thrashing restore/release."""
    cfg = EngineConfig(model="tiny-llama", max_seq_len=256, max_batch=2,
                       decode_chunk=4,
                       prefix_cache_pages=8, prefix_page_size=8)
    sched = ContinuousBatchingEngine(cfg, seed=0)
    try:
        from cyberfabric_core_tpu.runtime.scheduler import _SlotState, _Suspended

        events = []
        # simulate a pool whose capacity the request exceeds outright (e.g.
        # orphan pages shrank effective capacity); restore keeps MemoryError-ing
        def no_room(host_kv):
            raise MemoryError("pool exhausted")

        sched.pool.restore_chain_from_host = no_room
        n_pages = sched.pool.pages_for(200)
        sched.pool.num_pages = n_pages  # capacity (num_pages-1) < need
        rec = _Suspended(
            state=_SlotState(request_id="big", emit=events.append,
                             sampling=SamplingParams(max_tokens=4),
                             stops=frozenset()),
            host_kv=(np.zeros((1, n_pages, 8, 1, 4), np.float32),
                     np.zeros((1, n_pages, 8, 1, 4), np.float32)),
            length=200, last_token=5, slot_key=np.zeros((2,), np.uint32))
        sched._suspended.append(rec)
        sched.active[0] = True  # pool is NOT idle — old code would park forever
        sched._resume_suspended()
        assert not sched._suspended, "infeasible request must not stay parked"
        assert events and events[-1].finished == "length"
    finally:
        sched.active[0] = False
        sched.shutdown()
