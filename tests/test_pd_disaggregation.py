"""Prefill/decode disaggregation (runtime/pd.py + the PD handoff path).

The acceptance contract of the PD tentpole: splitting the serving pool into
prefill-role and decode-role replica groups — with page-granularity KV
handoff between them — is invisible to clients. Greedy AND seeded streams
through the split must be BIT-IDENTICAL to the unified single-engine
baseline across handoff × cancellation × deadline × tenant compositions,
decode-role engines must never run a prefill or mixed round, and the
export/import pair must conserve pages exactly (bitwise KV round-trip,
refcounts back to zero, radix pins released, warm prefixes retained on the
prefill radix).

The export/import unit layer runs on bare PrefixKVPools (float32 for
bitwise exactness, bf16 for the cast path, a tp=2 NamedSharding pair for
the head-sharded move); the end-to-end layer runs a real PDServingPool
against a unified ContinuousBatchingEngine baseline.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models.configs import get_config
from cyberfabric_core_tpu.modkit.flight_recorder import default_recorder
from cyberfabric_core_tpu.runtime.engine import EngineConfig, SamplingParams
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool
from cyberfabric_core_tpu.runtime.pd import PDServingPool
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

MODEL = get_config("tiny-llama")
L, H, D = MODEL.num_layers, MODEL.num_kv_heads, MODEL.head_dim


# ===================================================================== units

def _host_chain(n_pages: int, page_size: int = 8, seed: int = 0):
    """Random KV bytes shaped like a saved n-page chain."""
    rng = np.random.default_rng(seed)
    shape = (L, n_pages, page_size, H, D)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def _pool(dtype=jnp.float32, num_pages: int = 16, sharding=None):
    return PrefixKVPool(MODEL, num_pages=num_pages, page_size=8,
                        dtype=dtype, sharding=sharding)


def test_export_import_roundtrip_bitwise():
    """The KV bytes survive export → import bit-for-bit, and both pools'
    page accounting conserves exactly: the source releases everything it
    held (ownership transferred), the destination holds exactly the chain
    and frees it on release_slot."""
    src, dst = _pool(), _pool()
    host = _host_chain(3)
    free0 = src.stats()["pages_free"]
    chain = src.restore_chain_from_host(host)  # seed a private 3-page chain
    assert src.stats()["pages_referenced"] == 3

    exported = src.export_pages(chain)
    np.testing.assert_array_equal(exported[0], host[0])
    np.testing.assert_array_equal(exported[1], host[1])
    st = src.stats()
    assert st["pages_referenced"] == 0, "export must drop the chain refs"
    assert st["orphan_pages"] == 0
    assert st["pages_free"] == free0, "private pages return to the allocator"

    chain2 = dst.import_pages(exported)
    assert len(chain2) == 3
    out = dst.save_chain_to_host(chain2)
    np.testing.assert_array_equal(out[0], host[0])
    np.testing.assert_array_equal(out[1], host[1])
    dst.release_slot(chain2)
    assert dst.stats()["pages_referenced"] == 0
    assert dst.stats()["pages_free"] == dst.num_pages - 1


def test_export_releases_radix_pins_and_keeps_warm_prefix():
    """Export with ``prompt_ids`` drops the caller's match_prefix pins while
    the tree-shared prefix pages STAY cached on the source radix (the
    prefill replica keeps serving warm prefixes) — and, unpinned, they are
    evictable again under pool pressure."""
    pool = _pool(num_pages=8)
    prompt = list(range(17))  # 2 full pages + 1 tail token
    host = _host_chain(3, seed=1)
    chain = pool.restore_chain_from_host(host)
    pool.commit_chain(prompt, chain)  # the full pages become tree-shared
    pages, cached = pool.match_prefix(prompt)  # pins the shared prefix
    assert pages == chain[:2] and cached == 16

    pool.export_pages(chain, prompt_ids=prompt)
    st = pool.stats()
    assert st["pages_referenced"] == 0, "chain refs dropped"
    assert st["cached_pages"] == 2, "shared prefix stays on the radix"
    pages2, cached2 = pool.match_prefix(prompt)
    assert pages2 == pages and cached2 == 16, "prefix still warm"
    pool.release(prompt)
    # the pin released by export is observable: eviction can reclaim now
    assert sorted(pool.tree.evict(2)) == sorted(pages)


def test_import_casts_to_destination_dtype():
    """Cross-dtype handoff (a float32 prefill pool feeding a bf16 decode
    pool): import lands the bytes cast under the destination's dtype."""
    src, dst = _pool(jnp.float32), _pool(jnp.bfloat16)
    host = _host_chain(2, seed=2)
    exported = src.export_pages(src.restore_chain_from_host(host))
    chain = dst.import_pages(exported)
    out = dst.save_chain_to_host(chain)
    np.testing.assert_array_equal(
        out[0], np.asarray(jnp.asarray(host[0], jnp.bfloat16)))
    np.testing.assert_array_equal(
        out[1], np.asarray(jnp.asarray(host[1], jnp.bfloat16)))


def test_import_raises_when_pool_cannot_hold_chain():
    src = _pool()
    exported = src.export_pages(src.restore_chain_from_host(_host_chain(3)))
    tiny = _pool(num_pages=3)  # capacity 2 pages (page 0 is scratch)
    with pytest.raises(MemoryError):
        tiny.import_pages(exported)
    assert tiny.stats()["pages_referenced"] == 0


def test_export_import_tp2_head_sharded():
    """Same-tp mesh-to-mesh move: both pools shard the kv-head axis over a
    2-device tp mesh (tiny-llama has 2 kv heads — a real split). Host numpy
    is the sharding-agnostic format; import re-shards under the destination
    pool's NamedSharding and the bytes stay bit-identical."""
    from jax.sharding import Mesh

    from cyberfabric_core_tpu.parallel.sharding import llama_page_pool_sharding

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), ("tp",))
    sh = llama_page_pool_sharding(MODEL, mesh)
    src, dst = _pool(sharding=sh), _pool(sharding=sh)
    host = _host_chain(3, seed=3)
    exported = src.export_pages(src.restore_chain_from_host(host))
    chain = dst.import_pages(exported)
    out = dst.save_chain_to_host(chain)
    np.testing.assert_array_equal(out[0], host[0])
    np.testing.assert_array_equal(out[1], host[1])
    assert src.stats()["pages_referenced"] == 0


# ============================================================== end to end

CFG = dict(model="tiny-llama", max_seq_len=64, max_batch=4, decode_chunk=4,
           prefix_cache_pages=40, prefix_page_size=8)

#: the composition storm both arms run: a greedy shared-prefix pair (radix
#: warm-up — the second prompt's first page comes from the prefill radix), a
#: page-boundary greedy stream, and a SEEDED stochastic stream (the slot's
#: sampling key must survive the handoff for bit-identity)
REQUESTS = [
    ([5, 6, 7] * 3, SamplingParams(max_tokens=12)),
    ([5, 6, 7] * 3 + [9], SamplingParams(max_tokens=10)),
    ([20, 21, 22, 23] * 3, SamplingParams(max_tokens=10)),
    ([3, 4, 5, 6, 7], SamplingParams(max_tokens=10, temperature=0.8,
                                     top_p=0.9, seed=1234)),
]
#: request 2 carries a tenant tag through the handoff
TENANTS = {2: "acme"}


def _drive(target, requests, tenants=None, cancel_at=None,
           timeout: float = 240.0):
    """Submit ``requests`` to an engine or pool and collect each stream as
    [(token_id, finished), ...] plus its request id. ``cancel_at[i] = n``
    cancels request i from its own emit callback after n tokens."""
    tenants = tenants or {}
    cancel_at = cancel_at or {}
    streams: dict[int, list] = {i: [] for i in range(len(requests))}
    rids: dict[int, str] = {}
    done = threading.Event()
    left = [len(requests)]

    def mk(i):
        seen = [0]

        def emit(ev):
            streams[i].append((ev.token_id, ev.finished))
            if ev.token_id >= 0:
                seen[0] += 1
                if seen[0] == cancel_at.get(i):
                    target.cancel(rids[i], "cancelled")
            if ev.finished:
                left[0] -= 1
                if left[0] == 0:
                    done.set()
        return emit

    for i, (prompt, sampling) in enumerate(requests):
        rids[i] = target.submit(list(prompt), sampling, mk(i),
                                tenant=tenants.get(i))
    assert done.wait(timeout), "streams did not finish"
    return streams, rids


@pytest.fixture(scope="module")
def pd_runs():
    """One unified-engine baseline run and one PD-split (1 prefill +
    1 decode) run of the composition storm. Stats are snapshotted right
    after the drive so later tests can reuse the live pool (cancellation /
    deadline compositions) without perturbing the assertions."""
    base = ContinuousBatchingEngine(EngineConfig(**CFG), seed=0)
    base.start()
    baseline, _ = _drive(base, REQUESTS, tenants=TENANTS)
    base_stats = base.stats()
    base.shutdown()

    pool = PDServingPool(EngineConfig(**CFG), n_prefill=1, n_decode=1, seed=0)
    streams, rids = _drive(pool, REQUESTS, tenants=TENANTS)
    snap = {
        "pool": pool.stats(),
        "prefill": pool.replicas[0].stats(),
        "decode": pool.replicas[1].stats(),
    }
    yield {"pool": pool, "baseline": baseline, "streams": streams,
           "rids": rids, "stats": snap, "base_stats": base_stats}
    pool.shutdown()


def _kind_counts(engine_stats) -> dict[str, int]:
    by_kind = engine_stats["pipeline"]["dispatch_ms_by_kind"]
    return {k: v["count"] for k, v in by_kind.items()}


def test_pd_streams_bit_identical_to_unified(pd_runs):
    """Greedy, shared-prefix, and SEEDED streams through the PD split —
    tenant tag included — reproduce the unified baseline token for token,
    terminal for terminal."""
    assert pd_runs["streams"] == pd_runs["baseline"]


def test_every_stream_handed_off_exactly_once(pd_runs):
    pd = pd_runs["stats"]["pool"]["pd"]
    assert pd["handoffs"] == len(REQUESTS)
    assert pd["handoffs_failed"] == 0
    assert pd["roles"] == ["prefill", "decode"]


def test_role_purity_of_dispatch_rounds(pd_runs):
    """The structural claim of the split: the decode engine never ran a
    prefill or mixed round, the prefill engine never ran a pure-decode
    round — while the unified baseline mixes both families."""
    prefill = _kind_counts(pd_runs["stats"]["prefill"])
    decode = _kind_counts(pd_runs["stats"]["decode"])
    assert prefill["decode"] == 0
    assert prefill["prefill"] + prefill["mixed"] >= 1
    assert decode["mixed"] == 0 and decode["prefill"] == 0
    assert decode["decode"] >= 1
    base = _kind_counts(pd_runs["base_stats"])
    assert base["decode"] >= 1 and base["prefill"] + base["mixed"] >= 1


def test_round_dispatch_kind_percentiles(pd_runs):
    """stats()["pipeline"]["dispatch_ms_by_kind"] (the llm_round_dispatch_ms
    gauge's source): every kind reports p50/p99/count, with p50 <= p99 and
    both positive wherever rounds of that kind ran."""
    for stats in (pd_runs["base_stats"], pd_runs["stats"]["decode"]):
        by_kind = stats["pipeline"]["dispatch_ms_by_kind"]
        assert set(by_kind) == {"decode", "mixed", "prefill"}
        for row in by_kind.values():
            assert set(row) == {"p50", "p99", "count"}
            if row["count"]:
                assert 0 < row["p50"] <= row["p99"]
            else:
                assert row["p50"] == 0.0 and row["p99"] == 0.0


def test_handoff_events_in_flight_recorder(pd_runs):
    """One request id carries the whole story across BOTH engines:
    handoff_export (prefill side) then handoff_import (decode side), in
    order, exactly once each."""
    for i, rid in pd_runs["rids"].items():
        doc = default_recorder.lookup(rid)
        events = [e["event"] for e in (doc or {}).get("timeline", ())]
        assert events.count("handoff_export") == 1, (i, events)
        assert events.count("handoff_import") == 1, (i, events)
        assert (events.index("handoff_export")
                < events.index("handoff_import"))


def test_warm_prefix_served_from_prefill_radix(pd_runs):
    """Requests 0/1 share a 9-token prefix (page_size 8 → one shared page):
    the prefill engine's radix must have served it, and exporting the chains
    must have left zero refs/orphans behind on the prefill pool."""
    ps = pd_runs["stats"]["prefill"]["prefix_cache"]
    assert ps["hits"] >= 1
    assert ps["pages_referenced"] == 0
    assert ps["orphan_pages"] == 0


def test_pd_cancellation_composition(pd_runs):
    """Cancel a handed-off stream mid-decode (after 2 tokens — the stream
    already lives on the decode engine): exactly one 'cancelled' terminal,
    and the greedy survivor stays bit-identical to the unified baseline."""
    pool = pd_runs["pool"]
    victim = ([40, 41, 42, 43] * 3, SamplingParams(max_tokens=24))
    survivor_idx = 2  # same prompt/sampling as REQUESTS[2]
    streams, _ = _drive(pool, [victim, REQUESTS[survivor_idx]],
                        cancel_at={0: 2})
    terminals = [fin for _, fin in streams[0] if fin]
    assert terminals == ["cancelled"]
    assert streams[1] == pd_runs["baseline"][survivor_idx]


def test_pd_deadline_composition(pd_runs):
    """A request whose deadline lapsed in the queue gets a 'deadline'
    terminal with ZERO tokens — it is never admitted, never prefilled,
    never handed off."""
    pool = pd_runs["pool"]
    handoffs_before = pool.stats()["pd"]["handoffs"]
    rec: list = []
    done = threading.Event()

    def emit(ev):
        rec.append((ev.token_id, ev.finished))
        if ev.finished:
            done.set()

    pool.submit([7, 8, 9, 10], SamplingParams(max_tokens=8), emit,
                deadline=time.monotonic() - 1.0)
    assert done.wait(60.0)
    assert [fin for _, fin in rec if fin] == ["deadline"]
    assert all(tok < 0 for tok, _ in rec), "lapsed request emitted tokens"
    assert pool.stats()["pd"]["handoffs"] == handoffs_before


def test_flip_role_inline_rebuild():
    """An unsupervised flip_role retags the replica and rebuilds it in the
    new role immediately; the last replica of a role refuses to flip, and a
    same-role flip is a no-op."""
    pool = PDServingPool(EngineConfig(**CFG), n_prefill=2, n_decode=1, seed=0)
    try:
        out = pool.flip_role(1, "decode")
        assert out == {"index": 1, "role": "decode", "flipped": True,
                       "mode": "inline"}
        assert pool._roles == ["prefill", "decode", "decode"]
        assert pool.replicas[1].pd_role == "decode"
        assert pool.replicas[1]._handoff_sink is None
        # the reshaped pool still serves end-to-end through the handoff
        streams, _ = _drive(pool, [REQUESTS[0]])
        assert [fin for _, fin in streams[0] if fin] == ["length"]
        # guards: last-of-role refusal, same-role no-op, bad-role reject
        with pytest.raises(ValueError):
            pool.flip_role(0, "decode")
        assert pool.flip_role(0, "prefill")["flipped"] is False
        with pytest.raises(ValueError):
            pool.flip_role(0, "verify")
    finally:
        pool.shutdown()


def test_pd_constructor_validation():
    with pytest.raises(ValueError):
        PDServingPool(EngineConfig(**CFG), n_prefill=0, n_decode=1)
    with pytest.raises(ValueError):
        PDServingPool(EngineConfig(**CFG), n_prefill=1, n_decode=0)
    with pytest.raises(ValueError):
        ContinuousBatchingEngine(EngineConfig(**CFG, pd_role="verify"),
                                 seed=0)
