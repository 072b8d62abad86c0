"""Bounded model checking of the paged-pool page-ownership protocol.

Reference parity: `make safety` there gates on **kani** model checking
(/root/reference/Makefile:140-148) — exhaustive verification of unsafe-core
invariants. The equivalent load-bearing invariant surface here is the KV
page-ownership protocol (`runtime/paged.py:130-220`): allocator ↔ radix-tree
ownership ↔ slot refcounts ↔ orphan tracking. A latent bug there corrupts
serving state silently (a freed page still referenced by a live slot decodes
another request's KV; a leaked page shrinks the pool forever).

Method (kani's bounded-model-checking shape, not its symbolic engine):

- **Exhaustive**: enumerate EVERY interleaving of protocol operations
  (admit with shared/cold prefixes — the admission the server runs:
  ``match_prefix → ref_pages → release → extend_chain`` — the commit at the
  end of a prefill, decode-growth, completion, preempt, resume) up to a
  depth bound over a small pool, auditing the invariants
  after every step of every sequence. Within the bound this is a proof, not
  a sample. The REAL implementation is driven — the C++ allocator/radix
  tree and the Python bookkeeping — with only the device tensor moves
  stubbed out (they carry no ownership state).
- **Randomized deep walks**: the unbounded complement — long random op
  sequences re-auditing the same invariants far past the exhaustive depth.

Invariants (audited after every operation):

  I1 conservation   capacity - allocator.num_free == |tree ∪ orphans ∪ refs|
                    (catches both leaks and double-frees by counting)
  I2 orphan sanity  orphans ∩ tree_owned = ∅ and every orphan is ref'd
  I3 ref sanity     every refcount ≥ 1 (no zero/negative entries linger)
  I4 slot safety    every page of a live slot's chain is ref'd (never free)
  I5 chain shape    no duplicate pages within one chain
  I6 match safety   match_prefix only ever returns tracked (non-free) pages
"""

from __future__ import annotations

import numpy as np
import pytest

from cyberfabric_core_tpu.models.configs import ModelConfig
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool

PAGE = 2
POOL_PAGES = 5  # capacity 4 (page 0 is scratch) — eviction pressure is real

TINY = ModelConfig(
    name="mc-tiny", architecture="llama", vocab_size=32, hidden_size=4,
    intermediate_size=8, num_layers=1, num_heads=1, num_kv_heads=1,
    head_dim=2, max_position=32, rope_theta=10000.0)

#: prompts chosen to exercise prefix sharing, divergence, and cold paths:
#: p0/p1 share 2 full pages; p2 is disjoint; p0 has a partial tail page
PROMPTS = {
    "p0": [1, 2, 3, 4, 5],        # 2 full pages + tail
    "p1": [1, 2, 3, 4, 9, 10],    # shares p0's full pages, own 3rd page
    "p2": [7, 8, 6],              # cold
}


class _ProtocolPool(PrefixKVPool):
    """The real pool with device tensor traffic stubbed out — ownership
    bookkeeping, the C++ allocator, and the radix tree all stay real."""

    def __init__(self) -> None:
        super().__init__(TINY, num_pages=POOL_PAGES, page_size=PAGE,
                         dtype=np.float32)

    # device moves carry no ownership state
    def save_chain_to_host(self, chain):
        return (np.zeros((1, len(chain))), np.zeros((1, len(chain))))


def _served_admission(pool: PrefixKVPool, prompt: list[int]) -> list[int]:
    """What scheduler._admit_prefill_slot and _grow_chain_prefill do to the
    pool for one prompt: the matched pages are slot-ref'd BEFORE the radix
    pin is dropped and before anything is allocated, then private pages
    cover the rest of the prompt (the server takes them a chunk at a time;
    the mixed steps write the KV in place, so no device move is modelled).
    A pool that cannot hold the prompt gives its pages back (the server
    preempts such a slot to the host) and raises MemoryError."""
    cached, _ = pool.match_prefix(prompt)
    chain = list(cached)
    if chain:
        pool.ref_pages(chain)
    pool.release(prompt)
    try:
        pool.extend_chain(chain, len(prompt))
    except MemoryError:
        pool.release_slot(chain)
        raise
    return chain


class Model:
    """One machine state: the real pool + the scheduler-side records the
    invariants refer to (live slot chains with their prompts, suspended
    chain sizes). A slot is in prefill until its ``commit``."""

    MAX_SLOTS = 2
    MAX_SUSPENDED = 1

    def __init__(self) -> None:
        self.pool = _ProtocolPool()
        self.slots: dict[int, list[int]] = {}
        #: slot → its prompt, while the slot's prefill has not committed
        self.prefilling: dict[int, list[int]] = {}
        #: saved (chain length, prompt still to commit or None)
        self.suspended: list[tuple[int, list[int] | None]] = []
        self._next_slot = 0

    # ------------------------------------------------------------- op alphabet
    def ops(self) -> list[tuple]:
        out: list[tuple] = []
        if len(self.slots) < self.MAX_SLOTS:
            out += [("admit", name) for name in PROMPTS]
        for sid in self.slots:
            out.append(("complete", sid))
            out.append(("extend", sid))
            if sid in self.prefilling:
                out.append(("commit", sid))
            if len(self.suspended) < self.MAX_SUSPENDED:
                out.append(("preempt", sid))
        if self.suspended and len(self.slots) < self.MAX_SLOTS:
            out.append(("resume",))
        return out

    def apply(self, op: tuple) -> None:
        kind = op[0]
        pool = self.pool
        if kind == "admit":
            prompt = PROMPTS[op[1]]
            try:
                chain = _served_admission(pool, prompt)
            except MemoryError:
                return  # pool full even after eviction: request stays queued
            self.slots[self._next_slot] = chain
            self.prefilling[self._next_slot] = prompt
            self._next_slot += 1
        elif kind == "commit":
            # the end of a prefill (_finish_prefill), whatever the pool has
            # evicted, orphaned or handed to other slots since the admission
            pool.commit_chain(self.prefilling.pop(op[1]), self.slots[op[1]])
        elif kind == "complete":
            chain = self.slots.pop(op[1])
            self.prefilling.pop(op[1], None)  # a cancel mid-prefill
            pool.release_slot(chain)
        elif kind == "extend":
            chain = self.slots[op[1]]
            try:
                pool.extend_chain(chain, (len(chain) + 1) * PAGE)
            except MemoryError:
                pass  # decode-growth denied: scheduler would preempt
        elif kind == "preempt":
            chain = self.slots.pop(op[1])
            pool.save_chain_to_host(chain)
            pool.release_slot(chain)
            self.suspended.append((len(chain),
                                   self.prefilling.pop(op[1], None)))
        elif kind == "resume":
            n, prompt = self.suspended[0]
            # full pool-page shape [L, n, page, H, D]: restore scatters for
            # real (the device write is cheap at these dims and keeps the
            # ownership path identical to production)
            shape = (1, n, PAGE, 1, 2)
            host_kv = (np.zeros(shape, np.float32),
                       np.zeros(shape, np.float32))
            try:
                chain = pool.restore_chain_from_host(host_kv)
            except MemoryError:
                return  # still no room: stays suspended
            self.suspended.pop(0)
            self.slots[self._next_slot] = chain
            if prompt is not None:  # preempted mid-prefill: commits later
                self.prefilling[self._next_slot] = prompt
            self._next_slot += 1
        else:  # pragma: no cover
            raise AssertionError(op)

    # ------------------------------------------------------------- invariants
    def audit(self, trace: tuple) -> None:
        pool = self.pool
        tracked = (set(pool._tree_owned) | set(pool._orphans)
                   | set(pool._refs))
        free = pool.allocator.num_free
        ctx = f"trace={trace} tracked={sorted(tracked)} free={free}"
        # I1 conservation
        assert pool.capacity_pages - free == len(tracked), f"I1 {ctx}"
        # I2 orphan sanity
        assert not (pool._orphans & pool._tree_owned), f"I2 {ctx}"
        for p in pool._orphans:
            assert pool._refs.get(p, 0) >= 1, f"I2 orphan unref'd {p} {ctx}"
        # I3 ref sanity
        for p, c in pool._refs.items():
            assert c >= 1, f"I3 refs[{p}]={c} {ctx}"
        # I4/I5 slot safety + chain shape
        for sid, chain in self.slots.items():
            assert len(set(chain)) == len(chain), f"I5 dup in {chain} {ctx}"
            for p in chain:
                assert pool._refs.get(p, 0) >= 1, \
                    f"I4 slot {sid} page {p} unref'd {ctx}"
        # I6 match safety
        for prompt in PROMPTS.values():
            pages = pool.tree.match(prompt)
            pool.tree.release(prompt)
            for p in pages:
                assert p in tracked, f"I6 match returned free page {p} {ctx}"


def _replay(trace: tuple) -> Model:
    m = Model()
    for op in trace:
        m.apply(op)
    return m


def test_exhaustive_bounded_model_check():
    """Every op interleaving to the depth bound, invariants audited at every
    state — within the bound, a proof over the real allocator/tree/refcount
    code. CI runs depth 5 (~3k states, seconds); MODELCHECK_DEPTH=6 is the
    deeper offline bound (~25k states)."""
    import os

    depth = int(os.environ.get("MODELCHECK_DEPTH", "5"))
    frontier: list[tuple] = [()]
    states = 0
    for _ in range(depth):
        next_frontier: list[tuple] = []
        for trace in frontier:
            m = _replay(trace)
            for op in m.ops():
                t2 = trace + (op,)
                m2 = _replay(trace)
                m2.apply(op)
                m2.audit(t2)
                states += 1
                next_frontier.append(t2)
        frontier = next_frontier
    # the bound actually explored a meaningful space
    assert states > 3000, states


def test_randomized_deep_walks():
    """The unbounded complement: long random walks far past the exhaustive
    depth, same audits every step (seeded — failures replay exactly)."""
    rng = np.random.default_rng(1234)
    for walk in range(25):
        m = Model()
        trace: tuple = ()
        for step in range(60):
            ops = m.ops()
            if not ops:
                break
            op = ops[rng.integers(len(ops))]
            trace = trace + (op,)
            m.apply(op)
            m.audit(trace[-6:])  # short context in the failure message


def test_exhaustion_recovers_exactly():
    """Fill the pool with live slots, complete them all, and the allocator
    must be back to full capacity with zero tracked pages (no slow leak)."""
    m = Model()
    admitted = 0
    for name in ("p0", "p1", "p2", "p0"):
        before = len(m.slots)
        m.apply(("admit", name))
        admitted += len(m.slots) - before
        if len(m.slots) >= Model.MAX_SLOTS:
            break
    assert admitted >= 1
    for sid in list(m.slots):
        m.apply(("complete", sid))
    m.audit(("drain",))
    pool = m.pool
    # tree entries may legitimately persist (cache), but completing every
    # slot must leave refs empty and conservation exact
    assert not pool._refs
    assert not pool._orphans
    assert pool.capacity_pages - pool.allocator.num_free == \
        len(pool._tree_owned)


@pytest.mark.parametrize("force_python", [True, False])
def test_protocol_parity_python_vs_native(force_python):
    """The C++ allocator/tree and the Python fallback must walk the protocol
    identically (same chains, same free counts) — the dry-run/CI environments
    use whichever is available."""
    class _Pool(_ProtocolPool):
        def __init__(self) -> None:
            PrefixKVPool.__init__(self, TINY, num_pages=POOL_PAGES,
                                  page_size=PAGE, dtype=np.float32,
                                  force_python_native=force_python)

    pool = _Pool()
    assert pool.peek_prefix_len(PROMPTS["p0"]) == 0
    chain = _served_admission(pool, PROMPTS["p0"])
    assert len(chain) == 3  # 2 full pages + tail
    pool.commit_chain(PROMPTS["p0"], chain)
    cached2, clen2 = pool.match_prefix(PROMPTS["p1"])
    assert clen2 == 4  # shares both full pages
    pool.release(PROMPTS["p1"])
    pool.release_slot(chain)
    assert not pool._refs


def test_a_matched_page_evicted_under_the_admission_is_never_its_next_page():
    """The served-protocol shape of the ``chain [p, p]`` bug the exhaustive
    check once found in the old admission. The pool is full and the only
    evictable entry is the prefix the arriving prompt has just matched: the
    allocation for the rest of its prompt evicts exactly those pages. Held
    by the slot's refs (taken before the pin is dropped) they become
    orphans and the admission fails clean; were the pin dropped first, the
    same allocation would hand the chain its own first page again."""
    def full_pool_with_p0_cached() -> PrefixKVPool:
        pool = _ProtocolPool()
        chain = _served_admission(pool, PROMPTS["p0"])
        pool.commit_chain(PROMPTS["p0"], chain)
        pool.release_slot(chain)                  # 2 pages cached, unheld
        _served_admission(pool, PROMPTS["p2"])    # a live slot takes the rest
        assert pool.allocator.num_free == 0
        return pool

    # control: with the pin dropped and no ref, the next allocation IS one
    # of the matched pages
    pool = full_pool_with_p0_cached()
    matched, _ = pool.match_prefix(PROMPTS["p1"])
    pool.release(PROMPTS["p1"])
    assert pool._alloc(1)[0] in matched

    pool = full_pool_with_p0_cached()
    matched, _ = pool.match_prefix(PROMPTS["p1"])
    pool.release(PROMPTS["p1"])
    assert len(matched) == 2
    with pytest.raises(MemoryError):
        _served_admission(pool, PROMPTS["p1"])
    # the prefix left the cache, never the hands of a slot that held it;
    # the failed admission gave everything back
    assert not pool._orphans and not pool._tree_owned
    assert pool.allocator.num_free == 2
    m = Model()
    m.pool = pool
    m.audit(("chain-p-p",))

