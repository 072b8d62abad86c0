"""The page pool: every slot's KV, and the prompt prefixes requests share.

The PAPERS.md direction (ragged paged attention for TPU): a slot's KV lives in
pages of one device pool that the serving programs write in place, and a
committed prompt's full pages stay behind for **prompt prefix reuse**. The pool
is [L, num_pages, page_size, Hkv*D] (the two minor dimensions
are stored merged, head-major, which is the block the paged kernels read — on a
tiled TPU layout merging them in front of the kernel is a copy of the pool),
indexed by the native radix prefix cache (runtime/native.py — C++ fabric_host).

**The layout is the configuration's.** A model of latent attention
(``ModelConfig.is_latent``) caches one compressed row and one shared rotary
key a token, so its chain is the array ``latent_pool`` [L, num_pages,
page_size, latent_lanes] (``latent_width`` numbers in whole lane tiles) with
no kv-head axis and no V pool; where a query attends over a chosen set
(``ModelConfig.is_sparse``) the chain is TWO arrays, as the K/V chain is:
``index_pool`` [L, num_pages, page_size, index_lanes], a token's index key,
rides ``latent_pool`` under the same page ids, tree, refcounts and
allocator, as ``v_pool`` rides ``k_pool`` (a token's index key depends on
its prefix alone, like its latent row, so a prefix hit hands on both). The
arrays of a chain differ in their minor dimension only (``_page_tails``);
``pools``, ``cache_operands``/``adopt``, the preemption movers and
``stats()`` follow ``_pool_names``. The allocator, the radix tree, the
refcounts and the admission protocol count pages, whatever a page holds.

**Two page groups** (``ModelConfig.window_layers``: a stack whose layers
attend over everything in one layer of four and over the last
``sliding_window`` tokens in the others, on latent pages or on K/V pages).
The layers that keep a row's whole length are the pool above, ``kv_layers``
deep, with its tree, refcounts and prefix reuse. The window layers get
arrays of the same layout ``[window_layers, window_pages, page, .]``
(``window_pool`` beside ``latent_pool``; ``window_k_pool`` and
``window_v_pool`` beside ``k_pool`` and ``v_pool``: ``_window_names``), ONE
allocator of their own and NO tree: a row's window pages are private, named
by the SAME logical page index as its full chain (``extend_window``; 0 where
a page was given back), and ``trim_window`` returns every page whose last
token lies left of the window of the row's next query. The kernels start a
window layer's row at its span's first page (``ops/mla_attention.py``;
``ops/paged_attention.py: _span_first``), so a page given back is never read
again and may be another row's at once: steps run
on the device in the order they were launched, every launched step's queries
sit at or past the length the host has committed, and a step launched later
that writes the page runs after every step that still read it. ``match_prefix`` **treats every
prefix as no match** for such a model: a prefix is reusable only where the
window pages before its boundary were kept, and this pool keeps none past
their row (PERF.md section 7).

One manager for both kinds of cache. A model with recurrent state
(``ModelConfig.has_state``: falcon_h1, granite_hybrid, nemotron_h,
solar_open2) also gets a state slab here — ``{"ssm": [Ls, rows, H, P, N],
"conv": [Ls, rows, K-1, C]}`` f32 (a Mamba-2 layer's ``[P, N]`` a head, or a
kda layer's ``[keys, values]``; the conv tail over ``x B C``, or over ``q k
v`` and then one flat row), made by the model's own module (``init_state``) — **each cache as deep as the
layers of its kind**: the pool arrays have ``ModelConfig.kv_layers`` layers
and the slab ``state_layers`` (both ``num_layers`` where every layer is one
block; 1 and 9 for one period of granite's stack; 2 and 10 for nemotron_h's
22 layers, whose other 10 hold experts alone and neither cache). The slab's first
``state_slots`` rows are the slots' own (row = slot) and whose further rows
are **snapshots**, each owned by the prefix-tree page at whose end it was
taken. State does not grow with a row's length and cannot be shared by
aliasing a page-table line, so a prefix hit is only worth its pages where a
snapshot of the state at that boundary exists: ``match_prefix`` trims a hit
to the deepest page that owns one, admission copies it into the slot's row,
evicting the page frees it, and the preemption movers carry the slot's row.
The movers below hand out and take in request-sized tensors with their
[..., Hkv, D] tail and reshape them at this boundary.

The admission protocol, one for every request (scheduler.py:
``_admit_prefill_slot``, ``_grow_chain_prefill``, ``_finish_prefill``):

1. ``match_prefix`` finds the prompt's page-aligned prefix in the radix tree
   (pinning its nodes); ``ref_pages`` takes the slot's hold on those pages and
   ``release`` drops the pin,
2. ``extend_chain`` allocates private pages a chunk at a time while the mixed
   steps write the uncached suffix's KV straight into them,
3. ``commit_chain`` records the prompt's full pages in the tree after the last
   chunk, so later requests alias them in their page tables (zero-copy),
4. ``release_slot`` drops the chain's holds when the request leaves its slot.

The llm-gateway's shared system prompts are the canonical win (TTFT and
prefill FLOPs). Pool pressure is handled by LRU eviction of unpinned entries;
a page evicted while a slot still holds it is an orphan until that slot lets
go. Page id 0 is a scratch page: masked KV writes land there.
"""

from __future__ import annotations

import logging
import threading
from collections import OrderedDict
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models import decoder_module
from ..models.configs import ModelConfig
from ..modkit.metrics import bump_counter
from ..ops.sampling import sample_token
from .native import BlockAllocator, PrefixCache

logger = logging.getLogger("paged")


@partial(jax.jit, donate_argnums=(0,))
def state_copy_row(state: dict, src, dst) -> dict:
    """Row ``src`` of every layer of the state slab copied over row ``dst``,
    in place (snapshot ← slot at a chunk boundary, slot ← snapshot at
    admission)."""
    return {k: v.at[:, dst].set(v[:, src]) for k, v in state.items()}


@partial(jax.jit, donate_argnums=(0,))
def state_set_row(state: dict, row, values: dict) -> dict:
    """Row ``row`` set from ``values`` (``[L, ...]`` per leaf), in place:
    the resume half of preemption."""
    return {k: v.at[:, row].set(values[k].astype(v.dtype))
            for k, v in state.items()}


class PrefixKVPool:
    """Device page pool + native allocator/radix tree + jitted move programs."""

    def __init__(self, model_config: ModelConfig, *, num_pages: int = 64,
                 page_size: int = 64, dtype=jnp.bfloat16,
                 force_python_native: bool = False,
                 sharding: Optional[Any] = None,
                 state_slots: int = 0, state_snapshots: int = 0,
                 window_pages: int = 0) -> None:
        self.cfg = model_config
        self.page_size = page_size
        self.num_pages = num_pages
        self.dtype = dtype
        #: tensor-parallel serving: a NamedSharding for the pool arrays
        #: ([L, P, page, Hkv*D], the merged head axis on tp — parallel/
        #: sharding.py llama_page_pool_sharding). The preemption and PD
        #: movers run under GSPMD against the sharded pool; the host-side
        #: bookkeeping (allocator, radix tree, refcounts, page ids) is
        #: byte-count-agnostic and identical to the single-device pool.
        self.sharding = sharding
        L = model_config.kv_layers
        if model_config.is_latent:
            if sharding is not None:
                raise ValueError(
                    f"{model_config.name}: a latent page has no kv-head axis "
                    "to shard over tp")
            #: the pool arrays by attribute name, and a page's minor
            #: dimensions in each as the movers hand them out
            self._pool_names: tuple[str, ...] = ("latent_pool",)
            self._page_tails: tuple[tuple[int, ...], ...] = (
                (model_config.latent_lanes,),)
            if model_config.is_sparse:
                self._pool_names += ("index_pool",)
                self._page_tails += ((model_config.index_lanes,),)
        else:
            self._pool_names = ("k_pool", "v_pool")
            self._page_tails = ((model_config.num_kv_heads,
                                 model_config.head_dim),) * 2
        shape = (L, num_pages, page_size, int(np.prod(self._page_tails[0])))
        for name, tail in zip(self._pool_names, self._page_tails):
            pool = jnp.zeros((*shape[:3], int(np.prod(tail))), dtype)
            setattr(self, name, pool if sharding is None
                    else jax.device_put(pool, sharding))
        #: the window page group (module docstring): its pool follows the
        #: full group's in ``pools``; page 0 of it is scratch too
        self.window = model_config.sliding_window \
            if model_config.window_layers else 0
        self.window_pages = window_pages if self.window else 0
        self.window_pages_freed = 0
        self._window_names: tuple[str, ...] = ()
        if self.window:
            if window_pages < 2:
                raise ValueError(
                    f"{model_config.name}: {model_config.window_layers} "
                    "window layers need a window page group (window_pages)")
            if sharding is not None:
                raise ValueError(
                    f"{model_config.name}: the window page group has no "
                    "sharding over tp")
            #: the window group's arrays, one for each of the chain's
            self._window_names = tuple(
                "window_" + name.removeprefix("latent_")
                for name in self._pool_names)
            self._pool_names += self._window_names
            for name in self._window_names:
                setattr(self, name, jnp.zeros(
                    (model_config.window_layers, window_pages, *shape[2:]),
                    dtype))
            self.window_allocator = BlockAllocator(
                window_pages - 1, force_python=force_python_native)
        # page 0 is scratch (padding target); allocator hands out 1..num_pages-1
        self.allocator = BlockAllocator(num_pages - 1, force_python=force_python_native)
        self._page_offset = 1
        self.tree = PrefixCache(page_size, force_python=force_python_native)
        #: serializes radix-tree access: the tree has no internal lock and its
        #: pin counters / native handle are read-modify-write, so the replica
        #: pool's cache-affinity probe (``peek_prefix_len``, gateway threads)
        #: and monitoring's ``stats()`` scrape must not interleave with the
        #: scheduler thread's match/insert/evict/release
        self._tree_lock = threading.Lock()
        self.prefill_tokens_saved = 0
        #: hit-rate inputs: every match_prefix probe counts its prompt tokens;
        #: hits are probes that returned at least one cached page
        self.prefill_tokens_total = 0
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.admissions = 0
        # paged-decode bookkeeping: pages referenced by live slots must survive
        # tree eviction (the tree can drop a page from the *cache* while a slot
        # still reads it — it then becomes an orphan, returned to the allocator
        # only when the last referencing slot completes)
        self._refs: dict[int, int] = {}
        self._tree_owned: set[int] = set()
        self._orphans: set[int] = set()
        # recurrent state beside the pages (module docstring): the slab, the
        # free snapshot rows, and the snapshots the tree's pages own, oldest
        # use first (page id → row)
        self.state: Optional[dict[str, jnp.ndarray]] = None
        self.state_slots = state_slots
        self._free_snapshot_rows: list[int] = []
        self._snapshots: "OrderedDict[int, int]" = OrderedDict()
        self.snapshots_taken = 0
        self.snapshot_hits = 0
        self.snapshot_evictions = 0
        self.state_restores = 0
        if model_config.has_state and state_slots > 0:
            rows = state_slots + max(0, state_snapshots)
            self.state = decoder_module(model_config).init_state(
                model_config, rows)
            self._free_snapshot_rows = list(range(rows - 1, state_slots - 1, -1))
            # compile the row copy now, at build: its first use is otherwise
            # the first chunk boundary of a long prompt, mid-serving
            self.state = state_copy_row(self.state, 0, 0)

    @property
    def capacity_pages(self) -> int:
        """Pages a single chain could ever hold (page 0 is scratch) — the
        feasibility bound callers must check before parking a request on
        'the pool will free up eventually'."""
        return self.num_pages - self._page_offset

    # ------------------------------------------------------------ admission
    def _alloc(self, n: int) -> list[int]:
        """Allocate n pages, evicting unpinned tree entries as needed. Evicted
        pages still referenced by a live slot become orphans (freed at unref),
        so eviction may need several rounds to actually recover allocator space."""
        while True:
            try:
                return [p + self._page_offset for p in self.allocator.alloc(n)]
            except MemoryError:
                with self._tree_lock:
                    freed = self.tree.evict(n)
                if not freed:
                    raise
                now_free = []
                for p in freed:
                    self._tree_owned.discard(p)
                    self._drop_snapshot(p)
                    if self._refs.get(p, 0) > 0:
                        self._orphans.add(p)
                    else:
                        now_free.append(p - self._page_offset)
                self.allocator.free(now_free)

    # ------------------------------------------------------------ slot refs
    def ref_pages(self, pages: list[int]) -> None:
        for p in pages:
            self._refs[p] = self._refs.get(p, 0) + 1

    def unref_pages(self, pages: list[int]) -> None:
        """Drop a completed slot's references; frees pages nothing else owns."""
        to_free = []
        for p in pages:
            c = self._refs.get(p, 0) - 1
            if c <= 0:
                self._refs.pop(p, None)
                if p not in self._tree_owned:
                    self._orphans.discard(p)
                    self._drop_snapshot(p)
                    to_free.append(p - self._page_offset)
            else:
                self._refs[p] = c
        self.allocator.free(to_free)

    def match_prefix(self, prompt_ids: list[int]) -> tuple[list[int], int]:
        """Returns (pinned page ids, cached token count). Never returns the FULL
        prompt as cached — at least one token must go through prefill so the
        model produces the first-token logits."""
        with self._tree_lock:
            pages = [] if self.window else self.tree.match(prompt_ids)
        cached = len(pages) * self.page_size
        if cached >= len(prompt_ids):
            drop = (cached - len(prompt_ids)) // self.page_size + 1
            pages = pages[:-drop] if drop <= len(pages) else []
            cached = len(pages) * self.page_size
        if self.state is not None:
            pages = self._upto_snapshot(pages)
            cached = len(pages) * self.page_size
        self.prefix_lookups += 1
        self.prefill_tokens_total += len(prompt_ids)
        if pages:
            self.prefix_hits += 1
            self.prefill_tokens_saved += cached
        return pages, cached

    # ------------------------------------------------------------ state rows
    @property
    def has_state(self) -> bool:
        return self.state is not None

    @property
    def pools(self) -> tuple:
        """The pool arrays: K and V, or the latent pool (and the index pool
        beside it); then the window group's, as many again, where the model
        has one."""
        return tuple(getattr(self, name) for name in self._pool_names)

    @property
    def _n_chain_pools(self) -> int:
        """The pools a slot's (full) chain indexes."""
        return len(self._pool_names) - len(self._window_names)

    @property
    def window_pools(self) -> tuple:
        """The window group's arrays (none: one page group)."""
        return tuple(getattr(self, name) for name in self._window_names)

    def pool_bytes(self) -> int:
        return sum(int(p.size) * p.dtype.itemsize for p in self.pools)

    def index_pool_bytes(self) -> int:
        """The index pool's part of :meth:`pool_bytes` (0: none)."""
        pool = getattr(self, "index_pool", None)
        return 0 if pool is None else int(pool.size) * pool.dtype.itemsize

    def window_pool_bytes(self) -> int:
        """The window group's part of :meth:`pool_bytes`."""
        return sum(int(p.size) * p.dtype.itemsize for p in self.window_pools)

    def state_bytes(self) -> int:
        return sum(int(v.size) * v.dtype.itemsize
                   for v in (self.state or {}).values())

    def cache_operands(self) -> tuple:
        """What the serving programs take and give back, donated: the
        pools, and the state slab where the model has one."""
        return self.pools if self.state is None else (*self.pools, self.state)

    def adopt(self, outs: tuple) -> tuple:
        """Take a program's returned cache operands (they lead ``outs``);
        returns the rest."""
        n = len(self._pool_names)
        for name, pool in zip(self._pool_names, outs):
            setattr(self, name, pool)
        if self.state is not None:
            self.state = outs[n]
            n += 1
        return tuple(outs[n:])

    def _upto_snapshot(self, pages: list[int]) -> list[int]:
        """A matched chain cut to the deepest page that owns a snapshot of
        the state at its end: pages beyond it are worth nothing (a prompt
        that shares less than one snapshot boundary shares nothing)."""
        keep = max((i + 1 for i, p in enumerate(pages)
                    if p in self._snapshots), default=0)
        return pages[:keep]

    def _drop_snapshot(self, page: int) -> None:
        row = self._snapshots.pop(page, None)
        if row is not None:
            self._free_snapshot_rows.append(row)
            self.snapshot_evictions += 1
            bump_counter("llm_state_snapshot_evictions_total")

    def take_snapshot(self, slot: int) -> Optional[int]:
        """Copy slot ``slot``'s state row, as the last program left it, into
        a snapshot row the caller holds until :meth:`commit_chain` hands it
        to a page (or :meth:`drop_snapshot_rows` frees it). The set is
        bounded: with no row free the least recently used committed snapshot
        goes; with none of those either, no snapshot is taken (None)."""
        if self.state is None:
            return None
        if not self._free_snapshot_rows and self._snapshots:
            self._drop_snapshot(next(iter(self._snapshots)))
        if not self._free_snapshot_rows:
            return None
        row = self._free_snapshot_rows.pop()
        self.state = state_copy_row(self.state, slot, row)
        self.snapshots_taken += 1
        bump_counter("llm_state_snapshots_taken_total")
        return row

    def drop_snapshot_rows(self, rows: list[int]) -> None:
        """Free snapshot rows that never reached the tree (a prompt that was
        cancelled or preempted before its commit)."""
        self._free_snapshot_rows.extend(rows)

    def seed_state_row(self, slot: int, pages: list[int]) -> None:
        """Admission of a prefix hit: the snapshot owned by the last matched
        page becomes the slot's state (``match_prefix`` trimmed the hit to
        such a page). A row admitted with no pages needs nothing: a mixed
        step starts a row whose history is 0 from the zero state."""
        if self.state is None or not pages:
            return
        row = self._snapshots[pages[-1]]
        self._snapshots.move_to_end(pages[-1])
        self.state = state_copy_row(self.state, row, slot)
        self.snapshot_hits += 1
        bump_counter("llm_state_snapshot_hits_total")

    def state_row(self, row: int) -> dict[str, np.ndarray]:
        """One row of the slab on the host (tests, preemption)."""
        return {k: np.asarray(v[:, row]) for k, v in self.state.items()}

    def peek_prefix_len(self, prompt_ids: list[int]) -> int:
        """Non-pinning probe: how many head tokens of ``prompt_ids`` this
        pool could serve from cache right now. Used as a placement HINT
        (cache-aware routing in runtime/replicas.py) — it must not pin pages
        or skew the hit-rate stats, so it walks the tree and releases
        immediately."""
        if self.window:
            return 0
        with self._tree_lock:
            pages = self.tree.match(prompt_ids)
            try:
                if self.state is not None:
                    pages = self._upto_snapshot(pages)
                return min(len(pages) * self.page_size,
                           max(len(prompt_ids) - 1, 0))
            finally:
                if pages is not None:
                    self.tree.release(prompt_ids)

    def release(self, prompt_ids: list[int]) -> None:
        with self._tree_lock:
            self.tree.release(prompt_ids)

    # ------------------------------------------------------------ slot chains
    def pages_for(self, length: int) -> int:
        return (length + self.page_size - 1) // self.page_size

    def commit_chain(self, prompt_ids: list[int], chain: list[int],
                     snapshots: Optional[list[tuple[int, int]]] = None
                     ) -> None:
        """Mixed-batch chunked prefill wrote its KV straight into the chain's
        pages (no scatter pass) — after the final chunk, record the prompt's
        FULL pages in the radix tree so later requests share them zero-copy.
        Pages the tree declines (a racing same-prefix admission already
        cached those positions) simply stay PRIVATE to the chain —
        refcounted by the slot, never tree-owned (insert_tracked exists
        because a count-only contract leaked pages in the sanitizer
        exercise). ``snapshots`` (a model with
        recurrent state): ``(tokens, row)`` pairs the caller took with
        :meth:`take_snapshot` where a mixed call ended on ``tokens``; each
        goes to the page that ends there if the tree now owns that page and
        it has none yet, and is freed otherwise."""
        total_pages = 0 if self.window else len(prompt_ids) // self.page_size
        if total_pages <= 0:
            self.drop_snapshot_rows([row for _, row in snapshots or ()])
            return
        with self._tree_lock:
            _, unused = self.tree.insert_tracked(
                prompt_ids[: total_pages * self.page_size],
                chain[:total_pages])
        declined = set(unused)
        for p in chain[:total_pages]:
            if p not in declined:
                self._tree_owned.add(p)
                # a page the tree evicted mid-prefill (slot refs kept it
                # alive as an orphan) is tree-owned again — unmark it, or
                # the orphan stat leaks and unref would double-account
                self._orphans.discard(p)
        for tokens, row in snapshots or ():
            page = chain[tokens // self.page_size - 1] \
                if 0 < tokens <= total_pages * self.page_size else None
            if page is not None and page in self._tree_owned \
                    and page not in self._snapshots:
                self._snapshots[page] = row
            else:
                self._free_snapshot_rows.append(row)
        self.admissions += 1

    def extend_chain(self, chain: list[int], length_needed: int) -> list[int]:
        """Grow a slot's chain (private decode pages) to cover length_needed
        tokens. Returns the same list, extended in place."""
        add = self.pages_for(length_needed) - len(chain)
        if add > 0:
            ids = self._alloc(add)
            self.ref_pages(ids)
            chain.extend(ids)
        return chain

    def release_slot(self, chain: list[int]) -> None:
        """Drop a slot's chain references — the ONE release path shared by
        clean finishes, preemption, failover teardown, and the cancellation
        sweep: tree-shared prefix pages stay cached for other requests,
        private decode pages return to the allocator, and orphans (evicted
        mid-flight but slot-ref'd) free here. A cancel therefore needs no
        special pool handling to be leak-free."""
        self.unref_pages(chain)

    # ------------------------------------------------------------ window group
    def extend_window(self, wchain: list[int], length_needed: int) -> list[int]:
        """Grow a slot's WINDOW chain to cover ``length_needed`` tokens
        (:meth:`extend_chain` for the window group: private pages, no tree
        to evict from, MemoryError where the group is spent). The chain is
        indexed as the full chain is; pages given back stay 0."""
        add = self.pages_for(length_needed) - len(wchain)
        if add > 0:
            wchain.extend(p + 1 for p in self.window_allocator.alloc(add))
        return wchain

    def trim_window(self, wchain: list[int], length: int) -> int:
        """Give back the window pages of a row of ``length`` committed
        tokens that its next query (at ``length``) and every later one
        cannot see. Returns how many."""
        dead = min(max(length - self.window + 1, 0) // self.page_size,
                   len(wchain))
        first = dead        # the pages given back are a prefix: stop at it
        while first and wchain[first - 1]:
            first -= 1
        gone = wchain[first:dead]
        if gone:
            wchain[first:dead] = [0] * len(gone)
            self.window_allocator.free([p - 1 for p in gone])
            self.window_pages_freed += len(gone)
            bump_counter("llm_window_pages_freed_total", n=len(gone))
        return len(gone)

    def release_window(self, wchain: list[int], keep: int = 0) -> None:
        """A slot leaves: its window pages return (finish, cancel,
        preemption). ``keep``: only those past the chain's first ``keep``
        slots (a growth the full group could not match, taken back)."""
        self.window_allocator.free([p - 1 for p in wchain[keep:] if p])
        del wchain[keep:]

    def window_pages_in_use(self) -> int:
        return (self.window_pages - 1 - self.window_allocator.num_free
                if self.window else 0)

    def save_window_to_host(self, wchain: list[int]) -> dict:
        """The live pages of a window chain, device→host, for preemption:
        their logical indices and ``[window_layers, n, page, .]`` of every
        array of the group."""
        at = [j for j, p in enumerate(wchain) if p]
        idx = jnp.asarray([wchain[j] for j in at], jnp.int32)
        return {"at": at, "pages": len(wchain),
                "rows": tuple(np.asarray(pool[:, idx])
                              for pool in self.window_pools)}

    def restore_window_from_host(self, saved: dict) -> list[int]:
        """Fresh window pages for a saved window chain (MemoryError where
        the group lacks them; nothing is held then)."""
        ids = [p + 1 for p in self.window_allocator.alloc(len(saved["at"]))]
        wchain = [0] * saved["pages"]
        for j, p in zip(saved["at"], ids):
            wchain[j] = p
        if ids:
            idx = jnp.asarray(ids, jnp.int32)
            for name, rows in zip(self._window_names, saved["rows"]):
                pool = getattr(self, name)
                setattr(self, name, pool.at[:, idx].set(
                    jnp.asarray(rows, pool.dtype)))
        return wchain

    # ------------------------------------------------------------ preemption
    def save_chain_to_host(self, chain: list[int],
                           state_row: Optional[int] = None) -> tuple:
        """Copy a slot's chain pages device→host (KV eviction for preempted
        requests — SURVEY §5 checkpoint/resume; the serving analogue of the
        reference's suspend path). One gather per pool; the transfer is the
        chain's actual bytes, not the window. Returns [kv_layers, n, page,
        Hkv, D] each (the PD wire format; a host reshape is a view; a latent
        chain: [L, n, page, latent_lanes], and [L, n, page, index_lanes]
        where it has an index pool), and where the
        model has recurrent state one more entry: slot ``state_row``'s row
        of the slab, so that the request resumes exactly."""
        idx = jnp.asarray(chain, jnp.int32)
        out = (self.cfg.kv_layers, len(chain), self.page_size)
        host_kv = tuple(np.asarray(pool[:, idx]).reshape(*out, *tail)
                        for pool, tail in zip(self.pools, self._page_tails))
        if self.state is not None:
            if state_row is None:
                raise ValueError("a model with recurrent state is saved with "
                                 "its slot's state row")
            host_kv += (self.state_row(state_row),)
        return host_kv

    def restore_chain_from_host(self, host_kv: tuple,
                                state_row: Optional[int] = None) -> list[int]:
        """Allocate fresh pages and scatter a saved chain back (device resume).
        Raises MemoryError when the pool still lacks space — caller keeps the
        request suspended. Restored pages are private (shared-prefix structure
        is not reconstructed; correctness is unaffected). ``state_row``: the
        slot whose row of the state slab takes the saved state."""
        n = host_kv[0].shape[1]
        if n == 0:  # a prefill-phase preempt before any chunk landed
            return []
        ids = self._alloc(n)
        n_pools = self._n_chain_pools
        if self.state is not None:
            if state_row is None or len(host_kv) <= n_pools:
                self.allocator.free([p - self._page_offset for p in ids])
                raise ValueError("a model with recurrent state is restored "
                                 "into a slot's state row, from a save that "
                                 "carries one")
            self.state = state_set_row(
                self.state, state_row,
                {k: jnp.asarray(v) for k, v in host_kv[n_pools].items()})
            self.state_restores += 1
            bump_counter("llm_state_restores_total")
        self.ref_pages(ids)
        idx = jnp.asarray(ids, jnp.int32)
        merged = (*host_kv[0].shape[:3], -1)
        for name, saved in zip(self._pool_names[:n_pools], host_kv):
            pool = getattr(self, name)
            setattr(self, name, pool.at[:, idx].set(
                jnp.asarray(saved.reshape(merged), pool.dtype)))
        return ids

    # ------------------------------------------------------------ PD handoff
    def export_pages(self, chain: list[int],
                     prompt_ids: Optional[list[int]] = None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """PD disaggregation export: copy a committed chain's pages to host
        and release this pool's hold on them, transferring ownership of the
        KV bytes to the caller. Tree-shared prefix pages stay cached on THIS
        pool's radix (the prefill replica keeps serving warm prefixes);
        private pages return to the allocator. ``prompt_ids`` releases any
        radix pins the caller still holds from match_prefix. Host numpy is
        the transfer format on purpose — it is sharding-agnostic, so pages
        move between same-tp meshes (import re-shards under the destination
        pool's NamedSharding). Refused for a model with recurrent state:
        the export carries none."""
        self._refuse_pd()
        host_kv = self.save_chain_to_host(chain)
        if prompt_ids is not None:
            self.release(prompt_ids)
        self.release_slot(chain)
        return host_kv

    def import_pages(self, host_kv: tuple[np.ndarray, np.ndarray]) -> list[int]:
        """PD disaggregation import: allocate pages in THIS pool and land an
        exported chain's KV bytes in them (cast to this pool's dtype, placed
        under this pool's sharding). Pages are private to the importing slot;
        the radix structure is not reconstructed — the decode-role pool never
        serves prefix matches, so nothing is lost. Raises MemoryError when
        this pool cannot hold the chain even after eviction."""
        self._refuse_pd()
        return self.restore_chain_from_host(host_kv)

    def _refuse_pd(self) -> None:
        if self.cfg.is_latent:
            raise ValueError(
                f"{self.cfg.name}: the PD page export is K and V pages with a "
                "kv-head axis; a latent page has neither (and the export "
                "carries one page group)")
        if self.window:
            raise ValueError(
                f"{self.cfg.name}: the PD page export carries one page "
                "group, and a decode replica could not continue a row "
                "without its window pages")
        if self.state is not None:
            raise ValueError(
                f"{self.cfg.name}: the PD page export carries no recurrent "
                "state, so a decode replica could not continue the row")

    def stats(self) -> dict[str, Any]:
        with self._tree_lock:
            tree_stats = self.tree.stats()
        state_layers = (int(self.state["ssm"].shape[0])
                        if self.state is not None else 0)
        return {
            **tree_stats,
            "pages_free": self.allocator.num_free,
            "pages_total": self.num_pages - 1,
            "pages_referenced": len(self._refs),
            "orphan_pages": len(self._orphans),  # evicted but still slot-held
            "prefill_tokens_saved": self.prefill_tokens_saved,
            "prefill_tokens_total": self.prefill_tokens_total,
            # cached vs total prefill tokens: the fraction of prompt tokens
            # the cache let _admit skip entirely
            "hit_rate": round(
                self.prefill_tokens_saved / self.prefill_tokens_total, 4)
            if self.prefill_tokens_total else 0.0,
            "lookups": self.prefix_lookups,
            "hits": self.prefix_hits,
            "native": self.tree.native,
            # what a page holds: the layout is the configuration's
            "page_layout": ("latent" if self.cfg.is_latent else "kv"),
            "page_shape": [self.page_size, *self._page_tails[0]],
            # the index keys cached beside the latent rows (0, 0: none)
            "index_lanes": self.cfg.index_lanes,
            "index_topk": self.cfg.index_topk,
            "index_heads": self.cfg.index_heads if self.cfg.is_sparse else 0,
            "index_cache_bytes": self.index_pool_bytes(),
            "cache_bytes_per_token": self.cfg.cache_bytes_per_token(
                jnp.dtype(self.dtype).itemsize),
            # and in the window group, while it lies inside its row's window
            "window_bytes_per_token": self.cfg.window_bytes_per_token(
                jnp.dtype(self.dtype).itemsize),
            "pool_bytes": self.pool_bytes(),
            # what the caches were BUILT with: layers of the page pool, of
            # the state slab and of the model, and both caches' bytes
            "kv_layers": int(self.pools[0].shape[0]),
            "state_layers": state_layers,
            # those of them that hold a matrix of state under a delta rule
            "kda_layers": (state_layers if "kda" in self.cfg.layer_types
                           else 0),
            "model_layers": self.cfg.num_layers,
            # passes of the stack a token runs: kv_layers is this many
            # times the layers that attend
            "loop_steps": self.cfg.loop_steps,
            "cache_bytes": self.pool_bytes() + self.state_bytes(),
            # the window page group: its layers, pages, those in use, and
            # those rows have given back so far (0, 0, 0, 0: one group)
            "window_layers": self.cfg.window_layers,
            "window_pages_total": max(self.window_pages - 1, 0),
            "window_pages_in_use": self.window_pages_in_use(),
            "window_pages_freed": self.window_pages_freed,
            "window_pool_bytes": self.window_pool_bytes(),
            **self.state_stats(),
        }

    def state_stats(self) -> dict[str, Any]:
        """State rows beside pages: how many rows the slab has, how many are
        snapshots in use (held by a page or by a prompt still in prefill),
        and the slab's bytes. Slot rows in use are the scheduler's live
        slots (``stats()["state_rows_in_use"]`` there adds them)."""
        if self.state is None:
            return {}
        rows = self.state["ssm"].shape[1]
        return {
            "state_rows": rows,
            "state_slot_rows": self.state_slots,
            "state_snapshot_rows_in_use": (
                rows - self.state_slots - len(self._free_snapshot_rows)),
            "state_snapshots_cached": len(self._snapshots),
            "state_bytes": self.state_bytes(),
            "state_snapshots_taken": self.snapshots_taken,
            "state_snapshot_hits": self.snapshot_hits,
            "state_snapshot_evictions": self.snapshot_evictions,
            "state_restores": self.state_restores,
        }
