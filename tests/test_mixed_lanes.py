"""The token layout of a mixed step (``forward_paged_mixed`` with a decode
group and a lane) against the all-rows call of the same round.

A round here is ``B`` slots: the lane's slot takes a prompt chunk, some slots
decode one token, one may be frozen (active, finished: it computes and writes
to scratch) and the rest are empty. The serving call hands the model the
decode group ``[B]`` and the lane ``[1, Qc]``; the all-rows call hands it
``[B, Qc]`` with the decode rows as spans of one. Both start from the same
pools (and state slab) and must leave the same behind: the same hidden rows
to tolerance, K/V written at the same (page, offset), nothing else touched.
"""

import functools
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import decoder_module, get_config
from cyberfabric_core_tpu.models.llama import DecodeGroup
from cyberfabric_core_tpu.ops.rope import rope_frequencies

MODELS = ("tiny-llama", "tiny-qwen2", "tiny-falcon-h1")
B, PAGE, PMAX, QC = 5, 16, 8, 24
#: history of each slot where it decodes or resumes: one short of a page
#: boundary, inside a page, across two
LENGTHS = np.array([15, 18, 33, 7, 21], np.int32)
#: the lane's chunk: from hist 10 it crosses the boundary at 16; from 0 too
CHUNK, RESUME_AT = 20, 10
#: bf16 activations through two blocks, two attention kernels that round
#: differently (decode: one query; ragged: a block of 8)
TOL = dict(rtol=3e-2, atol=3e-2)


@functools.lru_cache(maxsize=None)
def _world(name: str):
    """Config, seeded weights and the jitted calls of one model; the lane's
    slot is an operand, so one compile serves every case."""
    cfg = get_config(name)
    model = decoder_module(cfg)
    params = model.init_params(cfg, jax.random.PRNGKey(3), jnp.bfloat16)
    rope = rope_frequencies(cfg.head_dim, PAGE * PMAX, cfg.rope_theta)
    has_state = cfg.architecture == "falcon_h1"

    def call(fn, ids, caches, *tail, **kw):
        if not has_state:
            hidden, pools = fn(params, cfg, ids, caches[:2], *tail, **kw)
            return hidden, (*pools,)
        hidden, pools, state = fn(params, cfg, ids, caches[:2], *tail,
                                  state=caches[2], **kw)
        return hidden, (*pools, state)

    @jax.jit
    def all_rows(ids, caches, table, hist, q_lens, write_mask):
        hidden, caches = call(model.forward_paged_mixed, ids, caches, table,
                              hist, q_lens, rope, write_mask=write_mask)
        return model.gather_last_hidden(hidden, q_lens), caches

    @jax.jit
    def lanes(ids, caches, table, hist, q_lens, rows, tokens, lengths, run):
        return call(model.forward_paged_mixed, ids, caches, table, hist,
                    q_lens, rope, rows=rows,
                    decode=DecodeGroup(tokens, lengths, run))

    @jax.jit
    def decode(tokens, caches, table, lengths, run):
        hidden, caches = call(model.forward_paged_decode, tokens[:, None],
                              caches, table, lengths, rope, write_mask=run)
        return hidden[:, 0], caches

    def fresh_caches():
        shape = (cfg.num_layers, B * PMAX + 1, PAGE,
                 cfg.num_kv_heads * cfg.head_dim)
        caches = (jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16))
        if has_state:
            # two rows beyond the slots stand for the pool's snapshots
            caches += (model.init_state(cfg, B + 2),)
        return caches

    return SimpleNamespace(cfg=cfg, has_state=has_state, all_rows=all_rows,
                           lanes=lanes, decode=decode,
                           fresh_caches=fresh_caches)


def _round(name, lane, resumed, running, frozen=()):
    """Start a round: every slot in ``running``/``frozen`` has its history in
    the pools (and the slab), the lane's slot its first RESUME_AT tokens where
    ``resumed``. Returns the operands of the round and the starting caches."""
    w = _world(name)
    cfg = w.cfg
    rng = np.random.default_rng(11)
    table = jnp.asarray(1 + np.arange(B * PMAX).reshape(B, PMAX), jnp.int32)
    lengths = np.zeros(B, np.int32)
    for b in (*running, *frozen):
        lengths[b] = LENGTHS[b]
    hist = RESUME_AT if resumed else 0
    setup_lens = lengths.copy()
    setup_lens[lane] = hist
    width = -(-int(setup_lens.max()) // 8) * 8
    ids = rng.integers(1, cfg.vocab_size, (B, width)).astype(np.int32)
    _, caches = w.all_rows(jnp.asarray(ids), w.fresh_caches(), table,
                         jnp.zeros(B, jnp.int32), jnp.asarray(setup_lens),
                         jnp.ones(B, bool))
    if w.has_state:
        # the snapshot rows hold something, so that "untouched" means more
        # than "still zero"
        mark = {k: v.at[:, B:].set(0.5) for k, v in caches[2].items()}
        caches = (*caches[:2], mark)
    run = np.zeros(B, bool)
    run[list(running)] = True
    active = run.copy()
    active[list(frozen)] = True
    return dict(
        table=table, lane=lane, hist=hist, lengths=lengths, run=run,
        active=active,
        tokens=rng.integers(1, cfg.vocab_size, B).astype(np.int32),
        chunk=rng.integers(1, cfg.vocab_size, CHUNK).astype(np.int32)), caches


def _serve(name, rnd, caches):
    """The serving call: decode group + one lane. Returns ([B, H], caches)."""
    lanes = _world(name).lanes
    ids = np.zeros((1, QC), np.int32)
    ids[0, :CHUNK] = rnd["chunk"]
    return lanes(jnp.asarray(ids), caches, rnd["table"],
                 jnp.asarray([rnd["hist"]], jnp.int32),
                 jnp.asarray([CHUNK], jnp.int32),
                 jnp.asarray([rnd["lane"]], jnp.int32),
                 jnp.asarray(rnd["tokens"]), jnp.asarray(rnd["lengths"]),
                 jnp.asarray(rnd["run"]))


def _all_rows(name, rnd, caches):
    """The same round as one all-rows call: decode rows are spans of one."""
    all_rows = _world(name).all_rows
    ids = np.zeros((B, QC), np.int32)
    ids[:, 0] = rnd["tokens"]
    ids[rnd["lane"], :CHUNK] = rnd["chunk"]
    q_lens = rnd["active"].astype(np.int32)
    q_lens[rnd["lane"]] = CHUNK
    hist = rnd["lengths"].copy()
    hist[rnd["lane"]] = rnd["hist"]
    write = rnd["run"].copy()
    write[rnd["lane"]] = True
    return all_rows(jnp.asarray(ids), caches, rnd["table"], jnp.asarray(hist),
                    jnp.asarray(q_lens), jnp.asarray(write))


def _written(before, after):
    """Where a pool changed, page 0 (scratch) aside: [L, N-1, page] bool."""
    return np.any(np.asarray(before[:, 1:], np.float32)
                  != np.asarray(after[:, 1:], np.float32), axis=-1)


def _check_round(name, rnd, start):
    has_state = _world(name).has_state
    got_h, got = _serve(name, rnd, start)
    want_h, want = _all_rows(name, rnd, start)
    lane, run = rnd["lane"], rnd["run"]
    read = run.copy()
    read[lane] = True               # rows whose hidden the head reads
    np.testing.assert_allclose(
        np.asarray(got_h, np.float32)[read],
        np.asarray(want_h, np.float32)[read], **TOL)

    pages_of = np.asarray(rnd["table"]) - 1     # without the scratch page
    for which in (0, 1):
        wrote = _written(start[which], got[which])
        # the same K/V at the same (page, offset) ...
        np.testing.assert_array_equal(wrote,
                                      _written(start[which], want[which]))
        np.testing.assert_allclose(
            np.asarray(got[which][:, 1:], np.float32),
            np.asarray(want[which][:, 1:], np.float32), **TOL)
        # ... which is each running row's one token and the lane's chunk
        expect = np.zeros_like(wrote[0])
        for b in np.flatnonzero(run):
            L = int(rnd["lengths"][b])
            expect[pages_of[b, L // PAGE], L % PAGE] = True
        for pos in range(rnd["hist"], rnd["hist"] + CHUNK):
            expect[pages_of[lane, pos // PAGE], pos % PAGE] = True
        for layer in wrote:
            np.testing.assert_array_equal(layer, expect)
        # pages of rows in neither group (and of a frozen row): bit for bit
        for b in range(B):
            if not read[b]:
                np.testing.assert_array_equal(
                    np.asarray(got[which][:, 1 + pages_of[b]], np.float32),
                    np.asarray(start[which][:, 1 + pages_of[b]], np.float32))
    if has_state:
        for key in ("ssm", "conv"):
            s0, s1, s2 = (np.asarray(c[2][key]) for c in (start, got, want))
            untouched = [b for b in range(B) if not read[b]] + [B, B + 1]
            np.testing.assert_array_equal(s1[:, untouched], s0[:, untouched])
            np.testing.assert_allclose(s1, s2, **TOL)
            assert not np.array_equal(s1[:, lane], s0[:, lane])
    return got_h, got


@pytest.mark.parametrize("resumed", [False, True], ids=["fresh", "resumed"])
@pytest.mark.parametrize("lane", [0, 2, B - 1], ids=["first", "middle", "last"])
@pytest.mark.parametrize("name", MODELS)
def test_lane_step_matches_all_rows_call(name, lane, resumed):
    """Two rows decode, one is empty, one is frozen; the lane's slot is the
    first, a middle or the last one, from an empty history or a resumed one
    whose chunk crosses a page boundary."""
    others = [b for b in range(B) if b != lane]
    rnd, start = _round(name, lane, resumed, running=others[:2],
                        frozen=others[3:])
    _check_round(name, rnd, start)


@pytest.mark.parametrize("group", ["empty", "full", "frozen"])
@pytest.mark.parametrize("name", MODELS)
def test_lane_step_with_decode_group(name, group):
    """The decode group empty (a prefill-only round: the prefill-role
    engine's, and a first arrival's), full (every other slot decodes) and all
    frozen (every other slot computes, writes to scratch and keeps its pages
    and state)."""
    lane = 2
    others = [b for b in range(B) if b != lane]
    rnd, start = _round(
        name, lane, True, running=others if group == "full" else [],
        frozen=others if group == "frozen" else [])
    _check_round(name, rnd, start)


@pytest.mark.parametrize("name", MODELS)
def test_decode_group_is_the_decode_step(name):
    """What the decode group leaves of a running row — hidden, K/V and, for a
    model with recurrent state, state and conv tail — is what
    ``forward_paged_decode`` leaves of it from the same start: the group runs
    that step's kernels, not the lane's."""
    w = _world(name)
    lane = 1
    others = [b for b in range(B) if b != lane]
    rnd, start = _round(name, lane, False, running=others[:3],
                        frozen=others[3:])
    got_h, got = _serve(name, rnd, start)
    want_h, want = w.decode(jnp.asarray(rnd["tokens"]), start, rnd["table"],
                          jnp.asarray(rnd["lengths"]), jnp.asarray(rnd["run"]))
    run = rnd["run"]
    tight = dict(rtol=1e-2, atol=1e-2)      # one kernel, two matmul shapes
    np.testing.assert_allclose(np.asarray(got_h, np.float32)[run],
                               np.asarray(want_h, np.float32)[run], **tight)
    pages_of = np.asarray(rnd["table"])
    for which in (0, 1):
        for b in np.flatnonzero(run):
            np.testing.assert_allclose(
                np.asarray(got[which][:, pages_of[b]], np.float32),
                np.asarray(want[which][:, pages_of[b]], np.float32), **tight)
    if w.has_state:
        for key in ("ssm", "conv"):
            np.testing.assert_allclose(
                np.asarray(got[2][key])[:, :B][:, run],
                np.asarray(want[2][key])[:, :B][:, run], **tight)
