"""The step programs (``mixed_step``, ``paged_decode_chunk``, ``restore_row``
and the speculative ``spec_mixed_step``) as a function of what they read.

Params, pools, rows and keys are arguments; everything else a program depends
on is configuration, gathered in ``ProgramKey``, and ``step_programs`` builds
a key's jitted functions once a process: a lifecycle rebuild, a pool's replica
and a test's second engine compile nothing the process already holds, and no
engine is needed to lower or run one. The uploads' layout lives here too.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..models import decoder_module, llama
from ..models.configs import ModelConfig
from ..ops.platform import default_interpret
from ..ops.rope import rope_tables
from ..ops.sampling import (block_unmask, sample_token_per_slot,
                            split_keys_per_slot)
from .speculative import greedy_accept_counts

#: most rows of a rope table (the largest published context of the llama
#: configurations served so far; a 262144-position model would carry a 134 MB
#: literal in each program); the served window is always covered
_ROPE_TABLE_ROWS = 32768
#: lanes of a ``mixed_step``: one prefilling slot's chunk a step, so the
#: compiled shape is keyed by the chunk's width alone (a warm-up that runs
#: each width once has run every shape; two arrivals in one round take two
#: steps, not a program of their own)
LANE_ROWS = 1
#: the rows the host owns and the programs only read, one int32 block
#: ``[B, pmax + _CTL + stop_width]`` uploaded whole when it changed: the page
#: table, then top_k, the limit length, the first generated position (a
#: block model), temperature and top_p (float32 bits), then the stop ids
_CTL = 5
#: what a mixed step's dispatch carries by slot, ahead of the lane's spans
#: in ONE flat int32 upload: active, sample, final_mask, final_lens, the
#: final chunk's key (2 words) and, for a block model, its opened block
_LANE_COLS = 6
#: key sets ``step_programs`` keeps: 30-60 MB resident a set at the tests'
#: shapes on the CPU backend (measured, PR 44); a server holds one a model
PROGRAM_SETS_KEPT = 16


@dataclass(frozen=True)
class ProgramKey:
    """What the step programs read that is not an argument: equal keys, the
    same programs. Of ``EngineConfig`` that is ``decode_chunk``,
    ``max_seq_len`` and, through ``n_slots``, ``pmax``, ``spec_k`` and
    ``attn_mesh``, ``max_batch``, ``prefix_page_size``, ``scheduler_spec_k``
    and ``tp``; what only sizes an operand (``device_stop_width``,
    ``prefix_cache_pages``, dtype and quantization through ``params``) keys
    JAX's own cache by shape. Nothing else reaches a program: not
    ``decode_lookahead`` (chunks the HOST keeps in flight), no policy of the
    loop. ``interpret`` is how the kernels lower, read at trace time: a key is
    made, and lowered, inside ``compiled_kernels()`` or outside it."""

    model_config: ModelConfig
    decode_chunk: int       # forwards a decode chunk fuses, at least 1
    max_seq_len: int
    n_slots: int
    pmax: int               # slots of a row's page table: the pages of a
    #                         full-window chain, for every page group
    n_cache: int            # donated cache operands, the state slab included
    has_state: bool
    step_counters: tuple    # the model module's ``STEP_COUNTERS``
    block: int              # width of a row's open block, or 0
    attn_mesh: Any          # mesh the paged kernels ``shard_map`` over, or None
    spec_k: int             # draft tokens verified a round; 0: no such program
    interpret: bool = field(default_factory=default_interpret)


def lane_words(n_slots: int, block: int, n_spans: int, width: int) -> int:
    """The int32 words of a mixed step's flat upload at a lane width."""
    return n_slots * (_LANE_COLS + block) + n_spans * (width + 3)


def serving_rope_tables(cfg: ModelConfig, max_seq_len: int) -> tuple:
    """The (cos, sin) tables a served model's programs carry as a constant."""
    return rope_tables(
        cfg, max(min(cfg.max_position, _ROPE_TABLE_ROWS), max_seq_len))


def _append_counts(toks: jnp.ndarray, counts: jnp.ndarray) -> jnp.ndarray:
    """A program's drained matrix (``[rows, columns]``, or ``[rows]`` of a
    mixed step) with the model's counters as rows past its own, padded to
    whole rows (``_take_step_counters`` takes them off); unchanged where the
    model counts nothing."""
    if not counts.shape[0]:
        return toks
    if toks.ndim == 1:
        return jnp.concatenate([toks, counts])
    pad = -counts.shape[0] % toks.shape[1]
    return jnp.concatenate(
        [toks, jnp.pad(counts, (0, pad)).reshape(-1, toks.shape[1])])


def _unpack_rows(rows: jnp.ndarray, pmax: int) -> tuple:
    """Inside a program: (page_table, top_k, limit_lens, gen_start, temp,
    top_p, stop_ids) of the host-owned block."""
    ctl = rows[:, pmax:]
    warp = jax.lax.bitcast_convert_type(ctl[:, 3:_CTL], jnp.float32)
    return (rows[:, :pmax], ctl[:, 0], ctl[:, 1], ctl[:, 2], warp[:, 0],
            warp[:, 1], ctl[:, _CTL:])


def _unpack_lane(lane: jnp.ndarray, n_slots: int, block: int,
                 n_spans: int) -> tuple:
    """Inside a mixed step, of its flat upload: by slot (active,
    sample_mask, final_mask, final_lens, final keys [B, 2], opened blocks
    [B, W]), then by span (q_ids [R, Qc], q_lens, hist, and the lane's slot
    or, in the all-rows step, the slot's draft length)."""
    cols = _LANE_COLS + block
    slot = lane[: n_slots * cols].reshape(n_slots, cols)
    span = lane[n_slots * cols:].reshape(n_spans, -1)
    return (slot[:, 0] > 0, slot[:, 1] > 0, slot[:, 2] > 0, slot[:, 3],
            jax.lax.bitcast_convert_type(slot[:, 4:_LANE_COLS], jnp.uint32),
            slot[:, _LANE_COLS:], span[:, :-3], span[:, -3], span[:, -2],
            span[:, -1])


def _step_counts(key: ProgramKey, aux: Optional[dict]) -> jnp.ndarray:
    """A forward's ``aux`` in ``step_counters``' order (zeros for None)."""
    if aux is None:
        return jnp.zeros((len(key.step_counters),), jnp.int32)
    return jnp.stack([aux[n] for n in key.step_counters]).astype(jnp.int32)


@functools.lru_cache(maxsize=PROGRAM_SETS_KEPT)
def step_programs(key: ProgramKey) -> tuple:
    """``(restore_row, paged_decode_chunk, mixed_step, spec_mixed_step or
    None)`` of ``key``, jitted: built on a process's first ask, the same after
    (``cache_clear()`` forgets them: for a test that asserts a compile)."""

    def restore_row(last_tokens, keys, lengths, finished, row):
        """A row comes back mid-stream (resume, handoff import): the one
        program beside the step programs that writes a slot's rows.
        ``row``: slot, length, the key's two words, then the last token
        or the open block."""
        slot = row[0]
        return (last_tokens.at[slot].set(
                    row[4:] if last_tokens.ndim == 2 else row[4]),
                keys.at[slot].set(jax.lax.bitcast_convert_type(
                    row[2:4], jnp.uint32)),
                lengths.at[slot].set(row[1]),
                finished.at[slot].set(False))

    rope = serving_rope_tables(key.model_config, key.max_seq_len)
    build = _block_programs if key.block else _token_programs
    return (jax.jit(restore_row), *build(key, rope))


def _block_programs(key: ProgramKey, rope: tuple) -> tuple:
    """``paged_decode_chunk`` and ``mixed_step`` of a model that generates
    by diffusion over blocks: the same two programs by the same names
    (the ring, the admission rule, the round records and the device
    trace's readers apply unchanged), whose carry is every row's OPEN
    BLOCK ``[B, W]`` where the llama family carries a last token.

    Each forward, every running row either DENOISES (its block still
    holds a mask: unmask by confidence, ``ops/sampling.py:block_unmask``)
    or COMMITS (no mask left: the forward that just ran has written the
    final tokens' K/V, so the row's length advances by ``W``, the block
    is emitted and the next one opens all-mask), each by its own phase:
    rows arrive mid-block of their neighbours. A denoise forward's K/V
    does not outlive it, since the row's next forward starts at the same
    length and scatters before it attends. ``decode_chunk`` counts
    forwards. Tokens leave as ``[B, forwards * W]`` with -1 where a
    forward committed nothing; one more column carries the forwards each
    row ran, one more row the counters (row forwards, commit row
    forwards) and the last the model's own (``_append_counts``), so one
    drain brings them all. Stops and
    the token limit cut inside a block on the host (``_emit_block``); the
    device freezes the row at that commit."""
    cfg = key.model_config
    model = decoder_module(cfg)
    k_steps = key.decode_chunk
    max_seq = key.max_seq_len
    W = key.block
    unmask = dict(
        mask_id=cfg.mask_token_id, per_step=W // cfg.denoising_steps,
        dynamic=cfg.remasking == "low_confidence_dynamic",
        threshold=cfg.confidence_threshold)
    offs = jnp.arange(W, dtype=jnp.int32)[None, :]
    n_slots, pmax = key.n_slots, key.pmax

    def advance(params, hidden, block, lens, run, fin, keys, gen_start,
                stop_ids, limit_lens, temp, top_p, top_k):
        """What one forward of the open blocks (``hidden`` [B, W, H])
        does to the rows that ran it. Returns (block, lens, fin, keys,
        emitted [B, W] or -1, (row forwards, commit row forwards))."""
        masked = jnp.any(block == cfg.mask_token_id, axis=1)
        denoise, commit = run & masked, run & jnp.logical_not(masked)
        logits = model.lm_head_logits(params, cfg, hidden)
        keys2, subs = split_keys_per_slot(keys)
        opened = block_unmask(block, logits, subs, temp, top_p, top_k,
                              **unmask)
        new_lens = jnp.where(commit, lens + W, lens)
        generated = (lens[:, None] + offs) >= gen_start[:, None]
        is_stop = jnp.any(block[:, :, None] == stop_ids[:, None, :],
                          axis=2) & generated
        hit = jnp.any(is_stop, axis=1) | (new_lens >= limit_lens) | (
            new_lens + W > max_seq)
        new_block = jnp.where(
            commit[:, None], cfg.mask_token_id,
            jnp.where(denoise[:, None], opened, block))
        return (new_block, new_lens, fin | (commit & hit),
                jnp.where(denoise[:, None], keys2, keys),
                jnp.where(commit[:, None], block, -1),
                jnp.stack([jnp.sum(run), jnp.sum(commit)]))

    def with_counters(toks, ran, counts, moe):
        toks = jnp.concatenate([toks, ran[:, None]], axis=1)
        row = jnp.zeros((1, toks.shape[1]), jnp.int32).at[0, :2].set(
            counts.astype(jnp.int32))
        return _append_counts(jnp.concatenate([toks, row], axis=0), moe)

    def paged_decode_chunk(params, k_pool, v_pool, rows, block,
                           lengths, active, finished, keys):
        (page_table, top_k, limit_lens, gen_start, temp, top_p,
         stop_ids) = _unpack_rows(rows, pmax)
        lengths = jnp.where(active, lengths, 0)

        def step(carry, _):
            pools, blk, lens, fin, keys, ran, counts, moe = carry
            run = active & jnp.logical_not(fin)
            hidden, pools, aux = model.forward_paged_decode(
                params, cfg, blk, pools, page_table, lens, rope,
                write_mask=run)
            blk, lens, fin, keys, emit, n = advance(
                params, hidden, blk, lens, run, fin, keys, gen_start,
                stop_ids, limit_lens, temp, top_p, top_k)
            return (pools, blk, lens, fin, keys,
                    ran + run.astype(jnp.int32), counts + n,
                    moe + _step_counts(key, aux)), emit

        (pools, blk, lens, fin, keys, ran, counts, moe), toks = \
            jax.lax.scan(step, ((k_pool, v_pool), block, lengths,
                                finished, keys, jnp.zeros_like(lengths),
                                jnp.zeros((2,), jnp.int32),
                                _step_counts(key, None)),
                         None, length=k_steps)
        toks = toks.transpose(1, 0, 2).reshape(block.shape[0], -1)
        lens = jnp.where(active, lens, 0)
        return (with_counters(toks, ran, counts, moe), *pools, blk,
                keys, lens, fin)

    def mixed_step(params, k_pool, v_pool, rows, lane, block, lengths,
                   finished, keys):
        """One forward of every open block beside the lane's chunk of
        whole prompt blocks. A lane samples no first token: a row whose
        prompt ends here (``final_mask``) flips to running at
        ``final_lens`` with its key and the open block the lane brought
        (the prompt's leftover, then masks), which this forward has not
        run. A row that is not active is not finished: the lane's slot
        starts its owner clean."""
        (page_table, top_k, limit_lens, gen_start, temp, top_p,
         stop_ids) = _unpack_rows(rows, pmax)
        (active, _, final_mask, final_lens, new_keys, opened, q_ids,
         q_lens, prefill_hist, lane_rows) = _unpack_lane(
             lane, n_slots, W, LANE_ROWS)
        finished = finished & active
        lengths = jnp.where(active, lengths, 0)
        keys = jnp.where(final_mask[:, None], new_keys, keys)
        block = jnp.where(final_mask[:, None], opened, block)
        run = active & jnp.logical_not(finished)
        hidden, pools, aux = model.forward_paged_mixed(
            params, cfg, q_ids, (k_pool, v_pool), page_table,
            prefill_hist, q_lens, rope, rows=lane_rows,
            decode=llama.DecodeGroup(block, lengths, run))
        blk, lens, fin, keys, emit, n = advance(
            params, hidden, block, lengths, run, finished, keys,
            gen_start, stop_ids, limit_lens, temp, top_p, top_k)
        lens = jnp.where(run, lens, jnp.where(
            final_mask, final_lens, jnp.where(active, lengths, 0)))
        return (with_counters(emit, run.astype(jnp.int32), n,
                              _step_counts(key, aux)),
                *pools, blk, keys, lens, fin, active | final_mask)

    return (jax.jit(paged_decode_chunk, donate_argnums=(1, 2)),
            jax.jit(mixed_step, donate_argnums=(1, 2)), None)


def _token_programs(key: ProgramKey, rope: tuple) -> tuple:
    """The programs of a model whose rows carry a last token."""
    cfg = key.model_config
    model = decoder_module(cfg)
    k_steps = key.decode_chunk
    max_seq = key.max_seq_len

    no_counts = _step_counts(key, None)
    n_slots, pmax = key.n_slots, key.pmax

    def paged_forward(forward, params, ids, caches, *tail, **kwargs):
        """One of the model's paged forward passes over the cache
        operands: the pools (K and V, or the one latent pool), and the
        state slab where the model has one. A forward hands back
        (hidden, pools), then its state where it has one, then its
        ``aux`` where its module counts its expert layers: the parts
        compose, a model may have both. Returns (hidden, caches, the
        forward's ``counters``)."""
        n_pools = len(caches) - key.has_state
        if key.has_state:
            kwargs["state"] = caches[-1]
        hidden, pools, *rest = forward(
            params, cfg, ids, caches[:n_pools], *tail, **kwargs)
        state = (rest.pop(0),) if key.has_state else ()
        counts = (_step_counts(key, rest[0]) if key.step_counters
                  else no_counts)
        return hidden, (*pools, *state), counts

    def decode_chunk_body(params, caches, rows, last_tokens, lengths,
                          active, finished, keys):
        """k fused paged decode steps; per-slot key streams so each
        request's seed reproduces its tokens (round-1 advisory).
        Lengths are device-resident: running rows advance by k inside
        the program; inactive rows pin back to 0 so garbage positions
        never creep past the rope table / page chain bounds.

        Device-side termination: each step matches the sampled token
        against the row's padded stop ids and its length limit
        (max-tokens bound; the window bound fires at the chunk's last
        step, mirroring the host force-length rule), and a finished
        row FREEZES — last token, key stream, length and KV writes
        all stop advancing (writes park on scratch page 0), emitting
        -1 sentinels. A chunk chained off this one therefore stays
        valid across mid-chunk finishes, which is what lets the
        lookahead ring survive them."""
        (page_table, top_k, limit_lens, _, temp, top_p,
         stop_ids) = _unpack_rows(rows, pmax)
        lengths = jnp.where(active, lengths, 0)

        def step(carry, j):
            caches, toks, lens, fin, keys, counts = carry
            run = active & jnp.logical_not(fin)
            hidden, caches, n = paged_forward(
                model.forward_paged_decode, params, toks[:, None],
                caches, page_table, lens, rope,
                write_mask=run, mesh=key.attn_mesh)
            logits = model.lm_head_logits(params, cfg, hidden[:, 0, :])
            keys2, subs = split_keys_per_slot(keys)
            nxt = sample_token_per_slot(logits, subs, temp, top_p,
                                        top_k)
            new_lens = lens + 1
            is_stop = jnp.any(nxt[:, None] == stop_ids, axis=1)
            hit = (new_lens >= limit_lens) | (
                (j == k_steps - 1) & (new_lens + k_steps > max_seq))
            emit = jnp.where(run, nxt, -1)
            return (caches, jnp.where(run, nxt, toks),
                    jnp.where(run, new_lens, lens),
                    fin | (run & (is_stop | hit)),
                    jnp.where(run[:, None], keys2, keys),
                    counts + n), emit

        (caches, last, lens, fin, keys, counts), toks = jax.lax.scan(
            step, (caches, last_tokens, lengths, finished, keys,
                   no_counts),
            jnp.arange(k_steps, dtype=jnp.int32))
        lens = jnp.where(active, lens, 0)
        return (_append_counts(toks.T, counts), *caches, last, keys,
                lens, fin)

    # the cache operands lead the programs' arguments, donated: K and V
    # for the llama family, as always; the state slab as a third where
    # the model has recurrent state; the one latent pool where the cache
    # is latent. One name for all: it is what the compile log and the
    # device trace show.
    n_cache = key.n_cache

    def paged_decode_chunk(params, *rest):
        return decode_chunk_body(params, rest[:n_cache], *rest[n_cache:])

    donate = tuple(range(1, 1 + n_cache))

    def mixed_step_body(params, caches, rows, lane, last_tokens,
                        lengths, finished, keys):
        """One mixed-batch round over the tokens it has: every decode
        row takes its next token (the decode group: ``last_tokens``,
        ``lengths``, ``run``) while the lane — ``q_ids [R, Qc]``, the
        chunk of the prefilling slot ``lane_rows`` names — consumes a
        prompt chunk, in ONE pass over the weights of
        ``B + R*Qc`` positions. The compiled shape is keyed by the
        lane's width alone. ``sample_mask`` rows (decode +
        final-chunk prefill) draw from their key stream; everyone
        else's key is untouched, so a mid-prefill request's seed
        reproduces its stream whatever rode beside it.

        Device-side termination + ring spanning: sampled rows run the
        same stop/limit/window checks as the decode chunk and fold
        into the finished mask; ``final_mask`` rows flip to decode ON
        DEVICE (active_out, lengths = final_lens, first token in
        last_out) so lookahead chunks can chain directly off this
        dispatch when the prefill queue drains — the mixed→pure
        transition needs no synchronous fallback round. The lane's slot
        starts its owner clean HERE: a row that is not active is not
        finished, and a ``final_mask`` row takes the key the lane
        brought before it samples."""
        (page_table, top_k, limit_lens, _, temp, top_p,
         stop_ids) = _unpack_rows(rows, pmax)
        (active, sample_mask, final_mask, final_lens, new_keys, _,
         q_ids, q_lens, prefill_hist, lane_rows) = _unpack_lane(
             lane, n_slots, 0, LANE_ROWS)
        finished = finished & active
        lengths = jnp.where(active, lengths, 0)
        keys = jnp.where(final_mask[:, None], new_keys, keys)
        run = active & jnp.logical_not(finished)
        last_h, caches, counts = paged_forward(
            model.forward_paged_mixed, params, q_ids, caches,
            page_table, prefill_hist, q_lens, rope,
            mesh=key.attn_mesh, rows=lane_rows,
            decode=llama.DecodeGroup(last_tokens, lengths, run))
        logits = model.lm_head_logits(params, cfg, last_h)
        keys2, subs = split_keys_per_slot(keys)
        nxt = sample_token_per_slot(logits, subs, temp, top_p, top_k)
        sample = sample_mask & jnp.logical_not(finished)
        keys_out = jnp.where(sample[:, None], keys2, keys)
        new_last = jnp.where(sample, nxt, last_tokens)
        new_lens = jnp.where(
            run, lengths + 1,
            jnp.where(final_mask, final_lens,
                      jnp.where(active, lengths, 0)))
        toks = jnp.where(sample, nxt, -1)
        is_stop = jnp.any(nxt[:, None] == stop_ids, axis=1)
        hit = (new_lens >= limit_lens) | (new_lens + k_steps > max_seq)
        fin_out = finished | (sample & (is_stop | hit))
        active_out = active | final_mask
        return (_append_counts(toks, counts), *caches, new_last,
                keys_out, new_lens, fin_out, active_out)

    def mixed_step(params, *rest):
        return mixed_step_body(params, rest[:n_cache], *rest[n_cache:])

    spec_w = key.spec_k + 1

    def spec_mixed_step(params, k_pool, v_pool, rows, lane,
                        last_tokens, lengths, finished, keys):
        """mixed_step + k-token speculation: speculating rows run
        their draft span (q_len = 1 + spec_lens ≤ spec_w, q_ids =
        [last_token, d_1..d_d]) through the SAME ragged dispatch
        as decode rows (q_len=1) and prefill-chunk rows. Greedy
        accept/reject, accepted-length, per-position stop/limit
        truncation and the length advance all happen HERE, on
        device — only the [N, spec_w] emit matrix (-1 sentinels
        past each row's commit) and the accept counts cross to
        the host.

        Rollback is rewrite-before-read: a rejected suffix's KV
        sits at positions new_length..L+d of the row's own chain
        pages — masked out of attention by the per-row length
        bounds, and every later dispatch's span starts at the
        committed length and scatters BEFORE it attends, so the
        stale entries are overwritten before any read (the same
        discipline the discarded-ring argument rests on). Non-
        speculating rows compute bit-identically to mixed_step;
        greedy speculating rows commit exactly the tokens plain
        decode would have produced (acceptance is argmax
        equality), so speculation changes speed, never text."""
        (page_table, top_k, limit_lens, _, temp, top_p,
         stop_ids) = _unpack_rows(rows, pmax)
        (active, sample_mask, final_mask, final_lens, new_keys, _,
         q_ids, q_lens, prefill_hist, spec_lens) = _unpack_lane(
             lane, n_slots, 0, n_slots)
        finished = finished & active
        lengths = jnp.where(active, lengths, 0)
        keys = jnp.where(final_mask[:, None], new_keys, keys)
        run = active & jnp.logical_not(finished)
        q_ids = q_ids.at[:, 0].set(
            jnp.where(active, last_tokens, q_ids[:, 0]))
        hist = jnp.where(active, lengths, prefill_hist)
        hidden, pools = llama.forward_paged_mixed(
            params, cfg, q_ids, (k_pool, v_pool), page_table,
            hist, q_lens, rope,
            write_mask=run | jnp.logical_not(active),
            mesh=key.attn_mesh)
        last_h = llama.gather_last_hidden(hidden, q_lens)
        logits = llama.lm_head_logits(params, cfg, last_h)
        keys2, subs = split_keys_per_slot(keys)
        nxt = sample_token_per_slot(logits, subs, temp, top_p,
                                    top_k)
        # verify: per-position argmax over the span's first
        # spec_w positions (q_lens ≤ spec_w for speculating rows;
        # prefill rows ignore these logits entirely)
        N = q_ids.shape[0]
        H = hidden.shape[-1]
        span_h = jax.lax.dynamic_slice_in_dim(hidden, 0, spec_w,
                                              axis=1)
        span_logits = llama.lm_head_logits(
            params, cfg, span_h.reshape(N * spec_w, H))
        outs = jnp.argmax(span_logits, axis=-1).astype(
            jnp.int32).reshape(N, spec_w)
        spec = (spec_lens > 0) & run
        a = greedy_accept_counts(outs, q_ids[:, 1:spec_w],
                                 spec_lens)
        # committed[i] = the model's token after the accepted
        # prefix of length i. Position 0 keeps the sampled path
        # for non-spec rows (bit-identity with mixed_step);
        # spec rows are greedy, so outs[:, 0] IS that argmax.
        committed = outs.at[:, 0].set(
            jnp.where(spec, outs[:, 0], nxt))
        n_commit = jnp.where(spec, a + 1, 1)
        idx = jnp.arange(spec_w, dtype=jnp.int32)[None, :]
        in_commit = idx < n_commit[:, None]
        is_stop = jnp.any(
            committed[:, :, None] == stop_ids[:, None, :],
            axis=2)
        # per-position termination, mirroring mixed_step's
        # single-token rule exactly at idx 0 (final-chunk prefill
        # rows carry lengths=0 on device — their post-token
        # length is final_lens, hence eff_len)
        eff_len = jnp.where(
            run, lengths,
            jnp.where(final_mask, final_lens - 1, lengths))
        len_after = eff_len[:, None] + idx + 1
        hit = (len_after >= limit_lens[:, None]) | (
            len_after + k_steps > max_seq)
        fin_at = (is_stop | hit) & in_commit
        # token i commits only while no stop/limit fired before
        # it: the accepted suffix past a terminal is dropped ON
        # DEVICE, the same truncation the scan chunk's freeze
        # gives mid-chunk finishes
        alive = jnp.cumprod(
            1 - jnp.pad(fin_at.astype(jnp.int32),
                        ((0, 0), (1, 0)))[:, :spec_w],
            axis=1) > 0
        emit = in_commit & alive
        n_emit = jnp.sum(emit.astype(jnp.int32), axis=1)
        sample = sample_mask & jnp.logical_not(finished)
        toks = jnp.where(emit & sample[:, None], committed, -1)
        new_last = jnp.where(
            sample,
            jnp.take_along_axis(
                committed,
                jnp.maximum(n_emit - 1, 0)[:, None],
                axis=1)[:, 0],
            last_tokens)
        keys_out = jnp.where(sample[:, None], keys2, keys)
        new_lens = jnp.where(
            run, lengths + n_emit,
            jnp.where(final_mask, final_lens,
                      jnp.where(active, lengths, 0)))
        fin_out = finished | (sample & jnp.any(fin_at & emit,
                                               axis=1))
        active_out = active | final_mask
        # accept counts ride the emit matrix's last column (-1
        # for non-spec rows): ONE drain carries tokens AND the
        # acceptance evidence — the round keeps its single
        # sanctioned sync point (AS04)
        a_out = jnp.where(spec, a, -1)
        toks_out = jnp.concatenate([toks, a_out[:, None]],
                                   axis=1)
        return (toks_out, pools[0], pools[1], new_last,
                keys_out, new_lens, fin_out, active_out)

    return (jax.jit(paged_decode_chunk, donate_argnums=donate),
            jax.jit(mixed_step, donate_argnums=donate),
            jax.jit(spec_mixed_step, donate_argnums=(1, 2))
            if key.spec_k else None)
