"""``models/solar_open2.py`` against the plain reference
(``benchmark/solar_open2_reference.py``: float32, ``highest``, no cache, the
delta rule one token at a time, attention by the formula, the held experts by
a loop) at ``tiny-solar-open2-share4`` (``a k k k`` twice; experts 4-7 of 16,
half the vocabulary) on seeded weights: prefill in chunks and then decode,
through pages in the attention layers and state in the KDA layers, compared
on logits.

The number compared is the judge's: per logits row rms(program - reference) /
std(reference), the reference computing with the experts the program chose
(routing is discontinuous: ``benchmark/adapters/solar_open2.py``). In
bfloat16 through 4-8 layers of 64 channels the rows read 0.015-0.035 over
seeds, the tolerance is 0.06, and computing one precision lower reads five
and more times that."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import solar_open2_reference as reference
from benchmark import solar_open2_weights
from cyberfabric_core_tpu.models import (ModelConfig, get_config,
                                         granite_hybrid, kimi_k2, llama,
                                         nemotron_h, solar_open2)
from cyberfabric_core_tpu.ops.rope import rope_tables
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool, state_copy_row
from cyberfabric_core_tpu.runtime.quant import (init_params_quantized,
                                                quantize_llama_params,
                                                quantized_bytes)
from granite_hybrid_helpers import PagedRun
from solar_open2_helpers import published
from test_nemotron_h_model import _plain, _worst

TOLERANCE = 0.06
FULL = get_config("tiny-solar-open2-share4")            # 8 layers
CFG = get_config("tiny-solar-open2-share4-4l")          # its first period
LENS, STEPS, PAD = [37, 20, 5], 3, 48


def _seqs(seed, cfg=CFG):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_rows, n + STEPS + 1).astype(np.int32)
            for n in LENS]


def _kw(cfg):
    return reference.reference_kwargs(published(cfg), cfg.num_layers)


def _reference_rows(weights, seqs, experts, lower=None, cfg=CFG):
    """The reference's logits at each row's last prompt position and the
    ``STEPS`` after it, every sequence padded to one length (one compile);
    with ``experts`` the choices it is held to, and its worst shortfall."""
    rows, worst = {}, 0.0
    for r, n in enumerate(LENS):
        ids = np.zeros(PAD, np.int32)
        ids[: n + STEPS] = seqs[r][: n + STEPS]
        at = np.arange(n - 1, n + STEPS)
        chosen = None
        if experts is not None:
            chosen = np.zeros((cfg.num_layers, PAD, cfg.experts_per_token),
                              np.int32)
            chosen[:, : n + STEPS] = experts[r]
            chosen = jnp.asarray(chosen)
        out, short = reference.forward_logits(
            weights, jnp.asarray(ids), jnp.asarray(at, jnp.int32), chosen,
            lower=lower, **_kw(cfg))
        rows.update({(r, int(p)): row for p, row in zip(at, np.asarray(out))})
        if experts is not None:
            worst = max(worst, float(np.asarray(short)[:, : n + STEPS].max()))
    return rows, worst


def _weights(seed, cfg=CFG):
    return solar_open2_weights.make_weights(published(cfg), seed,
                                            cfg.num_layers)


def _run(cfg, weights, rows):
    return PagedRun(cfg, weights, rows=rows, module=solar_open2)


@pytest.mark.parametrize("seed,cfg", [(1, FULL), (2147484001, CFG)])
def test_int8_program_equals_the_reference_on_int8_grid_weights(seed, cfg):
    """Chunks of 16 (two KDA chunks of 8 each), a prompt that ends inside a
    chunk, a short one; then decode through the state kernel's arithmetic and
    the paged decode kernel; both periods of the pattern and one. The experts
    the program chose are at most a rounding under the reference's own."""
    weights = _weights(seed, cfg)
    run = _run(cfg, weights, 3)
    got = run.run(_seqs(seed), LENS, STEPS)
    assert len(got) == 3 * (STEPS + 1)
    assert got[(0, 36)].shape == (cfg.vocab_rows,) == (256,)
    ref, shortfall = _reference_rows(weights, _seqs(seed), run.experts,
                                     cfg=cfg)
    assert _worst(got, ref) < TOLERANCE
    assert shortfall < 0.05


def test_a_lower_precision_reads_over_the_tolerance_and_the_experts_agree():
    """The tolerance tells precisions apart: the reference at float8
    activations, and the program on int4-grid weights, are over it; where no
    score is within a rounding of the next, the experts the program chose ARE
    the reference's: at least 95% of tokens a layer."""
    weights = _weights(3)
    seqs = _seqs(3)
    run = _run(CFG, weights, 3)
    run.run(seqs, LENS, STEPS)
    exact, _ = _reference_rows(weights, seqs, None)
    fp8, _ = _reference_rows(weights, seqs, None, lower="fp8")
    assert _worst(fp8, exact) > 2 * TOLERANCE
    low = _run(CFG, solar_open2_weights.to_int4_grid(weights), 3)
    got4 = low.run(seqs, LENS, STEPS)
    assert _worst(got4, exact) > 2 * TOLERANCE
    same = total = 0
    for r, n in enumerate(LENS):
        ids = np.zeros(PAD, np.int32)
        ids[: n + STEPS] = seqs[r][: n + STEPS]
        _, short = reference.forward_logits(
            weights, jnp.asarray(ids), jnp.asarray([0], jnp.int32),
            jnp.asarray(np.pad(run.experts[r],
                               ((0, 0), (0, PAD - n - STEPS), (0, 0)))),
            **_kw(CFG))
        short = np.asarray(short)[:, : n + STEPS]
        same += int((short <= 0).sum())
        total += short.size
    assert same / total > 0.95


def test_bf16_program_equals_the_reference():
    """The unquantised tree (``init_params``, bfloat16): the reference is
    handed the tree quantised and the program the SAME quantised tree
    dequantised to bfloat16."""
    tree = quantize_llama_params(
        solar_open2.init_params(CFG, jax.random.PRNGKey(5)), bits=8)
    run = _run(CFG, _plain(tree), 3)
    got = run.run(_seqs(5), LENS, STEPS)
    ref, _ = _reference_rows(tree, _seqs(5), run.experts)
    assert _worst(got, ref) < TOLERANCE


def test_a_mixed_step_with_a_decode_group_a_resumed_lane_and_idle_rows():
    """One mixed step as the scheduler builds it: rows 0 and 1 decode (the
    decode group), row 2 is idle (``run`` False), and the lane carries row
    3's chunk, resumed from a snapshot of row 0's state at token 16 plus row
    0's first page aliased in its page table. Row 3's logits are those of a
    row that prefilled the whole prompt itself; the idle row, the snapshot
    row and every row beyond come back bit for bit; a lane whose
    ``write_mask`` is False changes no state either. The counters are a
    count by hand of the experts chosen."""
    weights = _weights(4)
    rng = np.random.default_rng(4)
    base = rng.integers(3, CFG.vocab_rows, 40).astype(np.int32)
    other = rng.integers(3, CFG.vocab_rows, 40).astype(np.int32)
    run = _run(CFG, weights, 4)
    ids = np.stack([base[:16], other[:16], other[:16], base[:16]])
    run.mixed_step(ids, [0, 0, 0, 0], [16, 16, 0, 0])
    run.state = state_copy_row(run.state, 0, 4)          # the snapshot at 16
    more = np.stack([base[16:32], other[16:32], other[:16], base[:16]])
    run.mixed_step(more, [16, 16, 0, 0], [16, 16, 0, 0])
    # row 3 resumes: row 0's first page (tokens 0-15), the snapshot's state
    run.table = run.table.at[3, 0].set(run.table[0, 0])
    run.state = state_copy_row(run.state, 4, 3)
    before = jax.tree.map(np.asarray, run.state)
    lane = np.zeros((1, 16), np.int32)
    lane[0, :9] = base[16:25]
    decode = llama.DecodeGroup(
        jnp.asarray([base[32], other[32], 0, 0], jnp.int32),
        jnp.asarray([32, 32, 0, 16], jnp.int32),
        jnp.asarray([True, True, False, False]))
    logits = run.mixed_step(lane, [16], [9], rows=jnp.asarray([3]),
                            decode=decode)
    chosen = np.asarray(run.aux["experts"])
    assert chosen.shape == (CFG.num_layers, 4 + 16, CFG.experts_per_token)
    counts = {k: int(run.aux[k]) for k in solar_open2.STEP_COUNTERS}
    assert counts["assignments"] == chosen.size == (4 + 16) * 4 * 4
    assert counts["local"] == int(((chosen >= 4) & (chosen < 8)).sum())
    assert counts["touched"] == sum(
        len(set(layer.ravel()) & {4, 5, 6, 7}) for layer in chosen)
    assert counts["forwards"] == CFG.num_layers
    after = jax.tree.map(np.asarray, run.state)
    for leaf in ("ssm", "conv"):
        for row in (0, 1, 3):
            assert not np.array_equal(after[leaf][:, row], before[leaf][:, row])
        for row in (2, 4):
            assert np.array_equal(after[leaf][:, row], before[leaf][:, row])
    # what rows 0, 1 and 3 would read had each run alone, at the same shapes
    cold = _run(CFG, weights, 4)
    cold.mixed_step(np.stack([base[:16], other[:16], other[:16], base[:16]]),
                    [0, 0, 0, 0], [16, 16, 0, 16])
    tail = np.zeros((4, 16), np.int32)
    tail[0], tail[1], tail[3, :9] = base[16:32], other[16:32], base[16:25]
    want3 = cold.mixed_step(tail, [16, 16, 0, 16], [16, 16, 0, 9])[3]
    want = cold.decode(np.asarray([[base[32]], [other[32]], [0], [0]]),
                       [32, 32, 0, 25],
                       write_mask=jnp.asarray([True, True, False, False]))
    for got_row, want_row in ((logits[3], want3), (logits[0], want[0]),
                              (logits[1], want[1])):
        d = got_row - want_row
        assert float(np.sqrt((d * d).mean()) / want_row.std()) < TOLERANCE
    # a masked lane and decode rows that do not run move nothing
    before = after
    idle = llama.DecodeGroup(decode.tokens, decode.lengths,
                             jnp.asarray([True, False, False, False]))
    run.mixed_step(lane, [25], [9], rows=jnp.asarray([3]),
                   write_mask=jnp.asarray([False]), decode=idle)
    after = jax.tree.map(np.asarray, run.state)
    for leaf in ("ssm", "conv"):
        assert not np.array_equal(after[leaf][:, 0], before[leaf][:, 0])
        for row in (1, 2, 3, 4):
            assert np.array_equal(after[leaf][:, row], before[leaf][:, row])


def test_a_row_with_no_history_starts_from_the_zero_state():
    weights = _weights(4)
    ids = np.stack([np.resize(s, 16) for s in _seqs(4)])
    clean = _run(CFG, weights, 3)
    first = clean.mixed_step(ids, [0, 0, 0], [16, 16, 5])
    dirty = _run(CFG, weights, 3)
    dirty.state = jax.tree.map(lambda x: x + 3.0, dirty.state)
    again = dirty.mixed_step(ids, [0, 0, 0], [16, 16, 5])
    assert np.array_equal(first, again)


def test_the_four_shares_add_up_to_the_uncut_expert_layer():
    """The share test: the four chips' routed parts (each chip's held
    experts' gated sum, the gates normalised over ALL the chosen), with the
    shared expert counted ONCE, are the uncut reference's whole expert
    layer. In float32, so that nothing but the split shows."""
    uncut = dataclasses.replace(CFG, experts_held=0, expert_offset=0)
    tree = _weights(7, uncut)
    assert tree["layers"]["moe_up"]["q"].shape[:2] == (uncut.num_layers, 16)
    x = jax.random.normal(jax.random.PRNGKey(7), (24, uncut.hidden_size),
                          jnp.float32)
    normed = reference._rms_norm(x, jnp.ones(uncut.hidden_size),
                                 uncut.rms_norm_eps)
    kw = {**_kw(uncut), "expert_layer_only": True}
    whole, _ = reference.forward_logits(tree, normed, None, **kw)
    lw = tree["layers"]
    with jax.default_matmul_precision("highest"):
        gate, up, down = (reference._dequant(lw[n], 0) for n in
                          ("shared_gate", "shared_up", "shared_down"))
        shared = (jax.nn.silu(normed @ gate) * (normed @ up)) @ down
    total = -3.0 * np.asarray(shared)       # each share adds the shared expert
    stack = _plain(lw, jnp.float32)
    lp = {k: v[0] for k, v in stack.items() if k not in llama.MOE_LEAVES}
    lp["mlp_norm"] = jnp.ones(uncut.hidden_size)
    for offset in (0, 4, 8, 12):
        cfg = dataclasses.replace(uncut, experts_held=4, expert_offset=offset)
        moe = {k: stack[k][:, offset: offset + 4] for k in llama.MOE_LEAVES}
        out, top_idx, counts = kimi_k2._moe_residual(lp, moe, 0, x[None], cfg)
        total = total + np.asarray(out[0] - x)
        assert int(counts[1]) == int(
            ((top_idx >= offset) & (top_idx < offset + 4)).sum())
    scale = float(np.asarray(whole).std())
    assert float(np.abs(total - np.asarray(whole)).max()) < 1e-4 * scale
    # and one share alone is NOT the layer: the split is seen
    assert float(np.abs(np.asarray(out[0] - x) - np.asarray(whole)).max()) \
        > 0.05 * scale


def test_the_experts_counted_are_the_experts_chosen():
    """``aux["experts"]`` is [layers, tokens, K] in LAYER order over the
    router's 16 (a unit of four layers repeated: the scan's stacks are
    interleaved back), and the counters are a count by hand of it."""
    weights = _weights(6, FULL)
    run = _run(FULL, weights, 3)
    ids = np.stack([np.resize(s, 16) for s in _seqs(6)])
    run.mixed_step(ids, [0, 0, 0], [16, 16, 5])
    chosen = np.asarray(run.aux["experts"])
    assert chosen.shape == (FULL.num_layers, 3 * 16, FULL.experts_per_token)
    assert chosen.max() > 7 and chosen.min() < 4        # all 16 are routed
    assert all(len(set(row)) == FULL.experts_per_token
               for row in chosen.reshape(-1, FULL.experts_per_token))
    assert int(run.aux["assignments"]) == chosen.size
    mine = (chosen >= 4) & (chosen < 8)
    assert int(run.aux["local"]) == int(mine.sum())
    assert int(run.aux["touched"]) == sum(
        len(np.unique(layer[m])) for layer, m in zip(chosen, mine))
    # layer order: the reference, handed these choices layer by layer, finds
    # each within a rounding of its own (a permuted stack would not be)
    seq = np.zeros(PAD, np.int32)
    seq[:16] = ids[0]
    full = np.zeros((FULL.num_layers, PAD, FULL.experts_per_token), np.int32)
    full[:, :16] = chosen[:, :16]
    _, short = reference.forward_logits(
        weights, jnp.asarray(seq), jnp.asarray([0], jnp.int32),
        jnp.asarray(full), **_kw(FULL))
    assert float(np.asarray(short)[:, :16].max()) < 0.05


def test_the_caches_are_as_deep_as_their_kinds_and_the_bytes_to_the_byte():
    """The pool arrays have ``kv_layers`` layers and the slab
    ``state_layers`` (the kda layers); the configuration's byte functions
    agree with the arrays' ``nbytes``; the served cut is 3 and 9 of 12."""
    assert (FULL.kv_layers, FULL.state_layers, FULL.moe_layers,
            FULL.num_layers) == (2, 6, 8, 8)
    assert (CFG.kv_layers, CFG.state_layers, CFG.moe_layers) == (1, 3, 4)
    pool = PrefixKVPool(FULL, num_pages=9, page_size=16, state_slots=3,
                        state_snapshots=2)
    assert pool.k_pool.shape == (2, 9, 16, FULL.num_kv_heads * FULL.head_dim)
    assert pool.state["ssm"].shape == (6, 5, 4, 16, 16)
    assert pool.state["conv"].shape == (6, 5, 3 * 3 * 4 * 16)     # flat
    assert pool.pool_bytes() == 9 * 16 * FULL.cache_bytes_per_token(2) \
        == pool.k_pool.nbytes + pool.v_pool.nbytes
    assert pool.state_bytes() == 5 * FULL.state_bytes_per_row() \
        == sum(v.nbytes for v in pool.state.values())
    stats = pool.stats()
    assert (stats["kv_layers"], stats["state_layers"], stats["kda_layers"],
            stats["model_layers"]) == (2, 6, 6, 8)
    params = init_params_quantized(FULL, jax.random.PRNGKey(0))
    assert params["layers"]["moe_up"]["q"].shape == (8, 4, 64, 32)
    assert params["layers"]["moe_down"]["q"].shape == (8, 4, 32, 64)
    assert params["layers"]["router"].shape == (8, 64, 16)
    # the int8 tree's matrices and scales by kind, to the byte
    by_kind = FULL.weight_bytes(1)

    def nbytes(tree, names):
        return sum(quantized_bytes(tree[n]) for n in names)

    assert by_kind["kda"] == nbytes(params["kda"], FULL.kda_matrices())
    assert by_kind["attention"] == quantized_bytes(params["attention"])
    assert by_kind["experts"] == nbytes(params["layers"], llama.MOE_LEAVES)
    assert by_kind["moe_dense"] == nbytes(params["layers"], (
        "router", "router_bias", "shared_gate", "shared_up", "shared_down"))
    assert by_kind["vocab"] == quantized_bytes(params["embed"]) \
        + quantized_bytes(params["lm_head"])
    assert by_kind["mamba"] == 0
    served = get_config("solar-open2-share8-12l")
    assert (served.kv_layers, served.state_layers, served.moe_layers) == (
        3, 9, 12)
    assert served.layer_types == ("attention", "kda", "kda", "kda") * 3
    assert served.cache_bytes_per_token() == 3 * 4096
    assert served.state_bytes_per_row() == 9 * 4 * (64 * 128 * 128
                                                    + 3 * 24576)
    assert (served.experts_local, served.vocab_rows) == (40, 24576)
    assert 9.5e9 < sum(served.weight_bytes(1).values()) < 9.7e9
    full = get_config("solar-open2-250b")
    assert (full.kv_layers, full.state_layers, full.moe_layers) == (12, 36, 48)
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == list(range(0, 48, 4))
    # the kinds the others count are the kinds they counted
    assert get_config("granite-4.0-h-small").state_layers == 36
    nemotron = get_config("nemotron-3-super-share4-22l")
    assert (nemotron.state_layers, nemotron.state_bytes_per_row()) == (
        10, 10 * 4 * (128 * 64 * 128 + 3 * 10240))
    assert get_config("falcon-h1-34b-16l").state_layers == 16


def test_depth_is_runs_of_a_repeated_unit():
    """``a k k k`` repeated is ONE scan of a four-layer body, whatever the
    repetitions; a single period is ``a`` then ``k`` x 3; every layer is in
    exactly one run, at its index among the layers of its kind."""
    def shape(cfg):
        return [("".join(k[0] for k in unit), first, reps)
                for unit, first, _, reps in solar_open2.layer_runs(cfg)]

    assert shape(FULL) == [("akkk", 0, 2)]
    assert shape(CFG) == [("a", 0, 1), ("k", 1, 3)]
    assert shape(get_config("solar-open2-share8-12l")) == [("akkk", 0, 3)]
    assert shape(get_config("solar-open2-250b")) == [("akkk", 0, 12)]
    assert shape(FULL.cut_to(6)) == [("a", 0, 1), ("k", 1, 3), ("a", 4, 1),
                                     ("k", 5, 1)]
    for cfg in (FULL, CFG, FULL.cut_to(6)):
        seen, at = {"attention": 0, "kda": 0}, 0
        for unit, first, first_of, reps in solar_open2.layer_runs(cfg):
            assert first == at and first_of == seen
            assert cfg.layer_types[at: at + reps * len(unit)] == unit * reps
            for kind in unit:
                seen[kind] += reps
            at += reps * len(unit)
        assert at == cfg.num_layers


def test_quantised_init_and_param_count():
    params = init_params_quantized(CFG, jax.random.PRNGKey(0))
    assert params["kda"]["wq"]["q"].shape == (3, 64, 64)
    assert params["kda"]["wq"]["q"].dtype == jnp.int8
    assert params["kda"]["f_a"]["q"].shape == (3, 64, 16)
    assert params["kda"]["f_b"]["q"].shape == (3, 16, 64)
    assert params["kda"]["w_beta"]["q"].shape == (3, 64, 4)
    assert params["attention"]["wq"]["q"].shape == (1, 64, 64)
    assert params["attention"]["wk"]["q"].shape == (1, 64, 32)
    assert params["attention"]["w_gate"]["q"].shape == (1, 64, 64)
    assert params["layers"]["shared_down"]["q"].shape == (4, 32, 64)
    assert params["layers"]["router"].dtype == jnp.float32
    assert params["layers"]["router_bias"].shape == (4, 16)
    assert params["lm_head"]["q"].shape == (64, 256)
    assert params["embed"]["qe"].shape == (256, 64)
    for small, shape in (("conv_w", (3, 4, 192)), ("A_log", (3, 4)),
                         ("dt_bias", (3, 64)), ("o_norm", (3, 16))):
        assert params["kda"][small].dtype == jnp.float32, small
        assert params["kda"][small].shape == shape, small
    same = quantize_llama_params(
        solar_open2.init_params(CFG, jax.random.PRNGKey(0)), bits=8)
    assert jax.tree.structure(same) == jax.tree.structure(params)
    uncut = dataclasses.replace(CFG, experts_held=0, expert_offset=0,
                                vocab_held=0)
    leaves = jax.tree.leaves(
        solar_open2.init_params(uncut, jax.random.PRNGKey(0)))
    assert sum(x.size for x in leaves) == uncut.param_count()
    big = get_config("solar-open2-250b")
    assert 247.5e9 < big.param_count() < 252.5e9            # 250 B, +- 1%
    # active a token: everything but the experts not chosen: 14.7 B
    idle = big.moe_layers * (big.num_experts - big.experts_per_token) * (
        3 * big.hidden_size * big.expert_width)
    assert 14.55e9 < big.param_count() - idle < 14.85e9     # 14.7 B, +- 1%
    # the others count what they counted
    assert 120.6e9 < get_config(
        "nemotron-3-super-120b-a12b").param_count() < 120.8e9


def test_a_mesh_is_refused_with_a_line():
    with pytest.raises(ValueError, match="solar_open2 serves on one device"):
        solar_open2.forward_paged_decode(
            None, CFG, None, None, None, None, None, mesh=object(), state=None)


def test_unknown_kinds_are_refused_by_name():
    base = dict(name="x", architecture="solar_open2", vocab_size=8,
                hidden_size=8, intermediate_size=8, num_layers=2, num_heads=1,
                num_kv_heads=1, head_dim=8)
    with pytest.raises(ValueError, match="kinds: mamba, attention, moe, kda"):
        ModelConfig(**base, layer_types=("kda", "gdn"))
    cfg = ModelConfig(**base, layer_types=("attention", "kda"), ssm_heads=1,
                      ssm_head_dim=8, ssm_state=8)
    assert (cfg.kv_layers, cfg.state_layers, cfg.ssm_conv_dim) == (1, 1, 24)


def _primitives(jaxpr) -> list[str]:
    """The primitives of a traced program in order, sub-programs in place:
    what a change to the forward moves, whatever the variables are called."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    names.extend(_primitives(inner))
    return names


#: the decode step and a mixed step of the three models whose code this one
#: shares, traced at the PARENT of PR 45: (equations, a digest of the
#: primitives in order). A PR that changes one of them on purpose re-pins it
#: (kimi's: PR 49, whose latent decode kernel walks a row's pages inside one
#: program, so the work list in front of the scan went and the kernel's body
#: holds the trips' copies, waits and key blocks; they were (1379,
#: "57088846ee5f55da") and (1703, "a079161fd346b428"); and PR 53, whose
#: ragged latent kernel walks a q-block's pages the same way, through one
#: ``_Walk`` with the decode kernel's and with the bookkeeping in ``lax``
#: primitives; they were (4215, "761c459152d47736") and (4539,
#: "0c4f9bc2b1332b41"); granite's and nemotron's MIXED steps: PR 55, whose
#: ragged K/V kernel walks a q-block's pages inside one program through
#: that same ``_Walk`` (now ``ops/page_walk.py``), so its body holds the
#: trips' copies, waits and key blocks and the spans are worked out in front
#: of the call; they were (3465, "f7ce34d2268058cb") and (3225,
#: "c9671f2367cb4e41"). Their decode steps, and both of kimi's, held there:
#: the shared walk moved modules and traces to what it traced to; granite's
#: and nemotron's decode AND mixed steps: PR 57, whose K/V decode kernel
#: walks a row's pages inside one program through that ``_Walk`` too, so the
#: work list in front of the scan over layers went and the kernel's body
#: holds the trips' copies, waits and key blocks; they were (2786,
#: "2e0c6431a4b078a3") / (3865, "191fd682605baa0e") and (2546,
#: "c1f6a78f45ff9309") / (3625, "91b1392f16fa319b"); kimi's two held)
TRACED_AT_THE_PARENT = {
    "tiny-granite-hybrid-4l": [(2919, "b1a166d8016957ef"),
                               (3996, "e1c1abd1c6861aa7")],
    "tiny-nemotron-h-share4-8l": [(2679, "a28e024a7ae83f03"),
                                  (3756, "6733ae1807696238")],
    "tiny-kimi-share4": [(3881, "4800cad6e270bfdb"),
                         (5451, "d4074a4d09f51c13")],
}


@pytest.mark.parametrize("name", list(TRACED_AT_THE_PARENT))
def test_the_models_that_share_this_code_trace_as_they_did(name):
    """granite_hybrid gained a seam (``mixer=``, a gate where the tree holds
    ``w_gate``) and ``models/configs.py`` a kind: granite's, nemotron's and
    kimi's forwards trace to the programs they traced to before."""
    import hashlib

    cfg = get_config(name)
    module = {"granite_hybrid": granite_hybrid, "nemotron_h": nemotron_h,
              "kimi_k2": kimi_k2}[cfg.architecture]
    params = jax.eval_shape(
        lambda: init_params_quantized(cfg, jax.random.PRNGKey(0)))
    rows, page, pmax = 2, 16, 4
    pool = PrefixKVPool(cfg, num_pages=rows * pmax + 1, page_size=page,
                        state_slots=rows if cfg.has_state else 0)
    kw = {"state": pool.state} if cfg.has_state else {}
    table = jnp.asarray(1 + np.arange(rows * pmax).reshape(rows, pmax),
                        jnp.int32)
    rope = rope_tables(cfg, page * pmax)
    decode = jax.make_jaxpr(
        lambda p, pools: module.forward_paged_decode(
            p, cfg, jnp.zeros((rows, 1), jnp.int32), pools, table,
            jnp.full((rows,), 5, jnp.int32), rope, interpret=True, **kw))(
                params, pool.pools)
    mixed = jax.make_jaxpr(
        lambda p, pools: module.forward_paged_mixed(
            p, cfg, jnp.zeros((1, 16), jnp.int32), pools, table,
            jnp.asarray([16], jnp.int32), jnp.asarray([9], jnp.int32), rope,
            interpret=True, rows=jnp.asarray([1]),
            decode=llama.DecodeGroup(
                jnp.zeros((rows,), jnp.int32),
                jnp.asarray([7, 0], jnp.int32),
                jnp.asarray([True, False])), **kw))(params, pool.pools)
    got = []
    for traced in (decode, mixed):
        names = _primitives(traced.jaxpr)
        got.append((len(names), hashlib.sha256(
            " ".join(names).encode()).hexdigest()[:16]))
    assert got == TRACED_AT_THE_PARENT[name], got
