"""``models/nemotron_h.py`` against the plain reference
(``benchmark/nemotron_h_reference.py``: float32, ``highest``, no cache, the
recurrence one token at a time, attention by the formula, the held experts by
a loop) at ``tiny-nemotron-h-share4`` (``MEME*EME`` twice; experts 4-7 of 16,
half the vocabulary) on seeded weights: prefill in chunks and then decode,
through pages in the attention layers and state in the mamba layers, compared
on logits.

The number compared is the judge's: per logits row rms(program - reference) /
std(reference), the reference computing with the experts the program chose
(routing is discontinuous: ``benchmark/adapters/nemotron_h.py``). In bfloat16
through 8-16 layers of 64 channels the rows read 0.01-0.03 over seeds, the
tolerance is 0.06, and computing one precision lower reads ten times that."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import nemotron_h_reference as reference
from benchmark import nemotron_h_weights
from cyberfabric_core_tpu.models import ModelConfig, get_config, llama, nemotron_h
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool, state_copy_row
from cyberfabric_core_tpu.runtime.quant import (dequantize_weight,
                                                init_params_quantized,
                                                quantize_llama_params,
                                                quantized_bytes)
from granite_hybrid_helpers import PagedRun
from nemotron_h_helpers import published

TOLERANCE = 0.06
FULL = get_config("tiny-nemotron-h-share4")          # 16 layers
CFG = get_config("tiny-nemotron-h-share4-8l")        # its first period
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
            chosen = np.zeros((cfg.moe_layers, PAD, cfg.experts_per_token),
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


def _worst(got, ref):
    return max(float(np.sqrt(((got[k] - ref[k]) ** 2).mean()) / ref[k].std())
               for k in got)


def _weights(seed, cfg=CFG):
    return nemotron_h_weights.make_weights(published(cfg), seed,
                                           cfg.num_layers)


def _run(cfg, weights, rows):
    return PagedRun(cfg, weights, rows=rows, module=nemotron_h)


@pytest.mark.parametrize("seed,cfg", [(1, FULL), (2147484001, CFG)])
def test_int8_program_equals_the_reference_on_int8_grid_weights(seed, cfg):
    """Chunks of 16 (two mamba chunks of 8 each), a prompt that ends inside a
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
    """The tolerance tells precisions apart (the reference at float8
    activations, and the program on int4-grid weights, are over it; the
    state in bfloat16 is under it by far), and where no score is within a
    rounding of the next, the experts the program chose ARE the reference's:
    at least 95% of tokens a layer."""
    weights = _weights(3)
    seqs = _seqs(3)
    run = _run(CFG, weights, 3)
    run.run(seqs, LENS, STEPS)
    exact, _ = _reference_rows(weights, seqs, None)
    fp8, _ = _reference_rows(weights, seqs, None, lower="fp8")
    assert _worst(fp8, exact) > 2 * TOLERANCE
    bf16_state, _ = _reference_rows(weights, seqs, None, lower="state_bf16")
    assert _worst(bf16_state, exact) < TOLERANCE / 4
    low = _run(CFG, nemotron_h_weights.to_int4_grid(weights), 3)
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


def _plain(node, dtype=jnp.bfloat16):
    """An int8 tree dequantised to ``dtype``: the SAME numbers, unquantised."""
    if isinstance(node, dict) and "q" in node:
        return dequantize_weight(node, dtype)
    if isinstance(node, dict) and "qe" in node:
        return (node["qe"].astype(jnp.float32)
                * node["se"][:, None]).astype(dtype)
    if isinstance(node, dict):
        return {k: _plain(v, dtype) for k, v in node.items()}
    return node


def test_bf16_program_equals_the_reference():
    """The unquantised tree (``init_params``, bfloat16): the reference is
    handed the tree quantised and the program the SAME quantised tree
    dequantised to bfloat16."""
    tree = quantize_llama_params(
        nemotron_h.init_params(CFG, jax.random.PRNGKey(5)), bits=8)
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
    assert chosen.shape == (CFG.moe_layers, 4 + 16, CFG.experts_per_token)
    counts = {k: int(run.aux[k]) for k in nemotron_h.STEP_COUNTERS}
    assert counts["assignments"] == chosen.size == (4 + 16) * 3 * 4
    assert counts["local"] == int(((chosen >= 4) & (chosen < 8)).sum())
    assert counts["touched"] == sum(
        len(set(layer.ravel()) & {4, 5, 6, 7}) for layer in chosen)
    assert counts["decode_local"] == 0          # a mixed step is no decode
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
    assert int(cold.aux["decode_local"]) == int(cold.aux["local"])
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
    """The up-projection is linear: the four chips' routed parts (each chip's
    held experts' sum in the latent, through the up-projection every chip
    holds), with the shared expert counted ONCE, are the uncut reference's
    whole expert layer. In float32, so that nothing but the split shows."""
    uncut = dataclasses.replace(CFG, experts_held=0, expert_offset=0)
    tree = _weights(7, uncut)
    assert tree["moe"]["moe_up"]["q"].shape[:2] == (uncut.moe_layers, 16)
    x = jax.random.normal(jax.random.PRNGKey(7), (24, uncut.hidden_size),
                          jnp.float32)
    kw = {**_kw(uncut), "expert_layer_only": True}
    whole, _ = reference.forward_logits(tree, x, None, **kw)

    def held(lo, hi):
        moe = {**tree["moe"], **{
            name: {k: v[:, lo:hi] for k, v in tree["moe"][name].items()}
            for name in ("moe_up", "moe_down")}}
        return {**tree, "moe": moe}

    with jax.default_matmul_precision("highest"):
        shared = reference._relu2(
            x @ reference._dequant(tree["moe"]["shared_up"], 0)
        ) @ reference._dequant(tree["moe"]["shared_down"], 0)
    total = -3.0 * np.asarray(shared)       # each share adds the shared expert
    for offset in (0, 4, 8, 12):
        cfg = dataclasses.replace(uncut, experts_held=4, expert_offset=offset)
        stack = _plain(held(offset, offset + 4)["moe"], jnp.float32)
        small = {k: v[0] for k, v in stack.items()
                 if k not in llama.MOE_LEAVES}
        moe = {k: stack[k] for k in ("moe_up", "moe_down")}
        out, top_idx, counts = nemotron_h._experts(
            small, moe, 0, jnp.zeros((1, 24, uncut.hidden_size)), x[None], cfg)
        total = total + np.asarray(out[0])
        assert int(counts[1]) == int(
            ((top_idx >= offset) & (top_idx < offset + 4)).sum())
    scale = float(np.asarray(whole).std())
    assert float(np.abs(total - np.asarray(whole)).max()) < 1e-4 * scale
    # and one share alone is NOT the layer: the split is seen
    assert float(np.abs(np.asarray(out[0]) - np.asarray(whole)).max()) \
        > 0.05 * scale


def test_the_experts_counted_are_the_experts_chosen():
    """``aux["experts"]`` is [expert layers, tokens, K] in layer order over
    the router's 16, and the counters are a count by hand of it."""
    weights = _weights(6)
    run = _run(CFG, weights, 3)
    ids = np.stack([np.resize(s, 16) for s in _seqs(6)])
    run.mixed_step(ids, [0, 0, 0], [16, 16, 5])
    chosen = np.asarray(run.aux["experts"])
    assert chosen.shape == (CFG.moe_layers, 3 * 16, CFG.experts_per_token)
    assert chosen.max() > 7 and chosen.min() < 4        # all 16 are routed
    assert all(len(set(row)) == CFG.experts_per_token
               for row in chosen.reshape(-1, CFG.experts_per_token))
    assert int(run.aux["assignments"]) == chosen.size
    mine = (chosen >= 4) & (chosen < 8)
    assert int(run.aux["local"]) == int(mine.sum())
    assert int(run.aux["touched"]) == sum(
        len(np.unique(layer[m])) for layer, m in zip(chosen, mine))
    run.decode(ids[:, :1], [16, 16, 5])
    chosen = np.asarray(run.aux["experts"])
    assert chosen.shape == (CFG.moe_layers, 3, CFG.experts_per_token)
    assert int(run.aux["decode_local"]) == int(run.aux["local"]) \
        == int(((chosen >= 4) & (chosen < 8)).sum())


def test_the_caches_and_the_expert_stack_are_as_deep_as_their_kinds():
    """The pool arrays have ``kv_layers`` layers, the slab ``state_layers``,
    the expert stack ``moe_layers``; the configuration's byte functions agree
    with the arrays' ``nbytes``; the served cut is 2, 10 and 10 of 22."""
    assert (FULL.kv_layers, FULL.state_layers, FULL.moe_layers,
            FULL.num_layers) == (2, 6, 8, 16)
    assert (CFG.kv_layers, CFG.state_layers, CFG.moe_layers) == (1, 3, 4)
    pool = PrefixKVPool(FULL, num_pages=9, page_size=16, state_slots=3,
                        state_snapshots=2)
    assert pool.k_pool.shape == (2, 9, 16, FULL.num_kv_heads * FULL.head_dim)
    assert pool.state["ssm"].shape == (6, 5, FULL.ssm_heads,
                                       FULL.ssm_head_dim, FULL.ssm_state)
    assert pool.state["conv"].shape == (6, 5, 3, FULL.ssm_conv_dim)
    assert pool.pool_bytes() == 9 * 16 * FULL.cache_bytes_per_token(2) \
        == pool.k_pool.nbytes + pool.v_pool.nbytes
    assert pool.state_bytes() == 5 * FULL.state_bytes_per_row() \
        == sum(v.nbytes for v in pool.state.values())
    stats = pool.stats()
    assert (stats["kv_layers"], stats["state_layers"],
            stats["model_layers"]) == (2, 6, 16)
    params = init_params_quantized(FULL, jax.random.PRNGKey(0))
    assert params["moe"]["moe_up"]["q"].shape == (8, 4, 32, 24)
    assert params["moe"]["moe_down"]["q"].shape == (8, 4, 24, 32)
    assert "moe_gate" not in params["moe"]
    assert params["layers"]["norm"].shape == (16, 64)
    # the int8 tree's matrices and scales by kind, to the byte
    by_kind = FULL.weight_bytes(1)

    def nbytes(tree, names):
        return sum(quantized_bytes(tree[n]) for n in names)

    assert by_kind["mamba"] == nbytes(params["mamba"], ("ssm_in", "ssm_out"))
    assert by_kind["attention"] == quantized_bytes(params["attention"])
    assert by_kind["experts"] == nbytes(params["moe"], ("moe_up", "moe_down"))
    assert by_kind["moe_dense"] == nbytes(params["moe"], (
        "router", "router_bias", "latent_down", "latent_up", "shared_up",
        "shared_down"))
    assert by_kind["vocab"] == quantized_bytes(params["embed"]) \
        + quantized_bytes(params["lm_head"])
    served = get_config("nemotron-3-super-share4-22l")
    assert (served.kv_layers, served.state_layers, served.moe_layers) == (
        2, 10, 10)
    assert published(served)["hybrid_override_pattern"] \
        == "MEMEMEM*EMEMEMEM*EMEME"
    assert served.cache_bytes_per_token() == 2 * 1024
    assert served.state_bytes_per_row() == 10 * 4 * (128 * 64 * 128
                                                     + 3 * 10240)
    assert (served.experts_local, served.vocab_rows) == (128, 32768)
    assert 9.0e9 < sum(served.weight_bytes(1).values()) < 9.2e9
    full = get_config("nemotron-3-super-120b-a12b")
    assert (full.kv_layers, full.state_layers, full.moe_layers) == (8, 40, 40)
    assert [i for i, k in enumerate(full.layer_types)
            if k == "attention"] == [7, 16, 25, 36, 47, 58, 69, 78]
    # granite's "an expert layer after every layer" is its architecture's
    granite = get_config("granite-4.0-h-small")
    assert granite.moe_layers == granite.num_moe_layers == 40
    assert get_config("kimi-k2.5").moe_layers == 60
    assert get_config("tiny-llama").moe_layers == 0


def test_depth_is_runs_of_a_repeated_unit():
    """``MEME*EME`` twice is 8 scans; the served 22 layers are 7 scans of 10
    sub-layer bodies, not 22; every layer is in exactly one run, at its
    index among the layers of its kind."""
    def shape(cfg):
        return [("".join(k[0] for k in unit), first, reps)
                for unit, first, _, reps in nemotron_h.layer_runs(cfg)]

    assert shape(FULL) == [("mm", 0, 2), ("a", 4, 1), ("mm", 5, 3),
                           ("m", 11, 1), ("a", 12, 1), ("m", 13, 1),
                           ("m", 14, 1), ("m", 15, 1)]
    served = get_config("nemotron-3-super-share4-22l")
    runs = nemotron_h.layer_runs(served)
    assert [(len(u), r) for u, _, _, r in runs] == [
        (2, 3), (1, 1), (1, 1), (2, 4), (1, 1), (2, 2), (1, 1)]
    assert sum(len(u) for u, _, _, _ in runs) == 10
    assert len(nemotron_h.layer_runs(
        get_config("nemotron-3-super-120b-a12b"))) < 30
    for cfg in (FULL, served):
        seen, at = {"mamba": 0, "attention": 0, "moe": 0}, 0
        for unit, first, first_of, reps in nemotron_h.layer_runs(cfg):
            assert first == at and first_of == seen
            assert cfg.layer_types[at: at + reps * len(unit)] == unit * reps
            for kind in unit:
                seen[kind] += reps
            at += reps * len(unit)
        assert at == cfg.num_layers


def test_quantised_init_and_param_count():
    params = init_params_quantized(CFG, jax.random.PRNGKey(0))
    assert params["mamba"]["ssm_in"]["q"].shape == (3, 64, CFG.ssm_proj_dim)
    assert params["mamba"]["ssm_in"]["q"].dtype == jnp.int8
    assert params["attention"]["wq"]["q"].shape == (1, 64, 64)
    assert params["attention"]["wk"]["q"].shape == (1, 64, 16)
    assert params["moe"]["latent_down"]["q"].shape == (4, 64, 32)
    assert params["moe"]["shared_down"]["q"].shape == (4, 48, 64)
    assert params["moe"]["router"].shape == (4, 64, 16)
    assert params["moe"]["router"].dtype == jnp.float32
    assert params["lm_head"]["q"].shape == (64, 256)
    assert params["embed"]["qe"].shape == (256, 64)
    for small in ("conv_w", "conv_b", "A_log", "D", "dt_bias", "ssm_norm"):
        assert params["mamba"][small].dtype == jnp.float32, small
        assert params["mamba"][small].shape[0] == 3
    same = quantize_llama_params(
        nemotron_h.init_params(CFG, jax.random.PRNGKey(0)), bits=8)
    assert jax.tree.structure(same) == jax.tree.structure(params)
    uncut = dataclasses.replace(CFG, experts_held=0, expert_offset=0,
                                vocab_held=0)
    leaves = jax.tree.leaves(
        nemotron_h.init_params(uncut, jax.random.PRNGKey(0)))
    assert sum(x.size for x in leaves) == uncut.param_count()
    big = get_config("nemotron-3-super-120b-a12b")
    assert 120.6e9 < big.param_count() < 120.8e9          # 120B-A12B
    # active a token: everything but the experts not chosen and the
    # embedding table (a gather of one row): 12.23 B
    idle = big.moe_layers * (big.num_experts - big.experts_per_token) * (
        2 * big.moe_latent_size * big.intermediate_size) \
        + big.vocab_size * big.hidden_size
    assert 12.2e9 < big.param_count() - idle < 12.3e9


def test_a_mesh_is_refused_with_a_line():
    with pytest.raises(ValueError, match="nemotron_h serves on one device"):
        nemotron_h.forward_paged_decode(
            None, CFG, None, None, None, None, None, mesh=object(), state=None)


def test_unknown_kinds_and_activations_are_refused_by_name():
    base = dict(name="x", architecture="nemotron_h", vocab_size=8,
                hidden_size=8, intermediate_size=8, num_layers=2, num_heads=1,
                num_kv_heads=1, head_dim=8)
    with pytest.raises(ValueError, match="kinds: mamba, attention, moe"):
        ModelConfig(**base, layer_types=("mamba", "mlp"))
    with pytest.raises(ValueError, match="relu2"):
        ModelConfig(**base, hidden_act="relu")
    assert ModelConfig(**base, hidden_act="relu2",
                       layer_types=("moe", "moe")).moe_layers == 2
