"""``tiny-laguna`` (models/laguna.py) against the benchmark's plain reference
(benchmark/laguna_reference.py: imports nothing from the program, has no
cache and masks the window): the full forward; prefill in chunks and decode
through BOTH page groups of K/V pages with window pages freed and written
again on the way; which array each layer writes; the reference's three
controls of this architecture; the share test; the stack with its mechanisms
off is ``models/llama.py``'s; the int8 tree beside the float tree; the tree,
the plan and the counts of the published configuration."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import laguna_reference as reference
from benchmark.adapters import laguna as adapter
from cyberfabric_core_tpu.models import get_config, laguna, llama, motif
from cyberfabric_core_tpu.models.llama import moe_experts, moe_route
from cyberfabric_core_tpu.ops.rope import rope_tables
from cyberfabric_core_tpu.runtime.paged import PrefixKVPool

CONF = json.loads((Path(__file__).resolve().parents[1] / "benchmark/tests"
                   / "rehearsal/configs/tiny-laguna.json").read_text())
# a table of 32 pages a row: the interpreted kernels walk every slot of it
CONF["serving"]["max_seq_len"] = 128
CFG = get_config("tiny-laguna-share4")
PAGE, CHUNK, WINDOW = CONF["serving"]["page"], 32, CONF["sliding_window"]
#: a dense full layer, three window layers, a full expert layer, a window
#: layer: every body ``motif.layer_plan`` cuts the served stack into
DEPTH = 6
#: worst logits row rms(program - reference) / std(reference), bfloat16
#: activations and pages against float32: 0.017-0.028 over the scenario's 27
#: rows at these widths (hidden 64: a rounding is a larger share of a logit
#: than at 3072, where the judge reads a third of it); the reference one
#: precision lower reads 0.7
LIMIT = CONF["correctness"]["limit"]


def _rms(got, want):
    return float(np.sqrt(((got - want) ** 2).mean()) / want.std())


def _scenario(w, seed=0, steps=6):
    """The judge's scenario in small: row 0 fresh, its prompt of 53 tokens
    in two chunks (six windows long); row 1 shares row 0's first 24 tokens
    (no match: it prefills them itself) and brings a later chunk; row 2 a
    short prompt, then a decode rider; row 3 idle in every mixed call. Then
    decode steps through both page groups. Returns {(row, position):
    logits}, the sequences, the binding's last state."""
    binding = adapter.bind(CONF, DEPTH, 4)
    rng = np.random.default_rng(seed)
    lens = [CHUNK + 21, 6 * PAGE + 9, 6, 0]
    shared = 6 * PAGE
    seqs = [rng.integers(3, 256, n + steps + 6).astype(np.int32)
            for n in lens]
    seqs[1][:shared] = seqs[0][:shared]
    state = binding.share_prefix(binding.new_state(), 1, 0, shared)
    done = np.array([0, shared, 0, 0], np.int32)
    got = {}
    for call in range(2):
        q = np.zeros(4, np.int32)
        for r in range(3):
            if r == 1 and call == 0:
                continue
            left = lens[r] - done[r]
            q[r] = min(left, CHUNK) if left > 0 else (r == 2)
        ids = np.zeros((4, CHUNK), np.int32)
        for r in range(4):
            ids[r, : q[r]] = seqs[r][done[r]: done[r] + q[r]]
        idle_before = binding.row_state(state, 3)
        last, state = binding.mixed(w, ids, state, done, q)
        assert np.array_equal(idle_before, binding.row_state(state, 3))
        logits = binding.logits(w, last)
        for r in range(3):
            done[r] += q[r]
            if q[r] and done[r] >= lens[r]:
                got[(r, int(done[r]) - 1)] = logits[r]
    for _ in range(steps):
        ids = np.asarray([[seqs[r][done[r]]] for r in range(4)], np.int32)
        last, state = binding.decode(w, ids, state, done)
        logits = binding.logits(w, last)
        for r in range(4):
            got[(r, int(done[r]))] = logits[r]
            done[r] += 1
    return got, [s[: done[r]] for r, s in enumerate(seqs)], state


def _worst(got, seqs, w, ref, lower=None, rows=range(4)):
    worst = 0.0
    for r in rows:
        at = sorted(p for rr, p in got if rr == r)
        want = ref(w, seqs[r], np.asarray(at), lower=lower)
        worst = max([worst] + [_rms(got[(r, p)], row)
                               for p, row in zip(at, want)])
    return worst


@pytest.fixture(scope="module")
def judged():
    w = adapter.make_weights(CONF, 7, DEPTH)
    got, seqs, state = _scenario(w)
    return w, got, seqs, state


def test_chunked_prefill_and_decode_through_both_page_groups(judged):
    """Every logits row of the scenario against a whole forward of the
    reference, with window pages given back and handed out AGAIN on the way
    (the binding's pages are the program's own pool's, its window group kept
    short), over rows many windows long."""
    w, got, seqs, state = judged
    assert len(got) >= 3 + 4 * 6
    assert len(seqs[0]) > 3 * WINDOW and len(seqs[1]) > 3 * WINDOW
    assert state["reused"] >= 10                 # freed pages, written again
    assert not any(state["wchains"][0][:12])     # row 0 gave its first back
    assert all(state["chains"][0])               # and kept its full chain
    assert _worst(got, seqs, w, adapter.reference_logits(CONF, DEPTH)) < LIMIT


@pytest.mark.parametrize("control", ["no_window", "one_rope", "no_head_gate"])
def test_the_reference_without_a_mechanism_is_another_model(judged, control):
    """The three controls of this architecture: the reference with its
    window layers attending over everything, rotated with the full layers'
    tables, or ungated, reads far over the limit against the program."""
    w, got, seqs, _ = judged
    assert _worst(got, seqs, w, adapter.reference_logits(CONF, DEPTH),
                  lower=control, rows=[1]) > 4 * LIMIT


def _float32(tree):
    """The seeded tree with its bfloat16 norms as float32: the program then
    carries float32 activations over the SAME stored numbers."""
    return jax.tree.map(
        lambda x: x.astype(jnp.float32) if x.dtype == jnp.bfloat16 else x,
        tree)


def test_the_full_forward_is_the_references():
    """``laguna.forward`` (no cache, the window a mask, float32 activations
    over the int8 tree) against the reference given the experts it chose:
    1e-3 of a logit's spread, float32 sums in another order."""
    w = _float32(adapter.make_weights(CONF, 11, DEPTH))
    cfg = CFG.cut_to(DEPTH)
    ids = np.random.default_rng(1).integers(3, 256, 90).astype(np.int32)
    hidden, aux = jax.jit(lambda w, ids: laguna.forward(
        w, cfg, ids, rope_tables(cfg, 128)))(w, jnp.asarray(ids[None]))
    got = np.asarray(laguna.lm_head_logits(w, cfg, hidden[0]))
    assert aux["experts"].shape == (5, 90, 3)
    pad = np.zeros(128, np.int32)
    pad[:90] = ids
    choices = np.zeros((5, 128, 3), np.int32)
    choices[:, :90] = np.asarray(aux["experts"])
    want, short = reference.forward_logits(
        w, jnp.asarray(pad), jnp.arange(90), jnp.asarray(choices),
        **reference.reference_kwargs(CONF, DEPTH))
    assert float(np.asarray(short)[:, :90].max()) < 1e-4
    assert max(_rms(g, r) for g, r in zip(got, np.asarray(want))) < 1e-3


# ------------------------------------------------- which array a layer writes
_ONE_CHUNK = {}


def _pools_after_one_chunk(w, break_placement=False):
    """One fresh row's 24 tokens through one mixed call on a pool of its
    own: the four arrays, the row's pages."""
    cfg = CFG.cut_to(DEPTH)
    pool = PrefixKVPool(cfg, num_pages=17, page_size=PAGE, window_pages=17,
                        dtype=jnp.float32)
    chain = pool.extend_chain([], 24)
    wchain = pool.extend_window([], 24)
    table = np.zeros((1, 32), np.int32)
    table[0, :6], table[0, 16:22] = chain, wchain
    ids = np.random.default_rng(2).integers(3, 256, (1, 32)).astype(np.int32)
    if break_placement not in _ONE_CHUNK:   # one trace a variant
        _ONE_CHUNK[break_placement] = jax.jit(
            lambda w, ids, pools, table: laguna.forward_paged_mixed(
                w, cfg, ids, pools, table, jnp.zeros(1, jnp.int32),
                jnp.asarray([24]), rope_tables(cfg, 64))[1])
    written = laguna._written
    if break_placement:     # a window layer's K and V into the full group
        laguna._written = lambda pools, full, *a: written(pools, True, *a)
    try:
        pools = _ONE_CHUNK[break_placement](
            w, jnp.asarray(ids), pool.pools, jnp.asarray(table))
    finally:
        laguna._written = written
    return [np.asarray(p) for p in pools], chain, wchain


def _slots_written(pools, chain, wchain):
    """(full group, window group): per layer slot, whether the row's 24
    tokens hold K AND V and nothing else of the array does."""
    def one(k, v, pages):
        rows = np.zeros(k.shape[1], bool)
        rows[pages] = True
        return [bool(np.abs(k[i][rows]).min() > 0
                     and np.abs(v[i][rows]).min() > 0
                     and not k[i][~rows][1:].any()) for i in range(len(k))]

    return one(pools[0], pools[1], chain), one(pools[2], pools[3], wchain)


def test_full_layers_write_k_pool_and_window_layers_the_window_pair():
    """A direct read of the pools: the 2 full layers' K and V of the row's
    tokens lie in ``k_pool`` / ``v_pool`` at its chain's pages, the 4 window
    layers' in the window pair at its window chain's, each layer in its own
    slot (a layer's matrices set to zero empty exactly that slot). A program
    that wrote a window layer into the full group is caught."""
    w = _float32(adapter.make_weights(CONF, 5, DEPTH))
    pools, chain, wchain = _pools_after_one_chunk(w)
    assert [p.shape[0] for p in pools] == [2, 2, 4, 4]
    assert _slots_written(pools, chain, wchain) == ([True] * 2, [True] * 4)
    slots = [np.abs(p[i]).sum() for p in (pools[0], pools[2])
             for i in range(len(p))]
    assert len({round(float(s), 3) for s in slots}) == 6    # six layers' K
    for kind, i in (("full", 1), ("window", 2)):
        muted = jax.tree.map(lambda x: x, w)
        for name in ("wk", "wv"):
            muted[kind][name] = {**w[kind][name],
                                 "s": w[kind][name]["s"].at[i].set(0.0)}
        full, window = _slots_written(*_pools_after_one_chunk(muted))
        assert (full, window) == (
            [True, kind != "full"],
            [True, True, kind != "window", True]), kind
    broken = _slots_written(*_pools_after_one_chunk(w, break_placement=True))
    assert broken[1] == [False] * 4


# ------------------------------------------------------------- the share test
def test_the_shares_add_up_to_the_uncut_layer():
    """THE SHARE TEST. At 2 shares of 8 experts: the routed part each
    share's expert layer gives (``moe_experts`` under ``experts_held`` 4 at
    offsets 0 and 4), plus the shared expert counted ONCE, is the uncut
    layer: every expert computed under a gate that is zero off the chosen,
    gates ``2.5 s / sum`` over all the chosen of a softmax over all 8."""
    full = get_config("tiny-laguna")
    H, I, E, K = (full.hidden_size, full.expert_width, full.num_experts,
                  full.experts_per_token)
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(keys[0], (40, H), jnp.float32)
    router = jax.random.normal(keys[1], (H, E), jnp.float32) * H ** -0.5
    gate, up = (jax.random.normal(k, (1, E, H, I), jnp.float32) * H ** -0.5
                for k in keys[3:5])
    down = jax.random.normal(keys[5], (1, E, I, H), jnp.float32) * I ** -0.5
    sg, su = (jax.random.normal(k, (H, I), jnp.float32) * H ** -0.5
              for k in keys[6:8])
    sd = jax.random.normal(keys[2], (I, H), jnp.float32) * I ** -0.5
    gamma = full.routed_scaling_factor
    assert gamma == 2.5

    with jax.default_matmul_precision("highest"):
        top_idx, gates = moe_route(x, router, K, scale=gamma)
        np.testing.assert_allclose(np.asarray(gates).sum(1), 2.5, rtol=1e-6)
        parts = []
        for offset in range(0, E, 4):
            cfg = dataclasses.replace(full, experts_held=4,
                                      expert_offset=offset)
            held = {"moe_gate": gate[:, offset: offset + 4],
                    "moe_up": up[:, offset: offset + 4],
                    "moe_down": down[:, offset: offset + 4]}
            parts.append(np.asarray(jax.jit(
                lambda held, cfg=cfg: moe_experts(x, top_idx, gates, held,
                                                  cfg, 0))(held)))
        shared = (jax.nn.silu(x @ sg) * (x @ su)) @ sd
        s = jax.nn.softmax(x @ router, axis=-1)
        _, chosen = jax.lax.top_k(s, K)
        s_top = jnp.take_along_axis(s, chosen, 1)
        g = jnp.zeros_like(s).at[jnp.arange(40)[:, None], chosen].set(
            gamma * s_top / s_top.sum(1, keepdims=True))
        want = shared + sum(
            g[:, e: e + 1] * ((jax.nn.silu(x @ gate[0, e]) * (x @ up[0, e]))
                              @ down[0, e]) for e in range(E))
    assert all(np.abs(p).max() > 0 for p in parts)     # every share has work
    np.testing.assert_allclose(sum(parts) + np.asarray(shared),
                               np.asarray(want), rtol=2e-4, atol=2e-5)


# ------------------------------------------------ the stack, mechanisms off
def test_with_its_mechanisms_off_the_stack_is_llamas():
    """``window_num_heads = num_heads``, no window, no gate, one pair of
    rotary tables and no experts: ``laguna.forward`` equals ``llama.forward``
    on the same tree (float32, the same sums: 1e-5 of a hidden's spread)."""
    plain = dataclasses.replace(
        get_config("tiny-laguna"), name="tiny-laguna-plain", num_layers=3,
        window_num_heads=0, sliding_window=None, sliding_window_period=0,
        head_gate=False, window_rope_theta=0.0, num_experts=0,
        first_k_dense=0)
    params = jax.jit(lambda key: laguna.init_params(plain, key, jnp.float32))(
        jax.random.PRNGKey(3))
    assert params["window"]["wq"].shape[0] == 0 and "layers" not in params
    assert "w_gate" not in params["full"]
    same = {"embed": params["embed"], "final_norm": params["final_norm"],
            "lm_head": params["lm_head"],
            "layers": {**params["full"], **params["dense"]}}
    rope = rope_tables(plain, 64)
    assert len(rope) == 2 and rope[0].shape == (64, 8)  # half a head rotated
    ids = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 512)
    got, _ = jax.jit(lambda p: laguna.forward(p, plain, ids, rope))(params)
    positions = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    as_llama = dataclasses.replace(plain, architecture="llama")
    want, _ = jax.jit(lambda p: llama.forward(
        p, as_llama, ids, positions,
        llama.init_cache(as_llama, 2, 40, jnp.float32),
        jnp.zeros((2,), jnp.int32), rope))(same)
    assert _rms(np.asarray(got), np.asarray(want)) < 1e-5


def _float_and_int8(cfg, run=lambda f, *a: f(*a)):
    from cyberfabric_core_tpu.runtime.quant import quantize_llama_params

    params = run(lambda key: laguna.init_params(cfg, key, jnp.float32),
                 jax.random.PRNGKey(0))
    return params, run(quantize_llama_params, params)


def test_the_int8_tree_is_the_seeded_trees_layout():
    """What ``quantize_llama_params`` makes of this architecture's float
    tree, by shapes alone (nothing compiles): int8 under both attention
    stacks' names and the experts', the gate a head too, the router float32,
    leaf for leaf the layout of the benchmark's seeded tree, which the
    scenario above runs against the reference. The quantiser's error over
    the forward is the slow case below."""
    _, quantised = _float_and_int8(CFG.cut_to(2), jax.eval_shape)
    assert quantised["window"]["w_gate"]["q"].dtype == jnp.int8
    assert quantised["full"]["wq"]["q"].shape == (1, 64, 6 * 32)
    assert quantised["window"]["wq"]["q"].shape == (1, 64, 9 * 32)
    assert quantised["layers"]["moe_up"]["q"].dtype == jnp.int8
    assert quantised["layers"]["router"].dtype == jnp.float32
    seeded = jax.eval_shape(lambda: adapter.make_weights(CONF, 7, 2))
    assert jax.tree.structure(quantised) == jax.tree.structure(seeded)
    assert jax.tree.map(lambda x: x.shape, quantised) == \
        jax.tree.map(lambda x: x.shape, seeded)


@pytest.mark.slow   # four compiles for a number tests/test_quant.py holds
def test_the_int8_tree_answers_as_the_float_tree():
    """The tolerance ``tests/test_quant.py`` holds llama's int8 tree to: the
    last position's logits of the quantised tree correlate above 0.99 with
    the float tree's."""
    cfg = get_config("tiny-laguna").cut_to(2)   # dense + full, expert + window
    params, quantised = _float_and_int8(cfg, lambda f, *a: jax.jit(f)(*a))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 3, 512)
    rope = rope_tables(cfg, 64)

    @jax.jit
    def logits(p):
        h, _ = laguna.forward(p, cfg, ids, rope)
        return laguna.lm_head_logits(p, cfg, h[0, -1])

    assert np.corrcoef(logits(params), logits(quantised))[0, 1] > 0.99


# ------------------------------------------------- the tree, plan and counts
def test_the_tree_and_the_plan_are_the_configurations():
    """int8 matrices by kind, float32 router, rows and experts HELD; the
    served share's stack is a dense full layer, a run of 3 window layers and
    2 units; about 118 B parameters as published, the window layers at 72
    heads; both page groups' bytes a token."""
    from cyberfabric_core_tpu.parallel.sharding import abstract_params

    # shapes alone (tests/test_laguna_scheduler.py serves the tree that
    # ``init_params_quantized`` draws and holds it to this structure)
    tree = abstract_params(CFG, jnp.bfloat16, "int8")
    assert tree["full"]["wq"]["q"].shape == (2, 64, 6 * 32)
    assert tree["window"]["wq"]["q"].shape == (4, 64, 9 * 32)
    assert tree["window"]["wo"]["q"].shape == (4, 9 * 32, 64)
    assert tree["full"]["w_gate"]["q"].shape == (2, 64, 6)
    assert tree["dense"]["gate"]["q"].shape == (1, 64, 128)
    assert tree["layers"]["moe_gate"]["q"].shape == (5, 4, 64, 32)
    assert tree["layers"]["router"].shape == (5, 64, 8)
    assert tree["layers"]["router"].dtype == jnp.float32
    assert tree["embed"]["qe"].shape == (256, 64)
    served = get_config("laguna-s-2.1-share8-12l")
    assert motif.layer_plan(served) == ([(0, 1), (1, 3)], (4, 2), [])
    assert motif.layer_plan(CFG) == ([(0, 1), (1, 3)], (4, 0),
                                     [(4, 1), (5, 1)])
    assert [served.layer_is_full(i) for i in range(5)] == [
        True, False, False, False, True]
    assert (served.attention_layers, served.window_layers, served.kv_layers,
            served.moe_layers) == (3, 9, 3, 11)
    assert (served.cache_bytes_per_token(), served.window_bytes_per_token()
            ) == (3 * 4096, 9 * 4096)
    assert 4.3e9 < sum(served.weight_bytes(1).values()) < 4.4e9
    big = get_config("laguna-s-2.1")
    assert 117.0e9 < big.param_count() < 118.5e9            # about 118 B
    at_48_heads = dataclasses.replace(big, window_num_heads=0)
    assert big.param_count() - at_48_heads.param_count() == \
        36 * (2 * 3072 * 24 * 128 + 3072 * 24)
    # motif keeps its own reading of which layer of a period is full
    assert [get_config("tiny-motif").layer_is_full(i) for i in range(4)] == [
        False, False, False, True]


def test_rotary_tables_by_layer_kind():
    """Two pairs: the full layers' over HALF a head under YaRN's frequencies
    times the published attention factor (no field states it: it is YaRN's
    0.1 ln(factor) + 1, which the tables derive from ``rope_factor``), the
    window layers' plain over the whole head; ``apply_rope`` with the narrow
    pair leaves the head's second half as it was."""
    from cyberfabric_core_tpu.ops.rope import apply_rope, yarn_inv_freq

    served = get_config("laguna-s-2.1-share8-12l")
    published = json.loads(
        (Path(__file__).resolve().parents[1]
         / "benchmark/configs/laguna-s-2.1-int8.json").read_text())[
             "rope_parameters"]["full_attention"]["attention_factor"]
    assert published == 1.4852030263919618
    (cos_f, sin_f), (cos_w, sin_w) = rope_tables(served, 64)
    assert cos_f.shape == (64, 32) and cos_w.shape == (64, 64)
    np.testing.assert_allclose(cos_f[0], published, rtol=1e-7)
    # and the rehearsal's file states what ``tiny-laguna``'s tables carry
    np.testing.assert_allclose(
        rope_tables(CFG, 8)[0][0][0],
        CONF["rope_parameters"]["full_attention"]["attention_factor"],
        rtol=1e-7)
    np.testing.assert_allclose(cos_w[0], 1.0)
    freq = yarn_inv_freq(64, 500000.0, 128.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose(
        np.asarray(sin_f[3]), published * np.sin(3 * freq),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(cos_w[5]),
        np.cos(5 * 10000.0 ** (-np.arange(0, 128, 2) / 128)), rtol=1e-5,
        atol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 2, 128))
    positions = jnp.arange(4, dtype=jnp.int32)[None]
    out = apply_rope(x, positions, cos_f, sin_f)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    assert not np.allclose(out[0, 1:, :, :64], x[0, 1:, :, :64])


def test_a_per_layer_window_is_refused_where_no_module_serves_it():
    with pytest.raises(ValueError, match="two page groups"):
        dataclasses.replace(get_config("mistral-7b"),
                            sliding_window_period=4)
    with pytest.raises(ValueError, match="need a sliding_window_period"):
        dataclasses.replace(get_config("mistral-7b"), window_num_heads=16)
