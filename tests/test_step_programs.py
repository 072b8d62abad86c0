"""The step programs are a function of their ``ProgramKey``, built once a
process (ISSUE 44): engines of equal key dispatch the same jitted objects,
a field no program reads does not enter the key, the kernels' lowering mode
does, and a program runs from ``runtime/programs.py`` with no engine."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cyberfabric_core_tpu.models import get_config, llama
from cyberfabric_core_tpu.ops.platform import compiled_kernels
from cyberfabric_core_tpu.runtime import EngineConfig
from cyberfabric_core_tpu.runtime.programs import (
    _CTL, LANE_ROWS, ProgramKey, lane_words, serving_rope_tables,
    step_programs)
from cyberfabric_core_tpu.runtime.scheduler import ContinuousBatchingEngine

BASE = dict(model="tiny-llama", max_seq_len=128, max_batch=2, decode_chunk=4,
            prefix_cache_pages=80, prefix_page_size=16,
            prefill_budget_tokens=32)


def _programs(**over):
    """The programs an engine of ``BASE`` with ``over`` dispatches."""
    eng = ContinuousBatchingEngine(EngineConfig(**{**BASE, **over}), seed=0)
    try:
        return (eng._restore_row_fn, eng._paged_decode_fn,
                eng._mixed_step_fn, eng._spec_step_fn)
    finally:
        eng.shutdown()


def _key() -> ProgramKey:
    return ProgramKey(
        model_config=get_config("tiny-llama"), decode_chunk=4, max_seq_len=64,
        n_slots=1, pmax=4, n_cache=2, has_state=False, step_counters=(),
        block=0, attn_mesh=None, spec_k=0)


@pytest.fixture(scope="module")
def base():
    return _programs()


def test_engines_of_equal_key_share_their_programs(base):
    assert all(a is b for a, b in zip(base, _programs()))
    assert base[3] is None          # no speculation, no such program


@pytest.mark.parametrize("over", [
    dict(decode_lookahead=3), dict(tenant_fair=False),
    dict(prefill_budget_tokens=64), dict(prefix_cache_pages=120),
    dict(device_stop_width=4), dict(state_snapshots=0)], ids=str)
def test_a_field_no_program_reads_is_not_in_the_key(base, over):
    """Policy of the host's loop, and widths that only shape an operand
    (JAX keys those by the argument), name the same programs."""
    assert all(a is b for a, b in zip(base, _programs(**over)))


@pytest.mark.parametrize("over", [
    dict(decode_chunk=2), dict(max_seq_len=120), dict(max_batch=3),
    dict(prefix_page_size=32), dict(scheduler_spec_k=2), dict(tp=2),
    dict(model="tiny-qwen2")], ids=str)
def test_engines_that_differ_in_a_key_field_do_not_share(base, over):
    """One field of the key each: ``max_seq_len`` 120 keeps ``pmax`` at 8,
    a page of 32 changes ``pmax`` alone, ``tp`` the attention mesh."""
    other = _programs(**over)
    assert base[1] is not other[1] and base[2] is not other[2]
    assert (other[3] is not None) == ("scheduler_spec_k" in over)


#: another value for every field of the key
OTHER = dict(
    model_config=get_config("tiny-qwen2"), decode_chunk=8, max_seq_len=60,
    n_slots=2, pmax=5, n_cache=3, has_state=True,
    step_counters=("assignments",), block=4, attn_mesh="a mesh", spec_k=2,
    interpret=None)


@pytest.mark.parametrize("field", OTHER)
def test_every_field_of_the_key_names_other_programs(field):
    assert set(OTHER) == {f.name for f in dataclasses.fields(ProgramKey)}
    base, other = _key(), dataclasses.replace(_key(), **{field: OTHER[field]})
    assert step_programs(base) is step_programs(_key())
    assert step_programs(other) is not step_programs(base)
    assert all(a is not b for a, b in zip(step_programs(other)[:3],
                                          step_programs(base)[:3]))


def test_a_key_made_under_compiled_kernels_is_not_the_interpret_one():
    """What an AOT lowering gets was never traced in interpret mode: the
    key reads the kernels' lowering mode where it is made."""
    here = _key()
    with compiled_kernels():
        aot = _key()
    assert here.interpret is (jax.devices()[0].platform != "tpu")
    assert aot.interpret is False
    if here.interpret:
        assert step_programs(aot) is not step_programs(here)
        assert step_programs(aot)[1] is not step_programs(here)[1]


def test_a_program_lowers_and_runs_with_no_engine():
    """``mixed_step`` takes a prompt as its lane and ``paged_decode_chunk``
    four more steps, greedy, from the module alone (no engine, pool, thread
    or worker): the tokens are those of the plain forward over the whole
    sequence, recomputed a token at a time."""
    cfg, page, n_pages, stop_w = get_config("tiny-llama"), 16, 5, 1
    key = _key()
    _, paged_decode_chunk, mixed_step, _ = step_programs(key)
    params = llama.init_params(cfg, jax.random.PRNGKey(3), jnp.float32)
    prompt = np.random.default_rng(2).integers(3, 200, 21).astype(np.int32)
    pools = [jnp.zeros((cfg.num_layers, n_pages, page,
                        cfg.num_kv_heads * cfg.head_dim), jnp.float32)
             for _ in "kv"]         # donated: a buffer each

    rows = np.zeros((1, key.pmax + _CTL + stop_w), np.int32)
    rows[0, : key.pmax] = 1 + np.arange(key.pmax)       # page 0 is scratch
    rows[0, key.pmax + 1] = 60                          # the limit length
    rows[0, key.pmax + 3: key.pmax + _CTL] = np.asarray(
        [0.0, 1.0], np.float32).view(np.int32)          # greedy, top_p 1
    rows[0, key.pmax + _CTL:] = -1                      # no stop id
    width = 24
    lane = np.zeros((lane_words(1, 0, LANE_ROWS, width),), np.int32)
    lane[1:4] = 1, 1, len(prompt)       # sample, final_mask, final_lens
    span = lane[6:]                     # q_ids, q_len, hist, the lane's slot
    span[: len(prompt)], span[width] = prompt, len(prompt)
    carry = (jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32),
             jnp.zeros((1,), bool), jnp.zeros((1, 2), jnp.uint32))

    lowered = mixed_step.lower(params, *pools, rows, lane, *carry)
    assert "mixed_step" in lowered.as_text()[:200]
    toks, *pools, last, keys, lens, fin, active = mixed_step(
        params, *pools, rows, lane, *carry)
    got = [int(toks[0])]
    chunk, *_ = paged_decode_chunk(
        params, *pools, rows, last, lens, active, fin, keys)
    got += np.asarray(chunk)[0].tolist()
    assert np.asarray(lens).tolist() == [len(prompt)]

    rope = serving_rope_tables(cfg, key.max_seq_len)
    want, ids = [], prompt.tolist()
    for _ in range(1 + key.decode_chunk):
        n = len(ids)
        hidden, _ = llama.forward(
            params, cfg, jnp.asarray([ids], jnp.int32),
            jnp.arange(n, dtype=jnp.int32)[None],
            llama.init_cache(cfg, 1, n, jnp.float32),
            jnp.zeros((1,), jnp.int32), rope)
        want.append(int(jnp.argmax(
            llama.lm_head_logits(params, cfg, hidden[:, -1]), axis=-1)[0]))
        ids.append(want[-1])
    assert got == want


def test_every_field_of_the_engine_config_is_read_by_the_package():
    """An ``EngineConfig`` field nothing reads is an option that drives
    nothing: every field is loaded from a configuration (``config.x``,
    ``self.config.x``, ``entry.config.x``, ``eng_cfg.x``) somewhere in the
    package outside the class itself, or by one of the class's own methods
    that something outside it calls. The worker's ``_engine_config`` only
    WRITES fields (keywords of the constructor), which is no read."""
    import ast
    import pathlib

    import cyberfabric_core_tpu

    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    root = pathlib.Path(cyberfabric_core_tpu.__file__).parent
    own = {}        # a method of EngineConfig -> the names it loads from self
    seen = set()    # names loaded from a configuration outside the class

    def is_config(node):
        name = node.attr if isinstance(node, ast.Attribute) else getattr(
            node, "id", None)
        return name in ("config", "eng_cfg")

    for path in root.rglob("*.py"):
        tree = ast.parse(path.read_text())
        inside = set()
        for cls in ast.walk(tree):
            if isinstance(cls, ast.ClassDef) and cls.name == "EngineConfig":
                for fn in cls.body:
                    if isinstance(fn, ast.FunctionDef):
                        own[fn.name] = {
                            n.attr for n in ast.walk(fn)
                            if isinstance(n, ast.Attribute)
                            and getattr(n.value, "id", None) == "self"}
                        inside |= set(map(id, ast.walk(fn)))
        seen |= {node.attr for node in ast.walk(tree)
                 if isinstance(node, ast.Attribute) and id(node) not in inside
                 and isinstance(node.ctx, ast.Load) and is_config(node.value)}
    assert own, "EngineConfig was not found under the package"
    reached, todo = set(), list(seen & set(own))
    while todo:
        method = todo.pop()
        if method not in reached:
            reached.add(method)
            seen |= own[method] & fields
            todo += own[method] & set(own)
    assert sorted(fields - seen) == []
