"""The correctness child: the program's paged forward passes against the
plain reference, at the configuration's published widths with depth cut.

It holds the chip alone and exits before the server starts. The program is
driven the way ``runtime/scheduler.py`` drives it: ``forward_paged_mixed``
over a page pool in chunks of the prefill budget (rows at different stages in
one call: a fresh chunk, a later chunk of a long prompt, a row resumed from
pages another row wrote, a decode row riding along, an idle row), then
``forward_paged_decode`` steps. The reference runs each row's whole sequence
once, in float32, with no cache.

The number compared, per logits row (one position of one sequence, V values):
rms(program - reference) / std(reference). The worst row is held to the
limit in the configuration file. A row's rms averages V >= 32000 differences,
so it is steady from seed to seed, and the worst row still shows a fault that
touches one row only (a wrong page, mask or position moves it to about 1).

    python -m benchmark.correctness --config benchmark/configs/<name>.json --seed N
    ... --seeds 1,2,3 --control     several seeds in one process, each also
                                    with the controls

The controls, each one precision below what the configurations state (int8
weights, bfloat16 activations and K/V), must come out over the limit: the
program given int4-grid weights (``int4``); the reference put in the
program's place with its activations, q, K, V and attention weights rounded
to float8 e4m3 (``fp8``). A third reading, ``kv_int8`` (the reference with
K/V rounded to int8, one scale per token and head), is printed and not held
to the limit: its error is below bfloat16's own rounding, so no comparison
of logits can tell it from the program (PERF.md section 2).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path


def say(msg: str) -> None:
    print(msg, flush=True)


def scenario(chunk: int, page: int) -> dict:
    """Row lengths and the calls they make, from the chunk and page size."""
    shared = chunk - page                       # whole pages row C takes from row B
    return {"A": 2 * chunk + chunk // 7,        # three chunks, fresh
            "B": chunk + (3 * chunk) // 8,      # two chunks; its pages feed C
            "C_shared": shared, "C": shared + (3 * chunk) // 8 + page // 5,
            "D": page // 2 + page // 8}         # short: prefill, then decode rider


def bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


def run(args: argparse.Namespace) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import reference, weights
    from cyberfabric_core_tpu.models import get_config, llama
    from cyberfabric_core_tpu.ops.platform import enable_compile_cache, on_tpu
    from cyberfabric_core_tpu.ops.rope import rope_frequencies

    conf = json.loads(Path(args.config).read_text())
    serving, cc = conf["serving"], conf["correctness"]
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"correctness: device {json.dumps(device)}")
    if on_tpu() == args.rehearse:
        say(f"correctness: FAILED: platform {devs[0].platform!r}: "
            + ("a rehearsal runs on the CPU" if args.rehearse else "no TPU"))
        return 1
    cache_dir = enable_compile_cache()
    entries = list(Path(cache_dir).glob("*")) if cache_dir else []
    say(f"correctness: compile cache {cache_dir}: {len(entries)} entries, "
        f"{sum(e.stat().st_size for e in entries if e.is_file()) / 2**20:.0f} "
        f"MiB, size cap {jax.config.jax_compilation_cache_max_size}")

    cfg = dataclasses.replace(get_config(serving["model_config"]),
                              num_layers=cc["depth"])
    chunk, page, steps = cc["chunk"], serving["page"], cc["decode_steps"]
    sc = scenario(chunk, page)
    max_seq = serving["max_seq_len"]
    pmax = max_seq // page
    rope = rope_frequencies(cfg.head_dim, max_seq, cfg.rope_theta)
    ref_kw = reference.reference_kwargs(conf, cc["depth"])
    B = 4
    # page table: every row its own pages (page 0 is scratch); C's first
    # pages are B's — a prefix-cache hit as the pool hands it out
    table = 1 + np.arange(B * pmax, dtype=np.int32).reshape(B, pmax)
    table[2, : sc["C_shared"] // page] = table[1, : sc["C_shared"] // page]
    pool_shape = (cfg.num_layers, B * pmax + 1, page, cfg.num_kv_heads,
                  cfg.head_dim)

    mixed = jax.jit(lambda p, ids, pools, hist, qlens: llama.forward_paged_mixed(
        p, cfg, ids, pools, jnp.asarray(table), hist, qlens, rope))
    decode = jax.jit(lambda p, ids, pools, lens: llama.forward_paged_decode(
        p, cfg, ids, pools, jnp.asarray(table), lens, rope))
    head = jax.jit(lambda p, h: llama.lm_head_logits(p, cfg, h))

    def program_logits(params, seqs, lens):
        """[(row, position)] -> logits, through chunked mixed calls and
        decode steps. ``seqs[r]`` is row r's whole sequence (prompt + forced
        tokens), ``lens[r]`` its prompt length."""
        pools = (jnp.zeros(pool_shape, jnp.bfloat16),
                 jnp.zeros(pool_shape, jnp.bfloat16))
        done = np.zeros(B, np.int32)                 # tokens in pages so far
        done[2] = sc["C_shared"]
        out: dict[tuple[int, int], np.ndarray] = {}
        call = 0
        while True:
            q = np.zeros(B, np.int32)
            for r in range(B):
                left = lens[r] - done[r]
                if r == 2 and call == 0:
                    continue                          # B's pages are not written yet
                if left > 0:
                    q[r] = min(left, chunk)
                elif r == 3 and done[r] < lens[r] + 2:
                    q[r] = 1                          # decode rider: one forced token
            if not q[:3].any():
                break
            width = bucket(int(q.max()))
            ids = np.zeros((B, width), np.int32)
            for r in range(B):
                ids[r, : q[r]] = seqs[r][done[r]: done[r] + q[r]]
            hidden, pools = mixed(params, jnp.asarray(ids), pools,
                                  jnp.asarray(done), jnp.asarray(q))
            last = llama.gather_last_hidden(hidden, jnp.asarray(q))
            logits = np.asarray(head(params, last), np.float32)
            for r in range(B):
                done[r] += q[r]
                if q[r] and done[r] >= lens[r]:
                    out[(r, int(done[r]) - 1)] = logits[r]
            call += 1
        for _ in range(steps):
            ids = np.asarray([[seqs[r][done[r]]] for r in range(B)], np.int32)
            hidden, pools = decode(params, jnp.asarray(ids), pools,
                                   jnp.asarray(done))
            logits = np.asarray(head(params, hidden[:, 0]), np.float32)
            for r in range(B):
                out[(r, int(done[r]))] = logits[r]
                done[r] += 1
        return out, call

    def compare(got: dict, ref: dict) -> dict:
        worst_rms, worst_max, where = 0.0, 0.0, None
        for key, g in got.items():
            r = ref[key]
            if not np.isfinite(g).all():
                return {"worst_row_rms": float("inf"), "worst_max": float("inf"),
                        "row": list(key)}
            d, sd = g - r, float(r.std())
            rms, mx = float(np.sqrt((d * d).mean()) / sd), float(np.abs(d).max() / sd)
            if rms > worst_rms:
                worst_rms, where = rms, key
            worst_max = max(worst_max, mx)
        return {"worst_row_rms": worst_rms, "worst_max": worst_max,
                "row": list(where) if where else None, "rows": len(got)}

    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    limit = cc["limit"]
    all_ok, readings = True, []
    for seed in seeds:
        t0 = time.monotonic()
        w = jax.block_until_ready(weights.make_weights(conf, seed, cc["depth"]))
        rng = np.random.default_rng(seed)
        vocab = conf["vocab_size"]
        lens = [sc["A"], sc["B"], sc["C"], sc["D"]]
        extra = [steps, steps, steps, steps + 2]
        seqs = [rng.integers(3, vocab, n + e).astype(np.int32)
                for n, e in zip(lens, extra)]
        seqs[2][: sc["C_shared"]] = seqs[1][: sc["C_shared"]]
        got, calls = program_logits(w, seqs, lens)
        ref: dict[tuple[int, int], np.ndarray] = {}
        for r in range(B):
            at = sorted(p for (rr, p) in got if rr == r)
            logits = np.asarray(reference.forward_logits(
                w, jnp.asarray(seqs[r]), jnp.asarray(at, jnp.int32), **ref_kw),
                np.float32)
            for p, row in zip(at, logits):
                ref[(r, p)] = row
        res = compare(got, ref)
        line = {"seed": seed, "program": res, "mixed_calls": calls}
        ok = limit is not None and res["worst_row_rms"] <= limit
        say(f"correctness: seed {seed}: program vs reference: worst row rms "
            f"{res['worst_row_rms']:.5f} of a logit deviation (limit {limit}), "
            f"worst single logit {res['worst_max']:.4f}, {res['rows']} rows, "
            f"{calls} mixed calls + {steps} decode steps, row lengths {lens}"
            f" -> {'ok' if ok else 'NOT ok'}")
        if args.control:
            controls = {"int4": program_logits(weights.to_int4_grid(w), seqs,
                                               lens)[0]}
            for lower in ("fp8", "kv_int8"):
                rows = {}
                for r in range(B):
                    at = sorted(p for (rr, p) in got if rr == r)
                    rows.update({(r, p): row for p, row in zip(at, np.asarray(
                        reference.forward_logits(
                            w, jnp.asarray(seqs[r]), jnp.asarray(at, jnp.int32),
                            lower=lower, **ref_kw), np.float32))})
                controls[lower] = rows
            for name, logits in controls.items():
                ctl = compare(logits, ref)
                line[f"control_{name}"] = ctl
                caught = limit is None or ctl["worst_row_rms"] > limit
                say(f"correctness: seed {seed}: CONTROL {name} vs reference: "
                    f"worst row rms {ctl['worst_row_rms']:.5f} (limit {limit})"
                    f" -> {'caught' if caught else 'NOT caught'}"
                    + (" (read only)" if name == "kv_int8" else ""))
                all_ok = all_ok and (caught or name == "kv_int8")
        line["seconds"] = round(time.monotonic() - t0, 2)
        all_ok = all_ok and (ok or args.control and limit is None)
        readings.append(line)
    print("RESULT " + json.dumps({"ok": bool(all_ok), "device": device,
                                  "limit": limit, "readings": readings}),
          flush=True)
    return 0 if all_ok else 1


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", help="comma-separated; several in one process")
    ap.add_argument("--control", action="store_true",
                    help="also run the controls on every seed")
    ap.add_argument("--rehearse", action="store_true",
                    help="on the CPU at a tiny size; never a result")
    return run(ap.parse_args())


if __name__ == "__main__":
    sys.exit(main())
