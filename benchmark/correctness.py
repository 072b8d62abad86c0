"""The correctness child, the judge: the program's paged forward passes
against the plain reference, at the configuration's published widths with
depth cut.

It holds the chip alone and exits before the server starts. The program is
driven the way ``runtime/scheduler.py`` drives it: mixed calls over the
program's cache in chunks of the prefill budget (rows at different stages in
one call: a fresh chunk, a later chunk of a long prompt, a row resumed from
what another row wrote, a decode row riding along, an idle row), then decode
steps. The reference runs each row's whole sequence once, in float32, with
no cache.

What is judged is named by the configuration's file: ``correctness.adapter``
is a module (absent: ``benchmark.adapters.llama``) that gives the seeded
weights, the reference, its controls and the binding to the program, whose
cache state the judge passes through and never looks inside (README.md, "The
seam"). The judge owns the device check, the scenario, the comparison, the
limit, the controls' bookkeeping and the ``RESULT`` line.

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
of logits can tell it from the program (PERF.md section 2). Those are the
lists of a configuration that states none; ``correctness.controls`` has
``caught`` and ``read_only``. A name among the adapter's ``PROGRAM_CONTROLS``
is the program given lowered weights, any other the reference's ``lower=``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def say(msg: str) -> None:
    print(msg, flush=True)


def scenario(chunk: int, page: int, unit: int) -> dict:
    """Row lengths and the calls they make, from the chunk and page size and
    the adapter's ``prefix_unit``: the program can hand a row another row's
    first tokens at multiples of that many (absent: the page; a block with
    recurrent state keeps it at a chunk's end, and says the chunk). Row C
    takes from row B the most such units that B's first chunk holds short of
    its end, or where the unit is the chunk, that chunk whole."""
    shared = (chunk - 1) // unit * unit or unit
    if shared > chunk or shared % page:
        raise ValueError(f"prefix_unit {unit}: the shared boundary {shared} "
                         f"has to be whole pages of {page} inside row B's "
                         f"first chunk of {chunk}")
    return {"A": 2 * chunk + chunk // 7,        # three chunks, fresh
            "B": chunk + (3 * chunk) // 8,      # two chunks; its prefix feeds C
            "C_shared": shared, "C": shared + (3 * chunk) // 8 + page // 5,
            "D": page // 2 + page // 8}         # short: prefill, then decode rider


def bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


#: the controls of a configuration that lists none (the llama family's)
DEFAULT_CONTROLS = {"caught": ["int4", "fp8"], "read_only": ["kv_int8"]}
#: the rows of the scenario: A, B, C (resumed from B's prefix), D
ROWS, RESUMED, SOURCE = 4, 2, 1


def run(args: argparse.Namespace) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import names
    from cyberfabric_core_tpu.ops.platform import enable_compile_cache, on_tpu

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

    adapter = names.load(names.adapter_of(conf))
    say(f"correctness: adapter {adapter.__name__}")
    chunk, page, steps = cc["chunk"], serving["page"], cc["decode_steps"]
    B = ROWS
    binding = adapter.bind(conf, cc["depth"], B)
    try:
        sc = scenario(chunk, page, getattr(binding, "prefix_unit", page))
    except ValueError as e:
        say(f"correctness: FAILED: {adapter.__name__}: {e}")
        return 1
    reference = adapter.reference_logits(conf, cc["depth"])
    row_state = getattr(binding, "row_state", None)

    def program_logits(params, seqs, lens):
        """[(row, position)] -> logits, through chunked mixed calls and
        decode steps. ``seqs[r]`` is row r's whole sequence (prompt + forced
        tokens), ``lens[r]`` its prompt length. Also the number of mixed
        calls, and the (call, row) pairs in which a row that took no part in
        a mixed call came back with its state changed (None where the adapter
        exposes no row state)."""
        # the resumed row's first chunk comes only after the source row has
        # written the shared tokens (call 0): the hook may plan a snapshot of
        # a state that a mixed call returns
        state = binding.share_prefix(binding.new_state(), RESUMED, SOURCE,
                                     sc["C_shared"])
        done = np.zeros(B, np.int32)                 # tokens in the cache so far
        done[RESUMED] = sc["C_shared"]
        out: dict[tuple[int, int], np.ndarray] = {}
        touched = [] if row_state else None
        call = 0
        while True:
            q = np.zeros(B, np.int32)
            for r in range(B):
                left = lens[r] - done[r]
                if r == RESUMED and call == 0:
                    continue                          # B has not written them yet
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
            idle = [r for r in range(B) if row_state and not q[r]]
            before = [np.asarray(row_state(state, r)) for r in idle]
            last, state = binding.mixed(params, ids, state, done, q)
            for r, was in zip(idle, before):
                if not np.array_equal(was, np.asarray(row_state(state, r))):
                    touched.append([call, r])
            logits = np.asarray(binding.logits(params, last), np.float32)
            for r in range(B):
                done[r] += q[r]
                if q[r] and done[r] >= lens[r]:
                    out[(r, int(done[r]) - 1)] = logits[r]
            call += 1
        for _ in range(steps):
            ids = np.asarray([[seqs[r][done[r]]] for r in range(B)], np.int32)
            last, state = binding.decode(params, ids, state, done)
            logits = np.asarray(binding.logits(params, last), np.float32)
            for r in range(B):
                out[(r, int(done[r]))] = logits[r]
                done[r] += 1
        return out, call, touched

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

    def reference_rows(w, seqs, got, lower=None) -> dict:
        rows: dict[tuple[int, int], np.ndarray] = {}
        for r in range(B):
            at = sorted(p for (rr, p) in got if rr == r)
            logits = np.asarray(reference(w, jnp.asarray(seqs[r]),
                                          jnp.asarray(at, jnp.int32),
                                          lower=lower), np.float32)
            rows.update({(r, p): row for p, row in zip(at, logits)})
        return rows

    seeds = ([int(s) for s in args.seeds.split(",")] if args.seeds
             else [args.seed])
    limit = cc["limit"]
    listed = cc.get("controls", DEFAULT_CONTROLS)
    read_only = list(listed.get("read_only", []))
    all_ok, readings = True, []
    for seed in seeds:
        t0 = time.monotonic()
        w = jax.block_until_ready(adapter.make_weights(conf, seed, cc["depth"]))
        rng = np.random.default_rng(seed)
        vocab = conf["vocab_size"]
        lens = [sc["A"], sc["B"], sc["C"], sc["D"]]
        extra = [steps, steps, steps, steps + 2]
        seqs = [rng.integers(3, vocab, n + e).astype(np.int32)
                for n, e in zip(lens, extra)]
        seqs[RESUMED][: sc["C_shared"]] = seqs[SOURCE][: sc["C_shared"]]
        got, calls, touched = program_logits(w, seqs, lens)
        ref = reference_rows(w, seqs, got)
        res = compare(got, ref)
        line = {"seed": seed, "program": res, "mixed_calls": calls,
                "idle_rows_touched": touched}
        ok = limit is not None and res["worst_row_rms"] <= limit and not touched
        say(f"correctness: seed {seed}: program vs reference: worst row rms "
            f"{res['worst_row_rms']:.5f} of a logit deviation (limit {limit}), "
            f"worst single logit {res['worst_max']:.4f}, {res['rows']} rows, "
            f"{calls} mixed calls + {steps} decode steps, row lengths {lens}"
            f" -> {'ok' if ok else 'NOT ok'}"
            + (f" at row {res['row']}" if res["worst_row_rms"] > (limit or 0)
               else ""))
        if touched is not None:
            say(f"correctness: seed {seed}: rows idle in a mixed call whose "
                f"state came back changed, as [call, row] (limit: none): "
                f"{touched}")
        if args.control:
            for name in [*listed.get("caught", []), *read_only]:
                if name in adapter.PROGRAM_CONTROLS:
                    logits = program_logits(adapter.PROGRAM_CONTROLS[name](w),
                                            seqs, lens)[0]
                else:
                    logits = reference_rows(w, seqs, got, lower=name)
                ctl = compare(logits, ref)
                line[f"control_{name}"] = ctl
                caught = limit is None or ctl["worst_row_rms"] > limit
                say(f"correctness: seed {seed}: CONTROL {name} vs reference: "
                    f"worst row rms {ctl['worst_row_rms']:.5f} (limit {limit})"
                    f" -> {'caught' if caught else 'NOT caught'}"
                    + (" (read only)" if name in read_only else ""))
                all_ok = all_ok and (caught or name in read_only)
        line["seconds"] = round(time.monotonic() - t0, 2)
        all_ok = all_ok and (ok or args.control and limit is None)
        readings.append(line)
    print("RESULT " + json.dumps({"ok": bool(all_ok), "device": device,
                                  "adapter": adapter.__name__,
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
