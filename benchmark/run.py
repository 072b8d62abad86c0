"""One cell, once.

    python -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent never imports JAX: the chip belongs to one process at a time, and
here that is first the correctness child, then the server child. The cell is
looked up in BENCHMARK.json; its configuration, traffic mix and per-layer
metrics are files found by name (README.md). The last line of standard
output is the result as one JSON object. Without a TPU, or on a device that
peaks.json does not list, the run fails and prints no result.

    ... --rehearse           the whole flow on the CPU at a tiny size; exits
                             3 and its last line is not a result
"""

from __future__ import annotations

import argparse
import asyncio
import inspect
import json
import shutil
import sys
import time
from pathlib import Path

from . import layer_readers, loadgen, metrics, names
from .server import (COMPILE_LINES, CACHE_HITS, REPO, WORK,
                     HarnessFailure, Server, dedupe, http_json, run_child)

T_START = time.monotonic()
#: the jitted programs of the serving path (runtime/scheduler.py) of a
#: configuration whose ``serving`` block lists none under ``programs``; one
#: of them compiling inside the window makes the run not correct
SERVING_PROGRAMS = ("mixed_step", "paged_decode_chunk")
#: besides those the scheduler runs small op-by-op programs (a page-table
#: row patch, a slot's sampling row); warm-up brings up the ones it can
#: reach, and what still compiles inside the window may sum to this many
#: seconds, 1% of the shortest window a cell could have (PERF.md section 2)
COMPILE_S_LIMIT = 0.5


def say(msg: str) -> None:
    print(msg, flush=True)


def find_file(dirs: list[str], rel: str) -> Path:
    for d in [*dirs, "benchmark"]:
        p = REPO / d / rel
        if p.is_file():
            return p
    raise HarnessFailure(f"no {rel} under {dirs}")


def load_cell(bench_file: Path, workload: str) -> dict:
    bench = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise HarnessFailure(f"unknown workload {workload!r}; known: "
                             f"{sorted(cells)}")
    cell = cells[workload]
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf = json.loads((REPO / conf_entry["file"]).read_text())
    mix = json.loads(find_file(bench["paths"],
                               f"traffic/{cell['traffic']}.json").read_text())

    def reported(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    loaded = {"bench": bench, "cell": cell, "conf": conf, "mix": mix,
              "end_to_end": [m for m in bench["end_to_end"] if reported(m)],
              "per_layer": [m for m in bench["per_layer"] if reported(m)]}
    loaded["readers"] = resolve_names(loaded)
    return loaded


def resolve_names(loaded: dict) -> dict:
    """Every name in the cell's data files that stands for code, resolved
    before a child holds the chip: a wrong one fails here, in seconds, and not
    after set-up and the window, when a traced run first needs it. The adapter
    is only found (the correctness child imports it); the counts module and
    the readers are imported, as they would be later, by this process, which
    never imports JAX: so they may not either. Returns each per-layer
    metric's reader and the arguments its file gives it."""
    conf = loaded["conf"]
    names.find(names.adapter_of(conf))
    if "counts" in conf:
        names.load(conf["counts"])
    readers = {}
    for m in loaded["per_layer"]:
        spec = json.loads(find_file(
            loaded["bench"]["paths"],
            f"layer_metrics/{m['name']}.json").read_text())
        reader = layer_readers.resolve(spec.pop("kind"))
        spec.pop("what", None)
        try:
            inspect.signature(reader).bind({}, **spec)
        except TypeError as e:
            raise HarnessFailure(f"layer_metrics/{m['name']}.json does not "
                                 f"fit its reader {reader.__name__}: {e}")
        readers[m["name"]] = (reader, spec)
    if "jax" in sys.modules:
        raise HarnessFailure("a counts or reader module of this cell imports "
                             "JAX: the harness's parent may not (the chip "
                             "belongs to its children, one at a time)")
    return readers


def warm_widths(conf: dict, mix: dict) -> list[int]:
    """The mixed_step widths this cell's prompts can reach: a chunk is what
    is left of a prompt or of the round's budget, so any size up to the
    smaller of the longest prompt and the budget can occur."""
    top = min(mix["prompt_tokens"]["max"],
              conf["serving"]["prefill_budget_tokens"])
    return [w for w in conf["serving"]["mixed_widths"] if w // 2 < top]


async def warm_request(session, srv: Server, serving: dict, tokens: int,
                       max_tokens: int, temperature: float, rid: str,
                       abandon: bool = True) -> loadgen.Sent:
    """One warm-up request. It is abandoned at its first content event unless
    it has to be read to its end: the program's SLO engine leaves a cancelled
    request out of its objectives, so the slow first use of each program does
    not spend the error budget the window's traffic is held to."""
    plan = loadgen.Planned(rid=rid, prompt_tokens=tokens, max_tokens=max_tokens,
                           text_seed=sum(map(ord, rid)), temperature=temperature)
    rec = loadgen.Sent(plan=plan, due=time.monotonic())
    body = loadgen.body_for(plan, serving["model_id"],
                            serving["prompt_overhead_tokens"])
    await loadgen.stream_one(session, srv.base, body, rec, keep_text=not abandon,
                             abandon=abandon)
    if rec.first is None or rec.error or not (abandon or rec.ok):
        raise HarnessFailure(f"warm-up request {rid} failed: status "
                             f"{rec.status} finish {rec.finish!r} {rec.error}")
    return rec


def slo_line(srv: Server) -> tuple[str, dict]:
    """The program's SLO engine as it stands: state, and per objective the
    burn rates of its two windows with the samples behind them."""
    doc = http_json("GET", f"{srv.base}/v1/monitoring/slo")
    last = doc.get("last_eval") or {}
    rows = ", ".join(f"{o['name']} {o['burn_fast']}/{o['burn_slow']} "
                     f"({o['samples_fast']}/{o['samples_slow']})"
                     for o in last.get("objectives", []))
    cancel = last.get("cancellation") or {}
    return (f"state {doc.get('state')}, reasons {last.get('reasons')}; burn "
            f"fast/slow (samples): {rows}; cancelled of terminals in 60 s: "
            f"{cancel.get('cancelled_fast')}/{cancel.get('terminals_fast')}",
            doc)


async def warm_up(srv: Server, loaded: dict) -> dict:
    """Every program the window will use. All of it is set-up."""
    import aiohttp

    conf, mix = loaded["conf"], loaded["mix"]
    serving = conf["serving"]
    temp = float(mix.get("temperature", 0.0))
    long_answer = min(64, serving["max_output_tokens"])
    out: dict = {}
    timeout = aiohttp.ClientTimeout(total=None, sock_read=1500)

    async def idle() -> None:
        if not await asyncio.to_thread(srv.wait_idle, 60.0):
            raise HarnessFailure("warm-up: abandoned requests are still in "
                                 "flight after 60 s")

    async with aiohttp.ClientSession(timeout=timeout) as session:
        t0 = time.monotonic()
        await warm_request(session, srv, serving, 24, long_answer, 0.0,
                           "warm-first")
        out["first_request_s"] = time.monotonic() - t0
        say(f"warm-up: first request (engine build, first programs) "
            f"{out['first_request_s']:.1f} s")
        for w in warm_widths(conf, mix):
            t1 = time.monotonic()
            await warm_request(session, srv, serving, w, long_answer, temp,
                               f"warm-w{w}")
            say(f"warm-up: mixed_step width {w}: {time.monotonic() - t1:.2f} s")
        await idle()
        # the scheduler patches changed page-table rows to the device in
        # power-of-two groups, each its own small program: arrivals in step
        # bring up each group size once
        k = 2
        while k <= serving["max_batch"]:
            t1 = time.monotonic()
            await asyncio.gather(*(warm_request(
                session, srv, serving, 20 + i, long_answer, temp,
                f"warm-b{k}-{i}") for i in range(k)))
            await idle()
            say(f"warm-up: {k} arrivals in step: {time.monotonic() - t1:.2f} s")
            k *= 2
        # read to the end, twice: greedy answers repeat, and the server counts
        # the prompt as planned (prompt_overhead_tokens is right)
        a, b = [await warm_request(session, srv, serving, 48, 16, 0.0,
                                   "warm-greedy", abandon=False)
                for _ in range(2)]
        if a.input_tokens != 48:
            raise HarnessFailure(f"warm-up: asked for 48 prompt tokens, the "
                                 f"server counted {a.input_tokens}")
        out["greedy_identical"] = (a.text == b.text and bool(a.text)
                                   and a.output_tokens == b.output_tokens)
        say(f"warm-up: repeated greedy request identical: "
            f"{out['greedy_identical']} ({a.output_tokens} tokens)")
    say("warm-up: SLO engine: " + slo_line(srv)[0])
    return out


def log_programs(log_text: str) -> dict:
    compiles: dict[str, list[float]] = {}
    for name, secs in dedupe(COMPILE_LINES.findall(log_text)):
        compiles.setdefault(name, []).append(float(secs))
    hits: dict[str, int] = {}
    for name, _key in dedupe(CACHE_HITS.findall(log_text)):
        hits[name] = hits.get(name, 0) + 1
    return {"compiles": compiles, "hits": hits}


def measured_requests(driven: loadgen.Driven) -> list[loadgen.Sent]:
    """A closed loop's requests outlast a good part of the window: measured
    is every request that finished inside it (or failed, having been sent
    inside it)."""
    start, end = driven.window_start, driven.window_end
    return [r for r in driven.sent if not r.cancelled and (
        (r.finish and r.last is not None and start <= r.last < end)
        or (not r.ok and not r.finish and start <= r.sent < end))]


def check_requests(measured: list[loadgen.Sent]) -> tuple[int, list[str]]:
    failed, notes = 0, []
    for r in measured:
        why = None
        if not r.ok:
            why = f"status {r.status} finish {r.finish!r} {r.error}"
        elif r.output_tokens != r.plan.max_tokens:
            why = f"{r.output_tokens} output tokens of {r.plan.max_tokens}"
        elif r.input_tokens != r.plan.prompt_tokens:
            why = f"{r.input_tokens} prompt tokens of {r.plan.prompt_tokens}"
        if why:
            failed += 1
            if len(notes) < 5:
                notes.append(f"{r.plan.rid}: {why}")
    return failed, notes


async def run_window(srv: Server, loaded: dict, seed: int, seconds: float,
                     trace: bool) -> dict:
    conf, mix = loaded["conf"], loaded["mix"]
    serving = conf["serving"]
    schedule = loadgen.build_schedule(mix, seed)
    state: dict = {"hbm": [], "pages": [], "scrapes": [], "log": {},
                   "rounds": {},
                   "trace_dir": None, "trace_on": False, "trace_done": False,
                   "trace_span_s": 0.0}
    trace_s = min(3.0, seconds / 4)

    def fetch_rounds() -> None:
        got = http_json("GET", f"{srv.base}/v1/monitoring/rounds?limit=512")
        for r in got["rounds"].get(serving["model_id"], []):
            state["rounds"][r["ts"]] = r

    def sample() -> None:
        """The server's /metrics (kept whole for the ``counter`` readers; live
        device memory is one of its series), and the pool pages that requests
        hold."""
        state["scrapes"].append(srv.metrics())
        state["hbm"].append(state["scrapes"][-1].get("tpu_hbm_bytes_in_use", 0.0))
        tenants = http_json("GET", f"{srv.base}/v1/monitoring/tenants")
        state["pages"].append(float(sum(t.get("pages", 0)
                                        for t in tenants["tenants"])))

    def stop_trace() -> None:
        state["trace_span_s"] = time.monotonic() - state["trace_t0"]
        http_json("POST", f"{srv.base}/v1/monitoring/profiler/stop", {})

    async def on_window(which: str) -> None:
        await asyncio.to_thread(sample)
        state["log"][which] = srv.log_size()
        if which == "end":
            state["slo"] = await asyncio.to_thread(slo_line, srv)
            if trace:
                await asyncio.to_thread(fetch_rounds)

    async def on_tick(elapsed: float) -> None:
        if not trace:
            return
        if not state["trace_on"] and not state["trace_done"] \
                and elapsed >= 0.4 * seconds:
            state["trace_on"] = True
            got = await asyncio.to_thread(
                http_json, "POST", f"{srv.base}/v1/monitoring/profiler/start", {})
            state["trace_dir"] = got["dir"]
            state["trace_t0"] = time.monotonic()
        elif state["trace_on"] and time.monotonic() - state["trace_t0"] >= trace_s:
            state["trace_on"], state["trace_done"] = False, True
            await asyncio.to_thread(stop_trace)
        if int(elapsed) % 8 == 0 and elapsed - int(elapsed) < 0.5:
            await asyncio.to_thread(fetch_rounds)
            await asyncio.to_thread(sample)

    driven = await loadgen.drive(schedule, srv.base, serving["model_id"],
                                 serving["prompt_overhead_tokens"], seconds,
                                 on_window=on_window, on_tick=on_tick)
    if state["trace_on"]:       # the window ended inside the trace
        stop_trace()
    state["driven"] = driven
    return state


def flight_for(srv: Server, measured: list[loadgen.Sent]) -> dict:
    """The program's flight records of the measured requests still in its
    ring of 256 finished requests."""
    recent = http_json("GET", f"{srv.base}/v1/monitoring/requests?recent=256")
    have = {r["request_id"] for r in recent["recent"]}
    out = {}
    for r in measured:
        if r.plan.rid in have:
            try:
                out[r.plan.rid] = http_json(
                    "GET", f"{srv.base}/v1/monitoring/requests/{r.plan.rid}")
            except HarnessFailure:
                pass
    return out


def breakdown_of(trace: dict) -> dict:
    """The device ops that took most time, and idle time by the programs
    either side of each gap (reduce_trace.name_gaps says why not by the
    host's round records)."""
    ops = sorted(((k, v["total_s"]) for k, v in trace.get("ops", {}).items()),
                 key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, s] for k, s in ops],
            "idle_gaps": [[f"{name} ({n} gaps)", s] for name, s, n
                          in trace.get("gaps_by_neighbours", [])[:10]]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--benchmark-file", default="BENCHMARK.json")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    srv = None
    try:
        loaded = load_cell(REPO / args.benchmark_file, args.workload)
        conf, cell = loaded["conf"], loaded["cell"]
        peaks = json.loads((REPO / "benchmark" / "peaks.json").read_text())
        WORK.mkdir(parents=True, exist_ok=True)
        shutil.rmtree(WORK / "home" / "profiles", ignore_errors=True)
        say(f"run: cell {cell['name']} seed {args.seed} seconds {args.seconds}"
            f" trace {args.trace}" + (" REHEARSAL on the CPU" if args.rehearse
                                      else ""))

        # ---- correctness child: holds the chip, exits before the server
        conf_file = next(c["file"] for c in loaded["bench"]["configs"]
                         if c["name"] == cell["config"])
        t0 = time.monotonic()
        rc, res, out = run_child(
            "benchmark.correctness",
            ["--config", conf_file, "--seed", str(args.seed)]
            + (["--rehearse"] if args.rehearse else []), args.rehearse, 900)
        for line in out.splitlines():
            if line.startswith("correctness:"):
                say(line)
        if res is None:
            raise HarnessFailure(f"the correctness child gave no result "
                                 f"(exit {rc}):\n{out[-1500:]}")
        correctness_ok = bool(res["ok"]) and rc == 0
        say(f"run: correctness child {time.monotonic() - t0:.1f} s, "
            f"{'passed' if correctness_ok else 'FAILED'}")

        # ---- server child
        t0 = time.monotonic()
        srv = Server(conf["serving"]["yaml"], args.rehearse)
        srv.start()
        device = srv.device()
        say(f"run: server healthy in {time.monotonic() - t0:.1f} s, device "
            f"{json.dumps(device)}")
        want = "cpu" if args.rehearse else "tpu"
        if device["platform"] != want:
            raise HarnessFailure(f"the server runs on {device['platform']!r}: "
                                 "no TPU, no result")
        if device != res["device"]:
            raise HarnessFailure(f"the correctness child saw {res['device']}, "
                                 f"the server {device}")
        if device["count"] < cell["chips"]:
            raise HarnessFailure(f"the cell needs {cell['chips']} chips, the "
                                 f"server sees {device['count']}")
        dev_peaks = peaks["by_device_kind"].get(device["kind"])
        if dev_peaks is None and not args.rehearse:
            raise HarnessFailure(f"device kind {device['kind']!r} is not in "
                                 "benchmark/peaks.json: no default peak")

        warm = asyncio.run(warm_up(srv, loaded))
        if "fabric_host native library loaded" not in srv.log():
            raise HarnessFailure("the server did not load native/fabric_host: "
                                 "it would measure the Python fallback")

        # ---- the window (its lead-in is still set-up)
        setup_before_lead = time.monotonic() - T_START
        st = asyncio.run(run_window(srv, loaded, args.seed, args.seconds,
                                    bool(args.trace)))
        driven: loadgen.Driven = st["driven"]
        setup_s = driven.window_start - T_START
        measured = measured_requests(driven)
        failed, notes = check_requests(measured)
        flight = flight_for(srv, measured) if args.trace else {}
        log_text = srv.log()
        in_window = log_text.encode()[st["log"]["start"]: st["log"]["end"]].decode(
            "utf-8", "replace")
        in_win = log_programs(in_window)["compiles"]
        compile_s_in_window = sum(sum(v) for v in in_win.values())
        serving_programs = tuple(conf["serving"].get("programs",
                                                     SERVING_PROGRAMS))
        serving_in_window = sorted(k for k in in_win if k in serving_programs)
        progs = log_programs(log_text)
        idle = srv.wait_idle(30.0)
        rc_srv = srv.stop()
        srv = None
        say(f"run: server idle before SIGTERM: {idle}; exit code {rc_srv} "
            "(reported, not part of correct)")
        n_prog = sum(len(v) for k, v in progs["compiles"].items()
                     if k in serving_programs)
        n_hit = sum(v for k, v in progs["hits"].items()
                    if k in serving_programs)
        say(f"run: serving programs {n_prog}, persistent-cache hits {n_hit}"
            + ("" if n_hit >= n_prog else ": THIS RUN COMPILED (a first run "
               "in this checkout); its setup_s is a cold one"))
        say(f"run: set-up {setup_s:.1f} s (of which lead-in "
            f"{setup_s - setup_before_lead:.1f} s, first request "
            f"{warm['first_request_s']:.1f} s)")
        say(f"run: window {driven.window_end - driven.window_start:.1f} s: "
            f"requests sent {len(driven.sent)}, measured {len(measured)}, "
            f"completed {len(measured) - failed}, failed {failed}; in flight "
            f"mid {driven.inflight_mid} end {driven.inflight_end}")
        for n in notes:
            say(f"run: failed request {n}")
        hbm_peak = max(st["hbm"]) if st["hbm"] else 0.0
        say(f"run: tpu_hbm_bytes_in_use sampled max {hbm_peak:.0f} "
            f"({len(st['hbm'])} samples); pool pages held by requests, max "
            f"{max(st['pages'])} of {conf['serving']['pool_pages']}")
        say("run: SLO engine at the window's end: " + st["slo"][0])
        shed = [h for h in st["slo"][1].get("state_history", [])
                if h.get("to") == "shedding"]
        say(f"run: SLO engine went to shedding {len(shed)} times since the "
            f"server started" + (f": {json.dumps(shed[-2:])}" if shed else ""))
        say(f"run: compiled inside the window: serving programs "
            f"{serving_in_window} of {list(serving_programs)} (limit: none), "
            f"small programs "
            f"{sorted(set(in_win) - set(serving_in_window))} "
            f"{compile_s_in_window:.3f} s in all (limit {COMPILE_S_LIMIT} s)")

        checks = {"correctness child passed": correctness_ok,
                  "every measured request completed as planned": failed == 0
                  and len(measured) > 0,
                  "repeated greedy request identical": warm["greedy_identical"],
                  "no serving program compiled inside the window":
                  not serving_in_window,
                  f"small compiles inside the window <= {COMPILE_S_LIMIT} s":
                  compile_s_in_window <= COMPILE_S_LIMIT}
        for what, ok in checks.items():
            say(f"check: {what}: {'ok' if ok else 'NOT ok'}")
        correct = all(checks.values())
        judged = res["readings"][0]
        compared = [
            f"worst logits row rms(program - reference) / std(reference) "
            f"{judged['program']['worst_row_rms']} (limit {res['limit']}), at "
            f"[row, position] {judged['program']['row']}",
            f"idle rows whose state came back changed "
            f"{judged.get('idle_rows_touched')} (limit: none)",
            f"measured requests {len(measured)} (limit: at least 1), of which "
            f"failed {failed} (limit 0)",
            f"repeated greedy request identical {warm['greedy_identical']} "
            f"(limit: True)",
            f"serving programs compiled inside the window {serving_in_window} "
            f"(limit: none)",
            f"small compiles inside the window {compile_s_in_window:.3f} s "
            f"(limit {COMPILE_S_LIMIT} s)"]
        for line in compared:
            say("compared: " + line)
        result: dict = {"correct": bool(correct), "attempted": len(measured),
                        "failed": failed, "metrics": {},
                        "device": {**device, "memory_peak_bytes": int(hbm_peak)}}
        ttfts = [v for v in map(metrics.ttft_ms, measured) if v is not None]
        tpots = [v for v in map(metrics.tpot_ms, measured) if v is not None]
        say("stats: " + json.dumps({
            "ttft_ms": {**{q: metrics.percentile(ttfts, int(q[1:]))
                           for q in ("p50", "p80", "p90", "p95")},
                        "mean": metrics.stat(ttfts, "mean")},
            "tpot_ms": {**{q: metrics.percentile(tpots, int(q[1:]))
                           for q in ("p50", "p80", "p90", "p95")},
                        "mean": metrics.stat(tpots, "mean"),
                        "over_all_tokens": metrics.tpot_over_all_ms(measured)},
            "counts": [len(ttfts), len(tpots)],
            "out_tokens_per_s": metrics.out_tokens_per_s(
                driven.sent, driven.window_start, driven.window_end)}))
        if not args.trace:
            for m in loaded["end_to_end"]:
                if m["name"] == "setup_s":
                    value = setup_s
                else:
                    value = metrics.END_TO_END[m["name"]](
                        measured, driven.sent, driven.window_start,
                        driven.window_end)
                count = sum(1 for r in measured if r.ok)
                say(f"metric: {m['name']} = {value} {m['unit']} "
                    f"(over {count} completed requests)")
                if value is not None:
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
        else:
            _, trace, tout = run_child(
                "benchmark.reduce_trace",
                [str(st["trace_dir"]), str(st["trace_span_s"])], True, 600)
            trace = trace or {}
            if not trace.get("busy_s") and not args.rehearse:
                raise HarnessFailure("the trace shows no device operation:\n"
                                     + tout[-1500:])
            say(f"trace: {trace.get('file')} {trace.get('bytes')} bytes, "
                f"window {trace.get('window_s')} s, busy {trace.get('busy_s')} s"
                f", planes {json.dumps(trace.get('planes_seen'))[:1500]}")
            say("trace: modules " + json.dumps(
                {k: [v["count"], round(v["total_s"], 4)]
                 for k, v in trace.get("modules", {}).items()}))
            kinds = sorted(trace.get("op_kinds", {}).items(),
                           key=lambda kv: -kv[1]["total_s"])[:30]
            say("trace: op kinds by time " + json.dumps(
                [[k, v["count"], round(v["total_s"], 4)] for k, v in kinds]))
            rounds_in = [r for ts, r in sorted(st["rounds"].items())
                         if driven.window_start_wall <= ts
                         < driven.window_end_wall]
            ctx = {"requests": measured, "flight": flight, "rounds": rounds_in,
                   "server_log": log_text,
                   "samples": {"pool_pages": st["pages"]},
                   # sample() runs at the window's start, its end and between
                   "scrapes": {"start": st["scrapes"][0],
                               "end": st["scrapes"][-1], "all": st["scrapes"]},
                   "trace": trace, "config": conf, "peaks": dev_peaks,
                   "values": {}}
            for m in loaded["per_layer"]:
                reader, spec = loaded["readers"][m["name"]]
                value = reader(ctx, **spec)
                say(f"layer metric: {m['name']} = {value} {m['unit']} "
                    f"[{m['layer']}]")
                if value is not None:
                    ctx["values"][m["name"]] = value
                    result["metrics"][m["name"]] = {"value": value,
                                                    "unit": m["unit"]}
            result["device"]["busy_s"] = trace.get("busy_s", 0.0)
            result["device"]["window_s"] = trace.get("window_s", 0.0)
            result["breakdown"] = breakdown_of(trace)
            say(f"trace: flight records read {len(flight)} of {len(measured)},"
                f" round records {len(rounds_in)}")
        # each number compared, beside its limit, as the last lines of
        # standard error too: what is kept of a run that is not correct
        print("\n".join(f"compared: {line}" for line in compared)
              + f"\ncompared: correct {bool(correct)}", file=sys.stderr, flush=True)
        if args.rehearse:
            say("run: REHEARSAL: no device number above is a chip number")
            print("REHEARSAL " + json.dumps(result), flush=True)
            return 3
        print(json.dumps(result), flush=True)
        return 0
    except HarnessFailure as e:
        print(f"run: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        if srv is not None:
            srv.stop()


if __name__ == "__main__":
    sys.exit(main())
