#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the main path starts on the chip.

    python chip_smoke.py              one chip: serve mistral-7b int8 through the
                                      gateway, then check the kernels at its widths
    python chip_smoke.py --chips 4    four chips: tp=4 against tp=1, then four
                                      dp replicas — and no other phase
    python chip_smoke.py --rehearse   both of the above on the CPU: tiny-llama,
                                      interpret-mode kernels, four virtual devices

The main path is ``python -m cyberfabric_core_tpu.server run --config
config/chip_smoke.yaml`` → api_gateway → llm_gateway → LocalTpuWorker →
ContinuousBatchingEngine (paged, mixed-batch) → models/llama.py with the Pallas
flash / paged / ragged kernels. Weights are synthetic, made from seed 0.

A chip belongs to one process at a time, so this process never imports JAX:
it starts the server and the check phases as children, one after the other,
and takes the device from what they report. Every phase has to pass; the
last line of standard output is then ``{"ok": true, "device": {...}}``. A
rehearsal names the CPU and is marked as one. Without a TPU the run fails.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent
CONFIG = REPO / "config" / "chip_smoke.yaml"
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"     # brought back by the chip tool
WORK_DIR = REPO / ".chip_smoke_home"              # IR dumps; stays on the machine
#: ops/platform.py COMPILE_CACHE_DIR, repeated because this process may not
#: import the package; the children fail if the two ever differ
DEFAULT_CACHE_DIR = REPO / ".jax_cache"

#: the chip run's model and serving shape (config/chip_smoke.yaml) and the
#: rehearsal's stand-in. ``depth`` cuts layers for the model-level logit check
#: only (widths stay published); the served model keeps all of its layers.
CHIP = {"model": "mistral-7b", "quant": "int8", "max_seq_len": 2048,
        "max_batch": 8, "page": 64, "depth": 4, "prefill": 512, "chunk": 256,
        "kernel_batch": 8, "tp": 4}
REHEARSAL = {"model": "tiny-llama", "quant": "int8", "max_seq_len": 512,
             "max_batch": 8, "page": 64, "depth": 2, "prefill": 128,
             "chunk": 64, "kernel_batch": 4, "tp": 2}   # it has 2 kv heads

#: int8 mistral-7b is 7.25 GB of arguments to every serving program (compiler's
#: memory analysis); the server's device must show at least this much in use
MIN_RESIDENT_BYTES = 7.0e9

#: request prompts are sized so prefill chunks land in exactly these mixed-step
#: widths (prompt tokens in (32, 64] and (128, 256]); each is one cold compile
MIXED_WIDTHS = (64, 256)
PROMPT_SHORT = "The quick brown fox jumps over the lazy dog. "          # 46 tokens
PROMPT_B = "Pack my box with five dozen liquor jugs, quickly."           # 50 tokens
PROMPT_C = "How vexingly quick daft zebras jump over the lazy old dog."  # 59 tokens
PROMPT_LONG = ("A paged cache keeps each sequence's keys and values in "
               "fixed-size pages, so memory is claimed a page at a time. ") * 2
CHAT_TEXT = "Name three uses of a paged key-value cache."


class SmokeFailure(Exception):
    """A phase failed: the run ends non-zero and prints no success line."""


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ------------------------------------------------------------------ parent
def child_env(rehearse: bool, devices: int) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices}").strip()
    return env


def cache_dir() -> Path:
    return Path(os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR)


def cache_entries() -> int:
    d = cache_dir()
    return sum(1 for p in d.iterdir() if p.is_file()) if d.is_dir() else 0


def build_native() -> None:
    """Build native/fabric_host from fabric_host.cpp — never a .so left on
    disk. runtime/native.py would fall back to Python quietly; here a failed
    build fails the run, and the phases report which implementation loaded."""
    src = REPO / "native" / "fabric_host"
    t0 = time.monotonic()
    proc = subprocess.run(["make", "-B", "-C", str(src)], capture_output=True,
                          text=True, timeout=300)
    require(proc.returncode == 0,
            f"native build failed:\n{proc.stdout}\n{proc.stderr}")
    say(f"native: built libfabric_host.so from fabric_host.cpp in "
        f"{time.monotonic() - t0:.1f} s")


@contextlib.contextmanager
def request(method: str, url: str, body: dict | None = None,
            timeout: float = 900.0):
    """An open HTTP response; an error status fails the run with its body."""
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"content-type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            yield r
    except urllib.error.HTTPError as e:
        raise SmokeFailure(f"{method} {url} → HTTP {e.code}: "
                           f"{e.read().decode('utf-8', 'replace')[:500]}")


def http(method: str, url: str, body: dict | None = None) -> dict:
    with request(method, url, body) as r:
        return json.loads(r.read())


def sse(url: str, body: dict, on_first=None) -> dict:
    """POST a streaming request and read it to ``data: [DONE]``."""
    deltas: list[str] = []
    final: dict = {}
    done = False
    with request("POST", url, body) as r:
        for raw in r:
            line = raw.decode("utf-8", "replace").strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                done = True
                break
            ev = json.loads(line[len("data: "):])
            text = (ev.get("delta") or {}).get("content")
            if text:
                if not deltas and on_first is not None:
                    on_first()
                deltas.append(text)
            if ev.get("finish_reason"):
                final = ev
    require(done, f"stream from {url} ended without data: [DONE]")
    return {"deltas": deltas, "finish_reason": final.get("finish_reason"),
            "usage": final.get("usage") or {}}


def check_usage(name: str, usage: dict, finish: str | None, max_tokens: int,
                pieces: int) -> None:
    """A clean finish whose usage agrees with what was streamed. The byte
    tokenizer drops specials and holds back partial UTF-8, so text pieces
    never outnumber tokens; a 'length' finish used the whole budget."""
    require(finish in ("stop", "length"),
            f"{name}: finish_reason {finish!r} is not a clean finish")
    out = usage.get("output_tokens", 0)
    require(0 < out <= max_tokens, f"{name}: output_tokens {out} outside "
                                   f"(0, {max_tokens}]")
    require(finish != "length" or out == max_tokens,
            f"{name}: finished 'length' at {out} of {max_tokens} tokens")
    require(0 < pieces <= out,
            f"{name}: {pieces} text pieces for {out} output tokens")
    n_in = usage.get("input_tokens", 0)
    require(any(w // 2 < n_in <= w for w in MIXED_WIDTHS),
            f"{name}: {n_in} prompt tokens fall outside the designed "
            f"mixed-step widths {MIXED_WIDTHS}")


def drive_requests(base: str, model_id: str) -> dict[str, float]:
    """The traffic: one non-streamed completion (cold, then warm and equal),
    one streamed chat, then four at once so that prefill chunks ride decode
    rounds (mixed) and a pure decode round runs as well."""
    times: dict[str, float] = {}
    greedy = {"model": model_id, "prompt": PROMPT_SHORT, "max_tokens": 16,
              "temperature": 0}

    def completion(body: dict, name: str) -> dict:
        resp = http("POST", f"{base}/v1/completions", body)
        text = "".join(p.get("text", "") for p in resp.get("content", []))
        require(bool(text), f"{name}: empty completion")
        check_usage(name, resp.get("usage") or {}, resp.get("finish_reason"),
                    body["max_tokens"], len(text))
        return {"text": text, "usage": resp["usage"],
                "finish": resp["finish_reason"]}

    t0 = time.monotonic()
    first = completion(greedy, "first request")
    times["first_request_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    again = completion(greedy, "warm request")
    times["warm_request_s"] = time.monotonic() - t0
    require(first == again, f"the same greedy request gave {first} then {again}")
    say(f"requests: /v1/completions non-streamed ok, {first['usage']}, finish "
        f"{first['finish']}; repeated greedy request identical")

    chat = sse(f"{base}/v1/chat/completions", {
        "model": model_id, "stream": True, "max_tokens": 24, "temperature": 0,
        "messages": [{"role": "user",
                      "content": [{"type": "text", "text": CHAT_TEXT}]}]})
    check_usage("streamed chat", chat["usage"], chat["finish_reason"], 24,
                len(chat["deltas"]))
    say(f"requests: /v1/chat/completions streamed to [DONE], "
        f"{len(chat['deltas'])} pieces, {chat['usage']}, finish "
        f"{chat['finish_reason']}")

    # A streams alone until its first token; B, C, D then arrive together, so
    # their prefill chunks share rounds with A's decode rows
    started = threading.Event()
    results: dict[str, object] = {}

    def run(name: str, fn) -> None:
        try:
            results[name] = fn()
        except Exception as e:  # noqa: BLE001 — re-raised on the main thread
            results[name] = e
            started.set()

    threads = [threading.Thread(target=run, args=("A", lambda: sse(
        f"{base}/v1/completions",
        {"model": model_id, "prompt": PROMPT_SHORT, "stream": True,
         "max_tokens": 96, "temperature": 0}, on_first=started.set)))]
    threads[0].start()
    require(started.wait(900), "concurrent phase: request A never streamed")
    for name, prompt in (("B", PROMPT_B), ("C", PROMPT_C), ("D", PROMPT_LONG)):
        body = {"model": model_id, "prompt": prompt, "max_tokens": 24,
                "temperature": 0.7, "seed": 7}
        threads.append(threading.Thread(
            target=run, args=(name, lambda b=body, n=name: completion(b, n))))
        threads[-1].start()
    for t in threads:
        t.join(900)
        require(not t.is_alive(), "concurrent phase: a request never returned")
    for name, res in results.items():
        if isinstance(res, Exception):
            raise SmokeFailure(f"concurrent request {name}: {res}")
    a = results["A"]
    check_usage("A", a["usage"], a["finish_reason"], 96, len(a["deltas"]))
    say("requests: four concurrent (one streamed, three not) ok")
    return times


def parse_server_log(text: str) -> dict:
    """Per-program compile seconds and persistent-cache hits, as JAX logs
    them under JAX_LOG_COMPILES. JAX's own handler and the server's both
    print each record, so consecutive repeats count once."""
    def unique(found: list[tuple[str, str]]) -> list[tuple[str, str]]:
        return [x for i, x in enumerate(found) if i == 0 or x != found[i - 1]]

    compiles: dict[str, list[float]] = {}
    for name, secs in unique(re.findall(
            r"Finished XLA compilation of jit\((\w+)\) in ([0-9.]+) sec", text)):
        compiles.setdefault(name, []).append(float(secs))
    hits: dict[str, int] = {}
    for name, _key in unique(re.findall(
            r"Persistent compilation cache hit for 'jit_(\w+)' with key (\S+)",
            text)):
        hits[name] = hits.get(name, 0) + 1
    return {"compiles": compiles, "hits": hits}


def serve_phase(spec: dict, rehearse: bool, env: dict[str, str]) -> dict:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ir_dir = WORK_DIR / "ir"
    shutil.rmtree(ir_dir, ignore_errors=True)
    ir_dir.mkdir(parents=True)
    env = dict(env)
    env["APP__MODULES__API_GATEWAY__CONFIG__BIND_ADDR"] = f"127.0.0.1:{port}"
    env["JAX_DUMP_IR_TO"] = str(ir_dir)   # every module the server compiles
    env["JAX_LOG_COMPILES"] = "1"   # compile seconds and cache hits, by program
    if rehearse:
        env["APP__MODULES__MODEL_REGISTRY__CONFIG__MODELS"] = (
            f"[{{provider_slug: local, provider_model_id: {spec['model']}, "
            "approval_state: approved, managed: true, architecture: llama, "
            "capabilities: {chat: true, streaming: true}, engine_options: "
            f"{{model_config: {spec['model']}, quantization: {spec['quant']}, "
            f"max_seq_len: {spec['max_seq_len']}, max_batch: "
            f"{spec['max_batch']}, chat_family: mistral}}}}]")
    model_id = f"local::{spec['model']}"
    base = f"http://127.0.0.1:{port}"
    log_path = OUT_DIR / "server.log"
    entries_before = cache_entries()
    t_boot = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "cyberfabric_core_tpu.server", "run",
             "--config", str(CONFIG), "--mock"],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 180
        while True:
            require(proc.poll() is None,
                    f"server exited with code {proc.returncode} while booting")
            require(time.monotonic() < deadline, "server never became healthy")
            try:
                with urllib.request.urlopen(f"{base}/healthz", timeout=3):
                    break
            except (urllib.error.URLError, OSError):
                time.sleep(0.5)
        say(f"server: healthy {time.monotonic() - t_boot:.1f} s after start "
            f"(python -m cyberfabric_core_tpu.server run --config "
            f"{CONFIG.relative_to(REPO)} --mock)")

        nodes = http("GET", f"{base}/v1/nodes")["items"]
        accel = nodes[0]["accelerators"]
        require(bool(accel), "the server's node reports no accelerator")
        device = {"platform": accel[0]["platform"], "kind": accel[0]["model"],
                  "count": len(accel)}
        say(f"server: device {json.dumps(device)}, memory limit "
            f"{accel[0].get('total_memory_mb', 'not reported')} MB")
        require(device["platform"] == ("cpu" if rehearse else "tpu"),
                f"the server runs on {device['platform']!r}: JAX found no TPU")

        times = drive_requests(base, model_id)

        rounds = http("GET", f"{base}/v1/monitoring/rounds")["rounds"]
        kinds: dict[str, int] = {}
        for r in rounds.get(model_id, []):
            kinds[r["kind"]] = kinds.get(r["kind"], 0) + 1
        say(f"server: scheduler rounds by kind {json.dumps(kinds)}")
        require(kinds.get("mixed", 0) > 0, "no mixed round (prefill chunk + "
                                           "decode rows) ran")
        require(kinds.get("decode", 0) > 0, "no pure decode round ran")

        # read at scrape time from device.memory_stats() (modules/monitoring.py)
        with request("GET", f"{base}/metrics") as r:
            metrics = r.read().decode()
        in_use = float(re.search(r"^tpu_hbm_bytes_in_use (\S+)", metrics,
                                 re.M).group(1))
        if rehearse:
            say("server: device memory in use: not reported by the CPU backend")
        else:
            say(f"server: device memory in use {in_use / 1e9:.2f} GB "
                "(memory_stats()['bytes_in_use'], weights and page pool)")
            require(in_use >= MIN_RESIDENT_BYTES,
                    f"{in_use / 1e9:.2f} GB in use: the weights are not "
                    "resident on the device")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(30)
    log_text = log_path.read_text(errors="replace")
    require(proc.returncode == 0,
            f"server exited with code {proc.returncode} on SIGTERM")

    built = re.search(r"engine for \S+ built in ([0-9.]+) s", log_text)
    require(built is not None, "the server never logged its engine build")
    say(f"server: engine built in {built.group(1)} s (weight init, page pool);"
        f" first request {times['first_request_s']:.1f} s (that build, the "
        f"compiles, generation); warm request {times['warm_request_s']:.2f} s")
    say(f"compile cache: {cache_dir()} held {entries_before} entries before "
        f"the server ({'warm' if entries_before else 'cold'}), "
        f"{cache_entries()} after")

    # the serving programs: what JAX logged about compiling them, and the
    # modules it handed to the compiler (dumped cold or warm)
    parsed = parse_server_log(log_text)
    for name, want in (("mixed_step", len(MIXED_WIDTHS)),
                       ("paged_decode_chunk", 1)):
        secs = parsed["compiles"].get(name, [])
        dumps = sorted(ir_dir.glob(f"*_jit_{name}_compile.mlir"))
        require(len(dumps) == want, f"the server compiled {len(dumps)} "
                f"{name} programs, the traffic is designed for {want}")
        for d in dumps:
            mosaic = "tpu_custom_call" in d.read_text(errors="replace")
            require(mosaic != rehearse,
                    f"{d.name}: Mosaic custom call "
                    f"{'present in a rehearsal' if mosaic else 'missing'}")
        say(f"server: {want} {name} program(s): XLA compile or load from "
            f"cache {[round(x, 1) for x in secs]} s, persistent-cache hits "
            f"{parsed['hits'].get(name, 0)}; " + (
                "interpret mode, no Mosaic call (rehearsal)" if rehearse
                else "each holds a tpu_custom_call"))
    return device


def run_phase(phase: str, rehearse: bool, env: dict[str, str],
              timeout: float) -> dict:
    """One check phase as a child that holds the chip alone; its last line is
    ``RESULT {json}``."""
    cmd = [sys.executable, str(Path(__file__).resolve()), "--phase", phase,
           "--cache-dir", str(cache_dir())]
    if rehearse:
        cmd.append("--rehearse")
    proc = subprocess.Popen(cmd, cwd=REPO, env=env, stdout=subprocess.PIPE,
                            text=True)
    timer = threading.Timer(timeout, proc.kill)
    timer.start()
    result = None
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line.startswith("RESULT "):
                result = json.loads(line[len("RESULT "):])
            else:
                say(line)
        proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait(30)
    require(proc.returncode == 0,
            f"phase {phase} exited with code {proc.returncode}")
    require(result is not None, f"phase {phase} printed no result")
    return result


def parent(args: argparse.Namespace) -> int:
    require(CONFIG.is_file() and (REPO / "cyberfabric_core_tpu").is_dir(),
            f"{REPO} is not a checkout of the repository: chip_smoke.py "
            "drives the program, it is not the program")
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    spec = REHEARSAL if args.rehearse else CHIP
    t0 = time.monotonic()
    say(f"chip_smoke: {'REHEARSAL on the CPU' if args.rehearse else 'chip run'}"
        f", chips={args.chips}, model {spec['model']} {spec['quant']}, "
        f"max_seq_len {spec['max_seq_len']}, max_batch {spec['max_batch']}")
    devices = []
    if args.rehearse or args.chips == 1:
        env = child_env(args.rehearse, 1)
        build_native()
        devices.append(serve_phase(spec, args.rehearse, env))
        devices.append(run_phase("kernels", args.rehearse, env, 700)["device"])
        require(devices[0] == devices[1], f"the server saw {devices[0]}, the "
                                          f"kernel phase {devices[1]}")
    if args.rehearse or args.chips == 4:
        env = child_env(args.rehearse, 4)
        devices.append(
            run_phase("multichip", args.rehearse, env, 3000)["device"])
        require(devices[-1]["count"] == 4,
                f"the four-chip phases ran on {devices[-1]['count']} devices")
    say(f"chip_smoke: all phases passed in {time.monotonic() - t0:.0f} s")
    final = {"ok": True, "device": devices[-1]}
    if args.rehearse:
        final["rehearsal"] = True
    print(json.dumps(final), flush=True)
    return 0


# ---------------------------------------------------- children (import JAX)
def child_setup(args: argparse.Namespace):
    """Common start of a check phase: the platform, the versions, the compile
    cache and the native library. Returns (jax, device block, cache counts)."""
    import importlib.metadata as md

    import jax

    from cyberfabric_core_tpu.ops.platform import (default_interpret,
                                                   enable_compile_cache,
                                                   on_tpu)
    from cyberfabric_core_tpu.runtime import native

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    require(on_tpu() != args.rehearse,
            f"platform {devs[0].platform!r}: " + (
                "a rehearsal runs on the CPU" if args.rehearse
                else "JAX found no TPU"))
    require(default_interpret() == args.rehearse,
            f"default_interpret() is {default_interpret()}")
    stats = devs[0].memory_stats() or {}
    say(f"{args.phase}: python {sys.version.split()[0]}, jax {jax.__version__}"
        f", jaxlib {md.version('jaxlib')}, libtpu {md.version('libtpu')}")
    say(f"{args.phase}: device {json.dumps(device)}, bytes_limit "
        f"{stats.get('bytes_limit', 'not reported')}, default_interpret() "
        f"{default_interpret()}")
    used = enable_compile_cache()   # None on the CPU with no directory given
    require(used is None or Path(used) == Path(args.cache_dir),
            f"the program caches in {used}, chip_smoke.py expected "
            f"{args.cache_dir}")
    counts = {"hits": 0, "requests": 0}

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1

    jax.monitoring.register_event_listener(on_event)
    loaded = native._load() is not None
    say(f"{args.phase}: fabric_host: "
        f"{'native library loaded' if loaded else 'PYTHON FALLBACK'}")
    require(loaded, "native/fabric_host did not load")
    return jax, device, counts


#: kernel outputs are bf16 (8 mantissa bits, 2^-8 = 0.4% a rounding); flash
#: also rounds the probabilities to bf16 before p·v, and the f32 reference
#: sums in another order. Two roundings of values of order 1: 2e-2 absolute
#: plus 2e-2 relative. A wrong mask, page or head mapping is off by order 1.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
#: logits of a random-weight model are ~N(0, 1) per vocabulary entry. The
#: paths compared share every matmul and differ in how attention rounds, which
#: each layer's bf16 residual passes on: an error of about a hundredth of the
#: logits' standard deviation, so its worst over some 10^5 logits (4.5 sigma)
#: is held to a quarter of a standard deviation over the 4 layers of the
#: depth-cut model. Across engines at 32 layers (tp splits only the f32 sums
#: of wo/down, replicas run the same program) a fifth. A wrong mask, page
#: table, head mapping or sharding moves logits by about one deviation.
LOGIT_TOL_CUT, LOGIT_TOL_FULL = 0.25, 0.2


def own_pages(batch: int, pmax: int):
    """A page table that gives every row its own pages (page 0 is scratch)."""
    import numpy as np

    return 1 + np.arange(batch * pmax, dtype=np.int32).reshape(batch, pmax)


def check_close(name: str, got, ref) -> None:
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    require(bool(np.isfinite(got).all()), f"{name}: non-finite values")
    err = np.abs(got - ref)
    bound = KERNEL_ATOL + KERNEL_RTOL * np.abs(ref)
    say(f"kernels: {name}: shape {got.shape}, max |kernel - f32 reference| "
        f"{err.max():.4f} (tolerance {KERNEL_ATOL} + {KERNEL_RTOL}·|ref|)")
    require(bool((err <= bound).all()),
            f"{name}: {int((err > bound).sum())} of {err.size} values differ "
            f"from the f32 reference by more than the tolerance")


def check_logits(name: str, got, ref, tol: float) -> None:
    import numpy as np

    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    require(got.shape == ref.shape, f"{name}: shape {got.shape} vs {ref.shape}")
    require(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    err = np.abs(got - ref)
    worst, rms = float(err.max() / ref.std()), float(
        np.sqrt((err ** 2).mean()) / ref.std())
    agree = float((got.argmax(-1) == ref.argmax(-1)).mean())
    say(f"logits: {name}: shape {got.shape}, |Δ| / std: max {worst:.4f} "
        f"(tolerance {tol}), rms {rms:.4f}; arg-max agrees on {agree:.0%} of "
        "rows")
    require(worst <= tol, f"{name}: logits differ by {worst:.3f} standard "
                          f"deviations, tolerance {tol}")


def kernel_checks(jax, spec: dict, cfg) -> None:
    """The three Pallas kernels against ops/attention.py in float32, at the
    model's widths, in the dtype and forms the serving path uses."""
    import jax.numpy as jnp
    import numpy as np

    from cyberfabric_core_tpu.ops.attention import attention_with_cache
    from cyberfabric_core_tpu.ops.flash_attention import flash_self_attention
    from cyberfabric_core_tpu.ops.paged_attention import (
        paged_decode_attention, paged_gather_dense, ragged_paged_attention)
    from cyberfabric_core_tpu.ops.platform import default_interpret

    interpret = default_interpret()
    Hq, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    page, B = spec["page"], spec["kernel_batch"]
    pmax = spec["max_seq_len"] // page
    window = cfg.sliding_window
    bf16 = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))

    def rnd(*shape):
        return jax.random.normal(next(keys), shape, bf16)

    def reference(q, k, v, q_pos, kv_len):
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        with jax.default_matmul_precision("highest"):
            return jax.jit(lambda *a: attention_with_cache(
                *a, sliding_window=window))(f32(q), f32(k), f32(v), q_pos,
                                            kv_len)

    # flash prefill, one bucket, a full row and a ragged one
    T = spec["prefill"]
    lengths = jnp.asarray([T, T * 3 // 5], jnp.int32)
    q, k, v = rnd(2, T, Hq, D), rnd(2, T, Hkv, D), rnd(2, T, Hkv, D)
    out = flash_self_attention(q, k, v, lengths, interpret=interpret,
                               sliding_window=window)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (2, T))
    ref = reference(q, k, v, pos, lengths)
    for b, n in enumerate(np.asarray(lengths)):
        check_close(f"flash prefill T={T} row {b} (len {n})", out[b, :n],
                    ref[b, :n])

    # a two-layer page pool as the engine keeps it, read at its second layer
    n_pages, layer = B * pmax + 1, 1
    k_pool, v_pool = (rnd(2, n_pages, page, Hkv * D),
                      rnd(2, n_pages, page, Hkv * D))
    table = jnp.asarray(own_pages(B, pmax))
    k_dense, v_dense = paged_gather_dense(k_pool, v_pool, table, D, layer)
    cap = pmax * page

    # paged decode in the 2D-dot form a real compile uses, a page a trip
    # and the trip the serving path takes at these widths
    lens = jnp.asarray(([1, page - 1, page, page + 1, cap // 3, cap // 2,
                         cap - 1, cap] * B)[:B], jnp.int32)
    q = rnd(B, Hq, D)
    ref = reference(q[:, None], k_dense, v_dense, (lens - 1)[:, None], lens)
    for trip in (1, None):
        out = paged_decode_attention(
            q, k_pool, v_pool, table, lens, layer, interpret=interpret,
            sliding_window=window, two_d_dots=True, trip=trip)
        check_close(f"paged decode (two_d_dots, {trip or 'the shipped'} "
                    f"pages a trip) B={B} lens {lens.tolist()}", out,
                    ref[:, 0])

    # ragged mixed: decode rows, prefill chunks and idle rows in one call
    for width in MIXED_WIDTHS:
        q_lens = jnp.asarray(([1, width, 0, width * 5 // 8, 1, 0, width // 2,
                               1] * B)[:B], jnp.int32)
        hist = jnp.asarray(([cap // 2, 0, 0, page + 3, cap - 1, 0,
                             cap - width, page - 1] * B)[:B], jnp.int32)
        q = rnd(B, width, Hq, D)
        out = ragged_paged_attention(q, k_pool, v_pool, table, hist, q_lens,
                                     layer, interpret=interpret,
                                     sliding_window=window, two_d_dots=True)
        pos = hist[:, None] + jnp.arange(width, dtype=jnp.int32)[None]
        ref = reference(q, k_dense, v_dense, pos, hist + q_lens)
        for b, n in enumerate(np.asarray(q_lens)):
            if n:
                check_close(f"ragged mixed width {width} row {b} (q_len {n}, "
                            f"hist {int(hist[b])})", out[b, :n], ref[b, :n])


def model_checks(jax, spec: dict, cfg) -> None:
    """Prefill and a few decode steps of the model (published widths, depth
    cut, the serving quantization) through the kernels against the same steps
    through jnp attention over a dense cache. Logits are compared, not tokens:
    with random weights a rounding flips the arg-max."""
    import dataclasses

    import jax.numpy as jnp

    from cyberfabric_core_tpu.models import llama
    from cyberfabric_core_tpu.ops.rope import rope_frequencies
    from cyberfabric_core_tpu.runtime.quant import init_params_quantized

    cfg = dataclasses.replace(cfg, name=f"{cfg.name}-depth{spec['depth']}",
                              num_layers=spec["depth"])
    T, chunk, page, steps, B = spec["prefill"], spec["chunk"], spec["page"], 4, 2
    pmax = spec["max_seq_len"] // page
    t0 = time.monotonic()
    params = jax.block_until_ready(
        init_params_quantized(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    say(f"model: {cfg.name} ({spec['quant']}) weights made in "
        f"{time.monotonic() - t0:.1f} s")
    rope = rope_frequencies(cfg.head_dim, spec["max_seq_len"], cfg.rope_theta)
    rng = jax.random.PRNGKey(1)
    ids = jax.random.randint(rng, (B, T), 3, cfg.vocab_size, jnp.int32)
    forced = jax.random.randint(jax.random.fold_in(rng, 1), (steps, B), 3,
                                cfg.vocab_size, jnp.int32)
    lengths = jnp.asarray([T, T * 3 // 5], jnp.int32)
    zeros = jnp.zeros((B,), jnp.int32)

    # reference: jnp attention over a dense cache
    @jax.jit
    def ref_run(params, ids, forced):
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
        cache = llama.init_cache(cfg, B, spec["max_seq_len"], jnp.bfloat16)
        hidden, cache = llama.forward(params, cfg, ids, pos, cache, zeros, rope)
        out = [llama.lm_head_logits(
            params, cfg, llama.gather_last_hidden(hidden, lengths))]
        for i in range(steps):
            at = lengths + i
            hidden, cache = llama.forward(params, cfg, forced[i][:, None],
                                          at[:, None], cache, at, rope)
            out.append(llama.lm_head_logits(params, cfg, hidden[:, 0]))
        return jnp.stack(out)

    ref = ref_run(params, ids, forced)

    # flash prefill (the phase-separated and lockstep paths)
    flash = jax.jit(lambda params, ids: llama.lm_head_logits(
        params, cfg, llama.prefill_collect(params, cfg, ids, lengths, rope,
                                           use_flash=True)[0]))(params, ids)
    check_logits(f"flash prefill T={T}", flash, ref[0], LOGIT_TOL_CUT)

    # the serving path: chunked prefill through the ragged kernel into a page
    # pool, then paged decode steps
    @jax.jit
    def paged_run(params, ids, forced):
        shape = (cfg.num_layers, B * pmax + 1, page,
                 cfg.num_kv_heads * cfg.head_dim)
        pools = (jnp.zeros(shape, jnp.bfloat16), jnp.zeros(shape, jnp.bfloat16))
        table = own_pages(B, pmax)
        last = jnp.zeros((B, cfg.hidden_size), jnp.bfloat16)
        for c0 in range(0, T, chunk):
            q_lens = jnp.clip(lengths - c0, 0, chunk)
            hidden, pools = llama.forward_paged_mixed(
                params, cfg, ids[:, c0:c0 + chunk], pools, table,
                jnp.minimum(lengths, c0), q_lens, rope)
            ends_here = (lengths > c0) & (lengths <= c0 + chunk)
            last = jnp.where(ends_here[:, None],
                             llama.gather_last_hidden(hidden, q_lens), last)
        out = [llama.lm_head_logits(params, cfg, last)]
        for i in range(steps):
            hidden, pools = llama.forward_paged_decode(
                params, cfg, forced[i][:, None], pools, table, lengths + i,
                rope)
            out.append(llama.lm_head_logits(params, cfg, hidden[:, 0]))
        return jnp.stack(out)

    check_logits(f"ragged chunked prefill ({chunk}-token chunks) + {steps} "
                 "paged decode steps", paged_run(params, ids, forced), ref,
                 LOGIT_TOL_CUT)


def phase_kernels(args: argparse.Namespace) -> dict:
    jax, device, counts = child_setup(args)
    from cyberfabric_core_tpu.models import get_config

    spec = REHEARSAL if args.rehearse else CHIP
    cfg = get_config(spec["model"])
    kernel_checks(jax, spec, cfg)
    model_checks(jax, spec, cfg)
    say(f"kernels: compile cache: {counts['hits']} hits of "
        f"{counts['requests']} cacheable compiles in this phase")
    return {"device": device}


def engine_logits(jax, engine, ids, lengths, forced):
    """Logits of one ragged prefill and a few paged decode steps from an
    engine's own parameters, mesh and devices: [steps + 1, B, V] on the host.
    The prompts and forced tokens are the same for every engine compared."""
    import jax.numpy as jnp
    import numpy as np

    from cyberfabric_core_tpu.models import llama
    from cyberfabric_core_tpu.runtime.programs import serving_rope_tables

    cfg = engine.model_config
    page = engine.config.prefix_page_size
    (B, T), steps = ids.shape, forced.shape[0]
    pmax = -(-(T + steps) // page)
    shape = (cfg.num_layers, B * pmax + 1, page,
             cfg.num_kv_heads * cfg.head_dim)
    table, mesh = own_pages(B, pmax), engine._attn_mesh

    def run(params, rope, ids, lengths, forced):
        pools = (jnp.zeros(shape, engine.dtype), jnp.zeros(shape, engine.dtype))
        if engine._pool_sharding is not None:
            pools = jax.lax.with_sharding_constraint(
                pools, engine._pool_sharding)
        hidden, pools = llama.forward_paged_mixed(
            params, cfg, ids, pools, table, jnp.zeros((B,), jnp.int32),
            lengths, rope, mesh=mesh)
        first = llama.lm_head_logits(
            params, cfg, llama.gather_last_hidden(hidden, lengths))

        def step(carry, x):
            pools, at = carry
            hidden, pools = llama.forward_paged_decode(
                params, cfg, x[:, None], pools, table, at, rope, mesh=mesh)
            return (pools, at + 1), llama.lm_head_logits(params, cfg,
                                                         hidden[:, 0])

        _, rest = jax.lax.scan(step, (pools, lengths), forced)
        return jnp.concatenate([first[None], rest])

    with engine._device_ctx():
        out = jax.jit(run)(engine.params, engine._dev(serving_rope_tables(
                               cfg, engine.config.max_seq_len)),
                           *(engine._dev(np.asarray(x, np.int32))
                             for x in (ids, lengths, forced)))
        return np.asarray(out, np.float32)


MULTICHIP_PROMPTS = [PROMPT_SHORT, PROMPT_B, PROMPT_C,
                     CHAT_TEXT + " Answer briefly.", PROMPT_SHORT[::-1],
                     PROMPT_B[::-1], PROMPT_C[::-1], PROMPT_B + "?"]


def device0_in_use(jax) -> float | None:
    """GB held on the first device, where the backend reports it."""
    stats = jax.devices()[0].memory_stats() or {}
    return (round(stats["bytes_in_use"] / 1e9, 2)
            if "bytes_in_use" in stats else None)


def device0_holds(jax) -> str:
    held = device0_in_use(jax)
    return ("device 0 holds " + (f"{held} GB" if held is not None
                                 else "an amount the backend does not report"))


class Served:
    """One engine or pool built the way a user gets it — from
    ``engine_options`` through LocalTpuWorker — and driven through the
    worker's completion stream."""

    def __init__(self, jax, spec: dict, label: str, **parallel) -> None:
        import asyncio

        from cyberfabric_core_tpu.modules.llm_gateway.worker import \
            LocalTpuWorker
        from cyberfabric_core_tpu.modules.sdk import ModelInfo

        self.jax, self.label = jax, label
        self.model = ModelInfo(
            canonical_id=f"local::smoke-{label}", provider_slug="local",
            provider_model_id=f"smoke-{label}", managed=True,
            architecture="llama",
            engine_options={"model_config": spec["model"],
                            "quantization": spec["quant"],
                            "max_seq_len": spec["max_seq_len"],
                            "max_batch": spec["max_batch"], **parallel})
        self.worker = LocalTpuWorker({})
        t0 = time.monotonic()
        self.entry = asyncio.run(self.worker._entry_for(self.model))
        say(f"multichip: {label}: engine_options {parallel or '{}'} built in "
            f"{time.monotonic() - t0:.1f} s; {device0_holds(jax)}")

    def serve(self) -> list[list[int]]:
        """Every prompt at once, greedy; the token ids of each stream."""
        import asyncio

        async def one(prompt: str) -> list[int]:
            toks, finish = [], None
            async for chunk in self.worker.completion_stream(
                    self.model, prompt, {"max_tokens": 12, "temperature": 0,
                                         "_fed_token_stream": True}):
                if chunk.token_id is not None:
                    toks.append(chunk.token_id)
                finish = chunk.finish_reason or finish
            require(finish in ("stop", "length") and len(toks) > 0,
                    f"stream ended {finish!r} after {len(toks)} tokens")
            return toks

        async def all_of_them():
            return await asyncio.gather(*(one(p) for p in MULTICHIP_PROMPTS))

        t0 = time.monotonic()
        out = asyncio.run(all_of_them())
        say(f"multichip: {self.label}: {len(out)} concurrent requests served "
            f"in {time.monotonic() - t0:.1f} s (compiles included)")
        return out

    def retire(self) -> None:
        """Shut the engine down and drop it: chip 0 cannot hold the single
        engine, a tp shard and a replica at once."""
        import gc

        (self.entry.pool or self.entry.scheduler).shutdown()
        self.worker._entries.clear()
        self.worker = self.entry = None
        gc.collect()
        left = device0_in_use(self.jax)
        say(f"multichip: {self.label} shut down; {device0_holds(self.jax)}")
        require(left is None or left < 1.0,
                f"{left} GB still held on device 0 after {self.label}")


def phase_multichip(args: argparse.Namespace) -> dict:
    """tp against the single engine, then four dp replicas against it."""
    import numpy as np

    jax, device, counts = child_setup(args)
    spec = REHEARSAL if args.rehearse else CHIP
    require(device["count"] >= 4, "the four-chip phases need 4 devices, JAX "
                                  f"sees {device['count']}")
    rng = np.random.default_rng(0)
    B, T, steps = 4, 64, 2
    vocab = 256 if args.rehearse else 32000
    ids = rng.integers(3, vocab, (B, T))
    forced = rng.integers(3, vocab, (steps, B))
    lengths = np.asarray([T, T - 9, T // 2, 5])

    def same_tokens(a, b) -> str:
        same = sum(x == y for x, y in zip(a, b))
        return f"{same} of {len(a)} greedy streams token-identical"

    # ---- the single engine on chip 0: the reference for both phases
    one = Served(jax, spec, "tp1")
    ref_tokens = one.serve()
    ref = engine_logits(jax, one.entry.scheduler, ids, lengths, forced)
    one.retire()

    # ---- phase 1: the same model tensor-parallel
    tp = spec["tp"]
    sharded = Served(jax, spec, f"tp{tp}", tp=tp)
    eng = sharded.entry.scheduler
    leaves = jax.tree.leaves(eng.params)
    total = sum(x.nbytes for x in leaves)
    per_dev = sum(x.addressable_shards[0].data.nbytes for x in leaves)
    spans = {len(x.sharding.device_set) for x in leaves}
    k_pool = eng.pool.k_pool
    say(f"multichip: tp{tp}: parameters {total / 1e9:.2f} GB in all, "
        f"{per_dev / 1e9:.2f} GB on each of {max(spans)} devices; page pool "
        f"{k_pool.sharding.spec} over {len(k_pool.sharding.device_set)} "
        f"devices, shard {k_pool.addressable_shards[0].data.shape} of "
        f"{k_pool.shape}")
    require(spans == {tp}, f"parameter leaves span {spans} devices, not {tp}")
    require(per_dev < 0.75 * total, "the parameters are not partitioned")
    require(len(k_pool.sharding.device_set) == tp
            and "tp" in k_pool.sharding.spec,
            f"the page pool is not sharded over {tp} devices")
    say(f"multichip: tp{tp} vs tp1: {same_tokens(sharded.serve(), ref_tokens)}")
    check_logits(f"tp={tp} against tp=1",
                 engine_logits(jax, eng, ids, lengths, forced), ref,
                 LOGIT_TOL_FULL)
    del eng, leaves, k_pool
    sharded.retire()

    # ---- phase 2: four data-parallel replicas
    replicated = Served(jax, spec, "dp4", dp_replicas=4)
    pool = replicated.entry.pool
    homes = []
    for i, rep in enumerate(pool.replicas):
        on = {d for x in jax.tree.leaves(rep.params) for d in x.devices()}
        on |= rep.pool.k_pool.devices() | rep.pool.v_pool.devices()
        require(len(on) == 1, f"replica {i} is spread over {on}")
        homes.append(next(iter(on)))
    require(len(set(homes)) == 4, f"the replicas share devices: {homes}")
    say(f"multichip: dp4: parameters and page pool of each replica on its "
        f"own device: {[str(d) for d in homes]}")
    tokens = replicated.serve()
    served = [rep.requests_completed for rep in pool.replicas]
    say(f"multichip: dp4: requests completed per replica {served}; "
        f"vs tp1: {same_tokens(tokens, ref_tokens)}")
    require(sum(1 for n in served if n > 0) > 1,
            f"only one replica served requests: {served}")
    for i, rep in enumerate(pool.replicas):
        check_logits(f"dp replica {i} on {homes[i]} against the single "
                     "engine", engine_logits(jax, rep, ids, lengths, forced),
                     ref, LOGIT_TOL_FULL)
    del pool, rep
    replicated.retire()
    say(f"multichip: compile cache: {counts['hits']} hits of "
        f"{counts['requests']} cacheable compiles in this phase")
    return {"device": device}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[1],
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tp=4 and dp_replicas=4 phases, nothing else")
    ap.add_argument("--rehearse", action="store_true",
                    help="run every phase on the CPU at tiny size")
    ap.add_argument("--phase", choices=("kernels", "multichip"),
                    help=argparse.SUPPRESS)   # a child of this script
    ap.add_argument("--cache-dir", help=argparse.SUPPRESS)
    args = ap.parse_args()
    try:
        if args.phase:
            fn = {"kernels": phase_kernels, "multichip": phase_multichip}
            print("RESULT " + json.dumps(fn[args.phase](args)), flush=True)
            return 0
        return parent(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
