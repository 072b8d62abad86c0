"""Multi-process federation e2e: ONE gateway stack (grpc_hub + llm_gateway
with ``federation.enabled``) routing over TWO real worker subprocesses on
loopback. Proves the ISSUE's acceptance story over actual process
boundaries:

* both worker hosts announce and show up on ``GET /v1/monitoring/workers``;
* a repeated-prefix request lands on the host already holding the prefix
  (placement reason ``prefix`` on the flight-recorder timeline);
* a mid-stream SIGKILL of the serving host fails over to the survivor and
  the delivered SSE text is BIT-IDENTICAL to the clean run, with exactly
  one terminal; the corpse is evicted (reason ``crash``) and visible on the
  workers table;
* both hosts' decode chunks sit under ONE request id / trace — the
  gateway-to-tokens trace crosses the process boundary twice;
* fabric-fleetscope: worker heartbeats carry observability payloads, the
  gateway /metrics exports the workers' ``llm_*`` series host-labeled,
  ``GET /v1/monitoring/requests/{id}`` stitches the worker-side flight
  record into the gateway's under one request id, and a readback delay
  armed ON a worker over REST degrades it on ``GET /v1/monitoring/fleet``
  with the health rung provably steering new requests to the healthy host.

CPU JAX + tiny-llama; every endpoint is loopback. The in-process unit truth
lives in tests/test_federation.py and tests/test_fleetscope.py.
"""

import asyncio
import json
import os
import signal
import subprocess
import sys
import time

import aiohttp
import pytest

from conftest import boot_stack, stop_stack

MODEL_KEY = "local::tiny-llama"
# decode_chunk 2: itl_ms derives from gaps BETWEEN decode_chunk flight
# events — at the default chunk of 8 an 8-token request has a single event
# and the workers' itl objective never sees a sample
ENGINE_OPTIONS = {"model_config": "tiny-llama", "max_seq_len": 256,
                  "max_batch": 4, "decode_chunk": 2}

CONFIG = {
    "tracing": {"enabled": True, "sample_ratio": 1.0},
    "modules": {
        "api_gateway": {"config": {"bind_addr": "127.0.0.1:0",
                                   "timeout_secs": 30.0}},
        "tenant_resolver": {"config": {"tenants": {
            "root": {}, "acme": {"parent": "root"}}}},
        "authn_resolver": {"config": {"mode": "accept_all",
                                      "default_tenant": "acme"}},
        "authz_resolver": {},
        "types_registry": {}, "types": {},
        "module_orchestrator": {},
        "nodes_registry": {"config": {"tenant": "acme"}},
        "model_registry": {"config": {
            "seed_tenant": "acme",
            "models": [
                {"provider_slug": "local", "provider_model_id": "tiny-llama",
                 "approval_state": "approved", "managed": True,
                 "architecture": "llama", "format": "safetensors",
                 "capabilities": {"chat": True, "streaming": True},
                 "limits": {"max_input_tokens": 200,
                            "max_output_tokens": 64},
                 "engine_options": ENGINE_OPTIONS},
            ],
        }},
        # fast leases so the crash test observes eviction quickly; the
        # federated pool resolves the hub's WorkerRegistry lazily
        "grpc_hub": {"config": {"bind_addr": "127.0.0.1:0",
                                "worker_lease_ttl_s": 3.0,
                                "eviction_interval_s": 0.5}},
        "llm_gateway": {"config": {"federation": {
            "enabled": True, "failover_backoff_s": 0.01, "seed": 0}}},
        # this e2e asserts routing and failover, not the GATEWAY's SLOs: its
        # doctor is conftest's QUIET_DOCTOR, which neither CPU compiles nor
        # the DELIBERATE host kill burn (allow_fault_injection is for the
        # cross-host arm in the fleet-doctor test, where the fault fires in
        # a WORKER)
        "monitoring": {"config": {"allow_fault_injection": True}},
    }
}

#: the WORKER-side doctors run a TIGHT itl objective: 150ms sits far above
#: steady-state CPU mean itl (~tens of ms — itl_ms amortizes any one-off
#: stall over the whole request) and far below the armed 0.5s/chunk
#: readback delay (~250ms/token at decode_chunk 2), so only a deliberately
#: faulted host can degrade. min_samples 1 because a faulted request takes
#: longer than the fast window — terminals arrive one per window at best.
#: shed_after is high (the fleet tests prove the GATEWAY steers on
#: ``degraded`` — the worker never self-sheds) and recover_after is high so
#: the sick host stays degraded for the probe assertions (~14s: 4s fast
#: window drain + 40 clean evals) instead of flapping back mid-test
WORKER_OBSERVABILITY = {
    "allow_fault_injection": True,
    "doctor": {
        "eval_interval_s": 0.25, "fast_window_s": 4.0, "slow_window_s": 8.0,
        "min_samples": 1, "shed_after": 1000, "recover_after": 40,
        # ONLY the itl objective is under test — with min_samples 1 the
        # default ttft/queue/error objectives become hair-triggers (one
        # cold compile or stray error would degrade the HEALTHY host and
        # the router would rightly stop steering), so pin them untrippable
        "objectives": {"itl_p99": {"threshold_ms": 150.0},
                       "ttft_p95": {"threshold_ms": 120000.0},
                       "queue_wait_p95": {"threshold_ms": 120000.0},
                       "error_rate": {"budget": 1.0}},
        "stream_stall_s": 120.0, "round_stall_floor_s": 120.0,
        "queue_deadline_s": 120.0,
    },
}

# >= 2 digest blocks (48 chars each) so the gossiped chain carries a hint
PROMPT_A = "federated e2e prefix probe alpha " * 4
PROMPT_B = "federated e2e crash victim bravo " * 4


@pytest.fixture(scope="module")
def fed(tmp_path_factory):
    """Boot the gateway stack, then 2 worker subprocesses dialing its hub."""
    from cyberfabric_core_tpu.modules.llm_gateway.grpc_service import \
        model_ref_dict
    from cyberfabric_core_tpu.modules.sdk import ModelInfo

    loop = asyncio.new_event_loop()
    rt, base = loop.run_until_complete(boot_stack(CONFIG))
    hub = rt.registry.get("grpc_hub").instance

    model = ModelInfo(canonical_id=MODEL_KEY, provider_slug="local",
                      provider_model_id="tiny-llama", managed=True,
                      architecture="llama", engine_options=ENGINE_OPTIONS)
    procs, ready = [], []
    try:
        for i in range(2):
            worker_cfg = json.dumps({
                "hub_endpoint": hub.endpoint,
                "host": f"fedhost-{i}", "worker": {},
                "observability": WORKER_OBSERVABILITY,
                "models": [model_ref_dict(model)],
                "heartbeat_interval_s": 0.25})
            procs.append(subprocess.Popen(
                [sys.executable, "-m",
                 "cyberfabric_core_tpu.modules.llm_gateway.worker"],
                env={**os.environ, "JAX_PLATFORMS": "cpu",
                     "FED_WORKER_CONFIG": worker_cfg},
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
                text=True))

        async def read_ready(p):
            line = await asyncio.wait_for(
                asyncio.get_running_loop().run_in_executor(
                    None, p.stdout.readline), 240.0)
            if not line:
                raise RuntimeError(f"worker died before READY (rc={p.poll()})")
            return json.loads(line)

        for p in procs:
            ready.append(loop.run_until_complete(read_ready(p)))

        # warm BOTH hosts before any test runs: the first completion on a
        # host pays the CPU compile, and the workers run TIGHT itl doctors
        # — drain that transient here so only a deliberately armed fault
        # can degrade a host once the tests start
        async def warm():
            async with aiohttp.ClientSession() as s:
                served, i = set(), 0
                deadline = time.monotonic() + 120.0
                while served < {"fedhost-0", "fedhost-1"}:
                    assert time.monotonic() < deadline, \
                        f"warmup never reached both hosts: {served}"
                    rid = f"fed-e2e-warm-{i}"
                    async with s.post(
                            base + "/v1/completions",
                            headers={"X-Request-Id": rid},
                            json={"model": MODEL_KEY,
                                  "prompt": f"warmup probe {i} " * 4,
                                  "max_tokens": 4}) as r:
                        assert r.status == 200, await r.read()
                    async with s.get(
                            base + f"/v1/monitoring/requests/{rid}") as r:
                        served.add((await r.json()).get("worker_host"))
                    i += 1
                while True:  # compile-transient degradations must clear
                    assert time.monotonic() < deadline, "hosts never settled"
                    async with s.get(base + "/v1/monitoring/fleet") as r:
                        doc = await r.json()
                    states = {h.get("host"): h.get("state")
                              for h in doc.get("hosts", [])}
                    if states == {"fedhost-0": "healthy",
                                  "fedhost-1": "healthy"}:
                        return
                    await asyncio.sleep(0.25)

        loop.run_until_complete(warm())
        yield loop, base, ready
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
            if p.stdout is not None:
                p.stdout.close()
        loop.run_until_complete(stop_stack(rt))
        loop.close()


def req(fed, method, path, **kw):
    loop, base, _ = fed

    async def go():
        async with aiohttp.ClientSession() as s:
            async with s.request(method, base + path, **kw) as r:
                raw = await r.read()
                try:
                    return r.status, json.loads(raw)
                except (json.JSONDecodeError, UnicodeDecodeError):
                    return r.status, raw

    return loop.run_until_complete(go())


def completion(fed, prompt, rid, max_tokens=12):
    status, body = req(fed, "POST", "/v1/completions",
                       headers={"X-Request-Id": rid},
                       json={"model": MODEL_KEY, "prompt": prompt,
                             "max_tokens": max_tokens})
    assert status == 200, body
    return body["content"][0]["text"]


def timeline(fed, rid):
    status, body = req(fed, "GET", f"/v1/monitoring/requests/{rid}")
    assert status == 200, body
    return body


def wait_for(fed, cond, timeout_s=30.0, interval_s=0.2):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = cond()
        if got:
            return got
        time.sleep(interval_s)
    raise AssertionError("condition not met within timeout")


def workers_table(fed):
    status, body = req(fed, "GET", "/v1/monitoring/workers")
    assert status == 200, body
    return body


def test_both_hosts_announce_and_are_listed(fed):
    body = wait_for(fed, lambda: (
        lambda b: b if len(b["workers"]) == 2 else None)(workers_table(fed)))
    assert body["federation"] is True
    hosts = {w["host"] for w in body["workers"]}
    assert hosts == {"fedhost-0", "fedhost-1"}
    for w in body["workers"]:
        assert w["expires_in_s"] > 0 and w["endpoint"]
    # the per-worker drill-down resolves; an unknown id is a typed 404
    iid = body["workers"][0]["instance_id"]
    status, row = req(fed, "GET", f"/v1/monitoring/workers/{iid}")
    assert status == 200 and row["instance_id"] == iid
    status, problem = req(fed, "GET", "/v1/monitoring/workers/nope")
    assert status == 404 and problem["code"] == "unknown_worker"


def test_repeated_prefix_lands_on_the_prefix_host(fed):
    text1 = completion(fed, PROMPT_A, "fed-e2e-a1")
    first_host = timeline(fed, "fed-e2e-a1")["worker_host"]
    assert first_host

    # the serving host gossips its radix prefix on the next heartbeats;
    # once the chain is visible on the workers table, the repeat must land
    # on the SAME host for reason ``prefix``
    wait_for(fed, lambda: any(
        w["host"] == first_host and w["prefix_index"].get(MODEL_KEY)
        for w in workers_table(fed)["workers"]))
    text2 = completion(fed, PROMPT_A, "fed-e2e-a2")
    assert text2 == text1  # greedy decode: same prompt, same tokens
    tl = timeline(fed, "fed-e2e-a2")
    assert tl["worker_host"] == first_host
    # stitched timelines interleave the WORKER's own admitted events, which
    # carry no gateway placement — look only at the gateway's
    admitted = [e for e in tl["timeline"]
                if e["event"] == "admitted" and "placement" in e]
    assert admitted and admitted[-1]["placement"] == "prefix"


def _host_state(fed, host):
    status, doc = req(fed, "GET", f"/v1/monitoring/fleet?host={host}")
    if status != 200 or not doc.get("hosts"):
        return "unknown"
    return doc["hosts"][0].get("state", "unknown")


def test_stitched_timeline_under_one_request_id(fed):
    """The monitoring endpoint pulls the serving worker's flight record over
    the hub and stitches it into the gateway's — both origins, one wall-clock
    order, one request id."""
    completion(fed, "stitch this cross host story " * 4, "fed-e2e-s1")

    tl = wait_for(fed, lambda: (lambda d: d if d.get("stitched") else None)(
        timeline(fed, "fed-e2e-s1")))
    host = tl["worker_host"]
    assert "gateway" in tl["origins"] and host in tl["origins"]

    worker_events = [e for e in tl["timeline"] if e.get("origin") == host]
    assert worker_events, "no worker-side events made it into the stitch"
    assert tl["segments"][host]["events"] == len(worker_events)
    assert {e.get("origin") for e in tl["timeline"]} == {"gateway", host}
    ts = [float(e.get("ts") or 0.0) for e in tl["timeline"]]
    assert ts == sorted(ts), "stitched events out of wall-clock order"


def test_fleet_endpoint_lists_hosts_and_404s_unknown(fed):
    status, doc = req(fed, "GET", "/v1/monitoring/fleet")
    assert status == 200 and doc["federation"] is True
    assert {h["host"] for h in doc["hosts"]} == {"fedhost-0", "fedhost-1"}
    for h in doc["hosts"]:
        assert h["state"] in ("healthy", "recovering")
        assert h["lease_age_s"] < CONFIG["modules"]["grpc_hub"][
            "config"]["worker_lease_ttl_s"]
    status, problem = req(fed, "GET", "/v1/monitoring/fleet?host=no-such")
    assert status == 404 and problem["code"] == "unknown_host"


def test_host_labeled_worker_metrics_on_gateway(fed):
    import re

    def scrape():
        status, body = req(fed, "GET", "/metrics")
        assert status == 200
        return body.decode() if isinstance(body, (bytes, bytearray)) \
            else str(body)

    # both hosts report healthy 0/1 gauges under their own label, and the
    # workers' own llm_* series ride the scrape host-labeled
    text = wait_for(fed, lambda: (lambda t: t if (
        'llm_remote_workers_healthy{host="fedhost-0"} 1' in t
        and 'llm_remote_workers_healthy{host="fedhost-1"} 1' in t) else None
        )(scrape()))
    assert re.search(r'llm_[a-z_]+\{[^}]*host="fedhost-[01]"', text)
    # exposition stays valid: ONE TYPE header per family even when the
    # gateway and the fleet both carry the series
    families = [line.split()[2] for line in text.splitlines()
                if line.startswith("# TYPE ")]
    assert len(families) == len(set(families))


# waits out a real burn/steer/recover cycle (~60 s on top of the shared
# stack) — too heavy for the tier-1 budget; the fleet-doctor-shed faultlab
# scenario drives the same flow in `make chaos` and the CI faultlab leg
@pytest.mark.slow
def test_fleet_doctor_marks_sick_host_and_routing_steers(fed):
    """Arm a readback delay ON one worker over REST, watch its burn cross on
    the fleet endpoint, prove the health rung routes new requests to the
    healthy host with bit-identical tokens, then disarm and recover."""
    burn_prompt = "fleet burn victim prompt " * 4
    baseline = completion(fed, burn_prompt, "fed-e2e-f0", max_tokens=8)
    target = timeline(fed, "fed-e2e-f0")["worker_host"]
    healthy = next(h for h in ("fedhost-0", "fedhost-1") if h != target)

    status, body = req(fed, "PUT",
                       "/v1/monitoring/failpoints/scheduler.readback",
                       json={"spec": "delay(0.5)", "host": target})
    assert status == 200, body
    assert body == {"armed": "scheduler.readback", "host": target}

    try:
        # prefix affinity pins the burn to the armed host while it is still
        # healthy; each request feeds it ~500ms itl samples until the
        # worker doctor's fast window crosses the 300ms objective
        deadline, i = time.monotonic() + 90.0, 0
        while _host_state(fed, target) not in ("degraded", "shedding"):
            assert time.monotonic() < deadline, "burn never crossed"
            i += 1
            assert completion(fed, burn_prompt, f"fed-e2e-f{i}",
                              max_tokens=8) == baseline
        sick_state = _host_state(fed, target)
        assert sick_state == "degraded"  # shed_after is high: gateway steers

        # the fleet doc and /readyz both NAME the host; the gateway itself
        # stays ready — a sick worker is a routing problem, not an outage
        status, doc = req(fed, "GET", "/v1/monitoring/fleet")
        assert status == 200 and doc["state"] == "degraded"
        assert any(target in r for r in doc["reasons"])
        status, ready = req(fed, "GET", "/readyz")
        assert status == 200
        assert any(target in r for r in ready.get("reasons", []))

        # the SAME prompt (prefix on the sick host!) now steers to the
        # healthy host, tokens unchanged
        # the SAME prompt (prefix on the sick host!) keeps steering away —
        # which placement reason gets attributed depends on whose gossiped
        # chain wins once the healthy host caches the prompt too, so the
        # deterministic ``health``-attribution assertions live in
        # tests/test_fleetscope.py; here the behavioral truth is the host
        for j in range(3):
            rid = f"fed-e2e-fp{j}"
            assert completion(fed, burn_prompt, rid,
                              max_tokens=8) == baseline
            assert timeline(fed, rid)["worker_host"] == healthy
    finally:
        status, body = req(
            fed, "DELETE",
            f"/v1/monitoring/failpoints/scheduler.readback?host={target}")
        assert status == 200 and body.get("disarmed") is True

    # disarmed: the worker doctor walks the host back and it serves the
    # baseline again — leave the fleet clean for the crash test below
    wait_for(fed, lambda: _host_state(fed, target) == "healthy",
             timeout_s=60.0)
    assert completion(fed, burn_prompt, "fed-e2e-f-after",
                      max_tokens=8) == baseline


def test_midstream_sigkill_fails_over_bit_identical(fed):
    loop, base, ready = fed
    baseline = completion(fed, PROMPT_B, "fed-e2e-b0", max_tokens=16)
    rid = "fed-e2e-b1"

    async def crash_stream():
        text, finishes, killed = [], [], None
        async with aiohttp.ClientSession() as s:
            async with s.post(
                    base + "/v1/completions",
                    headers={"X-Request-Id": rid},
                    json={"model": MODEL_KEY, "prompt": PROMPT_B,
                          "max_tokens": 16, "stream": True}) as r:
                assert r.status == 200
                assert r.headers["X-Request-Id"] == rid
                buf = ""
                async for raw, _ in r.content.iter_chunks():
                    buf += raw.decode()
                    while "\n\n" in buf:
                        frame, buf = buf.split("\n\n", 1)
                        if not frame.startswith("data: "):
                            continue
                        payload = frame[len("data: "):]
                        if payload == "[DONE]":
                            continue
                        chunk = json.loads(payload)
                        if chunk.get("delta", {}).get("content"):
                            text.append(chunk["delta"]["content"])
                        if chunk.get("finish_reason"):
                            finishes.append(chunk["finish_reason"])
                        if text and killed is None:
                            # first token arrived: kill the serving host
                            async with s.get(
                                    base + f"/v1/monitoring/requests/{rid}"
                                    ) as mr:
                                host = (await mr.json())["worker_host"]
                            victim = next(r_ for r_ in ready
                                          if r_["host"] == host)
                            os.kill(victim["pid"], signal.SIGKILL)
                            killed = host
        return "".join(text), finishes, killed

    text, finishes, killed = loop.run_until_complete(crash_stream())
    assert killed, "no host was killed mid-stream"
    assert text == baseline  # bit-identical across the failover
    assert len(finishes) == 1 and finishes[0] in ("stop", "length")

    # the corpse is evicted (crash report beats the lease sweep) and the
    # workers table shows one survivor + the eviction reason
    body = wait_for(fed, lambda: (
        lambda b: b if len(b["workers"]) == 1 else None)(workers_table(fed)))
    assert body["workers"][0]["host"] != killed
    assert any(e["host"] == killed and e["reason"] in ("crash",
                                                       "lease_expired")
               for e in body["evicted"])

    # ONE request id covers tokens from BOTH processes: decode chunks in
    # the timeline carry both worker hosts, under a single trace
    tl = timeline(fed, rid)
    # worker-origin decode events carry no gateway worker_host — drop the
    # None the stitch introduces before counting gateway-side hosts
    chunk_hosts = {e.get("worker_host")
                   for e in tl["timeline"]
                   if e["event"] == "decode_chunk"} - {None}
    assert len(chunk_hosts) == 2
    failovers = [e for e in tl["timeline"] if e["event"] == "failover"]
    assert len(failovers) == 1
    assert failovers[0]["carried_tokens"] >= 1
    assert tl["trace_id"], "gateway trace id missing from the record"

    # the survivor keeps serving, baseline-identical (prefix now re-warmed)
    assert completion(fed, PROMPT_B, "fed-e2e-b2", max_tokens=16) == baseline


def test_federated_metrics_exported(fed):
    status, body = req(fed, "GET", "/metrics")
    assert status == 200
    text = body.decode() if isinstance(body, (bytes, bytearray)) else str(body)
    assert "llm_remote_workers_healthy" in text
    assert "llm_federated_placements_total" in text
