"""The load generator: one general generator, driven by a traffic file.

A traffic mix is data (``benchmark/traffic/<mix>.json``): its ``kind`` and
that kind's parameters. The one kind so far is ``closed``: ``clients``
requests kept outstanding against ``/v1/completions``, sizes drawn from the
stated distributions. A new mix of this kind is a new file, no code; a PR
that brings an open-loop cell brings its kind with it (PERF.md section 7 has
what the open loops tried in PR 23 read).

Every seed gives the *same multiset* of sizes: they are the quantiles of the
stated distribution at (i + 0.5) / n. Their order is drawn once, from
``PATTERN_SEED``; the run's ``--seed`` then permutes the sizes within blocks
of ``SEED_BLOCK`` consecutive requests, and gives every prompt its text. So
two seeds offer the same work in a locally different order, on different
tokens. The reason is the sample: at this system's speed a window holds some
tens of requests, and with the order left wholly to the seed a run's numbers
were set by where its long requests fell (PERF.md section 6). What a seed
cannot show, therefore, is how far traffic of other sizes would move a metric.

The schedule (pure, ``build_schedule``) is kept apart from the driver
(``drive``), which is one asyncio loop in the harness's own process. A
lead-in before the window (counted as set-up) brings the system to steady
state; requests that finish inside the window are measured.
"""

from __future__ import annotations

import asyncio
import json
import math
import random
import statistics
import time
from dataclasses import dataclass, field
from typing import Optional

_NORMAL = statistics.NormalDist()
_WORDS = ("page cache token batch decode chunk prefill tensor kernel queue "
          "router model weight vector stream window radix pool shard layer "
          "host device slot round budget prompt answer gateway worker trace"
          ).split()
#: the one order of sizes every run replays, and the span within which the
#: run's seed permutes it
PATTERN_SEED, SEED_BLOCK = 1, 4


# ------------------------------------------------------------------ schedule
def quantile_sizes(spec: dict, n: int) -> list[int]:
    """n sizes at the quantiles (i + 0.5) / n of the distribution ``spec``."""
    lo, hi = spec["min"], spec["max"]
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "lognormal":
            x = spec["median"] * math.exp(spec["sigma"] * _NORMAL.inv_cdf(u))
        elif spec["dist"] == "uniform":
            x = lo + (hi - lo) * u
        else:
            raise ValueError(f"unknown distribution {spec['dist']!r}")
        out.append(int(min(hi, max(lo, round(x)))))
    return out


def text_of(n_chars: int, rng: random.Random) -> str:
    """n_chars of lower-case words (one byte, so one token, a character)."""
    parts, size = [], 0
    while size < n_chars:
        w = rng.choice(_WORDS) + str(rng.randrange(10)) + " "
        parts.append(w)
        size += len(w)
    text = "".join(parts)[:n_chars]
    return text[:-1] + "." if text.endswith(" ") else text


@dataclass
class Planned:
    """One request as planned."""
    rid: str
    prompt_tokens: int
    max_tokens: int
    text_seed: int
    temperature: float


def _ordered(xs: list, pattern: random.Random, seed_rng: random.Random) -> list:
    """Pattern order, then the seed's permutation within each block."""
    xs = list(xs)
    pattern.shuffle(xs)
    for i in range(0, len(xs), SEED_BLOCK):
        block = xs[i: i + SEED_BLOCK]
        seed_rng.shuffle(block)
        xs[i: i + SEED_BLOCK] = block
    return xs


def build_schedule(mix: dict, seed: int) -> dict:
    """The whole plan of a run: ``items`` is the endless list (of ``cycle``
    requests) the ``clients`` draw from, in order."""
    if mix["kind"] != "closed":
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")
    pattern, rng = random.Random(PATTERN_SEED), random.Random(seed)
    n = int(mix.get("cycle", 256))
    prompts = _ordered(quantile_sizes(mix["prompt_tokens"], n), pattern, rng)
    outs = _ordered(quantile_sizes(mix["output_tokens"], n), pattern, rng)
    temp = float(mix.get("temperature", 0.0))
    items = [Planned(rid=f"c{i:05d}-{seed}", prompt_tokens=p, max_tokens=o,
                     text_seed=rng.getrandbits(48), temperature=temp)
             for i, (p, o) in enumerate(zip(prompts, outs))]
    return {"lead_in_s": float(mix.get("lead_in_s", 0.0)), "items": items,
            "clients": int(mix["clients"])}


def prompt_text(p: Planned, overhead: int) -> str:
    """The prompt of a planned request: ``prompt_tokens`` tokens once the
    endpoint's fixed overhead (the bos token) is added."""
    return text_of(p.prompt_tokens - overhead, random.Random(p.text_seed))


# -------------------------------------------------------------------- driver
@dataclass
class Sent:
    """What the client saw of one request. Times are time.monotonic()."""
    plan: Planned
    due: float
    sent: float = 0.0
    first: Optional[float] = None
    last: Optional[float] = None
    events: int = 0
    status: int = 0
    finish: Optional[str] = None
    input_tokens: int = 0
    output_tokens: int = 0
    error: str = ""
    cancelled: bool = False
    text: str = ""

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.finish in ("stop", "length")
                and not self.error)


@dataclass
class Driven:
    sent: list[Sent] = field(default_factory=list)
    window_start: float = 0.0
    window_end: float = 0.0
    window_start_wall: float = 0.0     # time.time(), to match round records
    window_end_wall: float = 0.0
    inflight_mid: int = 0
    inflight_end: int = 0


def body_for(p: Planned, model_id: str, overhead: int) -> dict:
    body = {"model": model_id, "stream": True, "max_tokens": p.max_tokens,
            "temperature": p.temperature, "prompt": prompt_text(p, overhead)}
    if p.temperature > 0:
        body["seed"] = p.text_seed & 0x7FFFFFFF
    return body


async def stream_one(session, base: str, body: dict, rec: Sent,
                     keep_text: bool = False, abandon: bool = False) -> None:
    """POST one streaming request and read it to ``data: [DONE]``; with
    ``abandon``, hang up at the first content event (a warm-up request: the
    program counts a client that went away as cancelled, and its SLO engine
    leaves cancelled requests out of every objective)."""
    rec.sent = time.monotonic()
    try:
        async with session.post(base + "/v1/completions", json=body,
                                headers={"x-request-id": rec.plan.rid}) as resp:
            rec.status = resp.status
            if resp.status != 200:
                rec.error = (await resp.text())[:300]
                return
            done = False
            async for raw in resp.content:
                line = raw.decode("utf-8", "replace").strip()
                if not line.startswith("data: "):
                    continue
                if line == "data: [DONE]":
                    done = True
                    break
                now = time.monotonic()
                ev = json.loads(line[6:])
                text = (ev.get("delta") or {}).get("content")
                if text:
                    if rec.first is None:
                        rec.first = now
                    rec.last = now
                    rec.events += 1
                    if keep_text:
                        rec.text += text
                    if abandon:
                        return
                if ev.get("finish_reason"):
                    rec.finish = ev["finish_reason"]
                    usage = ev.get("usage") or {}
                    rec.input_tokens = usage.get("input_tokens", 0)
                    rec.output_tokens = usage.get("output_tokens", 0)
                if ev.get("error"):
                    rec.error = json.dumps(ev["error"])[:300]
            if not done and not rec.error:
                rec.error = "stream ended without data: [DONE]"
    except asyncio.CancelledError:
        rec.cancelled = True
        raise
    except Exception as e:  # noqa: BLE001 — a failed request is counted, not raised
        rec.error = f"{type(e).__name__}: {e}"[:300]


async def drive(schedule: dict, base: str, model_id: str, overhead: int,
                seconds: float, on_window=None, on_tick=None) -> Driven:
    """Run the plan against the server. Returns when every answer that had
    started by the window's end has run out (a request still waiting for its
    first token then is cancelled)."""
    import aiohttp

    out = Driven()
    lead, clients = schedule["lead_in_s"], schedule["clients"]
    items, cursor = schedule["items"], 0
    t0 = time.monotonic()
    out.window_start, out.window_end = t0 + lead, t0 + lead + seconds
    out.window_start_wall = time.time() + lead
    out.window_end_wall = out.window_start_wall + seconds
    inflight = 0
    current: dict[int, Sent] = {}

    async def at(when: float) -> None:
        delay = when - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)

    async def client(i: int) -> None:
        # the clients start evenly spread over the lead-in, which the mix
        # sets to about one answer's length: completions, and so arrivals,
        # then come evenly spread from the start, with no burst at the start
        nonlocal inflight, cursor
        await at(t0 + lead * i / clients)
        while time.monotonic() < out.window_end:
            p = items[cursor % len(items)]
            cursor += 1
            p = Planned(**{**p.__dict__, "rid": f"{p.rid}-{cursor}"})
            rec = Sent(plan=p, due=time.monotonic())
            current[i] = rec
            out.sent.append(rec)
            inflight += 1
            try:
                await stream_one(session, base, body_for(p, model_id, overhead),
                                 rec)
            finally:
                inflight -= 1
            if rec.status == 429:       # shed: a client waits as it is told
                await asyncio.sleep(2.0)

    async def ticker() -> None:
        mid = out.window_start + seconds / 2
        await at(out.window_start)
        if on_window:
            await on_window("start")
        while time.monotonic() < out.window_end:
            if on_tick:
                await on_tick(time.monotonic() - out.window_start)
            await asyncio.sleep(0.5)
            if out.inflight_mid == 0 and time.monotonic() >= mid:
                out.inflight_mid = inflight
        out.inflight_end = inflight
        if on_window:
            await on_window("end")

    timeout = aiohttp.ClientTimeout(total=None, sock_read=300)
    conn = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(timeout=timeout, connector=conn) as session:
        tick = asyncio.create_task(ticker())
        tasks = [asyncio.create_task(client(i)) for i in range(clients)]
        await tick
        for i, t in enumerate(tasks):
            rec = current.get(i)
            if rec is not None and rec.first is None and not t.done():
                t.cancel()
        for r in await asyncio.gather(*tasks, return_exceptions=True):
            if isinstance(r, Exception) and not isinstance(
                    r, asyncio.CancelledError):
                raise r
    return out
