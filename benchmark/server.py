"""The server child and the few calls the harness makes to it besides the
load: health, device, metrics, monitoring, profiler. No JAX here."""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
WORK = REPO / ".bench_work"           # logs, traces, the server's home
#: ops/platform.py COMPILE_CACHE_DIR, repeated because the parent may not
#: import the package: one fixed path inside the checkout
DEFAULT_CACHE_DIR = REPO / ".jax_cache"

COMPILE_LINES = re.compile(
    r"Finished XLA compilation of jit\((\w+)\) in ([0-9.]+) sec")
CACHE_HITS = re.compile(
    r"Persistent compilation cache hit for 'jit_(\w+)' with key (\S+)")

class HarnessFailure(Exception):
    """The run cannot give a result: non-zero exit, no result line."""


def child_env(rehearse: bool) -> dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO) + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(DEFAULT_CACHE_DIR))
    # a cap on the cache's size turns a cell whose programs outgrow it into
    # one that compiles in every run (LRU, read in order): no cap
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    env.setdefault("TPU_LOG_DIR", str(WORK / "tpu_logs"))
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    return env


def http_json(method: str, url: str, body: dict | None = None,
              timeout: float = 120.0) -> dict:
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"content-type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        raise HarnessFailure(f"{method} {url} -> HTTP {e.code}: "
                             f"{e.read().decode('utf-8', 'replace')[:300]}")


def http_text(url: str, timeout: float = 60.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.read().decode()


def parse_prometheus(text: str) -> dict[str, float]:
    """Unlabelled samples only: name -> value."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*) ([0-9.eE+-]+|NaN)$", line)
        if m:
            out[m.group(1)] = float(m.group(2))
    return out


def dedupe(found: list) -> list:
    """JAX's handler and the server's both print each record."""
    return [x for i, x in enumerate(found) if i == 0 or x != found[i - 1]]


class Server:
    """``python -m cyberfabric_core_tpu.server run --config <yaml> --mock`` on
    a free port, its output in a log file the harness reads."""

    def __init__(self, yaml_path: str, rehearse: bool) -> None:
        self.yaml, self.rehearse = yaml_path, rehearse
        self.log_path = WORK / "server.log"
        self.proc: subprocess.Popen | None = None
        self.base = ""

    def start(self) -> None:
        WORK.mkdir(parents=True, exist_ok=True)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        env = child_env(self.rehearse)
        env["APP__MODULES__API_GATEWAY__CONFIG__BIND_ADDR"] = f"127.0.0.1:{port}"
        env["JAX_LOG_COMPILES"] = "1"
        self.base = f"http://127.0.0.1:{port}"
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "cyberfabric_core_tpu.server", "run",
                 "--config", self.yaml, "--mock"],
                cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT)
        deadline = time.monotonic() + 240
        while True:
            if self.proc.poll() is not None:
                raise HarnessFailure(
                    f"server exited with code {self.proc.returncode} while "
                    f"booting:\n{self.log()[-2000:]}")
            if time.monotonic() > deadline:
                raise HarnessFailure("server never became healthy")
            try:
                with urllib.request.urlopen(f"{self.base}/healthz", timeout=3):
                    return
            except (urllib.error.URLError, OSError):
                time.sleep(0.25)

    def device(self) -> dict:
        nodes = http_json("GET", f"{self.base}/v1/nodes")["items"]
        accel = nodes[0]["accelerators"] if nodes else []
        if not accel:
            raise HarnessFailure("the server's node reports no accelerator")
        return {"platform": accel[0]["platform"], "kind": accel[0]["model"],
                "count": len(accel)}

    def metrics(self) -> dict[str, float]:
        return parse_prometheus(http_text(f"{self.base}/metrics"))

    def log(self) -> str:
        return self.log_path.read_text(errors="replace")

    def log_size(self) -> int:
        return self.log_path.stat().st_size

    def wait_idle(self, timeout: float) -> bool:
        """Until the flight recorder's live table is empty: a SIGTERM with
        cancelled streams still unwinding ends the server with an abort."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            got = http_json("GET", f"{self.base}/v1/monitoring/requests?recent=0")
            if not got["in_flight"]:
                return True
            time.sleep(0.5)
        return False

    def stop(self) -> int | None:
        if self.proc is None:
            return None
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(30)
        return self.proc.returncode


def run_child(module: str, args: list[str], rehearse: bool,
              timeout: float) -> tuple[int, dict | None, str]:
    """A child that holds the chip alone; its last line is ``RESULT {json}``.
    Returns (exit code, result, output)."""
    cmd = [sys.executable, "-m", module, *args]
    try:
        proc = subprocess.run(cmd, cwd=REPO, env=child_env(rehearse),
                              stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                              text=True, timeout=timeout)
    except subprocess.TimeoutExpired as e:
        return 124, None, (e.stdout or "")[-4000:] if isinstance(
            e.stdout, str) else ""
    result = None
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            result = json.loads(line[7:])
    return proc.returncode, result, proc.stdout
