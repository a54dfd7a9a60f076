"""Start, probe and stop the system under test; read its ``/metrics``.

Copied in substance from ``chip_smoke.py`` (free_port / http / wait_ready /
stop / parse_metrics), which PERF.md's table of existing pieces judged sound.
The parent process that uses this never imports jax.
"""

from __future__ import annotations

import json
import os
import re
import signal
import socket
import subprocess
import time
import urllib.error
import urllib.request
from typing import Dict, List, Tuple

from .catalog import ROOT, BenchError

Series = List[Tuple[str, Dict[str, str], float]]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def child_env(extra: Dict[str, str]) -> Dict[str, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    # no tokenizer or hub code may look for a network that is not there
    env.setdefault("HF_HUB_OFFLINE", "1")
    env.setdefault("TRANSFORMERS_OFFLINE", "1")
    env.update(extra)
    return env


def spawn(cmd: List[str], log: str, env: Dict[str, str]) -> subprocess.Popen:
    """A child that leads its own session, so ``stop`` reaches whatever it
    starts; output goes to ``log``."""
    with open(log, "w") as lf:
        return subprocess.Popen(cmd, cwd=ROOT, env=env, stdout=lf,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)


def http(method: str, url: str, body=None, timeout: float = 600.0):
    """-> (status, parsed json or text); HTTP errors are returned."""
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method, headers={
        "Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            raw, status = r.read().decode(), r.status
    except urllib.error.HTTPError as e:
        raw, status = e.read().decode(), e.code
    try:
        return status, json.loads(raw)
    except ValueError:
        return status, raw


def wait_ready(url: str, proc: subprocess.Popen, log: str,
               timeout: float) -> float:
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if proc.poll() is not None:
            raise BenchError(f"server exited {proc.returncode} during "
                             f"start-up: {tail(log)[-3000:]}")
        try:
            status, _ = http("GET", url, timeout=2.0)
            if status == 200:
                return time.monotonic() - t0
        except (urllib.error.URLError, OSError):
            pass
        time.sleep(0.25)
    raise BenchError(f"server not ready after {timeout:.0f}s: "
                     f"{tail(log)[-3000:]}")


def stop(proc: subprocess.Popen, grace: float = 30.0) -> None:
    """Stop a child and everything in its session, and wait for it."""
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=grace)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)     # stragglers of the group
    except ProcessLookupError:
        pass
    proc.wait()


def parse_metrics(text: str) -> Series:
    """Prometheus text -> [(name, {label: value}, float)]."""
    out = []
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        m = re.match(r"^([a-zA-Z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$", line)
        if not m:
            continue
        labels = dict(re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"',
                                 m.group(2) or ""))
        try:
            out.append((m.group(1), labels, float(m.group(3))))
        except ValueError:
            pass
    return out


def scrape(base: str) -> Series:
    status, text = http("GET", base + "/metrics", timeout=30.0)
    if status != 200 or not isinstance(text, str):
        raise BenchError(f"/metrics: HTTP {status}")
    return parse_metrics(text)


def metric_sum(series: Series, name: str, **match: str) -> float:
    return sum(v for n, l, v in series if n == name
               and all(l.get(k) == w for k, w in match.items()))


def metric_max(series: Series, name: str) -> float:
    return max((v for n, _, v in series if n == name), default=0.0)


def delta(before: Series, after: Series, name: str, **match: str) -> float:
    return metric_sum(after, name, **match) - metric_sum(before, name, **match)
