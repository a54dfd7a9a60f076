"""Local multi-process orchestrator for @service graphs.

    serve = LocalServe("examples.hello_world:Frontend",
                       config={"Backend": {...}}, platform="cpu")
    serve.start()      # store + one process per service worker, TPU chips
    ...                # allocated per service `resources={"tpu": n}`
    serve.stop()

The orchestrator: (1) starts a dynstore coordination server unless given an
existing one, (2) walks the graph (links + depends) from the entry service,
(3) allocates accelerator chips per worker, (4) spawns each worker as
``python -m dynamo_tpu.sdk.serve_child`` with the per-service YAML config
injected through the DYN_SERVICE_CONFIG env JSON, and (5) waits for every
worker's READY line.

Reference capability: deploy/dynamo/sdk/cli/serving.py:120-251 (circus
watchers per service + GPU allocator + env-injected config).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Type

from .allocator import TpuAllocator
from .service import SERVICE_CONFIG_ENV, collect_graph
from .serve_child import READY_MARKER, load_class


def host_has_tpu() -> bool:
    """Does this machine expose TPU chips? Decided from the accelerator
    device nodes (``/dev/accel*`` up to v4, ``/dev/vfio/<n>`` from v5e on) —
    the orchestrator must not touch JAX itself: a parent that initialises
    the TPU runtime holds the chips its workers need."""
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


class LocalServe:
    def __init__(self, entry: str, config: Optional[Dict[str, Any]] = None,
                 store: Optional[str] = None, platform: str = "auto",
                 total_chips: int = 4, cwd: Optional[str] = None):
        self.entry_spec = entry
        self.entry: Type = load_class(entry) if isinstance(entry, str) else entry
        self.config = dict(config or {})
        self.store = store
        self.platform = platform
        self.total_chips = total_chips
        self.cwd = cwd or os.getcwd()
        self.procs: List[subprocess.Popen] = []
        self._store_proc: Optional[subprocess.Popen] = None

    # ------------------------------------------------------------------
    def _ensure_store(self) -> str:
        if self.store:
            return self.store
        # free port
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        self._store_proc = subprocess.Popen(
            [sys.executable, "-m", "dynamo_tpu.runtime.store_server",
             "--port", str(port)],
            cwd=self.cwd, stdout=subprocess.DEVNULL,
            stderr=subprocess.STDOUT)
        self.store = f"127.0.0.1:{port}"
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            try:
                probe = socket.create_connection(("127.0.0.1", port), 0.5)
                probe.close()
                return self.store
            except OSError:
                time.sleep(0.1)
        raise RuntimeError("dynstore failed to start")

    # ------------------------------------------------------------------
    def start(self, timeout: float = 120.0) -> "LocalServe":
        store = self._ensure_store()
        platform = self.platform
        if platform == "auto":
            platform = "tpu" if host_has_tpu() else "cpu"
        alloc = TpuAllocator(self.total_chips, platform)
        services = collect_graph(self.entry)

        waiters = []
        try:
            self._spawn_all(services, alloc, store, waiters)
        except BaseException:
            self.stop()
            raise
        return self._await_ready(waiters, timeout)

    def _spawn_all(self, services, alloc, store, waiters) -> None:
        for cls in services:
            spec = cls._dynamo_spec
            if not (spec.endpoints or spec.on_start or spec.dependencies):
                continue   # pure grouping node (a graph entry like AggGraph)
            mod = cls.__module__
            section = self.config.get(cls.__name__, {})
            workers = int(section.get("workers", spec.workers))
            chips = int(section.get("resources", {}).get(
                "tpu", spec.resources.get("tpu", 0)))
            for w in range(workers):
                env = dict(os.environ)
                env[SERVICE_CONFIG_ENV] = json.dumps(self.config)
                env.update(alloc.allocate(chips, service=spec.name))
                p = subprocess.Popen(
                    [sys.executable, "-m", "dynamo_tpu.sdk.serve_child",
                     f"{mod}:{cls.__name__}", "--store", store],
                    cwd=self.cwd, env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True)
                self.procs.append(p)
                waiters.append((spec.name, p))

    def _await_ready(self, waiters, timeout: float) -> "LocalServe":
        # wait for every worker's READY marker (reader threads keep pipes
        # drained afterwards so children never block on stdout)
        ready = {}
        lock = threading.Lock()

        def pump(name, p):
            for line in p.stdout:
                if READY_MARKER in line:
                    with lock:
                        ready[p] = True
                sys.stderr.write(f"[{name}] {line}")

        threads = [threading.Thread(target=pump, args=(n, p), daemon=True)
                   for n, p in waiters]
        for t in threads:
            t.start()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with lock:
                if len(ready) == len(waiters):
                    return self
            dead = [p for _, p in waiters if p.poll() is not None]
            if dead:
                self.stop()
                raise RuntimeError(
                    f"{len(dead)} service worker(s) exited during bring-up")
            time.sleep(0.1)
        self.stop()
        raise RuntimeError("serve bring-up timed out")

    def stop(self) -> None:
        for p in self.procs:
            p.terminate()
        for p in self.procs:
            try:
                p.wait(timeout=5)
            except subprocess.TimeoutExpired:
                p.kill()
        self.procs.clear()
        if self._store_proc is not None:
            self._store_proc.terminate()
            self._store_proc = None
