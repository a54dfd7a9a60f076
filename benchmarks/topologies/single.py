"""Topology ``single``: the whole system in one process on one chip —
``python -m dynamo_tpu.cli.run in=http out=jax`` (frontend, pre/post-
processing and engine), driven over HTTP by a parent that stays off jax.

A topology file offers ``start(plan) -> Handle``; the handle has ``base``
(the URL to drive), ``log`` (for error messages) and ``stop()``. ``plan``
carries: model_dir, model_name, engine (the configuration's engine block
with seed and warmup), env (extra environment), scratch, chips, ready_s.
"""

from __future__ import annotations

import json
import os
import sys

from benchmarks.harness import launch


class Handle:
    def __init__(self, proc, base: str, log: str):
        self.proc, self.base, self.log = proc, base, log

    def stop(self) -> None:
        launch.stop(self.proc)


def start(plan: dict) -> Handle:
    port = launch.free_port()
    log = os.path.join(plan["scratch"], "server.log")
    cmd = [sys.executable, "-m", "dynamo_tpu.cli.run", "in=http", "out=jax",
           "--http-host", "127.0.0.1", "--http-port", str(port),
           "--model-path", plan["model_dir"],
           "--model-name", plan["model_name"],
           "--extra-engine-args", json.dumps(plan["engine"])]
    proc = launch.spawn(cmd, log, launch.child_env(plan["env"]))
    handle = Handle(proc, f"http://127.0.0.1:{port}", log)
    try:
        handle.ready_s = launch.wait_ready(handle.base + "/health", proc, log,
                                           plan["ready_s"])
    except BaseException:
        handle.stop()
        raise
    return handle
