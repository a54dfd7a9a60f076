"""Find everything that belongs to one cell by the names in the manifest.

The harness holds no list of names. A cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration, traffic mix, arrival generator,
topology, float32 reference and metrics are files named after the manifest's
entries, or after a key of the file such an entry names:

    configs/<config>.json         traffic/<mix>.json
    generators/<kind>.py          topologies/<name>.py
    references/<name>.py          e2e_metrics/<metric>.py
    layer_metrics/<metric>.py

``<kind>`` and ``<name>`` of a generator and a topology are the mix's
``generator`` and ``topology``; ``<name>`` of a reference is the
configuration file's ``benchmark.reference``. An unknown name, or a
configuration that names no reference, is a ``BenchError``, never a default.

``roots`` is searched in order, so a test can put a directory of its own in
front of ``benchmarks/`` and add a cell without touching a file that exists.

What a file of each kind offers:

- generator: ``plan(params, seconds) -> {"block", "blocks"}`` and the
  coroutine ``run(load)`` that ``runner.drive_window`` awaits (described in
  ``generators/open_poisson.py``).
- topology: ``start(plan) -> handle`` with ``base`` (the URL to drive),
  ``log`` and ``stop()``; ``plan`` is described in ``topologies/single.py``.
- metric: ``reduce(run)`` (end to end) or ``reduce(scrapes, trace, run)``
  (per layer) -> a number, or None where there is nothing to read.
- reference: the model's mathematics in float32, written from its published
  description, and the only code of the benchmark that knows the layout of a
  model's weights. Exactly two functions, called by ``reference.py`` in a
  child of its own once the server has left the chip:
  ``build(config, seed) -> state``: ``config`` is the configuration file
  without its ``benchmark`` group; the weights come from the program's own
  seeded init, so that the same seed gives the server and the reference the
  same tensors, with whatever dimensions the file needs beside them.
  ``tail_logprobs(state, tokens, first, n_tail, variant) -> [n_tail, V]``:
  float32 log-softmax over the vocabulary at positions ``first ..
  first + n_tail - 1`` of the one sequence ``tokens`` (int32 [T], T a
  multiple of 128, zeros after the last real token), computed under
  ``jax.default_matmul_precision("highest")``. ``variant`` is ``full`` (the
  model), ``dropped_layer`` (its last layer switched off) or ``int8`` (every
  weight matrix rounded to int8 per output channel): the two broken ones are
  what the tolerance has to fail (``--probe``). How a file blocks its
  computation so that a long context fits is its own business.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional, Sequence

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class BenchError(Exception):
    """Anything that stops a run from printing a result line."""


class Catalog:
    def __init__(self, manifest_path: Optional[str] = None,
                 roots: Sequence[str] = ()):
        self.manifest_path = manifest_path or os.path.join(
            ROOT, "BENCHMARK.json")
        self.roots = [*roots, BENCH]
        try:
            with open(self.manifest_path) as f:
                self.manifest = json.load(f)
        except OSError as e:
            raise BenchError(f"no manifest: {e}") from e

    # -- manifest ---------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.manifest["workloads"])
        raise BenchError(f"no workload {name!r} in the manifest ({known})")

    def metrics(self, group: str, cell: str) -> List[Dict[str, Any]]:
        """The manifest's ``end_to_end`` or ``per_layer`` entries that this
        cell reports (an entry without ``workloads`` is for every cell)."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or cell in m["workloads"]]

    # -- files ------------------------------------------------------------
    def find(self, kind: str, name: str, ext: str) -> str:
        for root in self.roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise BenchError(f"no {kind}/{name}{ext} under {self.roots}")

    def data(self, kind: str, name: str) -> Dict[str, Any]:
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = self.find(kind, name, ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
