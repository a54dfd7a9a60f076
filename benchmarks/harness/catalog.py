"""Find everything that belongs to one cell by the names in the manifest.

The harness holds no list of names. A cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration, traffic mix, arrival generator,
topology and per-layer metrics are files named after the manifest's entries:

    configs/<config>.json         traffic/<mix>.json
    generators/<kind>.py          topologies/<name>.py
    layer_metrics/<metric>.py

``roots`` is searched in order, so a test can put a directory of its own in
front of ``benchmarks/`` and add a cell without touching a file that exists.
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
