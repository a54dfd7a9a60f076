#!/usr/bin/env python3
"""Builder's tool: cut a recorded ``.xplane.pb`` down to a fixture.

    JAX_PLATFORMS=cpu python benchmarks/tests/trim_trace.py <in.xplane.pb> \\
        <out.xplane.pb.gz> <expected.json> [--runs 4]

Keeps, of every ``/device:`` plane, the lines ``XLA Modules`` and ``XLA Ops``
from the start of the trace to the end of the first ``--runs`` runs of the
bucket programs (``jit_step``, ``jit_fn``), and of the host planes only the
``dynamo.*`` annotations in that span: names and times as recorded, nothing
made up. Writes the reduction of the result beside it, which
``test_xplane.py`` holds the reduction to.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import xplane  # noqa: E402

KEEP_LINES = ("XLA Modules", "XLA Ops")


def _quote(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _line(lid: int, name: str, events, meta: dict) -> str:
    """One line as text proto; ``meta`` (name -> id) is the plane's table of
    event names and grows as new names appear."""
    text = " ".join(
        f"events {{ metadata_id: {meta.setdefault(n, len(meta) + 1)} "
        f"offset_ps: {round(s * 1e12)} duration_ps: {round((e - s) * 1e12)} }}"
        for s, e, n in events)
    return f"lines {{ id: {lid} name: {_quote(name)} {text} }}"


def _plane(pid: int, name: str, lines) -> str:
    meta: dict = {}
    body = " ".join(_line(i, n, evs, meta)
                    for i, (n, evs) in enumerate(lines, 1))
    table = " ".join(
        f"event_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} "
        f"}} }}" for n, i in meta.items())
    return f"planes {{ id: {pid} name: {_quote(name)} {body} {table} }}"


def main() -> int:
    from jax.profiler import ProfileData

    p = argparse.ArgumentParser()
    p.add_argument("src")
    p.add_argument("dst")
    p.add_argument("expected")
    p.add_argument("--runs", type=int, default=4)
    a = p.parse_args()
    raw = xplane.read(a.src)
    out = []
    for name, lines in sorted(raw["devices"].items()):
        runs = sorted(r for r in lines.get("XLA Modules", [])
                      if xplane.base_name(r[2]) in ("jit_step", "jit_fn"))
        if not runs:
            continue
        cut = runs[min(a.runs, len(runs)) - 1][1]
        out.append(_plane(len(out) + 1, name, [
            (ln, [e for e in lines.get(ln, []) if e[1] <= cut])
            for ln in KEEP_LINES]))
        out.append(_plane(len(out) + 1, "/host:CPU", [
            ("jax-engine", [sp for sp in raw["host_spans"] if sp[1] <= cut])]))
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with gzip.open(a.dst, "wb") as f:
        f.write(blob)
    tmp = a.dst + ".tmp.pb"
    with open(tmp, "wb") as f:
        f.write(blob)
    try:
        summary = xplane.summarise(xplane.read(tmp))
    finally:
        os.remove(tmp)
    with open(a.expected, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"{os.path.getsize(a.dst)} bytes; busy {summary['busy_s']:.4f}s of "
          f"{summary['window_s']:.4f}s; modules "
          f"{ {k: v['runs'] for k, v in summary['modules'].items()} }")
    return 0


if __name__ == "__main__":
    sys.exit(main())
