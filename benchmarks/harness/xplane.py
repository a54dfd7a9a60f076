"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read. Runs as a child (it imports jax for ``ProfileData``; the parent never
does):

    python -m benchmarks.harness.xplane <trace.xplane.pb> <summary.json>
    python -m benchmarks.harness.xplane <trace.xplane.pb> --describe

What a TPU trace holds (looked at by hand on a v5e trace of this program,
PR 23): one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA
Modules`` (one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per executed HLO
operation, nested: a ``while`` holds the operations of its body) and
``Steps``; host planes whose thread lines hold the program's
``TraceAnnotation`` scopes (``dynamo.decode[S512]``, ``dynamo.prefill[...]``)
on the same clock.

The summary:
  window_s    first device operation start -> last device operation end
  busy_s      union of the device's operation intervals in it, averaged over
              the chips that ran anything
  modules     per program name: runs, total and median seconds of one run
  ops         per operation (name without its number + result type; a
              custom call with its target): events, seconds, counted on leaf
              operations only (an operation that contains others, such as a
              ``while``, is their sum and would count twice)
  ops_by_module  per program name, its ten leaf operations with most
              device time, each attributed to the program run that contains
              it (a Pallas kernel is a ``tpu_custom_call``; the trace does
              not name the kernel, ``kernel_metadata={}``: which program ran
              it, and under which scope, ``harness/scopes.py``, does)
  breakdown   device_ops: the ten operation names with most device time;
              idle_gaps: idle device time by what the host was doing, i.e.
              the annotation the gap starts in, or follows
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import sys
from collections import defaultdict
from typing import Any, Dict, List, Tuple

Interval = Tuple[float, float]
ANNOTATION_PREFIX = "dynamo."


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def leaves(events: List[Tuple[float, float, str]]
           ) -> List[Tuple[float, float, str]]:
    """Events of one line that contain no other event of it."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, (s, e, name) in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt[0] >= e:
            out.append((s, e, name))
    return out


def base_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``jit_step(4711)`` -> ``jit_step``."""
    name = re.sub(r"\(.*\)$", "", name)
    return re.sub(r"[._]\d+$", "", name)


def op_key(text: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO line,
    ``%copy.814 = bf16[28,2,2177,64,128]{...} copy(...)``. Operations add up
    under ``<name without its number> <result type>``; a custom call also
    says its target (``tpu_custom_call`` is a Pallas kernel, ``TopK`` the
    sampler's)."""
    head, _, rest = text.partition(" = ")
    base = re.sub(r"[._]\d+$", "", head.strip().lstrip("%"))
    if not rest:
        return base
    shape = re.match(r"\(?([a-z]+\d*\[[\d,]*\])", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    if target and target.group(1) != base:
        base = f"{base}:{target.group(1)}"
    return f"{base} {shape.group(1)}" if shape else base


def label_gaps(gaps: List[Interval], spans: List[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Idle seconds by host activity: a gap that starts inside an annotation
    is ``in <name>``; otherwise ``after <name>`` of the last one that ended
    before it (the host is between dispatches: scheduling, HTTP, sampling
    results); ``before first dispatch`` when there is none."""
    spans = sorted(spans)
    starts = [sp[0] for sp in spans]
    out: Dict[str, float] = defaultdict(float)
    for gs, ge in gaps:
        # the scopes come from one engine thread, one after another: the
        # last one begun before the gap either holds it or precedes it
        i = bisect.bisect_right(starts, gs) - 1
        if i < 0:
            label = "before first dispatch"
        else:
            _, end, name = spans[i]
            label = ("in " if gs < end else "after ") + base_bucket(name)
        out[label] += ge - gs
    return out


def base_bucket(annotation: str) -> str:
    """``dynamo.decode[S512]`` -> ``dynamo.decode``: gaps add up by kind."""
    return annotation.split("[", 1)[0]


def read(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Tuple[float, float, str]]]] = {}
    host_spans: List[Tuple[float, float, str]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                     e.name) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        host_spans.append((
                            e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9, e.name))
    return {"devices": devices, "host_spans": host_spans}


def summarise(raw: Dict[str, Any]) -> Dict[str, Any]:
    busy, windows = [], []
    ops: Dict[str, List[float]] = defaultdict(list)
    modules: Dict[str, List[float]] = defaultdict(list)
    gaps_by_label: Dict[str, float] = defaultdict(float)
    by_module: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    for name, lines in sorted(raw["devices"].items()):
        op_events = lines.get("XLA Ops", [])
        if not op_events:
            continue
        merged = union([(s, e) for s, e, _ in op_events])
        start, end = merged[0][0], merged[-1][1]
        busy.append(sum(e - s for s, e in merged))
        windows.append(end - start)
        runs = sorted(lines.get("XLA Modules", []))
        starts = [r[0] for r in runs]
        for s, e, n in runs:
            modules[base_name(n)].append(e - s)
        for s, e, n in leaves(op_events):
            key = op_key(n)
            ops[key].append(e - s)
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < runs[i][1]:
                by_module[base_name(runs[i][2])][key] += e - s
        gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for label, sec in label_gaps(gaps, raw["host_spans"]).items():
            gaps_by_label[label] += sec
    annotations: Dict[str, int] = defaultdict(int)
    for _, _, name in raw["host_spans"]:
        annotations[base_bucket(name)] += 1
    if not busy:
        return {"busy_s": 0.0, "window_s": 0.0, "modules": {}, "ops": {},
                "breakdown": {"device_ops": [], "idle_gaps": []},
                "devices": sorted(raw["devices"]),
                "annotations": dict(annotations)}
    n = len(busy)
    top = lambda d: sorted(([k, v] for k, v in d.items()),
                           key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": sum(busy) / n, "window_s": sum(windows) / n,
        "devices": sorted(raw["devices"]), "annotations": dict(annotations),
        "modules": {k: {"runs": len(v), "total_s": sum(v),
                        "median_s": statistics.median(v)}
                    for k, v in modules.items()},
        "ops": {k: {"events": len(v), "total_s": sum(v) / n}
                for k, v in ops.items()},
        "ops_by_module": {m: dict(top({k: v / n for k, v in d.items()}))
                          for m, d in by_module.items()
                          if sum(d.values()) / n > 1e-3},
        "breakdown": {
            "device_ops": top({k: sum(v) / n for k, v in ops.items()}),
            "idle_gaps": top({k: v / n for k, v in gaps_by_label.items()})},
    }


def describe(path: str) -> None:
    """What is in the trace, for a reader's eye: planes, lines, the most
    frequent event names of each line and the stats of one event."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        print("PLANE", plane.name, dict(list(plane.stats)[:8]))
        for line in plane.lines:
            evs = list(line.events)
            names: Dict[str, List[float]] = defaultdict(list)
            for e in evs:
                names[op_key(e.name) if " = " in e.name
                      else base_name(e.name)].append(e.duration_ns)
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for k, v in sorted(names.items(), key=lambda kv: -sum(kv[1]))[:14]:
                print(f"      {k[:70]:70s} n={len(v):7d} "
                      f"total={sum(v) * 1e-6:10.3f} ms")
            if evs and plane.name.startswith("/device"):
                e = max(evs, key=lambda e: e.duration_ns)
                print("      longest:", e.name, e.duration_ns,
                      list(e.stats)[:12])


def main(argv: List[str]) -> int:
    if len(argv) == 3 and argv[2] == "--describe":
        describe(argv[1])
        return 0
    summary = summarise(read(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
