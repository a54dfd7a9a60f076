#!/usr/bin/env python3
"""Builder's tool: the device's idle share in the LATER part of a trace.

    python benchmarks/tests/steady_idle.py <file.xplane.pb> [--after 2.0]

A cell's traced run captures the first ``trace_steps`` engine iterations, in
a closed-loop cell mostly the ramp from an empty batch, where every iteration
admits a prompt. ``trace_cost.py --steps N`` captures more; this reads that
trace through the harness's own ``xplane.read`` / ``summarise`` with every
device event that starts in the first ``--after`` seconds dropped, so that
busy time, window, idle gaps by phase and the programs' medians are those of
the steady state alone. Prints one JSON object. Not part of any check."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import xplane  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("path")
    p.add_argument("--after", type=float, default=2.0)
    a = p.parse_args()
    raw = xplane.read(a.path)
    starts = [e[0] for lines in raw["devices"].values()
              for e in lines.get("XLA Ops", [])]
    if not starts:
        print(json.dumps({"error": "no device operations in the trace"}))
        return 1
    cut = min(starts) + a.after
    for lines in raw["devices"].values():
        for name in lines:
            lines[name] = [e for e in lines[name] if e[0] >= cut]
    s = xplane.summarise(raw)
    out = {"after_s": a.after, "busy_s": s["busy_s"],
           "window_s": s["window_s"],
           "idle_share": (100.0 * (1 - s["busy_s"] / s["window_s"])
                          if s["window_s"] else None),
           "modules": s["modules"],
           "idle_gaps": s["breakdown"]["idle_gaps"],
           "device_ops": s["breakdown"]["device_ops"][:6]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
