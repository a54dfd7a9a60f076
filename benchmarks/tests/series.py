#!/usr/bin/env python3
"""Builder's tool: run one cell several times in one call, one seed a run,
as the check does, and keep every result line.

    python benchmarks/tests/series.py --workload <cell> --seeds 11,12,13 \\
        --seconds 45 [--trace 0] [--tag name] [--probe]

Appends each run's last line to ``chiprun_out/<tag>.jsonl`` and prints the
metrics of each; on a failure it prints the end of the run's errors and keeps
the server's log. Spreads are computed with ``stats.spread`` at the end.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks.harness.stats import spread  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--tag", default=None)
    p.add_argument("--probe", action="store_true")
    a = p.parse_args()
    tag = a.tag or f"{a.workload}.t{a.trace}"
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    values = {}
    for seed in a.seeds.split(","):
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
               "--workload", a.workload, "--seed", seed,
               "--seconds", str(a.seconds), "--trace", str(a.trace)]
        if a.probe:
            cmd.append("--probe")
        t0 = time.monotonic()
        r = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        took = time.monotonic() - t0
        lines = r.stdout.strip().splitlines()
        if r.returncode != 0 or not lines:
            print(f"RUN FAILED seed={seed} rc={r.returncode} {took:.0f}s: "
                  f"{r.stderr[-3000:]}", flush=True)
            log = os.path.join(ROOT, ".bench_scratch", a.workload,
                               "server.log")
            if os.path.exists(log):
                shutil.copy(log, os.path.join(out_dir, f"{tag}.server.log"))
            continue
        line = json.loads(lines[-1])
        line["run_took_s"] = took
        with open(os.path.join(out_dir, f"{tag}.jsonl"), "a") as f:
            f.write(json.dumps(line) + "\n")
        m = {k: v["value"] for k, v in line["metrics"].items()}
        for k, v in m.items():
            values.setdefault(k, []).append(v)
        print(json.dumps({
            "seed": seed, "took_s": round(took, 1),
            "correct": line["correct"], "attempted": line["attempted"],
            "failed": line["failed"], "metrics": m,
            "device": line["device"], "checks": line["checks"],
            "setup_split": line["setup_split"],
            "in_flight_at_close": line["in_flight_at_close"],
            "breakdown": line.get("breakdown"),
            "first_failure": line["first_failure"]}), flush=True)
    print("SPREADS", json.dumps({
        k: {"n": len(v), "median": sorted(v)[len(v) // 2],
            "spread": spread(v)} for k, v in values.items()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
