#!/usr/bin/env python3
"""Builder's tool, on the chip: does this tree's reference write what another
tree's wrote, to the digit?

    python benchmarks/tests/same_reference.py <reference_in.json> \\
        <reference_out.json> [--reference llama] [--out verdict.json]

``reference_in.json`` and ``reference_out.json`` are what a run of the OTHER
tree (a parent unpacked beside this one) left in its
``.bench_scratch/<cell>/``. The job is given to this tree's
``python -m benchmarks.harness.reference`` as a child (this process stays off
jax, so the child has the chip), with ``reference`` filled in where the other
tree's harness did not name one yet, and the four lists of every sample are
compared for equality under ``full`` and under each probe variant that the
other tree scored. For a PR that moves or blocks a reference's mathematics:
the same device and the same compiled mathematics give the same digits, and
anything else is a changed yardstick. Exit 0 when all are identical. Not
part of any check.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks.harness import launch  # noqa: E402
from benchmarks.harness.reference import PROBE_VARIANTS  # noqa: E402
from benchmarks.harness.catalog import ROOT  # noqa: E402

LISTS = ("logit_std", "served_logprob", "best_logprob", "best_token")


def differences(theirs, ours):
    """-> (lists compared, lists that differ, widest absolute difference)."""
    n = bad = 0
    widest = 0.0
    for a, b in zip(theirs, ours):
        for key in LISTS:
            n += 1
            if a[key] != b[key]:
                bad += 1
                widest = max([widest] + [abs(x - y)
                                         for x, y in zip(a[key], b[key])])
    return n, bad, widest


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("job")
    p.add_argument("expected")
    p.add_argument("--reference", default=None,
                   help="the name to give a job that carries none")
    p.add_argument("--out", default=None)
    a = p.parse_args()
    with open(a.job) as f:
        job = json.load(f)
    with open(a.expected) as f:
        theirs = json.load(f)
    if "reference" not in job:
        if not a.reference:
            p.error("the job names no reference: give --reference")
        job["reference"] = a.reference
    job.pop("catalog", None)           # the other tree's paths mean nothing
    scratch = os.path.join(ROOT, ".bench_scratch", "same_reference")
    os.makedirs(scratch, exist_ok=True)
    src, dst = (os.path.join(scratch, n) for n in ("in.json", "out.json"))
    with open(src, "w") as f:
        json.dump(job, f)
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.harness.reference", src, dst],
        cwd=ROOT, env=launch.child_env({}), capture_output=True, text=True)
    if r.returncode != 0:
        print(r.stdout[-3000:] + r.stderr[-3000:], file=sys.stderr)
        return 1
    with open(dst) as f:
        ours = json.load(f)
    verdict = {"device": [theirs["device"], ours["device"]],
               "samples": len(job["samples"]), "variants": {}}
    same = theirs["device"] == ours["device"]
    for variant in ("full", *PROBE_VARIANTS):
        if variant not in theirs:
            continue
        n, bad, widest = differences(theirs[variant], ours.get(variant, []))
        ok = (bad == 0 and variant in ours
              and len(theirs[variant]) == len(ours[variant]) > 0)
        same = same and ok
        verdict["variants"][variant] = {
            "identical": ok, "lists": n, "lists_that_differ": bad,
            "widest_abs_difference": widest}
    verdict["identical"] = same
    verdict["timing"] = {"theirs": theirs["timing"], "ours": ours["timing"]}
    text = json.dumps(verdict)
    if a.out:
        with open(a.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
