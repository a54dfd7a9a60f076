#!/usr/bin/env python3
"""Builder's tool, on the chip: one run of a cell whose sample is also put
to the configuration's OWN control variants of its reference, beyond the
probe's two, through the comparison that decides ``correct``.

    python benchmarks/tests/own_variants.py --workload <cell> --seed <n> \\
        --seconds <s> [--variants top7,experts_zeroed,...]

The cell runs exactly as ``benchmarks/run.py --probe`` runs it (the same
``cell.run_cell``: server, window, sample, reference child); nothing of the
harness is changed. The one thing added: ``correct.compare`` is watched, so
that the served tokens AND their served log-probabilities of the sample are
kept (``reference_in.json`` holds the tokens alone). Then, the chip free
again, a child of this file teacher-forces those tokens under every further
variant the reference file offers (``VARIANTS`` without ``full`` and the
probe's two) as ``harness/reference.py`` does it, and each goes through
``correct.compare`` with the configuration's own limit against the served
log-probabilities: a control has to come out NOT correct. The last line of
standard output is the run's own result line with one key more,
``own_variants``: per variant its verdict and the three numbers compared,
and what the reference said about near-tied routing.
"""

from __future__ import annotations

import time

_STARTED = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def score(job_path: str, served_path: str, variants) -> int:
    """The child: holds the chip; prints {variant: verdict}."""
    from benchmarks.harness import correct, reference
    from benchmarks.harness.catalog import Catalog
    from dynamo_tpu.utils.jaxenv import init_compile_cache

    init_compile_cache()
    with open(job_path) as f:
        job = json.load(f)
    with open(served_path) as f:
        kept = json.load(f)
    where = job.get("catalog", {})
    module = Catalog(where.get("manifest"), where.get("roots", ())).module(
        "references", job["reference"])
    state = module.build(job["config"], int(job["seed"]))
    skip = ("full",) + tuple(reference.PROBE_VARIANTS)
    out = {}
    for variant in variants or [v for v in module.VARIANTS if v not in skip]:
        scored = reference.score_samples(module, state, job["samples"],
                                         variant)
        verdict = correct.compare(kept["served"], scored, kept["rel_rms_tol"])
        out[variant] = {k: v for k, v in verdict.items()
                        if k.startswith("rel_") or k in ("ok", "argmax_agree")}
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int)
    p.add_argument("--seconds", type=float)
    p.add_argument("--variants", default="")
    p.add_argument("--rehearse", action="store_true",
                   help="the plumbing on the CPU, against the rehearsal "
                        "tree's cells (tests/rehearsal/): no result")
    p.add_argument("--score", nargs=2, metavar=("JOB", "SERVED"),
                   help="the child's entry: score and print")
    a = p.parse_args(argv)
    variants = [v for v in a.variants.split(",") if v]
    if a.score:
        return score(*a.score, variants)

    from benchmarks.harness import cell, correct, launch
    from benchmarks.harness.catalog import BenchError, Catalog
    from benchmarks.run import compared

    kept = {}
    compare = correct.compare

    def watched(served, reference, rel_rms_tol=correct.REL_RMS_TOL):
        kept.update(served=served, rel_rms_tol=rel_rms_tol)
        return compare(served, reference, rel_rms_tol)

    correct.compare = watched
    cat = None
    if a.rehearse:
        root = os.path.join(HERE, "rehearsal")
        cat = Catalog(os.path.join(root, "BENCHMARK.json"), roots=[root])
    try:
        code, line = cell.run_cell(a.workload, a.seed, a.seconds, False,
                                   _STARTED, catalog=cat,
                                   rehearsal=a.rehearse, probe=True)
    except BenchError as e:
        print(f"benchmark run failed: {e}", file=sys.stderr)
        return 1
    finally:
        correct.compare = compare
    print(compared(line), file=sys.stderr, flush=True)
    scratch = os.path.join(cell.SCRATCH, a.workload)
    served_path = os.path.join(scratch, "served_sample.json")
    with open(served_path, "w") as f:
        json.dump(kept, f)
    child = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--score",
         os.path.join(scratch, "reference_in.json"), served_path,
         "--variants", ",".join(variants)],
        cwd=ROOT, env=launch.child_env({}), capture_output=True, text=True)
    said = [l for l in child.stderr.splitlines() if "router logits" in l]
    if child.returncode != 0:
        print(child.stderr[-3000:], file=sys.stderr)
        return 1
    line["own_variants"] = json.loads(child.stdout.strip().splitlines()[-1])
    line["near_ties"] = said
    with open(os.path.join(scratch, "reference.log")) as f:
        line["near_ties_of_the_run"] = [l.strip() for l in f
                                        if "router logits" in l]
    for variant, v in line["own_variants"].items():
        print(f"own variant {variant}: correct {str(v['ok']).lower()}, "
              f"rel_rms {v['rel_rms_diff']:.6g} (limit "
              f"{kept['rel_rms_tol']:g}), rel_max {v['rel_max_diff']:.6g}, "
              f"rel_tie {v['rel_tie_gap']:.6g}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
