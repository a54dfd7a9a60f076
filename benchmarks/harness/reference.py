"""Run a cell's plain reference: the part no model owns.

The mathematics and the layout of a model's weights live in
``references/<name>.py``, found by the name the configuration file gives in
``benchmark.reference`` (the contract of such a file is in ``catalog.py``).
This file is the child's entry point, run while no server holds the chip:

    python -m benchmarks.harness.reference <in.json> <out.json>

``in.json``: {"config": <hf config>, "reference": <name>, "seed": n,
"samples": [{"prompt": [...], "served": [...]}], "probe": false, "catalog":
{"manifest": path, "roots": [...]}}. ``catalog`` says where names are looked
up and may be absent (the benchmark's own manifest and ``benchmarks/``); a
rehearsal puts its own directory in front, as everywhere else.

For every sample the served tokens are teacher-forced (one full forward over
prompt + served tokens, padded to a multiple of 128) and ``out.json`` gives,
per generated position, the reference log-probability of the served token,
the reference's own best log-probability and its argmax, and the standard
deviation of the reference logits over the vocabulary (the scale against
which a difference is small or large). With ``probe`` it also gives the same
under the reference's two deliberately broken variants (``dropped_layer``,
``int8``), which is how the tolerance in ``correct.py`` was shown to separate
them. It imports nothing of the program; the file it loads may.
"""

from __future__ import annotations

import json
import os
import sys
import time

PROBE_VARIANTS = ("dropped_layer", "int8")


def score_samples(module, state, samples, variant="full"):
    """Teacher-force each sample through ``module.tail_logprobs``;
    -> per sample dict of four lists."""
    import numpy as np

    n_tail = max(len(s["served"]) for s in samples)
    T = max(len(s["prompt"]) for s in samples) + n_tail
    T = -(-T // 128) * 128
    out = []
    for s in samples:
        served = list(s["served"])
        seq = list(s["prompt"]) + served[:-1]
        toks = np.zeros(T, np.int32)
        toks[: len(seq)] = seq
        lp = np.asarray(module.tail_logprobs(
            state, toks, len(s["prompt"]) - 1, n_tail, variant))[: len(served)]
        out.append({
            "logit_std": [float(x) for x in lp.std(-1)],
            "served_logprob": [float(lp[i, t]) for i, t in enumerate(served)],
            "best_logprob": [float(x) for x in lp.max(-1)],
            "best_token": [int(x) for x in lp.argmax(-1)],
        })
    return out


def main(argv) -> int:
    src, dst = argv[1], argv[2]
    with open(src) as f:
        job = json.load(f)
    from benchmarks.harness.catalog import ROOT, Catalog

    where = job.get("catalog", {})
    cat = Catalog(where.get("manifest"), where.get("roots", ()))
    t0 = time.monotonic()
    import jax

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        # the cache directory the server uses, so one limit bounds both
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(ROOT, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # loading the file imports what it needs of the program, as the server's
    # start-up does: counted with the runtime, not with the weights
    module = cat.module("references", job["reference"])
    t1 = time.monotonic()
    state = module.build(job["config"], int(job["seed"]))
    t2 = time.monotonic()
    result = {"device": {"platform": jax.devices()[0].platform,
                         "kind": jax.devices()[0].device_kind},
              "full": score_samples(module, state, job["samples"])}
    # the same two steps the server's start-up makes first, timed here
    # because the server logs neither: a baseline for a start-up PR
    result["timing"] = {"import_and_devices_s": t1 - t0,
                        "init_params_s": t2 - t1,
                        "score_s": time.monotonic() - t2}
    if job.get("probe"):
        for variant in PROBE_VARIANTS:
            result[variant] = score_samples(module, state, job["samples"],
                                            variant)
    with open(dst, "w") as f:
        json.dump(result, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
