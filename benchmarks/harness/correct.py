"""The comparison that decides ``correct``.

(a) every request of the window: ``client.Result.ok`` (HTTP 200, completion
    tokens as asked, prompt tokens as sent, finish reason ``length``, every
    token seen on the stream).
(b) a seeded sample served greedy with ``logprobs`` on, outside the window,
    against the configuration's float32 reference (``references/<name>.py``,
    run by ``reference.py``) teacher-forced on the served tokens: at every
    generated position the served token's log-probability must agree with
    the reference's for that same token, and the served token must be the
    reference's best or tie with it. Prefill (the first generated token) and
    decode through the paged cache (the rest) are both on that path.
(c) no program compiled between the two scrapes.

Tolerances and why. The served path computes in bfloat16 (weights and
activations; float32 logits and softmax), the reference in float32. A
random-init model's logits are small, so differences are taken in units of
the reference's own logit spread at that position (its standard deviation
over the vocabulary: ~0.1 at full size, ~0.5 at the tests' tiny size).
Measured on the chip (PR 23), 256 positions a run (4 prompts x 64 tokens);
"dropped" and "int8" are the probe of ``references/llama.py``: the same
served tokens scored by a deliberately broken reference:

                        rms                     max         tie gap
    qwen2-1.5b          0.19-0.24 (10 runs)     0.70-1.22   0.56-1.60
      last layer dropped  0.31                  1.11        1.72
      int8 weights        0.78                  3.42        3.43
    mistral-7b-16l      0.24-0.29 (7 runs)      0.86-1.62   1.13-1.77
      last layer dropped  0.42                  1.23        1.50
      int8 weights        0.93                  2.94        3.14

(with 64 positions a run, 27 earlier runs read 0.16-0.27: the statistic
wanders, which is why the sample is 256). The root mean square separates:
bf16 rounding through the layers is already 0.19-0.29 sigma, wider at
mistral's widths, and one dropped layer is 0.31 (1 of 28) or 0.42 (1 of 16).
So the limit is the configuration's own (``benchmark.reference_tolerance`` in
its file: 0.275 and 0.34), and a dropped layer or int8 weights fail it; a file
without one gets the tighter default. The maximum and the tie gap are noisy -
of 152k near-uniform candidates the best two lie ~0.2 sigma apart, so greedy
picks differ from the reference's at 1 position in 3 - and their limits only
catch a token that is no near-tie at all (a random one lies ~4.9 sigma below
the best; int8 reads 2.8-3.8).

Those two limits were 2.5 until PR 27. Over 34 sound runs of mistral-7b-16l
and 33 of qwen2-1.5b (PR 23 to PR 27, every one ``correct``) the maximum read
up to 2.29 and the tie gap up to 2.03 (both mistral; qwen2 1.46 and 1.60):
widest gaps of 256 positions swing by their nature, a check makes a dozen
runs or more of a cell, every one on a new seed, and PR 25 was refused on a
run of its untouched parent. The fault they are there to catch, a token that
is not the reference's near-best, reads ~4.9; int8 weights fail the root mean
square 2.6 times over whatever they read here. So both limits are 3.0: 1.3
and 1.5 times the sound runs' largest, 0.6 of the fault's.
"""

from __future__ import annotations

from typing import Any, Dict, List

# root-mean-square over positions of |served logprob - reference logprob of
# that token| / sigma: the limit that a dropped layer or int8 weights fail
REL_RMS_TOL = 0.25
# the same, maximum over positions
REL_TOL = 3.0
# how far below the reference's best token the served (greedy) token may
# rank, in sigma: a near-tie inside bf16 noise, not a miss
TIE_REL_TOL = 3.0


def compare(served: List[Dict[str, Any]], reference: List[Dict[str, Any]],
            rel_rms_tol: float = REL_RMS_TOL) -> Dict[str, Any]:
    """served[i]: {"tokens": [...], "logprobs": [...]}; reference[i]: what
    ``reference.score_samples`` returns for it."""
    worst, worst_tie, sq, n, agree, worst_abs = 0.0, 0.0, 0.0, 0, 0, 0.0
    for s, r in zip(served, reference):
        for i, lp in enumerate(s["logprobs"]):
            sigma = r["logit_std"][i]
            d = abs(lp - r["served_logprob"][i])
            worst_abs = max(worst_abs, d)
            worst = max(worst, d / sigma)
            worst_tie = max(worst_tie, (r["best_logprob"][i]
                                        - r["served_logprob"][i]) / sigma)
            sq += (d / sigma) ** 2
            n += 1
            agree += int(r["best_token"][i] == s["tokens"][i])
    rms = (sq / n) ** 0.5 if n else float("inf")
    ok = (n > 0 and len(served) == len(reference) and worst <= REL_TOL
          and worst_tie <= TIE_REL_TOL and rms <= rel_rms_tol)
    return {"ok": bool(ok), "positions": n, "argmax_agree": agree,
            "logprob_max_abs_diff": worst_abs,
            "rel_max_diff": worst, "rel_rms_diff": rms,
            "rel_tie_gap": worst_tie,
            "tolerances": {"rel_max": REL_TOL, "rel_tie": TIE_REL_TOL,
                           "rel_rms": rel_rms_tol}}
