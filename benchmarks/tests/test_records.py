"""``harness/records.py``: what a window keeps for whoever asks why two runs
differ."""

import json

from benchmarks.harness import records
from benchmarks.harness.client import Result


def test_a_window_keeps_every_request_and_what_the_counters_gained(tmp_path):
    done = Result(0, due=100.5, sent=100.6, first=101.0, last=101.9,
                  chunks=[(101.0, 4), (101.2, 4), (101.9, 4)], status=200,
                  prompt_tokens=7, completion_tokens=12, finish="length",
                  want_prompt=7, want_out=12)
    cut = Result(1, due=101.0, sent=101.0, want_prompt=9, want_out=5,
                 error="unfinished at the drain limit")
    before = [("dyn_moe_experts_hit_total", {"kind": "decode"}, 10.0),
              ("llm_decode_step_seconds_count", {}, 3.0),
              ("dyn_engine_info", {"platform": "tpu"}, 1.0)]
    after = [("dyn_moe_experts_hit_total", {"kind": "decode"}, 25.0),
             ("llm_decode_step_seconds_count", {}, 3.0),
             ("dyn_engine_dispatches_total", {"kind": "decode"}, 6.0),
             ("dyn_engine_info", {"platform": "tpu"}, 1.0)]
    path = tmp_path / "window_records.json"
    records.keep(str(path), {"results": [done, cut], "t0": 100.0,
                             "ended_s": 3.0}, before, after)
    kept = json.loads(path.read_text())
    a, b = kept["requests"]
    assert (a["idx"], a["tokens"], a["ok"], a["bursts"]) == (0, 12, True, 3)
    assert abs(a["first"] - 1.0) < 1e-9 and abs(a["last"] - 1.9) < 1e-9
    assert abs(a["widest_gap_s"] - 0.7) < 1e-9
    assert (b["ok"], b["first"], b["last"], b["widest_gap_s"]) == (
        False, None, None, 0.0)
    # a counter that did not move, and a series that is no counter, stay out
    assert kept["gained"] == {
        'dyn_moe_experts_hit_total{"kind": "decode"}': 15.0,
        'dyn_engine_dispatches_total{"kind": "decode"}': 6.0}
