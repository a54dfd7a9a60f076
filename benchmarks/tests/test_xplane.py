"""The trace reduction: its interval arithmetic on made-up events, and the
whole reduction on a small trace recorded on the chip (PR 23)."""

import gzip
import json
import os
import shutil

import pytest

from benchmarks.harness import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "fixtures", "v5e_small.xplane.pb.gz")


def test_union_merges_overlaps_and_keeps_gaps():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 10)]) == \
        [(0, 3), (5, 7), (9, 10)]


def test_leaves_drop_operations_that_contain_others():
    evs = [(0.0, 10.0, "while"), (1.0, 2.0, "fusion.1"),
           (2.0, 4.0, "custom-call.3"), (12.0, 13.0, "copy.2")]
    assert [n for _, _, n in xplane.leaves(evs)] == \
        ["fusion.1", "custom-call.3", "copy.2"]


def test_names_lose_their_numbers():
    assert xplane.base_name("fusion.123") == "fusion"
    assert xplane.base_name("jit_step(1234567)") == "jit_step"
    assert xplane.base_bucket("dynamo.prefill[B1,C128,S512]") == \
        "dynamo.prefill"
    assert xplane.op_key("fusion.7") == "fusion"
    assert xplane.op_key(
        "%copy.814 = bf16[28,2,2177,64,128]{4,1,3,2,0:T(2,128)} copy(bf16["
        "28,2,2177,64,128]{4,3,2,1,0} %x)") == "copy bf16[28,2,2177,64,128]"
    assert xplane.op_key(
        '%custom-call.323 = (f32[32,64]{1,0}, s32[32,64]{1,0}) custom-call('
        'f32[32,151936]{1,0} %a), custom_call_target="TopK"') == \
        "custom-call:TopK f32[32,64]"
    assert xplane.op_key(
        '%tpu_custom_call.335 = bf16[32,2,6,128]{3,2,1,0} custom-call(s32[32,'
        '16]{1,0} %g), custom_call_target="tpu_custom_call"') == \
        "tpu_custom_call bf16[32,2,6,128]"


def test_gaps_are_labelled_by_what_the_host_was_doing():
    spans = [(0.0, 1.0, "dynamo.decode[S256]"),
             (3.0, 4.0, "dynamo.prefill[B1,C32,S256]")]
    got = xplane.label_gaps([(0.5, 0.7), (1.5, 2.5), (4.2, 4.4)], spans)
    assert got == {"in dynamo.decode": pytest.approx(0.2),
                   "after dynamo.decode": pytest.approx(1.0),
                   "after dynamo.prefill": pytest.approx(0.2)}
    assert xplane.label_gaps([(0.1, 0.2)], []) == \
        {"before first dispatch": pytest.approx(0.1)}


def test_summary_of_made_up_device_lines():
    raw = {"devices": {"/device:TPU:0": {
        "XLA Ops": [(0.0, 1.0, "while.1"), (0.1, 0.4, "fusion.1"),
                    (0.5, 0.9, "%tpu_custom_call.2 = bf16[4,8]{1,0} "
                     'custom-call(), custom_call_target="tpu_custom_call"'),
                    (2.0, 3.0, "copy.7")],
        "XLA Modules": [(0.0, 1.0, "jit_step(11)"), (2.0, 3.0, "jit_fn(12)"),
                        ]}},
           "host_spans": [(0.0, 0.05, "dynamo.decode[S256]")]}
    s = xplane.summarise(raw)
    assert s["window_s"] == pytest.approx(3.0)
    assert s["busy_s"] == pytest.approx(2.0)
    assert s["modules"]["jit_step"]["runs"] == 1
    assert s["ops_by_module"]["jit_step"] == {
        "tpu_custom_call bf16[4,8]": pytest.approx(0.4),
        "fusion": pytest.approx(0.3)}
    assert s["ops_by_module"]["jit_fn"] == {"copy": pytest.approx(1.0)}
    assert s["breakdown"]["device_ops"][0] == ["copy", pytest.approx(1.0)]
    assert s["breakdown"]["idle_gaps"] == [["after dynamo.decode",
                                            pytest.approx(1.0)]]


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this tree")
def test_reduction_of_the_recorded_v5e_trace(tmp_path):
    pytest.importorskip("jax")
    path = tmp_path / "t.xplane.pb"
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = xplane.summarise(xplane.read(str(path)))
    with open(os.path.join(HERE, "fixtures", "v5e_small.expected.json")) as f:
        want = json.load(f)
    assert s["devices"] == want["devices"]
    assert 0 < s["busy_s"] <= s["window_s"]
    assert s["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert s["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    for name, m in want["modules"].items():
        assert s["modules"][name]["runs"] == m["runs"]
    assert [k for k, _ in s["breakdown"]["device_ops"]] == \
        [k for k, _ in want["breakdown"]["device_ops"]]
