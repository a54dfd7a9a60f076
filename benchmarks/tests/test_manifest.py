"""The manifest against the benchmark's contract, and against the files it
names: what the driver would refuse before a run is caught here first."""

import json
import os
import re

import pytest

from benchmarks.harness.catalog import BENCH, ROOT, BenchError, Catalog
from benchmarks.harness.peaks import peaks_for

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|"
                   r"_rank$|head_dim|expansion|experts_per_tok")

CATALOGS = {
    "benchmark": lambda: Catalog(),
    "rehearsal": lambda: Catalog(
        os.path.join(BENCH, "tests", "rehearsal", "BENCHMARK.json"),
        roots=[os.path.join(BENCH, "tests", "rehearsal")]),
}


@pytest.fixture(params=list(CATALOGS))
def cat(request):
    return CATALOGS[request.param]()


def test_keys_names_units_and_limits(cat):
    m = cat.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and 1 <= m["run_seconds"] <= 51
    assert all(isinstance(w, str) and not w.startswith("/")
               and ".." not in w for w in m["command"])
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[g]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({x["name"] for x in m[group]}) == len(m[group])
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in x["layer"] and 1 <= len(x["layer"]) <= 200
    for x in m["configs"] + m["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\t" not in x["why"]
    assert len(json.dumps(m)) < 64 * 1024


def test_cells_configurations_and_what_each_reports(cat):
    m = cat.manifest
    cells = [w["name"] for w in m["workloads"]]
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"]) for w in m["workloads"]}) \
        == len(cells)
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in m["workloads"])
    assert len(four) <= max(1, len(cells) // 4)
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for cell in cells:
        got = [x["name"] for x in cat.metrics("end_to_end", cell)]
        assert "setup_s" in got and len(got) >= 2
        assert cat.metrics("per_layer", cell)
    for x in m["per_layer"]:
        moved = e2e[x["moves"]]
        for cell in x.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (x["name"], cell)
    for x in m["end_to_end"] + m["per_layer"]:
        assert set(x.get("workloads", [])) <= set(cells)


def test_every_name_in_the_manifest_finds_its_files(cat):
    m = cat.manifest
    for c in m["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("benchmarks/")
        data = cat.data("configs", c["name"])
        bench = data["benchmark"]
        assert set(c["reduced"]) == set(bench["reduced"])
        assert not any(WIDTH.search(k) for k in c["reduced"])
        assert {"source", "assumed", "stands_for", "engine"} <= set(bench)
        assert len(c["source"]) <= 200
    for w in m["workloads"]:
        mix = cat.data("traffic", w["traffic"])
        gen = cat.module("generators", mix["generator"])
        assert callable(gen.plan) and callable(gen.run)
        assert callable(cat.module("topologies", mix["topology"]).start)
    for x in m["end_to_end"]:
        assert callable(cat.module("e2e_metrics", x["name"]).reduce)
    for x in m["per_layer"]:
        assert callable(cat.module("layer_metrics", x["name"]).reduce)


def test_every_configuration_names_a_reference_that_is_there(cat):
    pytest.importorskip("jax")      # a reference file may import the program
    for c in cat.manifest["configs"]:
        name = cat.data("configs", c["name"])["benchmark"]["reference"]
        ref = cat.module("references", name)
        assert callable(ref.build) and callable(ref.tail_logprobs)


@pytest.mark.parametrize("reference", [None, "no-such-reference"])
def test_no_reference_or_an_unknown_one_is_an_error_not_a_default(
        tmp_path, reference):
    from benchmarks.harness import cell

    rehearsal = os.path.join(BENCH, "tests", "rehearsal")
    with open(os.path.join(rehearsal, "configs", "tiny-qwen.json")) as f:
        config = json.load(f)
    del config["benchmark"]["reference"]
    if reference:
        config["benchmark"]["reference"] = reference
    os.makedirs(tmp_path / "configs")
    with open(tmp_path / "configs" / "tiny-qwen.json", "w") as f:
        json.dump(config, f)
    cat = Catalog(os.path.join(rehearsal, "BENCHMARK.json"),
                  roots=[str(tmp_path), rehearsal])
    with pytest.raises(BenchError, match="reference"):
        cell.prepare(cat, "tiny-qwen.drip", 1, False, rehearsal=True)


def test_files_under_paths_are_named_from_the_allowed_characters():
    bad = []
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        bad += [f for f in files + dirs
                if not re.match(r"^[A-Za-z0-9_.\-]+$", f)]
    assert not bad


def test_an_unknown_name_is_an_error_not_a_default():
    cat = Catalog()
    with pytest.raises(BenchError):
        cat.cell("no-such.cell")
    with pytest.raises(BenchError):
        cat.data("configs", "no-such-config")
    with pytest.raises(BenchError):
        peaks_for("TPU vNext")
    assert peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
