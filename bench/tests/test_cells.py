"""Discovery of cells from files by name, and BENCHMARK.json's shape."""
import json
import re

import pytest

from bench import cells, compare

BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", WORKLOADS)
def test_cell_found_by_name(name):
    cell = cells.find(BENCH, name)
    w = cell["workload"]
    assert cell["config"]["name"] == w["config"]
    conf = {c["name"]: c for c in BENCH["configs"]}[w["config"]]
    assert sorted(conf["reduced"]) == sorted(cell["config"]["reduced"])
    # what run.configure_jax sets, the reference computes, and the
    # control ("bf16", one pass) steps down from
    assert cell["config"]["matmul_precision"] == "high"
    assert set(cell["config"]["engine"]) == {"slots", "prefill_batch",
                                             "decode_horizon"}
    assert "engine" not in cell["traffic"]
    assert callable(cell["model"].draw) and callable(cell["model"].forward)
    assert cell["traffic"]["loop"] in ("open", "closed")
    assert compare.limits(name) and all(
        v > 0 for v in compare.limits(name).values())
    e2e = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert m["moves"] in e2e
        assert callable(cells.metric_reader(m["name"]).read)


def test_unknown_workload():
    with pytest.raises(KeyError):
        cells.find(BENCH, "no-such-cell")


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(BENCH["paths"][0] + "/")
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        names.append(w["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert all(NAME.match(n) for n in names)
    assert len(names) == len(set(names))
    assert len(json.dumps(BENCH)) < 64 * 1024
