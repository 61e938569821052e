"""``BENCHMARK.json`` as data: every name resolves to its file, the names
keep the rules, and a cell, mix, configuration or metric is added by new
files and entries alone."""
import json
import os
import shutil

import pytest

import _paths
from harness import spec

BENCH = spec.benchmark(_paths.ROOT)
NAMES = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][:2] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]


def test_names_and_units_keep_the_rules():
    assert spec.check_names(BENCH) == []
    everything = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
                  + BENCH["per_layer"])
    assert len({e["name"] for e in everything}) == len(everything)


@pytest.mark.parametrize("workload", NAMES)
def test_every_cell_resolves(workload):
    res = spec.resolve(workload, _paths.ROOT)
    assert res["config"]["name"] == res["cell"]["config"]
    assert res["traffic"]["generator"] in ("train", "serve")
    e2e = {m["name"] for m in res["metrics"]["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert res["metrics"]["per_layer"]
    for m in res["metrics"]["per_layer"]:
        assert callable(spec.metric_reader(m["name"], _paths.ROOT))
        assert m["moves"] in e2e, (m["name"], m["moves"])


def test_every_config_file_is_used_and_whole():
    used = {w["config"] for w in BENCH["workloads"]}
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"] == f"bench/configs/{c['name']}.json"
        files.add(c["file"])
        body = spec.config(c["name"], _paths.ROOT)
        assert body["reduced"] == c["reduced"]
        assert body["source"] == c["source"]
    assert len(files) == len(BENCH["configs"])


def test_four_chip_cells_within_limit():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert four <= max(len(BENCH["workloads"]) // 2, 1)


def test_per_layer_metrics_name_their_cells():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(NAMES)
        for w in m["workloads"]:
            reported = spec.metrics_of(BENCH, w)["end_to_end"]
            assert m["moves"] in {r["name"] for r in reported}


def test_run_seconds_fits_the_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_bounds():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def test_adding_a_cell_is_files_and_entries(tmp_path):
    """A copy of the benchmark gains a mix, a cell and a metric by new
    files and entries only; the harness finds each by name."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(_paths.ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads(json.dumps(BENCH))
    base = spec.resolve(NAMES[0], _paths.ROOT)
    (root / "bench" / "traffic" / "pool_small.json").write_text(
        json.dumps(dict(base["traffic"], pool_batches=3)))
    cell = dict(base["cell"], traffic="pool_small")
    (root / "bench" / "workloads" / "new-cell.json").write_text(
        json.dumps(cell))
    (root / "bench" / "metrics" / "new_metric.train.py").write_text(
        "def read(run):\n    return 1.0\n")
    bench["workloads"].append(dict(bench["workloads"][0], name="new-cell",
                                   traffic="pool_small"))
    for m in bench["end_to_end"]:
        if "workloads" in m and NAMES[0] in m["workloads"]:
            m["workloads"].append("new-cell")
    bench["per_layer"].append(dict(bench["per_layer"][0],
                                   name="new_metric.train",
                                   workloads=["new-cell"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = spec.resolve("new-cell", str(root))
    assert res["traffic"]["pool_batches"] == 3
    names = [m["name"] for m in res["metrics"]["per_layer"]]
    assert "new_metric.train" in names
    assert spec.metric_reader("new_metric.train", str(root))(None) == 1.0
