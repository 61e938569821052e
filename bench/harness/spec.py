"""Finds everything a run needs by the names in ``BENCHMARK.json``.

    bench/configs/<config>.json     sizes, precision, source, reduced
    bench/traffic/<traffic>.json    the mix's parameters; ``generator``
                                    names the module that reads it
    bench/workloads/<cell>.json     config, traffic, chips, the cell's own
                                    numbers (batch, rate) and its limits
    bench/metrics/<metric>.py       a per-layer metric's reader

A new configuration, mix, cell or metric is a new file and a new entry;
no file that is there changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Callable, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _file(root: str, kind: str, name: str, ext: str = ".json") -> str:
    return os.path.join(root, "bench", kind, name + ext)


def config(name: str, root: str = ROOT) -> Dict:
    return _json(_file(root, "configs", name))


def traffic(name: str, root: str = ROOT) -> Dict:
    return _json(_file(root, "traffic", name))


def cell(name: str, root: str = ROOT) -> Dict:
    return _json(_file(root, "workloads", name))


def metric_reader(name: str, root: str = ROOT) -> Callable:
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = _file(root, "metrics", name, ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(entry: Dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def metrics_of(bench: Dict, workload: str) -> Dict[str, List[Dict]]:
    """The end-to-end and per-layer metric entries one cell reports."""
    return {"end_to_end": [m for m in bench["end_to_end"]
                           if _applies(m, workload)],
            "per_layer": [m for m in bench["per_layer"]
                          if _applies(m, workload)]}


def resolve(workload: str, root: str = ROOT) -> Dict:
    """Everything one cell names, loaded."""
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if workload not in entries:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(entries)}")
    entry = entries[workload]
    c = cell(workload, root)
    for key in ("config", "traffic", "chips"):
        if c[key] != entry[key]:
            raise SystemExit(f"{workload}: {key} is {c[key]!r} in its cell "
                             f"file and {entry[key]!r} in BENCHMARK.json")
    return {"name": workload, "entry": entry, "cell": c, "root": root,
            "config": config(c["config"], root),
            "traffic": traffic(c["traffic"], root),
            "metrics": metrics_of(bench, workload)}


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch_id=cfg["name"], family="mixer", n_layers=cfg["n_layers"],
        d_model=cfg["d_emb"], wm_lat=cfg["lat"], wm_lon=cfg["lon"],
        wm_channels=cfg["channels"], wm_patch=cfg["patch"],
        wm_d_tok=cfg["d_tok"], wm_d_ch=cfg["d_ch"], norm="layernorm",
        scheme="none", kernel=cfg["kernel"], remat=cfg["remat"],
        supports_decode=False, supports_long_context=False,
        source=cfg["source"])


def check_names(bench: Dict) -> List[str]:
    """Breaches of the naming rules, as messages (empty when sound)."""
    bad = []
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [c["name"] for c in bench["configs"]]
    names += [w["config"] for w in bench["workloads"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    bad += [f"name {n!r}" for n in names if not NAME_RE.match(n)]
    bad += [f"unit {m['unit']!r}" for m in
            bench["end_to_end"] + bench["per_layer"]
            if not UNIT_RE.match(m["unit"])]
    return bad


