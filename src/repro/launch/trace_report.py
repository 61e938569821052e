"""Render a telemetry JSONL (the sibling of ``--trace out.trace.json``)
into a human-readable run report (DESIGN.md §14):

  PYTHONPATH=src python -m repro.launch.trace_report out.trace.jsonl
  PYTHONPATH=src python -m repro.launch.trace_report out.trace.jsonl --check

Sections, all from measured records:

  1. steps -- per-step wall time (submit to submit) and data wait over
     the run's step records;
  2. spans -- every span name with count / total / mean, straight from
     the tracer's span summary;
  3. counters and latency histograms.

``--check`` is the CI mode: exit non-zero unless the file has a meta
header and >= 1 step records whose ``dur_s`` and ``data_wait_s`` are
present, finite and not negative.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Any, Dict, List, Optional, Tuple


def load_records(path: str) -> List[Dict[str, Any]]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def split_records(recs: List[Dict[str, Any]]
                  ) -> Tuple[Dict, List[Dict], Dict, Dict, Dict, List[Dict]]:
    """(meta, steps, spans, counters, gauges, histograms)."""
    meta: Dict[str, Any] = {}
    steps: List[Dict[str, Any]] = []
    spans: Dict[str, Dict[str, float]] = {}
    counters: Dict[str, float] = {}
    gauges: Dict[str, float] = {}
    hists: List[Dict[str, Any]] = []
    for r in recs:
        kind = r.get("kind")
        if kind == "meta":
            meta.update({k: v for k, v in r.items() if k != "kind"})
        elif kind == "step":
            steps.append(r)
        elif kind == "spans":
            spans.update(r.get("spans", {}))
        elif kind == "counters":
            counters.update(r.get("counters", {}))
        elif kind == "gauges":
            gauges.update(r.get("gauges", {}))
        elif kind == "histogram":
            hists.append(r)
    return meta, steps, spans, counters, gauges, hists


def _mean(xs: List[float]) -> float:
    return sum(xs) / len(xs) if xs else float("nan")


def _pct(xs: List[float], p: float) -> float:
    if not xs:
        return float("nan")
    s = sorted(xs)
    return s[min(len(s) - 1, int(p * len(s)))]


STEP_FIELDS = ("dur_s", "data_wait_s")


def check(meta: Dict[str, Any], steps: List[Dict[str, Any]]) -> List[str]:
    """CI assertions; returns a list of failures (empty = pass)."""
    fails: List[str] = []
    if not meta:
        fails.append("no meta header record")
    if not steps:
        fails.append("no step records")
    for s in steps:
        i = s.get("step", "?")
        for key in STEP_FIELDS:
            v = s.get(key)
            if v is None:
                fails.append(f"step {i}: missing {key}")
            elif not math.isfinite(v):
                fails.append(f"step {i}: {key}={v} not finite")
            elif v < 0:
                fails.append(f"step {i}: {key}={v} negative")
    return fails


def render(path: str, out=sys.stdout) -> None:
    meta, steps, spans, counters, gauges, hists = split_records(
        load_records(path))

    w = out.write
    w(f"== trace report: {path} ==\n")
    head = {k: meta[k] for k in ("arch", "mesh_model", "mesh_data",
                                 "scheme", "impl", "kernel", "precision",
                                 "batch", "rollout", "mode")
            if k in meta}
    if head:
        w("run: " + " ".join(f"{k}={v}" for k, v in head.items()) + "\n")

    if steps:
        devices = sorted({str(s["device"]) for s in steps
                          if "device" in s})
        w(f"\n-- steps ({len(steps)}"
          + (f", on {', '.join(devices)}" if devices else "") + ") --\n")
        w(f"{'field':<18}{'mean':>12}{'p50':>12}{'p95':>12}"
          f"{'total':>12}\n")
        for name in STEP_FIELDS:
            xs = [s[name] for s in steps if name in s]
            if xs:
                w(f"{name:<18}{_mean(xs):>12.4g}{_pct(xs, 0.5):>12.4g}"
                  f"{_pct(xs, 0.95):>12.4g}{sum(xs):>12.4g}\n")

    if spans:
        w(f"\n-- spans --\n")
        w(f"{'name':<24}{'count':>8}{'total_s':>12}{'mean_s':>12}\n")
        for name in sorted(spans, key=lambda n: -spans[n]["total_s"]):
            agg = spans[name]
            w(f"{name:<24}{agg['count']:>8}{agg['total_s']:>12.4g}"
              f"{agg['mean_s']:>12.4g}\n")

    if counters:
        w(f"\n-- counters --\n")
        for name in sorted(counters):
            w(f"{name:<32}{counters[name]:>16,.0f}\n")

    if hists:
        w(f"\n-- histograms --\n")
        w(f"{'name':<32}{'count':>8}{'p50':>12}{'p95':>12}{'p99':>12}\n")
        for h in hists:
            if not h.get("count"):
                continue
            w(f"{h['name']:<32}{h['count']:>8}{h.get('p50', 0):>12.4g}"
              f"{h.get('p95', 0):>12.4g}{h.get('p99', 0):>12.4g}\n")


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("jsonl", help="telemetry JSONL (the .jsonl sibling "
                                  "of --trace's Chrome JSON)")
    ap.add_argument("--check", action="store_true",
                    help="CI mode: exit 1 unless every step record has "
                         "finite, non-negative dur_s and data_wait_s")
    args = ap.parse_args(argv)
    meta, steps, *_ = split_records(load_records(args.jsonl))
    if args.check:
        fails = check(meta, steps)
        if fails:
            for f in fails:
                print(f"[trace-check] FAIL: {f}")
            return 1
        print(f"[trace-check] OK: {len(steps)} step records, every "
              f"measured field finite and non-negative")
        return 0
    render(args.jsonl)
    return 0


if __name__ == "__main__":
    sys.exit(main())
