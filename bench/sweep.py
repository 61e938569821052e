"""Find the knee of a serving cell: the highest offered rate it sustains.

    python3 bench/sweep.py --workload <serving cell> --seed <n> \
        --seconds <s> --rates 1,1.5,2,3

One process sets the cell up once and runs its open loop at each rate in
turn, lowest first.  For each rate it prints the latency median and 95th
percentile, the latencies by lead, the backlog trend (the mean latency of
the last quarter of requests over that of the first quarter) and the
drain: how long the engine served on after the last arrival.

A rate is sustained when every request was delivered and its 95th
percentile is at most 1.5 times that of the lowest rate, where the queue
is short.  The knee is the highest rate that is sustained with every
rate below it; the last line gives it and four fifths of it, rounded to
two places, which is the cell's ``rate_per_s``, set once from this sweep
on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    # libtpu would log under /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import common, serve, spec
    res = spec.resolve(args.workload, ROOT)
    common.enable_compile_cache()
    common.devices(res["cell"]["chips"])
    states = serve.make_states(args.seed, res)
    eng = serve.build_engine(res, args.seed)
    base_p95, knee, broken = None, None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        res["cell"] = dict(res["cell"], rate_per_s=rate)
        sched = serve.schedule(res, args.seed, args.seconds)
        got = serve.serve_window(eng, sched, states, args.seconds)
        lat = [x for x in got["latency"] if x is not None]
        q = max(len(lat) // 4, 1)
        by_lead = {}
        for x, lead in zip(got["latency"], sched["leads"]):
            by_lead.setdefault(lead, []).append(
                None if x is None else round(x, 4))
        print(json.dumps({
            "rate_per_s": rate, "requests": len(sched["leads"]),
            "delivered": len(lat),
            "p50_s": common.percentile(lat, 0.5),
            "p95_s": common.percentile(lat, 0.95),
            "backlog_trend": (sum(lat[-q:]) / q) / (sum(lat[:q]) / q),
            "latency_by_lead": by_lead,
            "late_max_s": max(got["lateness"]),
            "bucket_steps": got["bucket_steps"],
            "drain_s": got["t_end"] - got["t0"] - float(sched["due"][-1]),
            "wall_s": got["t_end"] - got["t0"]}), flush=True)
        p95 = common.percentile(lat, 0.95)
        base_p95 = p95 if base_p95 is None else base_p95
        held = len(lat) == len(sched["leads"]) and p95 <= 1.5 * base_p95
        broken = broken or not held
        knee = rate if not broken else knee
        time.sleep(1.0)
    if knee is not None:
        print(json.dumps({"knee_per_s": knee,
                          "rate_per_s": round(0.8 * knee, 2)}), flush=True)


if __name__ == "__main__":
    main()
