"""Readings that set a cell's limits from below and above.

    python3 bench/controls.py --workload <cell> --seeds 1,2,3

For each seed, at the cell's own size, the reference is put in the
program's place and compared with the float32 reference by the same
numbers ``correct`` uses:

  control     the reference with every GEMM in float8_e4m3 (per-tensor
              scale), the precision next below the configuration's bf16;
  half_batch  (training) the loss and gradient over half of the batch,
              as a step that leaves half of it out would take them.

A step that returns its state unchanged reads 1 on ``change_gap`` and
``grad_gap`` by construction and needs no run.  One JSON line per seed
and reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=30.0,
                    help="serving: the window whose schedule is checked")
    args = ap.parse_args()
    # libtpu would log under /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import common, compare, serve, spec, synth, train
    res = spec.resolve(args.workload, ROOT)
    common.enable_compile_cache()
    common.devices(res["cell"]["chips"])
    generator = res["traffic"]["generator"]
    for seed in [int(s) for s in args.seeds.split(",")]:
        if generator == "train":
            B = res["cell"]["batch"]
            pool = [synth.host_batch(seed, 100 + j, res["config"],
                                     res["traffic"], B)
                    for j in range(res["traffic"]["pool_batches"])]
            ref = compare.reference_train(res, seed, pool)
            variants = {"control": dict(gemm="fp8")}
            if B >= 2:
                variants["half_batch"] = dict(rows=slice(0, B // 2))
            for name, kw in variants.items():
                got = compare.reference_train(res, seed, pool, **kw)
                print(json.dumps({"seed": seed, "reading": name,
                                  **compare.train_gaps(got, ref)}),
                      flush=True)
        else:
            sched = serve.schedule(res, seed, args.seconds)
            states = serve.make_states(seed, res)
            wanted = {}
            for i in sched["check"]:
                wanted.setdefault(sched["state"][i], set()).add(
                    sched["leads"][i])
            ref = compare.reference_rollouts(res, seed, states, wanted)
            low = compare.reference_rollouts(res, seed, states, wanted,
                                             gemm="fp8")
            gaps = [compare.forecast_gap(low[s][ld], ref[s][ld], states[s])
                    for s, leads in wanted.items() for ld in leads]
            print(json.dumps({"seed": seed, "reading": "control",
                              "forecast_gap": max(gaps),
                              "per_check": gaps}), flush=True)


if __name__ == "__main__":
    main()
