"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout on a machine that holds the cell's chips.
One process holds them: it sets up (weights and inputs from ``--seed``,
every program compiled or loaded from ``.jax_cache/``), measures for
``--seconds``, checks what the timed path produced against the plain
float32 reference, and prints one JSON object as the last line of
standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a profiler trace of the window.
The numbers compared for ``correct`` are the last lines of standard
error and the result's ``checks``.
"""
from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.exit("bench: this checkout holds no program under test "
                 "(src/repro); no result")

    # libtpu would log under /tmp/tpu_logs, outside the checkout
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import common, spec
    t_start = common.process_start()
    res = spec.resolve(args.workload, ROOT)
    common.enable_compile_cache()
    generator = importlib.import_module(
        f"harness.{res['traffic']['generator']}")
    out, checks = generator.run(res, args.seed, args.seconds,
                                bool(args.trace), t_start)
    timing = out.pop("timing", None)
    if timing:
        out["device"].update(timing)
    common.emit(out, checks)


if __name__ == "__main__":
    main()
