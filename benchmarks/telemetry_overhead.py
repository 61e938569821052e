"""Telemetry overhead benchmark (ISSUE 9): the span/record
instrumentation must cost < 2% of step time.

One ``TrainEngine`` runs alternating K-step blocks with the tracer
enabled and disabled (toggled between blocks, so compile state, input
pipeline, host thermal drift and jit caches are IDENTICAL across the
two populations -- the only difference is whether ``span()`` allocates
and buffers events).  Per-step wall times come from ``on_step``
timestamp deltas; the first block is warmup and every block drops its
first step (the toggle boundary).  Overhead = (median_on - median_off)
/ median_off over the pooled blocks, asserted < 2%.

The per-call cost of the primitives themselves (span enter/exit,
counter, gauge, step_record) is also measured in a tight loop --
those are the numbers the <2% budget is built from (DESIGN.md §14).

Writes results/telemetry_overhead.csv unless --tiny (the CI smoke,
which still asserts the budget).
"""
import argparse
import os
import statistics
import sys
import time

if __package__ in (None, ""):   # `python benchmarks/telemetry_overhead.py`
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.common import SRC, emit  # noqa: F401  (SRC sets sys.path)

from repro.launch.engine import EngineConfig, TrainEngine  # noqa: E402
from repro.telemetry.spans import Tracer  # noqa: E402

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results",
                       "telemetry_overhead.csv")


def measure_engine(arch="internlm2-1.8b", *, block=16, blocks=6,
                   batch=2, seq_len=32):
    """Alternating enabled/disabled blocks on one engine; returns
    (on_s, off_s, n_on, n_off).

    Estimator: the per-block MINIMUM step time (host scheduling noise
    only ever adds time), differenced between ADJACENT on/off block
    pairs (slow drift -- a loaded CI host warming up or backing off --
    cancels within a pair), medianed across pairs."""
    steps = block * (blocks + 1)          # +1 warmup block
    eng = TrainEngine(arch, config=EngineConfig(
        steps=steps, batch=batch, seq_len=seq_len,
        log_every=10 ** 9, telemetry=True))
    per_block = {}                        # block index -> [step times]
    state = {"t": None}

    def on_step(i, metrics):
        now = time.perf_counter()
        prev, state["t"] = state["t"], now
        b = i // block
        if b == 0 or i % block == 0 or prev is None:
            # warmup block / toggle-boundary step: discard, then flip
            # the tracer for the block that starts here
            eng.tracer.enabled = (b % 2 == 1)
            return
        per_block.setdefault(b, []).append(now - prev)

    eng.run(on_step=on_step)
    mins = {b: min(ts) for b, ts in per_block.items()}
    # block 1 is on, 2 off, 3 on, ... -> pairs (1,2), (3,4), ...
    diffs, offs, n_on, n_off = [], [], 0, 0
    for b in sorted(mins):
        if b % 2 == 0:
            continue
        if b + 1 not in mins:
            break
        diffs.append(mins[b] - mins[b + 1])
        offs.append(mins[b + 1])
        n_on += len(per_block[b])
        n_off += len(per_block[b + 1])
    t_off = statistics.median(offs)
    t_on = t_off + statistics.median(diffs)
    return t_on, t_off, n_on, n_off


def measure_primitives(n=20000):
    """Tight-loop cost of each tracer primitive, in us/call."""
    tr = Tracer()
    out = {}
    t0 = time.perf_counter()
    for i in range(n):
        with tr.span("bench", i=i):
            pass
    out["span"] = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        tr.counter("c")
    out["counter"] = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for i in range(n):
        tr.gauge("g", i)
    out["gauge"] = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for i in range(n):
        tr.step_record(step=i, dur_s=0.1, data_wait_s=0.0)
    out["step_record"] = (time.perf_counter() - t0) / n * 1e6
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: fewer blocks, no csv write "
                         "(the <2%% assertion still runs)")
    ap.add_argument("--budget", type=float, default=0.02,
                    help="max allowed relative step-time overhead")
    args = ap.parse_args()

    prim = measure_primitives(4000 if args.tiny else 20000)
    # enough samples per arm that host scheduling noise (which dwarfs
    # the ~15us of actual span work on a >10ms step) medians out
    block, blocks = (8, 14) if args.tiny else (16, 16)
    t_on, t_off, n_on, n_off = measure_engine(block=block, blocks=blocks)
    overhead = (t_on - t_off) / t_off

    rows = [("telemetry/step_overhead_pct", round(overhead * 100, 3),
             f"on_us={t_on * 1e6:.0f}|off_us={t_off * 1e6:.0f}"
             f"|steps={n_on}+{n_off}|budget={args.budget * 100:.0f}%")]
    for name, us in sorted(prim.items()):
        rows.append((f"telemetry/{name}", round(us, 3), "us_per_call"))
    emit(rows)

    if not args.tiny:
        with open(RESULTS, "w") as f:
            f.write("name,us_per_call,derived\n")
            for r in rows:
                f.write(",".join(str(x) for x in r) + "\n")
        print(f"wrote {os.path.relpath(RESULTS)}")

    assert overhead < args.budget, (
        f"telemetry overhead {overhead * 100:.2f}% exceeds the "
        f"{args.budget * 100:.0f}% budget "
        f"(on {t_on * 1e6:.0f}us vs off {t_off * 1e6:.0f}us per step)")
    print(f"OK: telemetry overhead {overhead * 100:+.2f}% "
          f"(budget {args.budget * 100:.0f}%)")


if __name__ == "__main__":
    main()
