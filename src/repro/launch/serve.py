"""Forecast serving driver: a thin CLI over ``ForecastEngine``
(mirrors launch/train.py).

CPU-runnable (reduced configs, host-emulated data mesh) and
production-shaped from the same entry point:

  PYTHONPATH=src python -m repro.launch.serve --arch weathermixer-1b \
      [--ckpt out/ckpt-100] [--mesh-data 4] [--precision bf16] \
      [--requests 32] [--leads 1,2,4,8] [--mode continuous|drain] \
      [--buckets 1,2,4,8] [--coalesce-ms 0]

``--ckpt`` restores the params group of ANY training checkpoint
(whatever mesh it was saved on) onto the serving mesh
(checkpoint/serving.py); without it the engine serves fresh-initialized
weights, which is still useful for load testing.  Requests are
synthetic initial conditions from the weather dataset, submitted
up-front with leads cycling through ``--leads``; the engine coalesces,
batches continuously at rollout-step boundaries, and reports
requests/s + latency percentiles.
"""
from __future__ import annotations

import argparse
import time
from typing import Optional, Sequence

from repro.configs.registry import ARCH_IDS
from repro.data.weather import WeatherDataConfig, WeatherDataset
from repro.launch import compile_cache
from repro.serve.engine import ForecastEngine, ServeConfig


def serve(arch: str, *, ckpt: Optional[str] = None, requests: int = 32,
          leads: Sequence[int] = (1, 2, 4, 8), mesh_data: int = 1,
          precision: Optional[str] = None, mode: str = "continuous",
          buckets: Sequence[int] = (1, 2, 4, 8), coalesce_ms: float = 0.0,
          seed: int = 0, reduced: bool = True, warmup: bool = True,
          trace: Optional[str] = None,
          config_override=None, quiet: bool = False):
    """Build an engine, push ``requests`` synthetic forecasts through
    it, and return ``(results, engine, wall_seconds)``."""
    engine = ForecastEngine(
        arch, reduced=reduced, ckpt=ckpt, mesh_data=mesh_data,
        config_override=config_override,
        config=ServeConfig(buckets=tuple(buckets), mode=mode,
                           coalesce_s=coalesce_ms / 1e3,
                           precision=precision, seed=seed, trace=trace))
    cfg = engine.cfg
    ds = WeatherDataset(WeatherDataConfig(
        lat=cfg.wm_lat, lon=cfg.wm_lon, channels=cfg.wm_channels,
        seed=seed))
    fields = ds.sample_batch(0, requests)["fields"]
    if warmup:
        engine.warmup()
        if not quiet:
            print(f"[serve] warmup: {engine.stats['compiles']} compiles "
                  f"in {engine.stats['warmup_s']:.2f}s")
    t0 = time.perf_counter()
    results = [engine.submit(fields[i], leads[i % len(leads)])
               for i in range(requests)]
    engine.drain()
    wall = time.perf_counter() - t0
    if not quiet:
        s = engine.summary(results)
        src = (f"ckpt {ckpt} (step {engine.restored_step})" if ckpt
               else "fresh init")
        print(f"[serve] {arch} from {src} on mesh_data={mesh_data} "
              f"precision={engine.policy.name} mode={mode}")
        print(f"[serve] {requests} requests in {wall:.2f}s = "
              f"{requests / wall:.1f} req/s | p50 {s['p50_s'] * 1e3:.1f}ms "
              f"p95 {s['p95_s'] * 1e3:.1f}ms | {s['device_steps']} rollout "
              f"steps, {s['formed']} batch forms, {s['grown']} grows, "
              f"{s['compiles']} compiles (0 post-warmup = steady state)")
    out = engine.export_trace()
    if out and not quiet:
        print(f"[serve] trace -> {out}")
    return results, engine, wall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="weathermixer-1b", choices=ARCH_IDS)
    ap.add_argument("--ckpt", default=None,
                    help="training checkpoint dir to serve (any saving "
                         "topology; params group only)")
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) config -- needs real hardware")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="data-parallel serving mesh size (batch sharded, "
                         "params replicated)")
    ap.add_argument("--precision", default=None,
                    choices=["fp32", "bf16", "bf16_pure"],
                    help="serving precision policy (may differ from the "
                         "checkpoint's -- weights are cast on restore)")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--leads", default="1,2,4,8",
                    help="comma-separated lead times (rollout steps), "
                         "assigned round-robin to requests")
    ap.add_argument("--mode", default="continuous",
                    choices=["continuous", "drain"],
                    help="continuous batching vs drain-and-refill baseline")
    ap.add_argument("--buckets", default="1,2,4,8",
                    help="padded batch buckets (one jit executable each)")
    ap.add_argument("--coalesce-ms", type=float, default=0.0,
                    help="idle burst-coalescing window")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None,
                    help="Chrome trace-event export path for the serving "
                         "spans + latency histograms")
    args = ap.parse_args()
    compile_cache.enable()
    serve(args.arch, ckpt=args.ckpt, requests=args.requests,
          leads=[int(x) for x in args.leads.split(",")],
          mesh_data=args.mesh_data, precision=args.precision,
          mode=args.mode, buckets=[int(x) for x in args.buckets.split(",")],
          coalesce_ms=args.coalesce_ms, seed=args.seed,
          reduced=not args.full, trace=args.trace)


if __name__ == "__main__":
    main()
