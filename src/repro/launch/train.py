"""End-to-end training driver: a thin CLI over ``TrainEngine``.

CPU-runnable (reduced configs, host mesh) and production-shaped (full
configs on the 16x16 mesh) from the same entry point:

  PYTHONPATH=src python -m repro.launch.train --arch weathermixer-1b \
      --reduced --steps 200 --batch 8 [--mesh-model 4 --mesh-data 2] \
      [--scheme 2d] [--rollout 3] [--ckpt out/ckpt] [--ckpt-every 50] \
      [--resume out/ckpt-50] [--pipeline sharded|sync-full] \
      [--prefetch 2] [--accum 2]

Checkpoints are zero-redundancy sharded (each rank writes only its
addressable shards, streamed by a background writer; DESIGN.md §9);
``--resume`` continues an interrupted run with a bit-identical loss
history.

The input path is the domain-parallel sharded pipeline by default: each
model-parallel rank generates only its (lon x channel) partition and a
background thread prefetches ahead of compute (paper §5).
``--pipeline sync-full`` restores the legacy full-batch host generation
for A/B comparison; both produce bit-identical batches.

Reduced configs run real optimization on the synthetic pipelines; the
loss curves in EXPERIMENTS.md come from here.

Fault tolerance (DESIGN.md §12): the CLI installs a
``PreemptionHandler`` -- SIGTERM/SIGUSR1 finishes the in-flight step,
takes a final synchronous save, and exits code 75 (resumable).
``--supervise --max-restarts N`` wraps the whole thing in the
``Supervisor`` relaunch loop, which rediscovers the latest COMPLETE
checkpoint before every launch and passes it as ``--resume``.
"""
from __future__ import annotations

import argparse
import sys

from repro.configs.registry import ARCH_IDS
from repro.core import jigsaw
from repro.launch import compile_cache, resilience
from repro.launch.engine import EngineConfig, TrainEngine


def train(arch: str, *, steps: int = 100, batch: int = 8, seq_len: int = 128,
          reduced: bool = True, mesh_model: int = 1, mesh_data: int = 1,
          scheme: str = None, impl: str = None, kernel: str = None,
          precision: str = None, rollout: int = 1,
          lr: float = 1e-3, log_every: int = 10, ckpt: str = None,
          ckpt_every: int = 0, keep_ckpts: int = 0, resume: str = None,
          async_save: bool = True,
          seed: int = 0, metrics_out: str = None,
          metrics_format: str = "jsonl", trace: str = None,
          telemetry: bool = True, init_params=None,
          pipeline: str = "sharded", prefetch: int = 2, accum: int = 1,
          zero1: bool = False, eval_every: int = 0, config_override=None,
          preemption: bool = False, preempt_at_step: int = None):
    """Back-compat functional entry point; returns (history, params).

    New callers should construct a :class:`TrainEngine` directly --
    it exposes the same behavior plus eval/checkpoint hooks.
    ``config_override`` replaces the registry config (custom model
    sizes)."""
    engine = TrainEngine(
        arch, reduced=reduced, mesh_model=mesh_model, mesh_data=mesh_data,
        scheme=scheme, impl=impl, kernel=kernel, init_params=init_params,
        config_override=config_override,
        config=EngineConfig(
            steps=steps, batch=batch, seq_len=seq_len, rollout=rollout,
            lr=lr, log_every=log_every, ckpt=ckpt, ckpt_every=ckpt_every,
            keep_ckpts=keep_ckpts, resume=resume, async_save=async_save,
            seed=seed, precision=precision,
            metrics_out=metrics_out, metrics_format=metrics_format,
            trace=trace, telemetry=telemetry,
            pipeline=pipeline, prefetch=prefetch,
            accum=accum, zero1=zero1, eval_every=eval_every,
            preemption=preemption, preempt_at_step=preempt_at_step))
    history = engine.run()
    return history, engine.params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="full (non-reduced) config -- needs real hardware")
    ap.add_argument("--mesh-model", type=int, default=1)
    ap.add_argument("--mesh-data", type=int, default=1)
    ap.add_argument("--scheme", default=None, choices=["1d", "2d", "none"])
    ap.add_argument("--impl", default=None, choices=jigsaw.Impl1D)
    ap.add_argument("--kernel", default=None, choices=["xla", "pallas"],
                    help="local GEMM engine (pallas = MXU-tiled fused "
                         "kernels; interpret mode on CPU)")
    ap.add_argument("--precision", default=None,
                    choices=["fp32", "bf16", "bf16_pure"],
                    help="precision policy (core/precision): bf16 = bf16 "
                         "compute/comm + fp32 master weights; bf16_pure = "
                         "bf16 everywhere (memory-minimal)")
    ap.add_argument("--rollout", type=int, default=1)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir (sharded manifest format); "
                         "periodic saves land at <ckpt>-<step>")
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="save every N steps (0 = final only)")
    ap.add_argument("--keep-ckpts", type=int, default=0,
                    help="keep only the last K periodic checkpoints "
                         "(0 = keep all; the best-eval marker's target "
                         "is never deleted)")
    ap.add_argument("--resume", default=None,
                    help="checkpoint dir to exact-resume from (restores "
                         "params/opt/step/rollout schedule/data cursor)")
    ap.add_argument("--sync-save", action="store_true",
                    help="block the loop on checkpoint writes instead of "
                         "the async background writer")
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--metrics-format", default="jsonl",
                    choices=["jsonl", "json"],
                    help="jsonl (default): crash-safe append, one JSON "
                         "object per line; json: legacy whole-history "
                         "dump written once at run end")
    ap.add_argument("--trace", default=None,
                    help="Chrome trace-event export path (load in "
                         "Perfetto); a sibling .jsonl gets the per-step "
                         "records (wall time, data wait) for "
                         "launch/trace_report.py")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable span tracing (the overhead benchmark's "
                         "baseline; counters stay live)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pipeline", default="sharded",
                    choices=["sharded", "sync-full"],
                    help="domain-parallel sharded reads (default) or the "
                         "legacy full-batch host generation")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="input batches prefetched by the background "
                         "thread (0 = synchronous)")
    ap.add_argument("--accum", type=int, default=1,
                    help="microbatch gradient-accumulation factor")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: shard optimizer moments over data")
    ap.add_argument("--eval-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--supervise", action="store_true",
                    help="run under the relaunch Supervisor: restart on "
                         "resumable exits / crashes, auto-resuming from "
                         "the latest complete checkpoint (needs --ckpt)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="relaunch budget under --supervise")
    args = ap.parse_args()
    if args.supervise:
        if not args.ckpt:
            ap.error("--supervise requires --ckpt (the supervisor "
                     "discovers resume points under its directory)")
        sys.exit(resilience.supervise_train_cli(args, sys.argv[1:]))
    compile_cache.enable()
    try:
        train(args.arch, steps=args.steps, batch=args.batch,
              seq_len=args.seq_len, reduced=not args.full,
              mesh_model=args.mesh_model, mesh_data=args.mesh_data,
              scheme=args.scheme, impl=args.impl, kernel=args.kernel,
              precision=args.precision, rollout=args.rollout,
              lr=args.lr, log_every=args.log_every,
              ckpt=args.ckpt, ckpt_every=args.ckpt_every,
              keep_ckpts=args.keep_ckpts,
              resume=args.resume, async_save=not args.sync_save,
              seed=args.seed,
              metrics_out=args.metrics_out,
              metrics_format=args.metrics_format, trace=args.trace,
              telemetry=not args.no_telemetry, pipeline=args.pipeline,
              prefetch=args.prefetch, accum=args.accum, zero1=args.zero1,
              eval_every=args.eval_every, preemption=True)
    except resilience.Preempted as p:
        print(f"[train] {p}; exiting resumable "
              f"({resilience.RESUMABLE_EXIT_CODE})")
        sys.exit(resilience.RESUMABLE_EXIT_CODE)


if __name__ == "__main__":
    main()
