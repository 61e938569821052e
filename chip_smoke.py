#!/usr/bin/env python3
"""Smoke run of WeatherMixer training and forecast serving on a TPU.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # one host with four chips

One chip: weathermixer-1b at the paper's full widths (728x1440x69 grid,
d_emb 4320, d_tok 8640, d_ch 4320, 3 blocks; weights random from a seed)
goes through the code ``python -m repro.launch.train`` and
``python -m repro.launch.serve`` run:

  device  the platform must be ``tpu``: there is no CPU fallback;
  train   ``TrainEngine`` under the ``bf16_pure`` policy (2 B each for
          weights, grads and both Adam moments: ~8 GB), batch 1, three
          steps through ``run()``; losses finite, params changed, and the
          compiled step holds the Pallas GEMMs as ``tpu_custom_call``;
  kernel  one batch evaluated with ``kernel="pallas"`` and with
          ``kernel="xla"``: the losses agree within ``KERNEL_BAND``;
  serve   ``ForecastEngine`` (bf16, buckets 1 and 2): warmup, four
          requests at leads 1 and 2, drain; every forecast finite and
          728x1440x69, zero compiles after warmup.

Four chips (``--four-chips``): weathermixer-1b under the ``bf16`` policy
(fp32 masters: 16 B/param, too much for one chip) on ``mesh_model=4``,
two steps under 2-D Jigsaw (2x2 Cannon) and two under 1-D Jigsaw
(``ring_chunked``), same seed and batch.  The step-0 losses agree within
``SCHEME_BAND``, every chip holds about a quarter of the parameter and
optimizer bytes, and each compiled step carries its collectives.

Times printed here are smoke readings of one run, not benchmark numbers.
The last line of standard output is the JSON result; it is printed only
when every phase passed.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

ARCH = "weathermixer-1b"
SEED = 0
# |loss(pallas) - loss(xla)| / loss(xla): both engines round every GEMM
# output to bf16 and differ only in f32 accumulation order, so the gap is
# a few bf16 unit roundoffs (2**-8) at most
KERNEL_BAND = 1e-2
# |loss(2-D) - loss(1-D)| / loss(1-D) at step 0 (same params and batch):
# bf16 compute, fp32 accumulation; Cannon and the ring sum partial
# products in different orders and round at different points
SCHEME_BAND = 1e-2
# share of the parameter + optimizer bytes one of 4 chips may hold: 1/4
# plus the replicated LayerNorm / blend leaves and uneven splits
SHARE_BAND = (0.20, 0.30)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {body}", flush=True)


def compiled(fn, *args):
    """The compiled executable of a jitted function for ``args`` (the
    persistent compilation cache makes this a load, not a second
    compile)."""
    return fn.lower(*args).compile()


def collective_counts(hlo: str) -> dict:
    ops = re.findall(r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)(?:-start)?\(", hlo)
    return {k: ops.count(k) for k in sorted(set(ops))}


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_device(want_count: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devs), jax=jax.__version__, devices=devs)
    if d.platform != "tpu":
        fail(f"JAX found platform {d.platform!r}, not a TPU; this smoke "
             f"has no CPU fallback")
    if len(devs) < want_count:
        fail(f"needs {want_count} chips, JAX found {len(devs)}")
    return d


def phase_train(reduced: bool = False, steps: int = 3):
    """TrainEngine at full widths, bf16_pure, batch 1, through run()."""
    import jax
    import numpy as np
    from repro.launch.engine import EngineConfig, TrainEngine

    eng = TrainEngine(ARCH, reduced=reduced, config=EngineConfig(
        steps=steps, batch=1, log_every=1, precision="bf16_pure",
        seed=SEED, prefetch=1))
    small = {jax.tree_util.keystr(k): np.asarray(v) for k, v in
             jax.tree_util.tree_leaves_with_path(eng.params)
             if v.size <= 1 << 16}
    marks = []

    def on_step(i, metrics):
        jax.block_until_ready(metrics)
        marks.append(time.perf_counter())

    t0 = time.perf_counter()
    eng.run(on_step=on_step)
    eng.pipeline.stop()
    first_s = marks[0] - t0
    steady = [b - a for a, b in zip(marks, marks[1:])]
    losses = [h["loss"] for h in eng.history if "loss" in h]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        fail(f"train losses {losses}")
    changed = sum(
        not np.array_equal(small[jax.tree_util.keystr(k)], np.asarray(v))
        for k, v in jax.tree_util.tree_leaves_with_path(eng.params)
        if jax.tree_util.keystr(k) in small)
    if not changed:
        fail("no parameter changed over the training steps")
    batch = eng.pipeline.get(0)
    t = time.perf_counter()
    exe = compiled(eng.step_fns[1], eng.params, eng.opt_state, batch)
    hlo_s = time.perf_counter() - t
    kernels = exe.as_text().count("tpu_custom_call")
    mem = exe.memory_analysis()
    stats = jax.devices()[0].memory_stats() or {}
    say("train", arch=ARCH, precision=eng.policy.name, batch=1,
        params=eng.cfg.param_count(), losses=losses,
        first_step_s=round(first_s, 3),
        steady_step_s=[round(s, 3) for s in steady],
        step_hlo_s=round(hlo_s, 3), tpu_custom_call=kernels,
        small_leaves_changed=f"{changed}/{len(small)}",
        step_argument_bytes=mem.argument_size_in_bytes,
        step_temp_bytes=mem.temp_size_in_bytes,
        peak_bytes=stats.get("peak_bytes_in_use", "not reported"))
    if kernels == 0 and jax.devices()[0].platform == "tpu":
        fail("the compiled train step holds no tpu_custom_call: the "
             "Pallas GEMMs did not run compiled")
    return eng, batch


def phase_kernel_check(eng, batch):
    """One batch through the eval step with each local-GEMM engine."""
    import jax
    from repro.train.step import make_eval_step

    losses = {}
    for kern in ("pallas", "xla"):
        fn = jax.jit(make_eval_step(eng.cfg.replace(kernel=kern),
                                    eng.jcfg.replace(kernel=kern)))
        losses[kern] = float(fn(eng.params, batch)["loss"])
    rel = abs(losses["pallas"] - losses["xla"]) / abs(losses["xla"])
    say("kernel", loss_pallas=losses["pallas"], loss_xla=losses["xla"],
        rel_diff=rel, band=KERNEL_BAND)
    if not (math.isfinite(rel) and rel <= KERNEL_BAND):
        fail(f"pallas vs xla loss differ by {rel} > {KERNEL_BAND}")


def phase_serve(reduced: bool = False):
    """The serve CLI's own function: 4 requests, leads 1 and 2."""
    import numpy as np
    from repro.launch.serve import serve

    results, engine, wall = serve(
        ARCH, requests=4, leads=(1, 2), buckets=(1, 2), precision="bf16",
        seed=SEED, reduced=reduced, quiet=True)
    s = engine.summary(results)
    shape = engine.field_shape
    outs = [np.asarray(r.output(ld)) for r in results for ld in r.leads]
    ok = [o.shape == shape and bool(np.isfinite(o).all()) for o in outs]
    post = engine.stats["compiles"] - engine.stats["warm_compiles"]
    say("serve", answered=sum(r.done() for r in results), forecasts=len(outs),
        finite_and_shaped=sum(ok), shape="x".join(map(str, shape)),
        warmup_s=round(engine.stats["warmup_s"], 3),
        compiles_after_warmup=post, p50_s=round(s["p50_s"], 4),
        wall_s=round(wall, 3))
    if not all(r.done() for r in results) or len(outs) != 4 or not all(ok):
        fail("not every forecast came back finite and full-shaped")
    if post != 0:
        fail(f"{post} compiles after warmup")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def phase_scheme(scheme: str, reduced: bool = False, steps: int = 2):
    """wm-1b on mesh_model=4 under one Jigsaw scheme."""
    import jax
    import numpy as np
    from repro.launch.engine import EngineConfig, TrainEngine

    eng = TrainEngine(ARCH, reduced=reduced, mesh_model=4, mesh_data=1,
                      scheme=scheme, impl="ring_chunked",
                      config=EngineConfig(steps=steps, batch=1, log_every=1,
                                          precision="bf16", seed=SEED,
                                          prefetch=1))
    marks = []
    t0 = time.perf_counter()
    eng.run(on_step=lambda i, m: (jax.block_until_ready(m),
                                  marks.append(time.perf_counter())))
    eng.pipeline.stop()
    losses = [h["loss"] for h in eng.history if "loss" in h]
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        fail(f"{scheme}: losses {losses}")
    per_dev, total = {}, 0
    for leaf in jax.tree.leaves((eng.params, eng.opt_state)):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) \
                + sh.data.nbytes
    shares = {d: round(b / total, 4) for d, b in sorted(per_dev.items())}
    # the batch as shapes in the pipeline's layout: the same program as
    # the steps ran, without generating another full-grid sample
    pipe = eng.pipeline
    batch = {k: jax.ShapeDtypeStruct(
        pipe.source.key_shape(k), np.float32,
        sharding=pipe._sharding_for(k, pipe.source.key_shape(k)))
        for k in pipe.source.keys}
    with jax.set_mesh(eng.mesh):
        hlo = compiled(eng.step_fns[1], eng.params, eng.opt_state,
                       batch).as_text()
    coll = collective_counts(hlo)
    say(f"scheme-{scheme}", mesh=dict(eng.mesh.shape), losses=losses,
        first_step_s=round(marks[0] - t0, 3),
        steady_step_s=[round(b - a, 3) for a, b in zip(marks, marks[1:])],
        state_bytes=total, share_per_device=shares, collectives=coll,
        tpu_custom_call=hlo.count("tpu_custom_call"))
    if len(shares) != 4 or not all(SHARE_BAND[0] <= v <= SHARE_BAND[1]
                                   for v in shares.values()):
        fail(f"{scheme}: param+optimizer bytes not ~1/4 per device: "
             f"{shares}")
    if not coll.get("collective-permute"):
        fail(f"{scheme}: compiled step has no collective-permute (the "
             f"Jigsaw rotate/ring hops): {coll}")
    return losses


def run_four_chips(reduced: bool = False) -> None:
    l2 = phase_scheme("2d", reduced)
    gc.collect()
    l1 = phase_scheme("1d", reduced)
    rel = abs(l2[0] - l1[0]) / abs(l1[0])
    say("schemes", step0_loss_2d=l2[0], step0_loss_1d=l1[0], rel_diff=rel,
        band=SCHEME_BAND)
    if not (math.isfinite(rel) and rel <= SCHEME_BAND):
        fail(f"2-D vs 1-D step-0 losses differ by {rel} > {SCHEME_BAND}")


def run_one_chip(reduced: bool = False) -> None:
    eng, batch = phase_train(reduced)
    phase_kernel_check(eng, batch)
    # the training state (~8 GB) leaves the chip before serving loads
    del eng, batch
    gc.collect()
    phase_serve(reduced)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded wm-1b path on 4 chips")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no repro package under {SRC}: run from a checkout of the "
             f"repository")
    sys.path.insert(0, SRC)
    from repro.launch import compile_cache
    compile_cache.enable()
    n = 4 if args.four_chips else 1
    dev = phase_device(n)
    if args.four_chips:
        run_four_chips()
    else:
        run_one_chip()
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
