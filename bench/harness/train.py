"""Training cells: ``TrainEngine.run()`` fed from a pool of staged samples.

Set-up builds the one engine the window drives, feeds it through the
engine's own ``InputPipeline`` from a ``BatchSource`` that cycles a pool
of distinct samples held in host memory (made on the device from the
seed), and runs its first three steps through ``run()``: those compile
the step and give the readings the reference is compared with.  The
window then calls ``run()`` again on the same engine and stops it at the
first step boundary past ``--seconds``; it ends when the last step's
parameters are ready.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from harness import common, compare, synth, weights
from harness.spec import model_config

CHECK_STEPS = 3


class PoolSource:
    """A ``BatchSource`` over a pool of whole batches in host memory:
    step s reads batch s mod len(pool).  One chip reads whole batches."""

    keys = ("fields", "target")

    def __init__(self, pool: List[Dict[str, np.ndarray]]):
        self.pool = pool

    def full_batch(self, step, horizon):
        return self.pool[step % len(self.pool)]


class WindowClosed(Exception):
    pass


def lr_schedule(cell: Dict) -> Dict:
    lr = dict(cell["lr"])
    # the engine's schedule: warm-up over a tenth of its steps from 1e-6,
    # cosine to a tenth of the base rate
    want = {"warmup_steps": max(lr["total_steps"] // 10, 1),
            "init_lr": 1e-6, "min_lr": lr["base_lr"] * 0.1}
    for k, v in want.items():
        if not np.isclose(lr[k], v):
            raise SystemExit(f"cell lr {k}={lr[k]} is not the engine's {v}")
    return lr


def build_engine(res: Dict, seed: int, pool, params):
    from repro.data.pipeline import InputPipeline
    from repro.launch.engine import EngineConfig, TrainEngine
    cfg, cell, traffic = res["config"], res["cell"], res["traffic"]
    lr = lr_schedule(cell)
    eng = TrainEngine(
        cfg["name"], reduced=False, kernel=cfg["kernel"],
        config_override=model_config(cfg),
        init_params=params,
        config=EngineConfig(
            steps=lr["total_steps"], batch=cell["batch"],
            lr=lr["base_lr"], precision=cfg["precision"],
            seed=seed % (1 << 31), log_every=1 << 62,
            prefetch=traffic["prefetch"]))
    eng.pipeline.stop()
    eng.pipeline = InputPipeline(PoolSource(pool),
                                 prefetch=traffic["prefetch"])
    return eng


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        tree)


@jax.jit
def _change_norms(master, start):
    return jax.tree.map(
        lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b.astype(jnp.float32)))), master, start)


def _keyed(tree) -> Dict[str, float]:
    return {jax.tree_util.keystr(p): float(v) for p, v in
            jax.tree_util.tree_leaves_with_path(tree)}


def run(res: Dict, seed: int, seconds: float, trace: bool,
        t_start: float, allow_cpu: bool = False):
    cfg, cell, traffic = res["config"], res["cell"], res["traffic"]
    devs = common.devices(cell["chips"], allow_cpu)
    B = cell["batch"]
    pool = [synth.host_batch(seed, 100 + j, cfg, traffic, B)
            for j in range(traffic["pool_batches"])]
    eng = build_engine(res, seed, pool, weights.make(seed, cfg))
    b1 = eng.adam_cfg.b1

    # -- set-up: the first steps, through the window's own call and feed
    losses, grad_norms = [], {}

    def first_steps(i, m):
        losses.append(m["loss"])
        if i == 0:
            mu = _leaf_norms(eng.opt_state["mu"])
            grad_norms.update({k: v / (1.0 - b1)
                               for k, v in _keyed(mu).items()})

    eng.config = dataclasses.replace(eng.config, steps=CHECK_STEPS)
    eng.run(on_step=first_steps)
    start = weights.make(seed, cfg)
    state = eng.opt_state.get("master", eng.params)
    change = _keyed(_change_norms(state, start))
    del start, state
    program = {"losses": [float(x) for x in losses],
               "grad_norms": grad_norms, "change_norms": change}
    jax.block_until_ready(eng.params)
    setup_s = time.time() - t_start

    # -- the window
    eng.config = dataclasses.replace(eng.config,
                                     steps=cell["lr"]["total_steps"])
    steps = [0]

    def window_step(i, m):
        steps[0] += 1
        if time.perf_counter() >= t_close:
            raise WindowClosed

    tracer = eng.tracer
    prof = common.Profile(trace)
    prof.start()
    t0 = time.perf_counter()
    t_close = t0 + seconds
    prof.sync_mark()
    try:
        eng.run(on_step=window_step)
    except WindowClosed:
        pass
    eng.pipeline.stop()
    jax.block_until_ready((eng.params, eng.opt_state))
    t_end = time.perf_counter()
    prof.stop()
    window_s = t_end - t0
    samples = steps[0] * B
    mem_peak = common.memory_peak(devs)
    spans = common.spans_in(tracer, t0, t_end,
                            threading.main_thread().ident)
    del eng, tracer
    common.free_device_memory()
    left = common.bytes_in_use(devs)

    # -- the reference, once the program's state is gone
    t_ref = time.perf_counter()
    checks, gaps = compare.train_checks(program, res, seed, pool)
    ref_s = time.perf_counter() - t_ref
    common.log(f"setup_s={setup_s:.3f} window_s={window_s:.3f} "
               f"steps={steps[0]} reference_s={ref_s:.3f} "
               f"left_before_reference_bytes={left} "
               f"losses={program['losses']} gaps={gaps}")
    device = common.device_info(devs, mem_peak)
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": samples, "failed": 0, "device": device}
    if not trace:
        out["metrics"] = {
            "train_samples_per_s": common.metric(samples / window_s,
                                                 "samples/s"),
            "setup_s": common.metric(setup_s, "s")}
    else:
        run_rec = common.RunRecord(
            res=res, devs=devs, window_s=window_s,
            spans=spans, counts={"samples": samples},
            profile=prof)
        out.update(common.per_layer(run_rec))
    return out, checks
