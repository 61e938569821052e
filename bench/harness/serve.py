"""Serving cells: an open loop of forecast requests into ``ForecastEngine``.

Set-up makes the weights and a pool of initial states on the device from
the seed (the states are then host arrays, as the API takes them), builds
the engine and warms every bucket the cell names.  The schedule is fixed
by the cell and the mix: ``rate * seconds`` requests whose gaps are the
quantiles of an exponential distribution at that rate and whose leads
come in the mix's fixed shares, in one fixed order.

In the window the main thread calls ``submit`` when a request falls due,
as an independent client would, while a second thread calls
``step_once`` in a loop, as the engine's own serving thread does.  A
request's latency runs from its due time to the return of the
``step_once`` that delivered its last lead as a host array.  After the
last arrival the engine serves what is left, for at most a minute.
"""
from __future__ import annotations

import math
import threading
import time
from collections import Counter
from typing import Dict, List, Optional

import numpy as np

from harness import common, compare, synth, weights
from harness.spec import model_config

DRAIN_LIMIT_S = 60.0
IDLE_POLL_S = 1e-3


def schedule(res: Dict, seed: int, seconds: float) -> Dict:
    """Due times (s from the window's start), leads, pool states and the
    requests checked against the reference.

    The arrival trace is the mix's own: its gaps and leads are shuffled
    by the mix's ``trace_seed``, not by ``--seed``, so every run replays
    the same arrivals.  With a few dozen requests a window, the order
    alone moved the tail by half (see PERF.md).  The seed picks each
    request's initial state and the requests checked."""
    cell, traffic = res["cell"], res["traffic"]
    rate = float(cell["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    shares = traffic["lead_shares"]
    counts = [int(math.floor(s * n)) for s in shares]
    for i in np.argsort([-(s * n - math.floor(s * n)) for s in shares]):
        if sum(counts) >= n:
            break
        counts[i] += 1
    leads = np.repeat(traffic["leads"], counts)
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
    order = np.random.default_rng(traffic["trace_seed"])
    gaps = order.permutation(gaps)
    leads = order.permutation(leads)
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    state_idx = rng.integers(0, traffic["pool_states"], n)
    check = []
    for lead, k in traffic["check_per_lead"].items():
        idx = np.flatnonzero(leads == int(lead))
        check += [int(i) for i in rng.choice(idx, min(k, len(idx)),
                                             replace=False)]
    return {"due": np.cumsum(gaps), "leads": [int(x) for x in leads],
            "state": [int(x) for x in state_idx], "check": sorted(check)}


def make_states(seed: int, res: Dict) -> List[np.ndarray]:
    cfg, traffic = res["config"], res["traffic"]
    pool = synth.host_batch(seed, 200, cfg, traffic,
                            traffic["pool_states"])["fields"]
    return [pool[i] for i in range(pool.shape[0])]


def build_engine(res: Dict, seed: int):
    from repro.serve.engine import ForecastEngine, ServeConfig
    cfg, cell = res["config"], res["cell"]
    eng = ForecastEngine(
        cfg["name"], reduced=False, params=weights.make(seed, cfg),
        config=ServeConfig(buckets=tuple(cell["buckets"]),
                           mode="continuous", precision=cfg["precision"],
                           seed=seed % (1 << 31)),
        config_override=model_config(cfg), clock=time.perf_counter)
    eng.warmup()
    return eng


def serve_window(eng, sched: Dict, states: List[np.ndarray],
                 seconds: float, prof: Optional["common.Profile"] = None
                 ) -> Dict:
    """Drive the open loop: this thread submits each request when it falls
    due, a second thread runs the engine's boundaries.  Returns latencies,
    lateness, outputs of the checked requests and the steps per bucket."""
    due, leads = sched["due"], sched["leads"]
    keep = set(sched["check"])
    n = len(leads)
    latency = [None] * n
    lateness = [0.0] * n
    outputs: Dict[int, np.ndarray] = {}
    inflight = {}
    bucket_steps: Counter = Counter()
    lock = threading.Lock()
    stop = threading.Event()
    failure = []

    def boundaries():
        try:
            while not stop.is_set():
                r = eng.step_once()
                t = time.perf_counter()
                if r == "step":
                    bucket_steps[eng._bucket] += 1
                with lock:
                    done = [i for i, req in inflight.items() if req.done()]
                    for i in done:
                        req = inflight.pop(i)
                        latency[i] = t - (t0 + due[i])
                        if i in keep:
                            outputs[i] = req.outputs[leads[i]]
                        req.outputs.clear()
                if r != "step":
                    time.sleep(IDLE_POLL_S)
        except BaseException as e:            # re-raised on the caller
            failure.append(e)

    server = threading.Thread(target=boundaries, name="bench-serve")
    if prof is not None:
        prof.start()
    t0 = time.perf_counter()
    if prof is not None:
        prof.sync_mark()
    server.start()
    try:
        for i in range(n):
            time.sleep(max(0.0, t0 + due[i] - time.perf_counter()))
            with lock:
                inflight[i] = eng.submit(states[sched["state"][i]],
                                         leads[i])
            lateness[i] = time.perf_counter() - (t0 + due[i])
        give_up = t0 + max(seconds, float(due[-1])) + DRAIN_LIMIT_S
        while not failure and time.perf_counter() < give_up:
            with lock:
                if not inflight:
                    break
            time.sleep(IDLE_POLL_S)
    finally:
        stop.set()
        server.join()
    t_end = time.perf_counter()
    if prof is not None:
        prof.stop()
    if failure:
        raise failure[0]
    return {"t0": t0, "t_end": t_end, "engine_thread": server.ident,
            "latency": latency,
            "lateness": lateness, "outputs": outputs,
            "bucket_steps": dict(bucket_steps),
            "slot_steps": sum(leads[i] for i in range(n)
                              if latency[i] is not None)}


def checks_for(res: Dict, seed: int, sched: Dict, states, outputs,
               gemm: str = "f32") -> List:
    """The worst gap of the checked forecasts against the reference."""
    wanted: Dict[int, set] = {}
    for i in sched["check"]:
        wanted.setdefault(sched["state"][i], set()).add(sched["leads"][i])
    ref = compare.reference_rollouts(res, seed, states, wanted, gemm)
    gaps = []
    for i in sched["check"]:
        s, lead = sched["state"][i], sched["leads"][i]
        if i not in outputs:
            gaps.append(math.inf)
            continue
        gaps.append(compare.forecast_gap(outputs[i], ref[s][lead],
                                         states[s]))
    return [("forecast_gap", max(gaps),
             res["cell"]["limits"]["forecast_gap"])]


def run(res: Dict, seed: int, seconds: float, trace: bool,
        t_start: float, allow_cpu: bool = False):
    cell = res["cell"]
    devs = common.devices(cell["chips"], allow_cpu)
    sched = schedule(res, seed, seconds)
    states = make_states(seed, res)
    eng = build_engine(res, seed)
    setup_s = time.time() - t_start

    prof = common.Profile(trace) if trace else None
    got = serve_window(eng, sched, states, seconds, prof)
    mem_peak = common.memory_peak(devs)
    spans = common.spans_in(eng.tracer, got["t0"], got["t_end"],
                            got["engine_thread"])
    compiles = eng.stats["compiles"] - eng.stats["warm_compiles"]
    del eng
    common.free_device_memory()

    t_ref = time.perf_counter()
    checks = checks_for(res, seed, sched, states, got["outputs"])
    common.log(f"setup_s={setup_s:.3f} "
               f"window_s={got['t_end'] - got['t0']:.3f} "
               f"reference_s={time.perf_counter() - t_ref:.3f}")
    lat = [x if x is not None else DRAIN_LIMIT_S + seconds
           for x in got["latency"]]
    failed = sum(x is None for x in got["latency"])
    late = got["lateness"]
    common.log(f"requests={len(lat)} failed={failed} "
               f"compiles_in_window={compiles} "
               f"generator_late_max_s={max(late):.6f} "
               f"generator_late_mean_s={sum(late) / len(late):.6f} "
               f"bucket_steps={got['bucket_steps']}")
    device = common.device_info(devs, mem_peak)
    out = {"correct": all(v <= lim for _, v, lim in checks),
           "attempted": len(lat), "failed": failed, "device": device,
           "compiles_in_window": compiles,
           "generator": {"late_max_s": max(late),
                         "late_mean_s": sum(late) / len(late)}}
    if not trace:
        out["metrics"] = {
            "forecast_p50_s": common.metric(common.percentile(lat, 0.50),
                                            "s"),
            "forecast_p95_s": common.metric(common.percentile(lat, 0.95),
                                            "s"),
            "setup_s": common.metric(setup_s, "s")}
    else:
        run_rec = common.RunRecord(
            res=res, devs=devs,
            window_s=got["t_end"] - got["t0"], spans=spans,
            counts={"bucket_steps": got["bucket_steps"],
                    "slot_steps": got["slot_steps"]},
            profile=prof)
        out.update(common.per_layer(run_rec))
    return out, checks
