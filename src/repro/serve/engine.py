"""Continuous-batching forecast serving engine (DESIGN.md §13).

The production inference path for the autoregressive WeatherMixer
rollout.  One ``ForecastEngine`` owns:

  * the serving mesh -- data-only (``model=1``), any shape: params
    restored from an arbitrary-topology training checkpoint replicate
    onto it via the sharded-restore spec refit
    (``checkpoint/serving.py``);
  * a *compile-cache* of jitted device functions, one set per padded
    batch bucket -- the rollout ``step`` (state donated: the forecast
    overwrites its own buffer), ``admit`` (dynamic row write of a new
    request's initial condition, state donated), ``peel`` (dynamic row
    read of a finished lead), ``zeros`` (fresh state) and adjacent
    bucket ``grow`` (pad) fns.  After ``warmup()`` steady-state serving
    performs ZERO compiles: every function traces exactly once per
    bucket, counted by ``stats["compiles"]`` (incremented at trace time,
    so retraces are caught), and asserted by
    ``tests/test_serve.py``;
  * a ``MicrobatchScheduler`` (serve/scheduler.py) deciding, at every
    rollout-step boundary, which queued requests to admit into free
    slots (continuous batching), when to coalesce, grow, or -- in the
    ``drain`` baseline mode -- wait for the batch to empty.

Requests are ``submit()``-ed (thread-safe) and return future-style
``ForecastResult`` handles; ``drain()`` (or the ``start()`` background
thread) advances boundaries until the queue empties.  Different lead
times share one rollout and peel off at their own step.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from functools import partial
from typing import Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.configs.registry import get_config
from repro.core import precision
from repro.launch import shapes as SH
from repro.launch import specs as S
from repro.launch.mesh import make_host_mesh
from repro.models import registry as M
from repro.serve.scheduler import (ForecastResult, MicrobatchScheduler,
                                   Lead)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Serving policy knobs (the engine ctor takes the topology)."""
    buckets: Tuple[int, ...] = (1, 2, 4, 8)
    mode: str = "continuous"          # | "drain" (static-batching baseline)
    coalesce_s: float = 0.0           # idle burst-coalescing window
    precision: Optional[str] = None   # serving policy preset (may differ
    seed: int = 0                     # from the checkpoint's)
    telemetry: bool = True            # span tracing (histograms stay live)
    trace: Optional[str] = None       # Chrome trace export path

    def replace(self, **kw) -> "ServeConfig":
        return dataclasses.replace(self, **kw)


class ForecastEngine:
    """Batched autoregressive forecast serving over a data-only mesh."""

    def __init__(self, arch: str, *, reduced: bool = True,
                 ckpt: Optional[str] = None, params=None,
                 mesh_data: int = 1, config: ServeConfig = ServeConfig(),
                 config_override=None, clock=time.monotonic):
        self.arch = arch
        self.config = config
        cfg = config_override if config_override is not None \
            else get_config(arch)
        if reduced:
            cfg = cfg.reduced()
        # serving is data-parallel only: every rank holds the full model
        # and the whole Jigsaw contraction is local (scheme="none")
        cfg = cfg.replace(scheme="none", impl="rs")
        if config.precision:
            cfg = precision.apply_policy(cfg, config.precision)
        self.policy = precision.policy_of(cfg)
        if cfg.family != "mixer":
            raise ValueError(
                f"ForecastEngine drives the autoregressive field rollout; "
                f"{arch} is family {cfg.family!r} (use serve.step for "
                "token decoding)")
        self.cfg = cfg
        self.jcfg = SH.jigsaw_for(cfg)
        self.field_shape = (cfg.wm_lat, cfg.wm_lon, cfg.wm_channels)

        self.mesh = (make_host_mesh(model=1, data=mesh_data)
                     if mesh_data > 1 else None)
        self.stats = {"compiles": 0, "device_steps": 0, "wait_ticks": 0,
                      "warmup_s": 0.0}
        # engine-local tracer: admission-to-delivery histograms (one per
        # lead time) + serve spans; not the process tracer, so several
        # engines in one process (A/B benchmarks) never mix percentiles
        self.tracer = telemetry.attach_profiler(
            telemetry.Tracer(enabled=config.telemetry))
        self.tracer.set_meta(surface="serve", arch=arch, reduced=reduced,
                             mesh_data=mesh_data, mode=config.mode,
                             buckets=list(config.buckets))
        self.sched = MicrobatchScheduler(
            config.buckets, mode=config.mode,
            coalesce_s=config.coalesce_s, clock=clock)
        self._clock = clock
        self._sleep = time.sleep

        # -- params: restore > passed-in > fresh init ----------------------
        like = jax.eval_shape(partial(M.init, cfg=cfg),
                              jax.random.PRNGKey(config.seed))
        self.restored_step = None
        if ckpt is not None:
            from repro.checkpoint.serving import restore_serving_params
            params, man = restore_serving_params(
                ckpt, arch=arch, like=like, mesh=self.mesh)
            self.restored_step = man.step
        elif params is None:
            params = M.init(jax.random.PRNGKey(config.seed), cfg)
        else:
            # the step never donates params, but cast to the serving policy
            params = jax.tree.map(
                lambda l, r: jnp.asarray(l, r.dtype), params, like)
        if self.mesh is not None and ckpt is None:
            params = jax.device_put(
                params, NamedSharding(self.mesh, P()))  # replicate
        self.params = params

        self._row_sharding = (NamedSharding(self.mesh, P())
                              if self.mesh is not None else None)
        self._bucket_fns = {}       # bucket -> {step, admit, peel, zeros}
        self._grow_fns = {}         # (b_from, b_to) -> jitted pad
        self._state = None
        self._bucket = 0
        self._idle_ns: Optional[int] = None  # open serve.idle period
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- compile-cache -----------------------------------------------------
    def _state_sharding(self, b: int):
        if self.mesh is None:
            return None
        spec = S.sanitize_spec((b, *self.field_shape), P("data"), self.mesh)
        return NamedSharding(self.mesh, spec)

    def _count(self, name: str) -> None:
        # called from INSIDE jitted bodies: runs at trace time only, so
        # it counts (re)compiles, not executions
        self.stats[name] += 1

    def _fns(self, b: int):
        if b in self._bucket_fns:
            return self._bucket_fns[b]
        shape = (b, *self.field_shape)
        sh = self._state_sharding(b)
        pin = (lambda x: x) if sh is None else \
            (lambda x: jax.lax.with_sharding_constraint(x, sh))

        def _step(params, state):
            self._count("compiles")
            return pin(M.forecast_step(params, state, self.cfg, self.jcfg))

        def _admit(state, fields, slot):
            self._count("compiles")
            row = fields.astype(state.dtype)[None]
            return pin(jax.lax.dynamic_update_index_in_dim(
                state, row, slot, 0))

        def _peel(state, slot):
            self._count("compiles")
            return jax.lax.dynamic_index_in_dim(state, slot, 0,
                                                keepdims=False)

        def _zeros():
            self._count("compiles")
            return pin(jnp.zeros(shape, jnp.float32))

        fns = {"step": jax.jit(_step, donate_argnums=(1,)),
               "admit": jax.jit(_admit, donate_argnums=(0,)),
               "peel": jax.jit(_peel),
               "zeros": jax.jit(_zeros)}
        self._bucket_fns[b] = fns
        return fns

    def _grow(self, b_from: int, b_to: int):
        key = (b_from, b_to)
        if key not in self._grow_fns:
            sh = self._state_sharding(b_to)
            pin = (lambda x: x) if sh is None else \
                (lambda x: jax.lax.with_sharding_constraint(x, sh))

            def _pad(state):
                self._count("compiles")
                return pin(jnp.pad(
                    state, ((0, b_to - b_from),) + ((0, 0),) * 3))

            # no donation: the padded output is LARGER than the input, so
            # XLA could never alias the buffers (it would only warn)
            self._grow_fns[key] = jax.jit(_pad)
        return self._grow_fns[key]

    def compile_cache_size(self) -> int:
        """Executables held by the jit caches (cross-check for the trace
        counter; jax internal, so best-effort)."""
        fns = [f for d in self._bucket_fns.values() for f in d.values()]
        fns += list(self._grow_fns.values())
        try:
            return sum(f._cache_size() for f in fns)
        except AttributeError:      # older/newer jaxlib
            return -1

    def warmup(self, buckets: Optional[Sequence[int]] = None) -> int:
        """Pre-compile every bucket's step/admit/peel/zeros (+ adjacent
        grows) with dummy states so steady-state serving never traces.
        Returns the compile count, also stamped into
        ``stats["warm_compiles"]`` -- the benchmark's zero-recompile
        assertion compares against it."""
        t0 = time.perf_counter()
        buckets = tuple(sorted(buckets or self.config.buckets))
        dummy = self._put_fields(np.zeros(self.field_shape, np.float32))
        for b in buckets:
            fns = self._fns(b)
            state = fns["zeros"]()
            state = fns["admit"](state, dummy, np.int32(0))
            state = fns["step"](self.params, state)
            np.asarray(fns["peel"](state, np.int32(0)))
        for b1, b2 in zip(buckets, buckets[1:]):
            self._grow(b1, b2)(self._fns(b1)["zeros"]())
        self.stats["warmup_s"] += time.perf_counter() - t0
        self.stats["warm_compiles"] = self.stats["compiles"]
        return self.stats["compiles"]

    # -- request path ------------------------------------------------------
    def _put_fields(self, fields: np.ndarray):
        if self._row_sharding is not None:
            return jax.device_put(fields, self._row_sharding)
        return jax.device_put(fields)

    def submit(self, fields, lead: Lead = 1) -> ForecastResult:
        """Enqueue one forecast request (thread-safe).

        fields: [lat, lon, C] initial condition.  lead: rollout steps
        ahead -- an int, or a sequence of horizons that share the rollout
        and peel off at their own step (lead-time fan-out)."""
        leads = (int(lead),) if np.isscalar(lead) else \
            tuple(sorted(set(int(x) for x in lead)))
        if not leads or leads[0] < 1:
            raise ValueError(f"leads must be >= 1, got {leads}")
        fields = np.asarray(fields, np.float32)
        if fields.shape != self.field_shape:
            raise ValueError(f"fields shape {fields.shape} != "
                             f"{self.field_shape}")
        req = ForecastResult(fields, leads, submit_t=self._clock(),
                             submit_ns=self.tracer.now_ns())
        self.sched.submit(req)
        self._wake.set()
        return req

    def step_once(self) -> str:
        """Advance one rollout-step boundary.

        Returns "idle" (nothing to do), "wait" (coalescing window still
        open) or "step" (one device rollout step ran).

        Spans name what the boundary waits on: ``serve.idle`` runs from
        the first boundary that found no request queued or in flight to
        the next one that found some, and is recorded when that one
        starts; ``serve.queue`` runs from a request's ``submit`` to its
        admission, on the submitting thread's track; in ``serve.peel``,
        ``serve.peel.wait`` is the device finishing the step and the
        slice, ``serve.peel.fetch`` the copy to the host."""
        tr = self.tracer
        t_ns = tr.now_ns()
        tick = self.sched.tick()
        if tick.idle:
            if self._idle_ns is None:
                self._idle_ns = t_ns
            return "idle"
        if self._idle_ns is not None:
            tr.complete("serve.idle", self._idle_ns, t_ns)
            self._idle_ns = None
        if tick.wait is not None:
            self.stats["wait_ticks"] += 1
            return "wait"
        t_ns = tr.now_ns()             # admission: after every submit_ns
        for _, req in tick.admit:
            tr.complete("serve.queue", req.submit_ns, t_ns,
                        tid=req.submit_tid, rid=req.rid)
        if tick.form is not None:
            with tr.span("serve.form", bucket=tick.form):
                self._state = self._fns(tick.form)["zeros"]()
            self._bucket = tick.form
        elif tick.grow is not None:
            with tr.span("serve.grow", b_from=self._bucket,
                         b_to=tick.grow):
                self._state = self._grow(self._bucket,
                                         tick.grow)(self._state)
            self._bucket = tick.grow
        fns = self._fns(self._bucket)
        if tick.admit:
            with tr.span("serve.admit", n=len(tick.admit),
                         bucket=self._bucket,
                         rids=[req.rid for _, req in tick.admit]):
                for slot, req in tick.admit:
                    self._state = fns["admit"](self._state,
                                               self._put_fields(req.fields),
                                               np.int32(slot))
        with tr.span("serve.step", bucket=self._bucket):
            self._state = fns["step"](self.params, self._state)
        self.stats["device_steps"] += 1
        tr.counter("serve.device_steps")
        peels, _finished = self.sched.advance()
        now = self._clock()
        for slot, req, lead in peels:
            with tr.span("serve.peel", lead=lead, rid=req.rid):
                piece = fns["peel"](self._state, np.int32(slot))
                with tr.span("serve.peel.wait"):
                    jax.block_until_ready(piece)
                with tr.span("serve.peel.fetch"):
                    out = np.asarray(piece)
            req.deliver(lead, out, now)
            # admission-to-delivery latency histograms: the engine's
            # serving SLO, one track per lead time plus the overall one
            lat = now - req.submit_t
            tr.observe("serve.latency_s", lat)
            tr.observe(f"serve.latency_s/lead={lead}", lat)
        return "step"

    def drain(self, poll_s: float = 1e-3) -> None:
        """Run boundaries until queue and batch are empty."""
        while True:
            r = self.step_once()
            if r == "idle":
                return
            if r == "wait":
                self._sleep(poll_s)

    def serve(self, fields_batch, leads: Sequence[Lead]):
        """Convenience: submit a batch of requests and drain."""
        out = [self.submit(f, ld) for f, ld in zip(fields_batch, leads)]
        self.drain()
        return out

    # -- background serving loop (for live submitters, e.g. the CLI) ------
    def start(self) -> None:
        if self._thread is not None:
            raise RuntimeError("engine already started")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                r = self.step_once()
                if r == "idle":
                    self._wake.wait(0.005)
                    self._wake.clear()
                elif r == "wait":
                    self._sleep(1e-3)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="forecast-serve")
        self._thread.start()

    def stop(self) -> None:
        if self._thread is None:
            return
        self._stop.set()
        self._wake.set()
        self._thread.join()
        self._thread = None

    # -- reporting ---------------------------------------------------------
    def summary(self, results: Sequence[ForecastResult]) -> dict:
        """Serving report over everything this engine delivered: the
        admission-to-delivery percentiles come from the engine's
        telemetry histograms (p50/p95/p99 overall and per lead time),
        not a private sort of ``results``."""
        h = self.tracer.hist_summary("serve.latency_s")
        nan = float("nan")
        sc = self.sched.counters
        leads = {}
        for name in self.tracer.hist_names():
            if name.startswith("serve.latency_s/lead="):
                lead = int(name.split("=", 1)[1])
                leads[lead] = self.tracer.hist_summary(name)
        return {"requests": len(results),
                "p50_s": h.get("p50", nan), "p95_s": h.get("p95", nan),
                "p99_s": h.get("p99", nan),
                "deliveries": h.get("count", 0),
                "lead_latency_s": leads,
                "device_steps": self.stats["device_steps"],
                "compiles": self.stats["compiles"],
                "admitted": sc["admitted"], "completed": sc["completed"],
                "formed": sc["formed"], "grown": sc["grown"]}

    def export_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write this engine's Chrome trace (+ sibling JSONL) to
        ``path`` or ``config.trace``; returns the path (None = no-op)."""
        path = path or self.config.trace
        if not path:
            return None
        self.tracer.export_chrome(path)
        self.tracer.export_jsonl(telemetry.jsonl_path_for(path))
        return path
