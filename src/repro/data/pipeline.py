"""Pluggable input-pipeline subsystem: domain-parallel sharded reads with
background prefetch (the paper's §5 data-loading contribution as the
*actual training path*, not just a property test).

Every model-parallel rank reads only its own (longitude x channel)
partition of each weather sample -- and every data-parallel rank only its
own batch rows -- so host-side generation ("I/O") scales with the number
of ranks: the source of the paper's superscalar weak scaling in
I/O-bandwidth-limited systems.  A background thread generates and
transfers the next batches while the device computes the current step
(double-buffered prefetch), overlapping input with compute.

Three pieces (DESIGN.md §7):

* ``BatchSource``       -- dataset adapter exposing full-batch and
                           per-shard reads that are bit-identical to
                           slicing the full batch.
* ``InputPipeline``     -- derives a per-HOST read plan from the mesh +
                           batch PartitionSpecs (the UNIQUE index slices
                           across this host's addressable devices,
                           computed once since specs are step-invariant),
                           reads each unique slice exactly once per step,
                           fans it out to the devices that replicate it,
                           assembles the global jax.Array with
                           ``make_array_from_single_device_arrays``, and
                           (optionally) prefetches on a worker thread.
                           ``mode="sync-full"`` preserves the legacy
                           generate-everything-then-device_put behavior
                           for A/B benchmarking.
* ``make_pipeline``     -- family dispatch (mixer / lm / vlm / audio).

Determinism: batches are a pure function of (seed, step, horizon); the
prefetch thread changes timing only, never values (property-tested in
tests/test_pipeline.py and tests/dist_scenarios.py).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Dict, Iterable, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# hashable (start, stop) bounds per dim from a sharding index tuple
# (``slice`` is unhashable on py<3.12); shared with the checkpoint
# subsystem, which records the same bounds in its manifest
from repro import telemetry
from repro.checkpoint.manifest import normalize_index as _normalize_index
from repro.data.tokens import TokenDataConfig, TokenDataset
from repro.data.weather import WeatherDataConfig, WeatherDataset


# ---------------------------------------------------------------------------
# Stats
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PipelineStats:
    """Host-side I/O accounting, updated by the pipeline once per batch.

    ``generated_bytes[key]``  bytes actually produced by shard reads on
                              this host (deduplicated across devices that
                              own identical replicas);
    ``rank_bytes[key][dev]``  logical bytes each device's rank read --
                              this is what ``io_bytes_per_rank`` models
                              and what the ∝ 1/ranks test measures.

    Updates go through :meth:`record_batch`, which holds the process
    tracer's lock for the whole batch: the prefetch worker and a
    same-process consumer (stats readers, a ``sync-full`` A/B run) never
    interleave read-modify-writes on these counters, and the aggregate
    totals land in the tracer's counter table in the same critical
    section (one lock acquisition per batch, not one per device read).
    """
    steps: int = 0
    plan_builds: int = 0
    generated_bytes: Dict[str, int] = dataclasses.field(default_factory=dict)
    rank_bytes: Dict[str, Dict[int, int]] = dataclasses.field(
        default_factory=dict)

    def record(self, key: str, device_id: int, nbytes: int,
               generated: bool) -> None:
        """Single-read back-compat shim; prefer :meth:`record_batch`."""
        self.record_batch([(key, device_id, nbytes, generated)])

    def record_batch(self, reads: Sequence[Tuple[str, int, int, bool]],
                     steps: int = 0, plan_builds: int = 0) -> None:
        """Apply one batch's worth of read records ``(key, device_id,
        nbytes, generated)`` atomically under the telemetry lock."""
        gen = 0
        dev_bytes = 0
        tr = telemetry.get_tracer()
        with tr.lock:
            self.steps += steps
            self.plan_builds += plan_builds
            for key, device_id, nbytes, generated in reads:
                if generated:
                    self.generated_bytes[key] = (
                        self.generated_bytes.get(key, 0) + nbytes)
                    gen += nbytes
                per = self.rank_bytes.setdefault(key, {})
                per[device_id] = per.get(device_id, 0) + nbytes
                dev_bytes += nbytes
            updates = {}
            if steps:
                updates["pipeline.batches"] = steps
            if plan_builds:
                updates["pipeline.plan_builds"] = plan_builds
            if gen:
                updates["pipeline.generated_bytes"] = gen
            if dev_bytes:
                updates["pipeline.device_bytes"] = dev_bytes
            if updates:
                tr.add_counters_locked(updates)


# ---------------------------------------------------------------------------
# Batch sources (dataset adapters)
# ---------------------------------------------------------------------------

class BatchSource:
    """Adapter between a synthetic dataset and the pipeline.

    ``read_key(key, step, horizon, idx)`` must be bit-identical to
    ``full_batch(step, horizon)[key][idx]`` -- the paper's data-loading
    correctness invariant."""

    keys: Tuple[str, ...] = ()

    def full_batch(self, step: int, horizon: int) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def read_key(self, key: str, step: int, horizon: int,
                 idx: Tuple[Tuple[int, int], ...]) -> np.ndarray:
        """``idx`` is a per-dim tuple of (start, stop) bounds."""
        raise NotImplementedError

    def key_shape(self, key: str) -> Tuple[int, ...]:
        """Global shape of ``key`` (step-invariant)."""
        raise NotImplementedError


class WeatherBatchSource(BatchSource):
    """ERA5-like fields: true partitioned reads over (rows, lat, lon,
    channels) -- each rank evaluates only its sub-grid."""

    keys = ("fields", "target")

    def __init__(self, ds: WeatherDataset, batch_size: int):
        self.ds = ds
        self.batch_size = batch_size
        self._memo_key = None
        self._memo: Dict[Tuple, Dict[str, np.ndarray]] = {}

    def full_batch(self, step, horizon):
        return self.ds.sample_batch(step, self.batch_size, horizon=horizon)

    def key_shape(self, key):
        c = self.ds.cfg
        return (self.batch_size, c.lat, c.lon, c.channels)

    def read_key(self, key, step, horizon, idx):
        # fields and target share shape/spec, hence the same index map:
        # one sample_shard call serves both (memoized per step).
        if self._memo_key != (step, horizon):
            self._memo_key = (step, horizon)
            self._memo = {}
        got = self._memo.get(idx)
        if got is None:
            b, la, lo, ch = _slices(idx)
            got = self.ds.sample_shard(
                step, self.batch_size, row_slice=b, lat_slice=la,
                lon_slice=lo, chan_slice=ch, horizon=horizon)
            self._memo[idx] = got
        return got[key]


class TokenBatchSource(BatchSource):
    """LM token rows (+ optional dense side inputs for vlm/audio): true
    per-data-rank row reads for tokens/labels; the dense ``embeds`` /
    ``frames`` are a full host draw sliced per device (they model
    preprocessed modality features, not the paper's grid I/O)."""

    def __init__(self, ds: TokenDataset, batch_size: int,
                 extras: Optional[Dict[str, Tuple[int, ...]]] = None):
        self.ds = ds
        self.batch_size = batch_size
        self.extras = dict(extras or {})   # name -> trailing shape
        self.keys = ("tokens", "labels") + tuple(self.extras)
        self._memo_key = None
        self._rows: Dict[Tuple[int, int], Dict[str, np.ndarray]] = {}
        self._full_extras: Dict[str, np.ndarray] = {}

    def _sync_step(self, step: int) -> None:
        """Invalidate the per-step memos when the step changes (both the
        full-batch and the sharded read path go through here)."""
        if self._memo_key != step:
            self._memo_key = step
            self._rows = {}
            self._full_extras = {}

    def _extra(self, key: str, step: int) -> np.ndarray:
        self._sync_step(step)
        got = self._full_extras.get(key)
        if got is None:
            rng = np.random.default_rng(step)
            got = rng.normal(0, 1, (self.batch_size,) + self.extras[key]
                             ).astype(np.float32)
            self._full_extras[key] = got
        return got

    def full_batch(self, step, horizon):
        del horizon
        out = self.ds.sample_batch(step, self.batch_size)
        for k in self.extras:
            out[k] = self._extra(k, step)
        return out

    def key_shape(self, key):
        if key in self.extras:
            return (self.batch_size,) + self.extras[key]
        return (self.batch_size, self.ds.cfg.seq_len)

    def read_key(self, key, step, horizon, idx):
        del horizon
        self._sync_step(step)
        if key in self.extras:
            return np.ascontiguousarray(self._extra(key, step)[_slices(idx)])
        rows = idx[0]
        got = self._rows.get(rows)
        if got is None:
            got = self.ds.sample_shard(step, self.batch_size,
                                       row_slice=slice(*rows))
            self._rows[rows] = got
        return got[key]


# ---------------------------------------------------------------------------
# The pipeline
# ---------------------------------------------------------------------------



def _slices(nidx: Tuple[Tuple[int, int], ...]) -> Tuple[slice, ...]:
    return tuple(slice(a, b) for a, b in nidx)


@dataclasses.dataclass(frozen=True)
class _ReadPlan:
    """Per-host read plan for one batch key: the UNIQUE index slices any
    addressable device needs, each with the devices that replicate it.
    Built once per pipeline (specs and shapes are step-invariant), so
    multi-replica meshes never read the same slice once per local
    device -- only once per host."""
    shape: Tuple[int, ...]
    sharding: NamedSharding
    reads: Tuple[Tuple[Tuple[Tuple[int, int], ...], Tuple], ...]


class InputPipeline:
    """Domain-parallel, prefetching input pipeline.

    Parameters
    ----------
    source : BatchSource
    mesh : Mesh or None -- None means single-device (no sharding).
    specs : dict key -> PartitionSpec (global batch layout, unsanitized);
        required when ``mesh`` is given.
    mode : "sharded" (per-rank partitioned reads, the paper's path) or
        "sync-full" (generate the full global batch then device_put --
        the legacy behavior, kept for A/B benchmarking).
    prefetch : number of batches the background thread keeps in flight
        (0 disables the thread; 2 = double buffering).
    """

    def __init__(self, source: BatchSource, *, mesh: Optional[Mesh] = None,
                 specs: Optional[Dict[str, P]] = None, mode: str = "sharded",
                 prefetch: int = 2):
        if mode not in ("sharded", "sync-full"):
            raise ValueError(f"unknown pipeline mode {mode!r}")
        if mesh is not None and specs is None:
            raise ValueError("specs required when a mesh is given")
        self.source = source
        self.mesh = mesh
        self.specs = specs or {}
        self.mode = mode
        self.prefetch = int(prefetch)
        self.stats = PipelineStats()
        # next step this pipeline will serve (checkpointed + restored by
        # the engine for exact resume; batches are pure functions of the
        # step so the cursor IS the full pipeline state)
        self.cursor = 0
        self._plans: Dict[str, _ReadPlan] = {}
        # live prefetch machinery of the most recent iterate() (for
        # stop(): a preempting process must be able to cancel the worker
        # without waiting out the full horizon)
        self._queue: Optional["queue.Queue"] = None
        self._stop_event: Optional[threading.Event] = None
        self._thread: Optional[threading.Thread] = None

    # -- host-side ------------------------------------------------------
    def host_batch(self, step: int, horizon: int = 1
                   ) -> Dict[str, np.ndarray]:
        """The full global batch on host (reference / sync-full path)."""
        return self.source.full_batch(step, horizon)

    def _sharding_for(self, key: str, shape) -> NamedSharding:
        from repro.launch import specs as S
        spec = S.sanitize_spec(shape, self.specs.get(key, P()), self.mesh)
        return NamedSharding(self.mesh, spec)

    # -- device-side ----------------------------------------------------
    def get(self, step: int, horizon: int = 1, *,
            wait: bool = False) -> Dict[str, jax.Array]:
        """The global (possibly sharded) device batch for ``step``.

        The host reads come first, then the transfer.  With ``wait``
        (the prefetch thread's call) the transfer runs in a
        ``pipeline.h2d`` span that ends on ``block_until_ready`` of the
        batch's arrays, so a queued batch is already on the device.

        Stats are collected locally while reading and committed in ONE
        ``record_batch`` call at the end -- the whole batch's accounting
        is a single critical section, so a concurrent stats reader never
        observes a half-applied batch."""
        reads: list = []
        host = self._read(step, horizon, reads)
        if wait:
            with telemetry.get_tracer().span("pipeline.h2d", step=step):
                out = jax.block_until_ready(
                    {k: self._put(k, v) for k, v in host.items()})
        else:
            out = {k: self._put(k, v) for k, v in host.items()}
        self.stats.record_batch(reads, steps=1)
        return out

    def _read(self, step: int, horizon: int, reads: list) -> Dict:
        """The batch on the host, per key: whole arrays, or under
        ``sharded`` on a mesh each unique slice of the read plan with
        the devices that replicate it."""
        if self.mesh is not None and self.mode == "sharded":
            return {k: self._read_slices(k, step, horizon, reads)
                    for k in self.source.keys}
        hb = self.host_batch(step, horizon)
        if self.mesh is not None:
            reads.extend((k, -1, v.nbytes, True) for k, v in hb.items())
        return hb

    def _put(self, key: str, host) -> jax.Array:
        """One key's host data onto the device(s)."""
        if self.mesh is None:
            return jnp.asarray(host)
        if self.mode == "sync-full":
            return jax.device_put(jnp.asarray(host),
                                  self._sharding_for(key, host.shape))
        plan = self._plan_for(key)
        arrays = [jax.device_put(buf, dev)
                  for buf, devs in host for dev in devs]
        return jax.make_array_from_single_device_arrays(
            plan.shape, plan.sharding, arrays)

    def _plan_for(self, key: str) -> _ReadPlan:
        """The (cached) per-host read plan for ``key``: unique slices
        across this host's addressable devices, grouped."""
        plan = self._plans.get(key)
        if plan is None:
            shape = self.source.key_shape(key)
            sharding = self._sharding_for(key, shape)
            idx_map = sharding.addressable_devices_indices_map(shape)
            groups: Dict[Tuple[Tuple[int, int], ...], list] = {}
            for dev, idx in idx_map.items():
                groups.setdefault(_normalize_index(idx, shape),
                                  []).append(dev)
            plan = _ReadPlan(shape, sharding,
                             tuple((nidx, tuple(devs))
                                   for nidx, devs in groups.items()))
            self._plans[key] = plan
            self.stats.record_batch([], plan_builds=1)
        return plan

    def _read_slices(self, key: str, step: int, horizon: int,
                     reads: list) -> list:
        """Per-host partitioned reads of ``key``: each unique slice in
        the plan is generated ONCE, for every device that replicates it
        (``_put`` fans it out).  Read records are appended to ``reads``
        for the caller's one-shot ``record_batch``."""
        out = []
        for nidx, devs in self._plan_for(key).reads:
            buf = np.ascontiguousarray(
                self.source.read_key(key, step, horizon, nidx))
            reads.extend((key, dev.id, buf.nbytes, j == 0)
                         for j, dev in enumerate(devs))
            out.append((buf, devs))
        return out

    # -- resume state ----------------------------------------------------
    def state(self) -> Dict[str, int]:
        """Checkpointable cursor (batches are pure functions of the step,
        so this one integer restarts the stream exactly)."""
        return {"cursor": int(self.cursor)}

    def set_state(self, state: Dict[str, int]) -> None:
        self.cursor = int(state["cursor"])

    # -- prefetching iterator -------------------------------------------
    def iterate(self, horizons: Sequence[int],
                start_step: Optional[int] = None
                ) -> Iterable[Dict[str, jax.Array]]:
        """Yield device batches for steps ``start_step + i`` with per-step
        rollout horizons ``horizons[i]``.  ``start_step=None`` continues
        from the pipeline's cursor (0 on a fresh pipeline, the restored
        step after a resume).  With ``prefetch > 0`` a daemon thread
        generates and transfers batches ahead of the consumer and queues
        each once it is on the device (``pipeline.h2d``); values are
        identical either way (pure function of the step)."""
        n = len(horizons)
        if start_step is None:
            start_step = self.cursor
        if self.prefetch <= 0:
            for i in range(n):
                batch = self.get(start_step + i, int(horizons[i]))
                self.cursor = start_step + i + 1
                yield batch
            return

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        stop = threading.Event()
        tr = telemetry.get_tracer()

        def worker():
            try:
                for i in range(n):
                    if stop.is_set():
                        return
                    with tr.span("pipeline.produce", step=start_step + i):
                        batch = self.get(start_step + i, int(horizons[i]),
                                         wait=True)
                    while not stop.is_set():
                        # bounded put: never blocks forever against a
                        # consumer that has already given up (stop()
                        # from a preempting process)
                        try:
                            q.put((batch, None), timeout=0.1)
                            break
                        except queue.Full:
                            continue
            except BaseException as e:       # surfaced on the consumer
                q.put((None, e))

        t = threading.Thread(target=worker, name="input-pipeline",
                             daemon=True)
        self._queue, self._stop_event, self._thread = q, stop, t
        t.start()
        try:
            for i in range(n):
                # depth BEFORE the blocking get: the signal the engine's
                # data_wait spans are cross-checked against (0 here means
                # the consumer is about to stall on the producer)
                tr.gauge("pipeline.queue_depth", q.qsize())
                batch, err = q.get()
                if err is not None:
                    raise err
                self.cursor = start_step + i + 1
                yield batch
        finally:
            self.stop()

    def stop(self, timeout: float = 5.0) -> bool:
        """Cancel the prefetch worker: set its stop flag, drain the
        queue so a blocked ``put`` wakes up, and join with ``timeout``.
        Returns True when the thread is down (always safe to call --
        idempotent, and a no-op when prefetch is disabled).  The worker
        is a daemon, so even a join timeout (it only happens mid-
        ``get()``, i.e. mid batch generation) cannot hang process exit
        -- the preemption path needs bounded shutdown latency."""
        t, q, stop = self._thread, self._queue, self._stop_event
        if t is None:
            return True
        stop.set()
        while True:                          # unblock a producer in put()
            try:
                q.get_nowait()
            except queue.Empty:
                break
        t.join(timeout=timeout)
        alive = t.is_alive()
        if not alive:
            self._queue = self._stop_event = self._thread = None
        return not alive

    # -- modeled I/O -----------------------------------------------------
    def io_bytes_per_rank(self, n_ranks: int) -> int:
        """Modeled per-rank bytes per step for the primary array (delegates
        to the dataset's model; compared against measured ``stats`` in
        tests)."""
        ds, bsz = self.source.ds, self.source.batch_size
        return ds.io_bytes_per_rank(bsz, n_ranks)


# ---------------------------------------------------------------------------
# Family dispatch
# ---------------------------------------------------------------------------

def make_source(cfg, batch_size: int, seq_len: int = 128,
                seed: int = 0) -> BatchSource:
    """BatchSource for a ModelConfig family (mixer / lm / vlm / audio)."""
    if cfg.family == "mixer":
        ds = WeatherDataset(WeatherDataConfig(
            lat=cfg.wm_lat, lon=cfg.wm_lon, channels=cfg.wm_channels,
            seed=seed))
        return WeatherBatchSource(ds, batch_size)
    ds = TokenDataset(TokenDataConfig(vocab_size=cfg.vocab_size,
                                      seq_len=seq_len, seed=seed))
    extras: Dict[str, Tuple[int, ...]] = {}
    if cfg.family == "vlm":
        extras["embeds"] = (cfg.n_patches, cfg.d_model)
    if cfg.family == "audio":
        extras["frames"] = (cfg.n_frames, cfg.d_model)
    return TokenBatchSource(ds, batch_size, extras)


def make_pipeline(cfg, *, mesh: Optional[Mesh] = None, rules=None,
                  batch_size: int, seq_len: int = 128, mode: str = "sharded",
                  prefetch: int = 2, seed: int = 0) -> InputPipeline:
    """InputPipeline for a ModelConfig on ``mesh`` (None = single device)."""
    source = make_source(cfg, batch_size, seq_len=seq_len, seed=seed)
    specs = None
    if mesh is not None:
        from repro.launch import specs as S
        specs = S.batch_specs(cfg, rules)
    return InputPipeline(source, mesh=mesh, specs=specs, mode=mode,
                         prefetch=prefetch)
