"""TrainEngine: the training loop as a small reusable subsystem.

Replaces the monolithic ``train()`` loop: the engine owns

  * mesh / sharding-rule resolution and the jitted step functions
    (one per rollout length, the paper's §6 randomized-rollout schedule),
  * the input pipeline (domain-parallel sharded reads + background
    prefetch, ``repro.data.pipeline``; ``sync-full`` preserves the legacy
    host-side full-batch generation for A/B runs),
  * microbatch gradient accumulation (``accum``),
  * eval cadence (held-out steps on a separate pipeline instance, so the
    prefetch thread and eval reads never share dataset memo state),
  * metrics history, logging, and zero-redundancy sharded checkpoints
    (async background writes, ``EngineConfig(resume=...)`` exact resume
    restoring params/opt/step/rollout-schedule/pipeline-cursor --
    DESIGN.md §9).

``launch/train.py``, the examples, ``chip_smoke.py`` and the chip
benchmark (``bench/``) are thin callers of this class (DESIGN.md §7).

Typical use:

    eng = TrainEngine("weathermixer-1b", mesh_model=4, mesh_data=2,
                      config=EngineConfig(steps=100, batch=8, rollout=3))
    history = eng.run()
    params = eng.params
"""
from __future__ import annotations

import dataclasses
import os
import time
from contextlib import nullcontext
from functools import partial
from typing import Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro import checkpoint as ckpt
from repro import telemetry
from repro.configs.registry import get_config
from repro.core import precision
from repro.core.sharding import RULES_1D
from repro.data.pipeline import InputPipeline, make_pipeline
from repro.launch import shapes as SH
from repro.models import registry as M
from repro.optim import adam, schedule as sched
from repro.train.step import make_eval_step, make_train_step

# held-out validation stream: step indices far past any training step
EVAL_STEP_OFFSET = 1 << 20


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Step-dispatch policy of a TrainEngine (everything that is not the
    model / mesh itself)."""
    steps: int = 100
    batch: int = 8
    seq_len: int = 128
    rollout: int = 1           # randomized-rollout fine-tuning upper bound
    lr: float = 1e-3
    log_every: int = 10
    eval_every: int = 0        # 0 = no mid-training eval
    eval_batches: int = 2
    accum: int = 1             # microbatch gradient accumulation
    zero1: bool = False        # ZeRO-1: shard optimizer moments over data
    precision: Optional[str] = None   # policy preset (core/precision):
                               # fp32|bf16|bf16_pure; None = config dtypes
    ckpt: Optional[str] = None
    ckpt_every: int = 0        # 0 = only a final checkpoint (if ckpt set)
    keep_ckpts: int = 0        # keep last k periodic ckpts (0 = keep all)
    resume: Optional[str] = None   # checkpoint dir: exact-resume from it
    async_save: bool = True    # background checkpoint writes (DESIGN §9)
    seed: int = 0
    pipeline: str = "sharded"  # "sharded" | "sync-full"
    prefetch: int = 2          # 0 disables the background thread
    metrics_out: Optional[str] = None
    metrics_format: str = "jsonl"  # "jsonl" (crash-safe append, one
                               # line per record) | "json" (legacy full
                               # dump at the end of the run)
    telemetry: bool = True     # span/step-record tracing (DESIGN.md §14;
                               # counters stay live even when False)
    trace: Optional[str] = None    # Chrome trace-event export path; a
                               # sibling .jsonl gets the step records
    preemption: bool = False   # SIGTERM/SIGUSR1 -> final save + Preempted
    preempt_at_step: Optional[int] = None  # chaos hook: self-SIGTERM
                               # after this step (or REPRO_PREEMPT_AT_STEP)


class TrainEngine:
    """Owns params/opt state, the jitted steps, and the input pipeline."""

    def __init__(self, arch: str, *, reduced: bool = True,
                 mesh_model: int = 1, mesh_data: int = 1,
                 scheme: Optional[str] = None, impl: Optional[str] = None,
                 kernel: Optional[str] = None,
                 config: EngineConfig = EngineConfig(),
                 init_params=None, config_override=None):
        self.arch = arch
        self.config = config
        self.reduced = reduced
        if config.metrics_format not in ("jsonl", "json"):
            raise ValueError(
                f"unknown metrics_format {config.metrics_format!r} "
                f"(expected 'jsonl' or 'json')")
        cfg = config_override if config_override is not None \
            else get_config(arch)
        if reduced:
            cfg = cfg.reduced()
        if scheme:
            cfg = cfg.replace(scheme=scheme)
        if impl:
            cfg = cfg.replace(impl=impl)
        if kernel:
            cfg = cfg.replace(kernel=kernel)
        if config.precision:
            # precision policy (core/precision, DESIGN.md §10): one
            # replace threads param/compute dtypes into the config; the
            # JigsawConfig (ring wire/accum dtypes) and AdamConfig
            # (masters/moments) below are derived from the same policy
            cfg = precision.apply_policy(cfg, config.precision)
        self.policy = precision.policy_of(cfg)

        self.use_mesh = mesh_model * mesh_data > 1
        if self.use_mesh:
            from repro.launch.mesh import make_host_mesh
            self.mesh = make_host_mesh(model=mesh_model, data=mesh_data,
                                       two_d=cfg.scheme == "2d")
            self.rules = SH.rules_for(cfg)
        else:
            self.mesh = None
            cfg = cfg.replace(scheme="none")
            self.rules = RULES_1D
        self.cfg = cfg
        self.jcfg = SH.jigsaw_for(cfg).replace(rules=self.rules)
        self.mesh_model, self.mesh_data = mesh_model, mesh_data

        # telemetry (DESIGN.md §14): the engine owns the process tracer;
        # the pipeline / checkpoint writer / resilience hooks report
        # into it via telemetry.get_tracer(), its spans show in any
        # jax.profiler trace and backend compiles land in it as spans.
        # The analytic cost model's counts go into the meta header
        # (telemetry/accounting.py); step records hold measured fields.
        self.tracer = telemetry.attach_profiler(
            telemetry.Tracer(enabled=config.telemetry))
        telemetry.set_tracer(self.tracer)
        device = (self.mesh.devices.flat[0] if self.use_mesh
                  else jax.devices()[0])
        self.cost_model = telemetry.build_cost_model(
            cfg, n_model=mesh_model, n_data=mesh_data,
            batch=config.batch, seq_len=config.seq_len, device=device)
        self.tracer.set_meta(
            arch=arch, reduced=reduced, mesh_model=mesh_model,
            mesh_data=mesh_data, scheme=cfg.scheme, impl=cfg.impl,
            kernel=cfg.kernel, precision=self.policy.name,
            steps=config.steps, batch=config.batch,
            rollout=config.rollout, zero1=config.zero1,
            device=self.cost_model.device,
            cost_model=self.cost_model.as_meta())

        key = jax.random.PRNGKey(config.seed)
        # copy init_params: the step donates its buffers, and the caller
        # may still hold them (e.g. to evaluate the base model after)
        self.params = M.init(key, cfg) if init_params is None \
            else jax.tree.map(jnp.copy, init_params)
        if init_params is not None and config.precision:
            # external params adopt the policy's storage dtype (masters
            # are re-derived fp32 from them in adam.init below)
            self.params = jax.tree.map(
                lambda p: p.astype(jnp.dtype(cfg.param_dtype))
                if jnp.issubdtype(p.dtype, jnp.floating) else p,
                self.params)
        pol = self.policy
        moment_dt = pol.moment_dtype
        self.adam_cfg = adam.AdamConfig(
            weight_decay=0.0, master_weights=pol.master_weights,
            state_dtype=None if moment_dt is None
            else jnp.dtype(moment_dt).name)
        # Engine-level param-spec pinning (ROADMAP PR-3 follow-up): pin
        # params to their jigsaw PartitionSpecs at init AND at every step
        # output, so non-zero1 runs no longer come back GSPMD-replicated
        # (which made sharded checkpoints dump all bytes on one rank).
        self._param_shardings = None
        self._opt_shardings = None
        if self.use_mesh:
            self._param_shardings = self._param_pins()
            self.params = jax.device_put(self.params,
                                         self._param_shardings)
            # Optimizer state is BORN in its layout -- the parameters'
            # specs (zero redundancy), plus a data-axis shard of every
            # moment under ZeRO-1 (DESIGN.md §6.5) -- never whole on one
            # device; the step output is pinned to the same layout so it
            # survives across updates.
            self._opt_shardings = self._opt_pins()
            self.opt_state = jax.jit(
                partial(adam.init, cfg=self.adam_cfg),
                out_shardings=self._opt_shardings)(self.params)
        else:
            self.opt_state = adam.init(self.params, self.adam_cfg)
        self.lr_fn = partial(
            sched.warmup_cosine, base_lr=config.lr,
            warmup_steps=max(config.steps // 10, 1),
            total_steps=config.steps, min_lr=config.lr * 0.1)

        def _jit_step(r: int):
            fn = make_train_step(cfg, self.jcfg, adam_cfg=self.adam_cfg,
                                 lr_fn=self.lr_fn, rollout=r,
                                 accum=config.accum)
            psh, osh = self._param_shardings, self._opt_shardings
            if psh is not None or osh is not None:
                base = fn

                def fn(params, opt_state, batch):
                    p, o, m = base(params, opt_state, batch)
                    if psh is not None:
                        p = jax.tree.map(jax.lax.with_sharding_constraint,
                                         p, psh)
                    if osh is not None:
                        o = jax.tree.map(jax.lax.with_sharding_constraint,
                                         o, osh)
                    return p, o, m
            return jax.jit(fn, donate_argnums=(0, 1))

        # randomized-rollout fine-tuning (paper §6): each update draws a
        # rollout length r in [1, rollout]; one jitted step per r.
        self.step_fns = {r: _jit_step(r)
                         for r in range(1, config.rollout + 1)}
        r_rng = np.random.default_rng(config.seed + 1)
        self.r_sched = (
            r_rng.integers(1, config.rollout + 1, config.steps)
            if config.rollout > 1 else np.ones(config.steps, np.int64))

        self.pipeline = self._make_pipeline(config.pipeline,
                                            config.prefetch)
        self._eval_pipeline: Optional[InputPipeline] = None
        self._eval_fn = None
        self.history: List[Dict] = []
        self._metrics_flushed = 0   # history records already appended
        self.step_idx = 0
        # async sharded checkpointing (repro.checkpoint, DESIGN.md §9):
        # snapshot on this thread, stream files from a background one
        self._writer = ckpt.AsyncCheckpointWriter()
        self.last_save = None      # Snapshot of the most recent save
        self._ckpt_history: List[str] = []   # periodic dirs, oldest first
        self._prune_backlog: List[str] = []  # GC'd paths pending deletion
        self._stale_ckpt_error: Optional[BaseException] = None
        self.preempt_stats: Optional[Dict] = None  # final-save timing
        self.best_val = float("inf")
        self.best_ckpt: Optional[str] = None
        if config.resume:
            self._restore(config.resume)

    # -- construction helpers -------------------------------------------
    def _param_pins(self):
        """NamedShardings pinning every parameter to its jigsaw
        PartitionSpec (launch/specs.param_specs)."""
        from repro.launch import specs as S
        pspecs = S.param_specs(self.params, self.cfg, self.rules, self.mesh)
        pspecs = S.sanitize_tree(self.params, pspecs, self.mesh)
        return S.to_shardings(pspecs, self.mesh)

    def _opt_pins(self):
        """NamedShardings for the optimizer state: moments (and fp32
        masters under the bf16 policy) inherit the param specs; under
        ZeRO-1 plus a data-axis shard on their first evenly divisible
        unsharded dim (launch/specs.opt_specs)."""
        from repro.launch import specs as S
        pspecs = S.param_specs(self.params, self.cfg, self.rules, self.mesh)
        pspecs = S.sanitize_tree(self.params, pspecs, self.mesh)
        state = jax.eval_shape(partial(adam.init, cfg=self.adam_cfg),
                               self.params)
        ospecs = S.opt_specs(state["mu"], pspecs,
                             zero1_axis=(self.rules.batch_axes[-1]
                                         if self.config.zero1 else None),
                             mesh=self.mesh, master="master" in state)
        ospecs = S.sanitize_tree(state, ospecs, self.mesh)
        return S.to_shardings(ospecs, self.mesh)

    def _make_pipeline(self, mode: str, prefetch: int) -> InputPipeline:
        return make_pipeline(self.cfg, mesh=self.mesh, rules=self.rules,
                             batch_size=self.config.batch,
                             seq_len=self.config.seq_len, mode=mode,
                             prefetch=prefetch, seed=self.config.seed)

    def _mesh_ctx(self):
        return jax.set_mesh(self.mesh) if self.use_mesh \
            else nullcontext()

    # -- single dispatch -------------------------------------------------
    def dispatch(self, batch, rollout_len: int = 1) -> Dict[str, float]:
        """Run one update on ``batch``; returns raw device metrics."""
        self.params, self.opt_state, metrics = \
            self.step_fns[rollout_len](self.params, self.opt_state, batch)
        self.step_idx += 1
        return metrics

    # -- the loop --------------------------------------------------------
    def run(self, on_step: Optional[Callable[[int, Dict], None]] = None
            ) -> List[Dict]:
        """Train for ``config.steps`` steps; returns the metrics history
        (same record format as the legacy train() loop).

        With ``config.preemption`` (or the ``preempt_at_step`` chaos
        hook) a SIGTERM/SIGUSR1 lets the in-flight step complete, then
        takes a final SYNCHRONOUS checkpoint and raises
        :class:`repro.launch.resilience.Preempted` -- the orderly-exit
        half of the DESIGN.md §12 preemption choreography."""
        from repro.launch import resilience
        c = self.config
        start = self.step_idx          # > 0 after a resume
        handler = None
        if c.preemption or c.preempt_at_step is not None:
            handler = resilience.PreemptionHandler(
                preempt_at_step=c.preempt_at_step).install()
        tr = self.tracer
        try:
            with self._mesh_ctx():
                t0 = time.time()
                it = iter(self.pipeline.iterate(self.r_sched[start:],
                                                start_step=start))
                t_prev = time.perf_counter()
                for i in range(start, c.steps):
                    # data_wait: time the loop spends blocked on the
                    # input pipeline (0 when prefetch is ahead)
                    with tr.span("data_wait", step=i) as dw:
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                    r = int(self.r_sched[i])
                    # "step" is the PARENT span of everything this
                    # iteration does after the batch arrives: dispatch,
                    # eval, ckpt_submit nest under it in the trace
                    with tr.span("step", step=i, rollout=r):
                        with tr.span("dispatch", step=i):
                            metrics = self.dispatch(batch, r)
                        # per-step wall time = submit-to-submit delta:
                        # jax dispatch is async, so the device time of
                        # step i surfaces as backpressure on iteration
                        # i+1; the deltas sum to true wall time without
                        # forcing a per-step sync (which would serialize
                        # the overlap this repo exists to measure)
                        now = time.perf_counter()
                        wall, t_prev = now - t_prev, now
                        tr.step_record(
                            step=i, rollout=r, dur_s=wall,
                            data_wait_s=dw.dur_s,
                            device=self.cost_model.device)
                        if i % c.log_every == 0 or i == c.steps - 1:
                            m = {k: float(v) for k, v in metrics.items()}
                            m["step"] = i
                            m["wall_s"] = round(time.time() - t0, 1)
                            self.history.append(m)
                            self._write_metrics()
                            print(f"step {i:5d}  loss {m['loss']:.4f}  "
                                  f"lr {m['lr']:.2e}  ({m['wall_s']}s)")
                        pending_val = None
                        if c.eval_every and i and i % c.eval_every == 0:
                            with tr.span("eval", step=i):
                                em = self.evaluate()
                            self.history.append(dict(em, step=i,
                                                     eval=True))
                            self._write_metrics()
                            print(f"step {i:5d}  "
                                  f"val_loss {em['val_loss']:.4f}")
                            pending_val = em["val_loss"]
                        if on_step is not None:
                            on_step(i, metrics)
                        if c.ckpt and c.ckpt_every and i \
                                and i % c.ckpt_every == 0:
                            self.save(f"{c.ckpt}-{i}", periodic=True)
                        if pending_val is not None:
                            # after the save: when eval and ckpt
                            # cadences align, the marker points at THIS
                            # step's checkpoint, not the previous one
                            self._mark_best(pending_val)
                    if handler is not None and handler.poll(i):
                        self._preempt_finalize(i, handler)
            if c.ckpt:
                self.save(c.ckpt)
                print(f"checkpoint -> {c.ckpt}")
            self.wait_checkpoints()    # barrier for in-flight writes
            self._write_metrics(final=True)
            self._export_telemetry()
            return self.history
        finally:
            if handler is not None:
                handler.uninstall()

    def _preempt_finalize(self, i: int, handler) -> None:
        """Orderly preemption exit: the step that was in flight has
        completed.  Stop the prefetch thread, drain (and absorb) any
        pending async-write error, take a final SYNCHRONOUS checkpoint,
        persist the metrics history, and raise ``Preempted`` for
        ``launch/train.py`` to translate into the resumable exit code."""
        from repro.launch import resilience
        c = self.config
        sig = handler.received
        self.tracer.event("preempt.signal", signum=sig, step=i)
        print(f"[preempt] signal {sig} after step {i}: "
              f"final synchronous save, then resumable exit")
        self.pipeline.stop()
        try:
            self.wait_checkpoints()
        except Exception as e:
            # an EARLIER async write failed; its prune list is still in
            # _prune_backlog (re-queued by the next save) -- it must not
            # abort the final preemption save, which may become the only
            # durable copy of this run segment
            print(f"[preempt] pending async save had failed: {e!r}; "
                  f"final save proceeds")
        path = None
        if c.ckpt:
            path = f"{c.ckpt}-{i}"
            if self._ckpt_history and self._ckpt_history[-1] == path:
                # the periodic cadence saved this very step already
                pass
            else:
                t0 = time.time()
                self.save(path, block=True, periodic=True)
                self.preempt_stats = {"step": i,
                                      "final_save_s": time.time() - t0}
                self.tracer.event("preempt.final_save", step=i,
                                  dur_s=self.preempt_stats["final_save_s"],
                                  path=path)
            print(f"[preempt] checkpoint durable -> {path}")
        self._write_metrics(final=True)
        # flush the trace BEFORE raising: the Preempted exit is exactly
        # when the operator needs to see where the run's time went
        self._export_telemetry()
        raise resilience.Preempted(step=self.step_idx, checkpoint=path,
                                   signum=sig)

    def _write_metrics(self, final: bool = False) -> None:
        """Persist the metrics history.

        Default ``metrics_format="jsonl"``: crash-safe APPEND of the
        records added since the last flush, one JSON object per line --
        called at every log/eval cadence, so a kill -9 loses at most one
        flush window and never tears the file, and the cost per call is
        O(new records), not O(run length).  ``"json"`` keeps the legacy
        whole-history dump, written only when ``final`` (run end /
        preemption) -- rewriting it per flush would be O(n^2) over a
        long run and a torn file if killed mid-dump."""
        if not self.config.metrics_out:
            return
        import json
        if self.config.metrics_format == "json":
            if final:
                with open(self.config.metrics_out, "w") as f:
                    json.dump(self.history, f, indent=1)
            return
        new = self.history[self._metrics_flushed:]
        if not new:
            return
        with open(self.config.metrics_out, "a") as f:
            for rec in new:
                f.write(json.dumps(rec) + "\n")
        self._metrics_flushed = len(self.history)

    def _export_telemetry(self) -> None:
        """Write the Chrome trace (+ sibling step-record JSONL) when
        ``config.trace`` is set.  Called at run end AND on the
        preemption path, so a reclaimed node still leaves its trace."""
        c = self.config
        if not c.trace:
            return
        self.tracer.export_chrome(c.trace)
        jsonl = telemetry.jsonl_path_for(c.trace)
        self.tracer.export_jsonl(jsonl)
        print(f"trace -> {c.trace} (+ {jsonl})")

    # -- evaluation ------------------------------------------------------
    def evaluate(self, n_batches: Optional[int] = None) -> Dict[str, float]:
        """Mean metrics over held-out batches (step indices offset past
        the training stream; separate pipeline instance so prefetch and
        eval never share memo state)."""
        n = n_batches or self.config.eval_batches
        if self._eval_pipeline is None:
            self._eval_pipeline = self._make_pipeline(
                self.config.pipeline, prefetch=0)
            self._eval_fn = jax.jit(make_eval_step(self.cfg, self.jcfg))
        vals: Dict[str, List[float]] = {}
        with self._mesh_ctx():
            for j in range(n):
                b = self._eval_pipeline.get(EVAL_STEP_OFFSET + j)
                for k, v in self._eval_fn(self.params, b).items():
                    vals.setdefault(k, []).append(float(v))
        out = {f"val_{k}": float(np.mean(v)) for k, v in vals.items()}
        return out

    # -- checkpointing ---------------------------------------------------
    def save(self, path: str, block: Optional[bool] = None,
             periodic: bool = False) -> None:
        """Sharded checkpoint of params/opt_state/step + resume state.

        Each rank serializes only its addressable shards (no full-model
        gather); with ``config.async_save`` the device->host snapshot
        happens here and the file writes stream from a background thread
        while training continues (``wait_checkpoints`` is the barrier).

        ``periodic=True`` registers the path for keep-last-k GC
        (``EngineConfig(keep_ckpts=k)``): once more than k periodic
        checkpoints exist, the oldest are deleted -- except the one the
        ``best`` marker points at.  The GC list is handed to the writer,
        which prunes only AFTER the new checkpoint is fully on disk."""
        c = self.config
        block = (not c.async_save) if block is None else block
        prune = []
        if periodic:
            self._ckpt_history.append(path)
            if c.keep_ckpts > 0:
                keep = set(self._ckpt_history[-c.keep_ckpts:])
                if self.best_ckpt:
                    keep.add(self.best_ckpt)
                prune = [p for p in self._ckpt_history if p not in keep]
                self._ckpt_history = [p for p in self._ckpt_history
                                      if p not in prune]
                # re-queue paths whose earlier prune never ran (a failed
                # async write skips its prune) so GC'd dirs cannot leak
                prune += [p for p in self._prune_backlog
                          if p not in prune and p not in keep
                          and os.path.isdir(p)]
        else:
            # final / preemption saves drain the backlog too: this may
            # be the run's last save, so an orphaned prune list would
            # leak GC'd directories forever
            prune = [p for p in self._prune_backlog if os.path.isdir(p)]
        self._prune_backlog = prune
        extra = {"arch": self.arch, "reduced": self.reduced,
                 "seed": c.seed, "steps": c.steps, "rollout": c.rollout,
                 "scheme": self.cfg.scheme,
                 "precision": self.policy.name,
                 "pipeline": self.pipeline.state(),
                 # GC/best state survives a resume: without it a restarted
                 # run would re-mark a worse best and never prune the
                 # pre-resume periodic checkpoints
                 "best": {"val": (None if self.best_val == float("inf")
                                  else self.best_val),
                          "ckpt": self.best_ckpt},
                 "ckpt_history": list(self._ckpt_history),
                 # prune list persisted with the save: if this process
                 # dies before the deletions run, the resumed run
                 # re-queues them instead of orphaning the GC state
                 "prune_backlog": list(self._prune_backlog)}
        try:
            self._writer.wait()
        except Exception as e:
            # a FAILED earlier async write surfaces at the writer's
            # in-flight guard.  It must not abort THIS save (a final
            # preemption save may be the last durable copy of the run);
            # its prune list stays queued in _prune_backlog, and the
            # error is re-raised at the next wait_checkpoints() barrier.
            print(f"[ckpt] earlier async checkpoint write failed: {e!r}; "
                  f"proceeding with save of {path!r}")
            self._stale_ckpt_error = e
        # ckpt_submit covers the synchronous part the train loop pays
        # for: the device->host snapshot (plus, under block=True, the
        # whole write); the background streaming shows up as ckpt.write
        # spans on the writer thread's own track
        with self.tracer.span("ckpt_submit", path=path, block=block,
                              step=self.step_idx):
            self.last_save = self._writer.save(
                path, {"params": self.params,
                       "opt_state": self.opt_state},
                step=self.step_idx, extra=extra, mesh=self.mesh,
                block=block, prune=prune,
                process_index=jax.process_index(),
                process_count=jax.process_count())

    def _mark_best(self, val_loss: float) -> None:
        """Track the best eval loss; point the ``<ckpt>-best.json`` marker
        at the newest periodic checkpoint at-or-before the eval when it
        improves.  The marker is honest about the misaligned-cadence case:
        ``eval_step``/``val_loss`` describe the weights that were
        evaluated, ``ckpt_step`` the (possibly earlier) checkpoint the
        path refers to."""
        if val_loss >= self.best_val:
            return
        self.best_val = float(val_loss)
        if not (self.config.ckpt and self._ckpt_history):
            return
        self.best_ckpt = self._ckpt_history[-1]
        suffix = self.best_ckpt.rsplit("-", 1)[-1]
        import json
        marker = {"path": self.best_ckpt, "val_loss": self.best_val,
                  "eval_step": self.step_idx,
                  "ckpt_step": int(suffix) if suffix.isdigit() else None}
        with open(f"{self.config.ckpt}-best.json", "w") as f:
            json.dump(marker, f, indent=1)

    def wait_checkpoints(self) -> None:
        """Barrier for in-flight checkpoint writes (re-raises their
        errors on this thread) -- including an absorbed error from an
        earlier failed write that ``save`` proceeded past."""
        self._writer.wait()
        if self._stale_ckpt_error is not None:
            err, self._stale_ckpt_error = self._stale_ckpt_error, None
            raise err

    def _restore(self, path: str) -> None:
        """Exact resume: params, opt state (incl. Adam step), loop step
        index, rollout schedule (revalidated from config), and the data
        pipeline cursor -- an interrupted run continues with a
        bit-identical loss history (``resume_exact`` dist scenario).

        The restore is ELASTIC (DESIGN.md §12): the checkpoint may have
        been written on a different mesh shape.  Every leaf is
        reassembled from the manifest's global index bounds against THIS
        engine's own param / ZeRO-1 layouts (``specs=`` override below),
        so moments and fp32 masters land sharded over the current data
        axis even when the saved topology -- and hence the saved specs'
        divisibility choices -- differ (``elastic_reshard_resume``
        scenario).  The data pipeline needs no refit: its read plans are
        derived from the current mesh at construction, only the cursor
        is restored."""
        c = self.config
        man = ckpt.load_manifest(path)
        for field in ("seed", "rollout", "steps"):
            want, got = getattr(c, field), man.extra.get(field)
            if got is not None and got != want:
                raise ValueError(
                    f"resume {path!r}: checkpoint {field}={got} != engine "
                    f"{field}={want} -- the rollout schedule / lr "
                    f"schedule would diverge; pass the saved value")
        arch = man.extra.get("arch")
        if arch is not None and arch != self.arch:
            raise ValueError(f"resume {path!r}: checkpoint arch {arch!r} "
                             f"!= engine arch {self.arch!r}")
        prec = man.extra.get("precision")
        if prec is not None and prec != self.policy.name:
            hint = ("omit --precision (the checkpoint predates the "
                    "policy presets)" if prec == "legacy"
                    else f"pass --precision {prec}")
            raise ValueError(
                f"resume {path!r}: checkpoint precision {prec!r} != engine "
                f"policy {self.policy.name!r} -- param dtypes and the "
                f"master-weight state would not line up; {hint}")
        cur_shape = (None if self.mesh is None
                     else tuple(self.mesh.devices.shape))
        if (man.mesh_shape is not None and cur_shape is not None
                and tuple(man.mesh_shape) != cur_shape):
            print(f"[resume] elastic reshard: checkpoint mesh "
                  f"{tuple(man.mesh_shape)} -> current mesh {cur_shape}")
        pspecs = ospecs = None
        if self._param_shardings is not None:
            pspecs = jax.tree.map(lambda s: s.spec, self._param_shardings)
        if self._opt_shardings is not None:
            ospecs = jax.tree.map(lambda s: s.spec, self._opt_shardings)
        params = ckpt.restore_tree(path, "params", like=self.params,
                                   mesh=self.mesh, specs=pspecs)
        opt = ckpt.restore_tree(path, "opt_state", like=self.opt_state,
                                mesh=self.mesh, specs=ospecs)
        if self.mesh is None:
            params = jax.tree.map(jnp.asarray, params)
            opt = jax.tree.map(jnp.asarray, opt)
        self.params, self.opt_state = params, opt
        self.step_idx = man.step
        self.pipeline.set_state(man.extra.get("pipeline",
                                              {"cursor": man.step}))
        # best-marker state: the synchronously-written <ckpt>-best.json is
        # authoritative (the manifest's copy can be one eval stale when
        # the eval and ckpt cadences align); manifest extra is the
        # fallback when this run has no --ckpt or the marker is gone
        best = man.extra.get("best") or {}
        marker_file = f"{c.ckpt}-best.json" if c.ckpt else None
        if marker_file and os.path.exists(marker_file):
            import json
            with open(marker_file) as f:
                m = json.load(f)
            best = {"val": m.get("val_loss"), "ckpt": m.get("path")}
        if best.get("val") is not None:
            self.best_val = float(best["val"])
            self.best_ckpt = best.get("ckpt")
        self._ckpt_history = [p for p in man.extra.get("ckpt_history", [])
                              if os.path.isdir(p)]
        # deletions the dead process never ran: re-queued at the next save
        self._prune_backlog = [
            p for p in man.extra.get("prune_backlog", [])
            if os.path.isdir(p)]
