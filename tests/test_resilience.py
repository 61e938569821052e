"""Fault-tolerant elastic training (ISSUE 7, DESIGN.md §12): the
PreemptionHandler signal choreography, the Supervisor relaunch loop,
engine preempt -> final synchronous save -> exact resume, pipeline
shutdown hardening, the ``--supervise`` CLI end-to-end, and the two
chaos dist scenarios (``preempt_resume_exact``,
``elastic_reshard_resume``) via subprocess."""
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.checkpoint import sharded
from repro.launch import resilience
from repro.launch.engine import EngineConfig, TrainEngine

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))


# -- PreemptionHandler -------------------------------------------------

def test_handler_catches_sigterm_and_restores_previous():
    prev = signal.getsignal(signal.SIGTERM)
    h = resilience.PreemptionHandler().install()
    try:
        assert h.installed and not h.should_stop
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.should_stop and h.received == signal.SIGTERM
    finally:
        h.uninstall()
    assert signal.getsignal(signal.SIGTERM) == prev
    assert not h.installed


def test_handler_catches_sigusr1():
    with resilience.PreemptionHandler() as h:
        os.kill(os.getpid(), signal.SIGUSR1)
        assert h.should_stop and h.received == signal.SIGUSR1


def test_handler_chaos_hook_delivers_real_signal():
    """poll(step) at the armed step must go through the REAL signal
    path (os.kill on ourselves), not just flip a flag."""
    with resilience.PreemptionHandler(preempt_at_step=2) as h:
        assert not h.poll(0)
        assert not h.poll(1)
        assert h.poll(2)
        assert h.received == signal.SIGTERM   # a real delivered signal
        assert h.poll(3)                      # latched


def test_handler_reads_chaos_env(monkeypatch):
    monkeypatch.setenv(resilience.PREEMPT_ENV, "5")
    assert resilience.PreemptionHandler().preempt_at_step == 5
    # explicit argument beats the env
    assert resilience.PreemptionHandler(
        preempt_at_step=1).preempt_at_step == 1
    monkeypatch.delenv(resilience.PREEMPT_ENV)
    assert resilience.PreemptionHandler().preempt_at_step is None


def test_handler_non_main_thread_degrades_to_inert():
    import threading
    out = {}

    def worker():
        with pytest.warns(UserWarning, match="main thread"):
            h = resilience.PreemptionHandler().install()
        out["installed"] = h.installed
        out["poll"] = h.poll(0)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert out == {"installed": False, "poll": False}


# -- Supervisor --------------------------------------------------------

def test_supervisor_resumable_exit_restarts_immediately():
    rcs = iter([resilience.RESUMABLE_EXIT_CODE, 0])
    sleeps = []
    sup = resilience.Supervisor(
        lambda resume, attempt: ["train", str(attempt)],
        run_cmd=lambda argv: next(rcs), sleep_fn=sleeps.append)
    assert sup.run() == 0
    assert sup.attempts == [resilience.RESUMABLE_EXIT_CODE, 0]
    assert sleeps == []                       # no backoff on preemption


def test_supervisor_crash_backoff_is_exponential():
    rcs = iter([1, 1, 1, 0])
    sleeps = []
    sup = resilience.Supervisor(
        lambda resume, attempt: ["train"], max_restarts=5, backoff=1.0,
        run_cmd=lambda argv: next(rcs), sleep_fn=sleeps.append)
    assert sup.run() == 0
    assert len(sleeps) == 3
    # delay doubles each crash; jitter adds up to +25%
    assert 1.0 <= sleeps[0] <= 1.25
    assert 2.0 <= sleeps[1] <= 2.5
    assert 4.0 <= sleeps[2] <= 5.0


def test_supervisor_gives_up_after_max_restarts():
    sup = resilience.Supervisor(
        lambda resume, attempt: ["train"], max_restarts=2, backoff=0.0,
        run_cmd=lambda argv: 1, sleep_fn=lambda s: None)
    assert sup.run() == 1
    assert sup.attempts == [1, 1, 1]          # initial + 2 restarts


def test_supervisor_rediscovers_latest_checkpoint(tmp_path):
    """The resume point is rediscovered before EVERY launch -- a
    checkpoint written by the first (preempted) child is what the
    second child resumes from."""
    launched = []

    def run_cmd(argv):
        if not launched:
            launched.append(argv)
            sharded.save_checkpoint(
                str(tmp_path / "ck-3"), {"g": {"x": np.arange(2.0)}},
                step=3)
            return resilience.RESUMABLE_EXIT_CODE
        launched.append(argv)
        return 0

    sup = resilience.Supervisor(
        lambda resume, attempt: ["train"] + (["--resume", resume]
                                             if resume else []),
        ckpt_root=str(tmp_path), prefix="ck", run_cmd=run_cmd)
    assert sup.run() == 0
    assert sup.resumes == [None, str(tmp_path / "ck-3")]
    assert launched[1][-2:] == ["--resume", str(tmp_path / "ck-3")]


def test_supervisor_skips_torn_checkpoints(tmp_path):
    torn = tmp_path / "ck-9"
    torn.mkdir()
    (torn / "shard-d00000.npz").write_bytes(b"partial")   # no manifest
    sharded.save_checkpoint(str(tmp_path / "ck-2"),
                            {"g": {"x": np.arange(2.0)}}, step=2)
    sup = resilience.Supervisor(lambda r, a: ["train"],
                                ckpt_root=str(tmp_path), prefix="ck",
                                run_cmd=lambda argv: 0)
    sup.run()
    assert sup.resumes == [str(tmp_path / "ck-2")]


def test_strip_args():
    argv = ["--arch", "a", "--supervise", "--max-restarts", "5",
            "--resume=old", "--steps", "3"]
    assert resilience.strip_args(
        argv, flags=("--supervise",), valued=("--max-restarts",
                                              "--resume")) == \
        ["--arch", "a", "--steps", "3"]


# -- engine preempt -> final save -> resume (single device) ------------

def test_engine_preempt_finalize_and_exact_resume(tmp_path):
    path = str(tmp_path / "ck")
    mfile = str(tmp_path / "m.json")

    def engine(**kw):
        return TrainEngine("internlm2-1.8b", config=EngineConfig(
            steps=4, batch=2, seq_len=16, log_every=1, **kw))

    h_full = engine().run()

    prev = signal.getsignal(signal.SIGTERM)
    eng = engine(ckpt=path, preempt_at_step=1, metrics_out=mfile)
    with pytest.raises(resilience.Preempted) as ei:
        eng.run()
    assert signal.getsignal(signal.SIGTERM) == prev   # handler restored
    assert ei.value.step == 2                 # the in-flight step finished
    assert ei.value.checkpoint == path + "-1"
    assert ei.value.signum == signal.SIGTERM
    assert sharded.checkpoint_complete(path + "-1")
    assert eng.preempt_stats["step"] == 1
    assert eng.preempt_stats["final_save_s"] > 0
    import json
    with open(mfile) as f:
        logged = [json.loads(line) for line in f if line.strip()]
    assert [h["step"] for h in logged] == [0, 1]   # metrics persisted

    resumed = engine(resume=path + "-1")
    assert resumed.step_idx == 2
    assert resumed.pipeline.cursor == 2
    h_res = resumed.run()
    tail = [h for h in h_full if h["step"] >= 2]
    assert len(h_res) == len(tail) == 2
    for a, b in zip(tail, h_res):
        assert a["loss"] == b["loss"]
        assert a["lr"] == b["lr"]
        assert a["grad_norm"] == b["grad_norm"]


def test_engine_preempt_without_ckpt_still_exits_orderly():
    eng = TrainEngine("internlm2-1.8b", config=EngineConfig(
        steps=3, batch=2, seq_len=16, log_every=1, preempt_at_step=0))
    with pytest.raises(resilience.Preempted) as ei:
        eng.run()
    assert ei.value.checkpoint is None and ei.value.step == 1


# -- pipeline shutdown hardening ---------------------------------------

def test_pipeline_stop_cancels_mid_prefetch():
    from repro.configs.registry import get_config
    from repro.data.pipeline import make_pipeline
    cfg = get_config("weathermixer-1b").reduced()
    pipe = make_pipeline(cfg, batch_size=2, prefetch=2)
    it = pipe.iterate([1] * 200)
    next(it)                                  # worker is prefetching ahead
    assert pipe._thread is not None and pipe._thread.daemon
    t0 = time.time()
    assert pipe.stop(timeout=5.0)             # cancels promptly...
    assert time.time() - t0 < 5.0             # ...without the full horizon
    assert pipe._thread is None
    assert pipe.stop()                        # idempotent no-op


def test_pipeline_stop_noop_without_prefetch():
    from repro.configs.registry import get_config
    from repro.data.pipeline import make_pipeline
    cfg = get_config("weathermixer-1b").reduced()
    pipe = make_pipeline(cfg, batch_size=2, prefetch=0)
    list(pipe.iterate([1, 1]))
    assert pipe.stop()                        # nothing to join


def test_pipeline_iterate_still_exact_after_stop_resume():
    """stop() mid-stream + a fresh iterate from the cursor reproduces
    the uninterrupted stream (determinism is cursor-only state)."""
    from repro.configs.registry import get_config
    from repro.data.pipeline import make_pipeline
    cfg = get_config("weathermixer-1b").reduced()
    ref = make_pipeline(cfg, batch_size=2, prefetch=0)
    want = [ref.get(i, 1) for i in range(4)]

    pipe = make_pipeline(cfg, batch_size=2, prefetch=2)
    it = pipe.iterate([1] * 4)
    got = [next(it), next(it)]
    pipe.stop()
    got += list(pipe.iterate([1] * 2))        # continues from cursor=2
    for g, w in zip(got, want):
        for k in w:
            np.testing.assert_array_equal(np.asarray(g[k]),
                                          np.asarray(w[k]))


# -- CLI: --supervise end-to-end ---------------------------------------

def test_cli_supervise_preempt_and_resume(tmp_path):
    """Full stack in subprocesses: child 0 self-SIGTERMs after step 0
    (chaos env), exits 75 with a durable checkpoint; the supervisor
    relaunches with --resume; child 1 finishes; overall rc == 0."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env[resilience.PREEMPT_ENV] = "0"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "internlm2-1.8b", "--steps", "2", "--batch", "2",
         "--seq-len", "16", "--log-every", "1",
         "--ckpt", str(tmp_path / "ck"),
         "--supervise", "--max-restarts", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
    assert "resumable exit" in res.stdout     # supervisor saw code 75
    assert "[preempt]" in res.stdout          # child ran the final save
    assert sharded.latest_checkpoint(str(tmp_path), prefix="ck") == \
        str(tmp_path / "ck")                  # final save outranks ck-0


def test_supervisor_parent_stays_off_the_backend():
    """The --supervise parent imports the training stack but must not
    start a JAX backend: on a TPU host it would hold the chip its child
    needs.  Importing never initialises one, and neither does the
    checkpoint discovery the supervisor runs before every launch."""
    code = ("import repro.launch.train\n"
            "from repro.checkpoint import sharded\n"
            "sharded.latest_checkpoint('.', prefix='ck')\n"
            "from jax._src import xla_bridge\n"
            "print(sorted(xla_bridge._backends))\n")
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.strip() == "[]"


def test_cli_supervise_requires_ckpt():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train",
         "--arch", "internlm2-1.8b", "--steps", "1", "--supervise"],
        env=env, capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert "--supervise requires --ckpt" in res.stderr


# -- chaos dist scenarios (16 emulated devices, subprocess) ------------

def _run_scenario(name, timeout=900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    env.pop(resilience.PREEMPT_ENV, None)
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist_scenarios.py"), name],
        env=env, capture_output=True, text=True, timeout=timeout)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")


def test_preempt_resume_exact_scenario():
    """SIGTERM mid-run -> supervisor restart -> bit-identical history."""
    _run_scenario("preempt_resume_exact")


def test_elastic_reshard_resume_scenario():
    """8-way save resumes on a 4-way mesh with zero1 refit + pod-scale
    per-process index completeness."""
    _run_scenario("elastic_reshard_resume")
