"""Serving subsystem tests (ISSUE 8).

* scheduler policy units (fake clock, no devices): coalescing window,
  step-boundary admission, drain vs continuous, bucket growth, lead
  fan-out ordering;
* ForecastEngine on one device: batch-bucket compile-cache hits
  (trace-time compile counter), mid-rollout admission correctness
  (outputs bitwise equal solo rollouts), continuous < drain step
  counts;
* serve/step satellites: fused prefill parity vs the token-wise
  reference, donated decode cache (buffers actually deleted), no
  per-step device->host round-trips, jit-cache reuse across generate
  calls;
* read-only serving restore: arch validation + precision cast;
* the 8-way-ckpt -> {1,2,4,8}-way serving-mesh bit-identity scenario
  (subprocess with 16 emulated devices; also the serve CI job).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config
from repro.launch import shapes as SH
from repro.models import registry as M
from repro.serve import step as SS
from repro.serve.engine import ForecastEngine, ServeConfig
from repro.serve.scheduler import ForecastResult, MicrobatchScheduler

HERE = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# scheduler policy (host-only, fake clock)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _req(clock, leads=(1,)):
    return ForecastResult(None, tuple(sorted(leads)), submit_t=clock())


def test_scheduler_coalescing_window():
    clk = FakeClock()
    s = MicrobatchScheduler((1, 2, 4), coalesce_s=0.5, clock=clk)
    s.submit(_req(clk))
    t = s.tick()
    assert t.wait == pytest.approx(0.5) and not t.step
    clk.t = 0.3
    t = s.tick()
    assert t.wait == pytest.approx(0.2) and not t.step
    clk.t = 0.51          # window expired: form the batch
    t = s.tick()
    assert t.form == 1 and len(t.admit) == 1 and t.step
    assert s.counters["waited"] == 2


def test_scheduler_coalescing_full_bucket_bypasses_window():
    clk = FakeClock()
    s = MicrobatchScheduler((1, 2, 4), coalesce_s=10.0, clock=clk)
    for _ in range(4):    # a full max-size bucket never waits
        s.submit(_req(clk))
    t = s.tick()
    assert t.form == 4 and len(t.admit) == 4 and t.step


def test_scheduler_bucket_for():
    s = MicrobatchScheduler((1, 2, 4, 8))
    assert [s.bucket_for(n) for n in (1, 2, 3, 5, 8, 100)] == \
        [1, 2, 4, 8, 8, 8]


def test_scheduler_continuous_admission_at_boundaries():
    clk = FakeClock()
    s = MicrobatchScheduler((1, 2, 4), clock=clk)
    s.submit(_req(clk, (3,)))
    t = s.tick()
    assert t.form == 1 and len(t.admit) == 1
    s.advance()
    # a new request arrives mid-rollout: admitted at the NEXT boundary,
    # growing the live batch one bucket hop
    s.submit(_req(clk, (1,)))
    t = s.tick()
    assert t.grow == 2 and len(t.admit) == 1 and t.step
    peels, finished = s.advance()     # ages: 2 and 1
    assert [lead for _, _, lead in peels] == [1]
    assert len(finished) == 1 and s.active() == 1
    t = s.tick()                      # freed slot, empty queue: just step
    assert t.grow is None and not t.admit and t.step
    s.advance()                       # first request hits lead 3
    assert s.active() == 0


def test_scheduler_drain_mode_no_midflight_admission():
    clk = FakeClock()
    s = MicrobatchScheduler((1, 2, 4), mode="drain", clock=clk)
    s.submit(_req(clk, (2,)))
    assert s.tick().form == 1
    s.advance()
    s.submit(_req(clk, (1,)))
    t = s.tick()                      # drain: queued request NOT admitted
    assert not t.admit and t.grow is None and t.step
    s.advance()                       # batch empties
    t = s.tick()                      # only now the next batch forms
    assert t.form == 1 and len(t.admit) == 1


def test_scheduler_fanout_ordering():
    clk = FakeClock()
    s = MicrobatchScheduler((4,), clock=clk)
    r = _req(clk, (2, 1, 5))          # unsorted on purpose
    assert r.leads == (1, 2, 5)
    s.submit(r)
    s.tick()
    seen = []
    for _ in range(5):
        peels, _ = s.advance()
        seen += [lead for _, req, lead in peels if req is r]
        s.tick()
    assert seen == [1, 2, 5]          # peeled in rollout order
    assert s.counters["completed"] == 1


# ---------------------------------------------------------------------------
# ForecastEngine (single device, tiny mixer)
# ---------------------------------------------------------------------------

def tiny_engine(**kw):
    cfg = get_config("weathermixer-1b").reduced().replace(
        wm_lat=16, wm_lon=32, wm_channels=4, d_model=64,
        wm_d_tok=64, wm_d_ch=64)
    config = kw.pop("config", ServeConfig(buckets=(1, 2, 4)))
    return ForecastEngine("weathermixer-1b", reduced=False,
                          config_override=cfg, config=config, **kw)


def _fields(n, eng, seed=0):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, *eng.field_shape)).astype(np.float32)


def test_engine_zero_recompiles_across_buckets():
    eng = tiny_engine()
    warm = eng.warmup()               # 3 buckets x 4 fns + 2 grows
    assert warm == 14
    assert eng.compile_cache_size() == warm
    fs = _fields(7, eng)
    rs = [eng.submit(fs[i], (i % 3) + 1) for i in range(7)]
    eng.drain()
    assert all(r.done() for r in rs)
    # the load exercised forms, admissions, steps and peels across
    # multiple buckets -- with ZERO new traces or executables
    assert eng.stats["compiles"] == warm
    assert eng.compile_cache_size() == warm
    assert eng.sched.counters["formed"] >= 1


def test_engine_midflight_admission_bitwise_vs_solo():
    eng = tiny_engine()
    eng.warmup()
    fs = _fields(5, eng, seed=1)
    first = eng.submit(fs[0], 4)
    assert eng.step_once() == "step"  # first request in flight...
    late = [eng.submit(fs[i], i) for i in (1, 2, 3)]
    eng.drain()                       # ...the rest admitted mid-rollout
    assert first.done() and all(r.done() for r in late)

    # solo reference: each request alone through the same jitted bucket
    # step (bucket 1) -- continuous batching must not perturb outputs
    def solo(f, lead):
        fns = eng._fns(1)
        state = fns["admit"](fns["zeros"](), eng._put_fields(f),
                             np.int32(0))
        for _ in range(lead):
            state = fns["step"](eng.params, state)
        return np.asarray(fns["peel"](state, np.int32(0)))

    assert np.array_equal(first.result(), solo(fs[0], 4))
    for i, r in zip((1, 2, 3), late):
        assert np.array_equal(r.result(), solo(fs[i], i))


def test_engine_fanout_outputs_and_latency():
    eng = tiny_engine()
    eng.warmup()
    r = eng.submit(_fields(1, eng)[0], (1, 2, 4))
    eng.drain()
    assert sorted(r.outputs) == [1, 2, 4]
    assert r.done() and r.latency() >= 0 and r.queue_delay() >= 0
    # each peeled horizon is a genuine intermediate state of ONE rollout
    fns = eng._fns(1)
    state = fns["admit"](fns["zeros"](), eng._put_fields(r.fields),
                         np.int32(0))
    for lead in (1, 2, 3, 4):
        state = fns["step"](eng.params, state)
        if lead in r.outputs:
            assert np.array_equal(r.output(lead),
                                  np.asarray(state[0]))


def test_engine_continuous_beats_drain_in_steps():
    # mixed leads: drain pays max(lead) per batch, continuous ~mean(lead)
    leads = [1, 4, 1, 4, 1, 4, 1, 4]
    steps = {}
    for mode in ("continuous", "drain"):
        eng = tiny_engine(config=ServeConfig(buckets=(1, 2, 4),
                                             mode=mode))
        eng.warmup()
        fs = _fields(len(leads), eng, seed=2)
        rs = [eng.submit(fs[i], leads[i]) for i in range(len(leads))]
        eng.drain()
        assert all(r.done() for r in rs)
        steps[mode] = eng.stats["device_steps"]
    assert steps["continuous"] < steps["drain"], steps


def test_engine_coalescing_with_fake_clock():
    clk = FakeClock()
    eng = tiny_engine(clock=clk,
                      config=ServeConfig(buckets=(1, 2, 4),
                                         coalesce_s=1.0))
    eng.warmup()
    r1 = eng.submit(_fields(1, eng)[0], 1)
    assert eng.step_once() == "wait"      # window open: no batch yet
    r2 = eng.submit(_fields(1, eng, seed=9)[0], 1)
    clk.t = 1.5
    assert eng.step_once() == "step"      # window closed: ONE batch of 2
    assert r1.done() and r2.done()
    assert eng.sched.counters["formed"] == 1


def test_engine_validation():
    eng = tiny_engine()
    with pytest.raises(ValueError, match="fields shape"):
        eng.submit(np.zeros((3, 3, 3), np.float32), 1)
    with pytest.raises(ValueError, match="leads"):
        eng.submit(np.zeros(eng.field_shape, np.float32), 0)
    with pytest.raises(ValueError, match="family"):
        ForecastEngine("stablelm-3b")
    with pytest.raises(ValueError, match="mode"):
        ServeConfig(mode="nope") and MicrobatchScheduler((1,), mode="nope")


# ---------------------------------------------------------------------------
# serve/step satellites: fused prefill + donated decode
# ---------------------------------------------------------------------------

def _lm(arch="stablelm-3b", **repl):
    cfg = get_config(arch).reduced()
    if repl:
        cfg = cfg.replace(**repl)
    jcfg = SH.jigsaw_for(cfg)
    params = M.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 9)),
                          jnp.int32)
    return cfg, jcfg, params, prompts


@pytest.mark.parametrize("arch", ["stablelm-3b", "h2o-danube-1.8b"])
def test_fused_prefill_parity(arch):
    cfg, jcfg, params, prompts = _lm(arch)
    n_f, c_f = SS.prefill(params, prompts, cfg, jcfg, 24,
                          cache_dtype=jnp.float32, fused=True)
    n_t, c_t = SS.prefill_tokenwise(params, prompts, cfg, jcfg, 24,
                                    cache_dtype=jnp.float32)
    assert np.array_equal(n_f, n_t)
    assert np.array_equal(c_f["pos"], c_t["pos"])
    for k in ("k", "v"):
        assert np.allclose(c_f[k], c_t[k], rtol=5e-3, atol=1e-4)
    g_f = SS.generate(params, prompts, cfg, jcfg, steps=6, max_len=24,
                      fused=True)
    g_t = SS.generate(params, prompts, cfg, jcfg, steps=6, max_len=24,
                      fused=False)
    assert np.array_equal(np.asarray(g_f), np.asarray(g_t))


def test_fused_prefill_rolling_overflow_parity():
    # prompt LONGER than the rolling window: only the last s_max tokens
    # survive, at the same slots token-wise writes would have used
    cfg, jcfg, params, _ = _lm("h2o-danube-1.8b", sliding_window=8)
    rng = np.random.default_rng(1)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 13)),
                          jnp.int32)
    n_f, c_f = SS.prefill(params, prompts, cfg, jcfg, 32,
                          cache_dtype=jnp.float32, fused=True)
    n_t, c_t = SS.prefill_tokenwise(params, prompts, cfg, jcfg, 32,
                                    cache_dtype=jnp.float32)
    assert c_f["k"].shape[2] == 8
    assert np.array_equal(n_f, n_t)
    assert np.allclose(c_f["k"], c_t["k"], rtol=5e-3, atol=1e-4)


def test_fused_prefill_unsupported_family_falls_back():
    cfg, jcfg, params, prompts = _lm("gemma3-27b")   # local:global stack
    assert cfg.local_global_ratio > 0
    with pytest.raises(NotImplementedError):
        SS.prefill(params, prompts, cfg, jcfg, 24, fused=True)
    nxt, cache = SS.prefill(params, prompts, cfg, jcfg, 24)  # auto
    assert nxt.shape == (2, 1) and "lk" in cache


def test_generate_donates_cache_and_stays_on_device():
    cfg, jcfg, params, prompts = _lm()
    _, cache = SS.prefill(params, prompts, cfg, jcfg, 24,
                          cache_dtype=jnp.float32)
    step = SS.jit_serve_step(cfg, jcfg)
    tok = jnp.zeros((2, 1), jnp.int32)
    old_k = cache["k"]
    tok, cache = step(params, cache, tok)     # donation: buffers consumed
    assert old_k.is_deleted()
    # steady-state decode performs no device->host round-trips
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            tok, cache = step(params, cache, tok)
    assert SS.jit_serve_step(cfg, jcfg) is step   # lru-cached wrapper


def test_generate_jit_cache_reused_across_calls():
    cfg, jcfg, params, prompts = _lm()
    SS.generate(params, prompts, cfg, jcfg, steps=4, max_len=24)
    step = SS.jit_serve_step(cfg, jcfg)
    before = step._cache_size()
    SS.generate(params, prompts, cfg, jcfg, steps=4, max_len=24)
    assert step._cache_size() == before       # no re-jit per generate


# ---------------------------------------------------------------------------
# read-only serving restore (single device; mesh reshaping under the
# subprocess scenario below)
# ---------------------------------------------------------------------------

def test_serving_restore_validates_and_casts(tmp_path):
    from functools import partial

    from repro.checkpoint.serving import restore_serving_params
    from repro.core import precision
    from repro.launch.engine import EngineConfig, TrainEngine

    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b",
                      config=EngineConfig(steps=2, batch=2, log_every=10))
    eng.run()
    eng.save(path, block=True)

    with pytest.raises(ValueError, match="arch"):
        restore_serving_params(path, arch="stablelm-3b")

    params, man = restore_serving_params(path, arch="weathermixer-1b")
    assert man.step == 2
    # cast-on-restore: a bf16 serving policy gets bf16 leaves from the
    # fp32 checkpoint (the blend stays f32: init keeps it f32 always)
    cfg16 = precision.apply_policy(eng.cfg, "bf16")
    like = jax.eval_shape(partial(M.init, cfg=cfg16), jax.random.PRNGKey(0))
    p16, _ = restore_serving_params(path, like=like)
    assert p16["encoder"]["w"].dtype == jnp.bfloat16
    assert p16["blend"].dtype == jnp.float32
    assert np.allclose(np.asarray(p16["encoder"]["w"], np.float32),
                       params["encoder"]["w"], atol=0.02)

    # shape validation names the offending leaf
    bad = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((1,) + tuple(l.shape), l.dtype),
        like)
    with pytest.raises(ValueError, match="shape"):
        restore_serving_params(path, like=bad)


def test_engine_serves_checkpoint(tmp_path):
    from repro.launch.engine import EngineConfig, TrainEngine

    path = str(tmp_path / "ck")
    eng = TrainEngine("weathermixer-1b",
                      config=EngineConfig(steps=2, batch=2, log_every=10))
    eng.run()
    eng.save(path, block=True)
    se = ForecastEngine("weathermixer-1b", ckpt=path,
                        config=ServeConfig(buckets=(1, 2)))
    assert se.restored_step == 2
    r = se.submit(np.zeros(se.field_shape, np.float32), 2)
    se.drain()
    assert r.done() and np.isfinite(r.result()).all()


# ---------------------------------------------------------------------------
# 8-way ckpt -> {1,2,4,8}-way serving meshes (subprocess, 16 devices)
# ---------------------------------------------------------------------------

def test_serving_restore_scenario():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "dist_scenarios.py"),
         "serving_restore"],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "ALL-OK" in res.stdout, (
        f"\nstdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}")
