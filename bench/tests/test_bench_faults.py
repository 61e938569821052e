"""Each fault the cells can have, planted under a whole run at a small
size on the CPU (the look for a chip skipped), turns ``correct`` false;
the sound run stays true.  The control, the reference computed in
float8, fails the cells' own limits at the same size.

The training runs here are float32: at this size bf16's per-leaf norm
gaps are a few times those of the full-size cell, on whose readings the
limits were set, while a fault reads ten to a thousand times more."""
import time

import numpy as np
import pytest

import _paths

from harness import compare, serve, spec, synth, train


def _small(workload, precision=None):
    res = spec.resolve(workload, _paths.ROOT)
    res["config"] = dict(res["config"], **{
        k: v for k, v in _paths.TINY.items()
        if k not in ("name", "precision", "source")})
    if precision:
        res["config"]["precision"] = precision
    return res


TRAIN = "train-wm-zoo-4t-1chip"
SERVE = "serve-wm-1b-poisson-1chip"
SEED = (1 << 33) + 17


def _train():
    res = _small(TRAIN, "fp32")
    out, checks = train.run(res, SEED, 0.5, False, time.time(),
                            allow_cpu=True)
    return out, {n: v for n, v, _ in checks}


def test_train_sound_run_is_correct():
    out, gaps = _train()
    assert out["correct"], gaps


def test_train_state_left_unchanged(monkeypatch):
    from repro.optim import adam
    monkeypatch.setattr(adam, "update",
                        lambda params, grads, state, lr, cfg: (params, state))
    out, gaps = _train()
    assert not out["correct"]
    assert gaps["change_gap"] == pytest.approx(1.0)


def test_train_half_the_batch_left_out(monkeypatch):
    from repro.train import step
    full = step.loss_fn

    def half(params, batch, cfg, jcfg, rollout=1):
        n = batch["fields"].shape[0] // 2
        return full(params, {k: v[:n] for k, v in batch.items()}, cfg, jcfg,
                    rollout)

    monkeypatch.setattr(step, "loss_fn", half)
    out, gaps = _train()
    assert not out["correct"], gaps


def _serve():
    res = _small(SERVE)
    res["cell"] = dict(res["cell"], rate_per_s=20.0)
    out, checks = serve.run(res, SEED, 1.0, False, time.time(),
                            allow_cpu=True)
    return out, {n: v for n, v, _ in checks}


def test_serve_sound_run_is_correct():
    out, gaps = _serve()
    assert out["correct"], gaps
    assert out["failed"] == 0


def test_serve_answer_altered(monkeypatch):
    from repro.serve import scheduler
    deliver = scheduler.ForecastResult.deliver

    def altered(self, lead, out, now):
        out = np.array(out)
        out[: out.shape[0] // 8] = 0.0       # one band of the grid lost
        deliver(self, lead, out, now)

    monkeypatch.setattr(scheduler.ForecastResult, "deliver", altered)
    out, gaps = _serve()
    assert not out["correct"], gaps


def test_train_control_fails_the_limits():
    res = _small(TRAIN)
    pool = [synth.host_batch(SEED, 100 + j, res["config"], res["traffic"],
                             res["cell"]["batch"]) for j in range(3)]
    ref = compare.reference_train(res, SEED, pool)
    low = compare.reference_train(res, SEED, pool, gemm="fp8")
    gaps = compare.train_gaps(low, ref)
    lim = res["cell"]["limits"]
    assert any(gaps[k] > lim[k] for k in lim), gaps


def test_serve_control_fails_the_limits():
    res = _small(SERVE)
    states = serve.make_states(SEED, res)
    wanted = {0: {1, 8}}
    ref = compare.reference_rollouts(res, SEED, states, wanted)
    low = compare.reference_rollouts(res, SEED, states, wanted, gemm="fp8")
    gap = max(compare.forecast_gap(low[0][ld], ref[0][ld], states[0])
              for ld in (1, 8))
    assert gap > res["cell"]["limits"]["forecast_gap"]
