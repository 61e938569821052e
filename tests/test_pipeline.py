"""Input-pipeline subsystem: sharded reads == full batches (incl. the
rollout-horizon fix), prefetcher determinism, engine step dispatch, and
I/O accounting.  Multi-device variants live in dist_scenarios.py
(``input_pipeline`` / ``engine_pipeline``), run via test_distributed."""
import numpy as np
import pytest

from repro.data.pipeline import (InputPipeline, TokenBatchSource,
                                 WeatherBatchSource, make_pipeline)
from repro.data.tokens import TokenDataConfig, TokenDataset
from repro.data.weather import WeatherDataConfig, WeatherDataset

WCFG = WeatherDataConfig(lat=16, lon=32, channels=6, seed=3)


# -- dataset-level sharded reads ---------------------------------------

@pytest.mark.parametrize("horizon", [1, 2, 4])
def test_weather_shard_respects_horizon(horizon):
    """Regression: sample_shard used to hardcode t = dt_phase, breaking
    shard == full-slice for rollout fine-tuning targets."""
    ds = WeatherDataset(WCFG)
    full = ds.sample_batch(2, 4, horizon=horizon)
    shard = ds.sample_shard(2, 4, lon_slice=slice(8, 24),
                            chan_slice=slice(1, 5), row_slice=slice(1, 3),
                            lat_slice=slice(4, 12), horizon=horizon)
    np.testing.assert_array_equal(
        shard["fields"], full["fields"][1:3, 4:12, 8:24, 1:5])
    np.testing.assert_array_equal(
        shard["target"], full["target"][1:3, 4:12, 8:24, 1:5])


def test_token_shard_equals_row_slice():
    ds = TokenDataset(TokenDataConfig(vocab_size=97, seq_len=48, seed=5))
    full = ds.sample_batch(7, 8)
    shard = ds.sample_shard(7, 8, row_slice=slice(2, 6))
    np.testing.assert_array_equal(shard["tokens"], full["tokens"][2:6])
    np.testing.assert_array_equal(shard["labels"], full["labels"][2:6])
    # io model: row sharding divides the read
    assert ds.io_bytes_per_rank(8, 4) * 4 == ds.io_bytes_per_rank(8, 1)


# -- source adapters ----------------------------------------------------

def test_weather_source_read_key_matches_full():
    src = WeatherBatchSource(WeatherDataset(WCFG), batch_size=4)
    full = src.full_batch(1, 3)
    idx = ((0, 2), (0, 16), (8, 24), (2, 5))
    for key in src.keys:
        got = src.read_key(key, 1, 3, idx)
        np.testing.assert_array_equal(got, full[key][0:2, :, 8:24, 2:5])
    assert src.key_shape("fields") == (4, 16, 32, 6)


def test_token_source_extras_sliced_from_full_draw():
    ds = TokenDataset(TokenDataConfig(vocab_size=64, seq_len=16, seed=1))
    src = TokenBatchSource(ds, batch_size=4, extras={"embeds": (8, 32)})
    full = src.full_batch(2, 1)
    assert set(src.keys) == {"tokens", "labels", "embeds"}
    got = src.read_key("embeds", 2, 1, ((1, 3), (0, 8), (16, 32)))
    np.testing.assert_array_equal(got, full["embeds"][1:3, :, 16:32])
    rows = src.read_key("tokens", 2, 1, ((1, 3), (0, 16)))
    np.testing.assert_array_equal(rows, full["tokens"][1:3])
    # regression: the extras memo must roll over with the step on the
    # full-batch path too (they used to freeze at the first step)
    assert not np.array_equal(full["embeds"], src.full_batch(3, 1)["embeds"])


# -- pipeline (single device: mesh=None) --------------------------------

def test_pipeline_no_mesh_roundtrip():
    from repro.configs.registry import get_config
    cfg = get_config("weathermixer-1b").reduced()
    pipe = make_pipeline(cfg, batch_size=2, mode="sharded", prefetch=0)
    got = pipe.get(0, 2)
    want = pipe.host_batch(0, 2)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]), want[k])


def test_pipeline_prefetch_deterministic():
    from repro.configs.registry import get_config
    cfg = get_config("weathermixer-1b").reduced()
    sync = make_pipeline(cfg, batch_size=2, prefetch=0)
    pref = make_pipeline(cfg, batch_size=2, prefetch=2)
    horizons = [1, 3, 2, 1, 2]
    got = list(pref.iterate(horizons))
    assert len(got) == len(horizons)
    for i, h in enumerate(horizons):
        want = sync.get(i, h)
        for k in want:
            np.testing.assert_array_equal(np.asarray(got[i][k]),
                                          np.asarray(want[k]))


def test_pipeline_prefetch_propagates_errors():
    class Boom(WeatherBatchSource):
        def full_batch(self, step, horizon):
            if step >= 2:
                raise RuntimeError("disk on fire")
            return super().full_batch(step, horizon)

    src = Boom(WeatherDataset(WCFG), batch_size=2)
    pipe = InputPipeline(src, prefetch=2)
    it = pipe.iterate([1, 1, 1, 1])
    next(it), next(it)
    with pytest.raises(RuntimeError, match="disk on fire"):
        for _ in it:
            pass


def test_pipeline_h2d_span_on_the_prefetch_thread():
    """The transfer and its wait sit in a pipeline.h2d span inside
    pipeline.produce on the prefetch thread, one per batch; the consumer
    and a pipeline without prefetch never block on it."""
    import threading
    from repro import telemetry
    from repro.configs.registry import get_config
    cfg = get_config("weathermixer-1b").reduced()
    tr = telemetry.Tracer()
    prev = telemetry.set_tracer(tr)
    try:
        pref = make_pipeline(cfg, batch_size=2, prefetch=2)
        assert len(list(pref.iterate([1, 2, 1]))) == 3
        xs = [e for e in tr.chrome_events() if e.get("ph") == "X"]
        h2d = [e for e in xs if e["name"] == "pipeline.h2d"]
        produce = [e for e in xs if e["name"] == "pipeline.produce"]
        assert len(h2d) == len(produce) == 3
        main = threading.get_ident()
        for e in h2d:
            assert e["tid"] != main
            assert sum(p["tid"] == e["tid"] and p["ts"] <= e["ts"]
                       and e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
                       for p in produce) == 1
        names = {e["tid"]: e["args"]["name"] for e in tr.chrome_events()
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        assert {names[e["tid"]] for e in h2d} == {"input-pipeline"}
        sync = make_pipeline(cfg, batch_size=2, prefetch=0)
        list(sync.iterate([1, 1]))
        assert len([e for e in tr.chrome_events()
                    if e.get("name") == "pipeline.h2d"]) == 3
    finally:
        telemetry.set_tracer(prev)


def test_pipeline_rejects_bad_mode():
    src = WeatherBatchSource(WeatherDataset(WCFG), batch_size=2)
    with pytest.raises(ValueError):
        InputPipeline(src, mode="async-magic")


# -- engine (single device) ---------------------------------------------

def test_engine_matches_legacy_format_and_evaluates(tmp_path):
    from repro.launch.engine import EngineConfig, TrainEngine
    eng = TrainEngine("internlm2-1.8b",
                      config=EngineConfig(steps=6, batch=4, seq_len=32,
                                          log_every=5, lr=2e-3,
                                          eval_batches=1))
    hist = eng.run()
    assert {"loss", "lr", "step", "wall_s"} <= set(hist[0])
    assert np.isfinite(hist[-1]["loss"])
    em = eng.evaluate()
    assert np.isfinite(em["val_loss"])
    # checkpoint hook (async by default: wait_checkpoints is the barrier)
    path = str(tmp_path / "ck")
    eng.save(path)
    eng.wait_checkpoints()
    from repro.checkpoint import io as ckpt_io
    import jax
    from repro.models import registry as M
    like = M.init(jax.random.PRNGKey(0), eng.cfg)
    _, _, step = ckpt_io.restore(path, like_params=like)
    assert step == 6


def test_engine_accum_close_to_full_batch():
    from repro.launch.engine import EngineConfig, TrainEngine

    def final(accum):
        eng = TrainEngine("internlm2-1.8b",
                          config=EngineConfig(steps=3, batch=4, seq_len=32,
                                              log_every=2, accum=accum))
        return eng.run()[-1]["loss"]

    assert np.allclose(final(1), final(2), rtol=1e-4)
