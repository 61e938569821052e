"""The readers of the serving boundary's and the input feed's spans, on
run records made by hand: a trace of known busy intervals and spans
placed where a hand sum can check them."""
import pytest

import _paths
from harness import common, spec, xtrace

SERVE = spec.resolve("serve-wm-1b-poisson-1chip", _paths.ROOT)
TRAIN = spec.resolve("train-wm-zoo-4t-1chip", _paths.ROOT)


class _Dev:
    device_kind = "TPU v5 lite"


def reader(name):
    return spec.metric_reader(name, _paths.ROOT)


def record(res, spans, window_s=10.0, busy=None, chips=1):
    """A RunRecord whose trace ran ops over ``busy`` (per chip) and whose
    program spans are ``spans``: (name, start, duration, on the engine's
    thread), in seconds from the window's start."""
    run = common.RunRecord(res=res, devs=[_Dev()] * chips,
                           window_s=window_s, spans=spans, counts={},
                           profile=None)
    if busy is not None:
        ops = [xtrace.Op("%op = f32[] fusion()", s, e, chip)
               for chip, ivs in enumerate(busy) for s, e in ivs]
        run.trace = xtrace.Reduced(ops, [], chips, window_s, spans)
    return run


def test_device_starved_share_by_hand():
    # device idle [0,1] [2,4] [5,10]: 8 s; serve.idle covers [0,0.5] and
    # [6,9]; what is left, 0.5 + 2 + 1 + 1 = 4.5 s, is starved
    spans = [("serve.idle", 0.0, 0.5, True), ("serve.idle", 6.0, 3.0, True),
             ("serve.peel", 1.0, 1.5, True),
             ("serve.idle", 2.0, 2.0, False)]    # not the engine thread
    run = record(SERVE, spans, busy=[[(1.0, 2.0), (4.0, 5.0)]])
    assert run.trace.idle_share() == pytest.approx(0.8)
    assert reader("device_starved_share.serve")(run) == pytest.approx(45.0)


def test_device_starved_share_averages_chips():
    # chip 0 as above (4.5 s); chip 1 ran nothing: all 10 s idle, 7 s
    # outside serve.idle
    spans = [("serve.idle", 0.0, 0.5, True), ("serve.idle", 6.0, 3.0, True)]
    run = record(SERVE, spans, busy=[[(1.0, 2.0), (4.0, 5.0)]], chips=2)
    got = reader("device_starved_share.serve")(run)
    assert got == pytest.approx(100.0 * (4.5 + 6.5) / 20.0)


def test_peel_fetch_share():
    spans = [("serve.peel", 1.0, 0.6, True),
             ("serve.peel.wait", 1.0, 0.4, True),
             ("serve.peel.fetch", 1.4, 0.2, True),
             ("serve.peel.fetch", 5.0, 0.3, True)]
    got = reader("peel_fetch_share.serve")(record(SERVE, spans))
    assert got == pytest.approx(5.0)


def test_queue_wait_counts_whole_spans_only():
    spans = [("serve.queue", 0.0, 0.4, False),     # clipped at the start
             ("serve.queue", 2.0, 0.1, False),
             ("serve.queue", 4.0, 0.3, False),
             ("serve.queue", 9.5, 0.5, False)]     # clipped at the end
    got = reader("queue_wait_s.serve")(record(SERVE, spans))
    assert got == pytest.approx(0.2)


def test_h2d_gbps_from_tensor_sizes():
    cfg, batch = TRAIN["config"], TRAIN["cell"]["batch"]
    per_batch = 2 * batch * cfg["lat"] * cfg["lon"] * cfg["channels"] * 4
    assert per_batch == 1_157_345_280 and batch == 2
    spans = [("pipeline.h2d", 0.0, 0.2, False),    # clipped at the start
             ("pipeline.h2d", 1.0, 0.25, False),
             ("pipeline.h2d", 2.0, 0.25, False),
             ("pipeline.produce", 2.0, 0.3, False)]
    got = reader("h2d_gbps.train")(record(TRAIN, spans))
    assert got == pytest.approx(per_batch / 0.25 / 1e9)


@pytest.mark.parametrize("name,res", [
    ("device_starved_share.serve", SERVE),
    ("peel_fetch_share.serve", SERVE),
    ("queue_wait_s.serve", SERVE),
    ("h2d_gbps.train", TRAIN),
])
def test_reader_finds_nothing_without_its_span(name, res):
    # a program without the spans (the parent of the change that added
    # them) reads as no metric, not as zero and not as an error
    spans = [("serve.peel", 1.0, 0.6, True), ("data_wait", 0.0, 0.1, True),
             ("pipeline.produce", 0.0, 0.5, False)]
    run = record(res, spans, busy=[[(1.0, 2.0)]])
    assert reader(name)(run) is None
