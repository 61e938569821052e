"""The trace reduction, on a trace recorded on a TPU v5e (five seconds of
the training cell's window, 11 steps of ``jit_train_step``) and on
intervals made by hand."""
import os

import pytest

import _paths
from harness import flops, gemm, peaks, spec, xtrace

TRACE = os.path.join(_paths.BENCH, "tests", "data",
                     "train-wm-zoo-4t-1chip.xplane.pb.gz")
WINDOW_S = 6.402330207999995       # the run's own window
CFG = spec.config("wm-zoo-4t", _paths.ROOT)
PEAK = peaks.peaks_for("TPU v5 lite")


def test_union_subtract_length():
    u = xtrace.union([(0, 2), (1, 3), (5, 6)])
    assert u == [(0, 3), (5, 6)]
    assert xtrace.length(u) == 4
    assert xtrace.subtract([(0, 10)], u) == [(3, 5), (6, 10)]
    assert xtrace.subtract(u, [(0, 10)]) == []


@pytest.fixture(scope="module")
def reduced():
    host = [("data_wait", 0.0, 0.5, True), ("pipeline.produce", 0.0, 6.0,
                                            False)]
    return xtrace.reduce(TRACE, WINDOW_S, 1, host)


def test_busy_and_idle(reduced):
    assert reduced.busy_s == pytest.approx(6.0751, abs=1e-3)
    assert 100 * reduced.idle_share() == pytest.approx(5.11, abs=0.01)


def test_programs_and_steps(reduced):
    steps = [m for m in reduced.modules if m.name.startswith("jit_train_step")]
    assert len(steps) == 11
    assert reduced.module_time(r"^jit_train_step\b") == pytest.approx(
        6.0754, abs=1e-3)


def test_gemm_rule_and_roofline(reduced):
    picked = reduced.picked(gemm.is_gemm)
    # 17 Pallas GEMMs per mixing block, 3 blocks, 5 for encoder and
    # decoder, in each of 11 steps
    assert len(picked) == 11 * (17 * 3 + 5)
    # 11 steps of 2 samples: the work of 22 samples, priced from the
    # model's shapes, over the device time of the picked ops
    share = gemm.roofline_share(reduced, PEAK, flops.train_gemms(CFG), 22)
    assert share == pytest.approx(36.696, abs=0.01)
    assert gemm.roofline_share(reduced, PEAK, flops.train_gemms(CFG),
                               0) is None


def test_one_gemm_priced_by_hand():
    # wm-zoo-4t's token-mixing GEMM: d_emb rows, d_tok out, the 16,380
    # tokens contracted; the padding a kernel adds is not work
    g = flops.forward_gemms(CFG)[1]
    assert g == ("tok_fc1", 2192, 4320, 16380)
    assert gemm.least_time([g], PEAK) == pytest.approx(
        2.0 * 2192 * 4320 * 16380 / 197e12)
    assert flops.gemm_bytes(g) == 2 * (2192 * 16380 + 16380 * 4320
                                       + 2192 * 4320)


def test_no_collectives_on_one_chip(reduced):
    assert reduced.exposed_collective_s() == 0.0


def test_breakdown(reduced):
    b = reduced.breakdown()
    assert b["device_ops"][0][0] == "matmul (custom-call)"
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert all(not n.startswith("while") for n, _ in b["device_ops"])
    assert b["idle_gaps"][0][0] in ("data_wait",
                                    "pipeline.produce (other thread)")
