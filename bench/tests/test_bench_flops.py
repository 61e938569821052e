"""The FLOP functions and the peaks table kept with the benchmark."""
import json
import os

import pytest

import _paths
from harness import flops, peaks


def _cfg(name):
    with open(os.path.join(_paths.BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,tflop", [("wm-1b", 12.255),
                                        ("wm-zoo-4t", 3.440)])
def test_forward_flops_pinned(name, tflop):
    assert flops.forward_flops(_cfg(name)) / 1e12 == pytest.approx(
        tflop, abs=1e-3)


@pytest.mark.parametrize("name", ["wm-1b", "wm-zoo-4t"])
def test_training_is_three_forwards_without_remat(name):
    cfg = _cfg(name)
    assert flops.train_flops(cfg) == 3 * flops.forward_flops(cfg)
    assert flops.train_flops(dict(cfg, remat=False)) == flops.train_flops(cfg)


@pytest.mark.parametrize("name,params", [("wm-1b", 999_429_465),
                                         ("wm-zoo-4t", 472_866_761)])
def test_param_count(name, params):
    assert flops.param_count(_cfg(name)) == params == _cfg(name)["params"]


def test_unknown_device_kind_raises():
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_for("TPU v9 imaginary")


def test_v5e_peaks():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9


def test_least_time_takes_the_larger_bound():
    row = peaks.peaks_for("TPU v5 lite")
    assert flops.least_time(197e12, 1.0, row) == pytest.approx(1.0)
    assert flops.least_time(1.0, 819e9, row) == pytest.approx(1.0)


@pytest.mark.parametrize("name", ["wm-1b", "wm-zoo-4t"])
def test_train_gemms_count_remat_and_skip_the_input_gradient(name):
    cfg = _cfg(name)
    fwd = flops.forward_flops(cfg)
    enc, blocks = flops.forward_gemms(cfg)[0], flops.forward_gemms(cfg)[1:-1]
    blocks_fwd = sum(map(flops.gemm_flops, blocks))

    def total(c):
        return sum(map(flops.gemm_flops, flops.train_gemms(c)))

    assert total(dict(cfg, remat=False)) == pytest.approx(
        3 * fwd - flops.gemm_flops(enc))
    assert total(cfg) == pytest.approx(total(dict(cfg, remat=False))
                                       + blocks_fwd)
    # every GEMM of the list is compute-bound on a v5e at these sizes
    row = peaks.peaks_for("TPU v5 lite")
    for g in flops.train_gemms(cfg):
        assert (flops.gemm_flops(g) / row["bf16_flops_per_s"]
                > flops.gemm_bytes(g) / row["hbm_bytes_per_s"])
