"""The GEMM rule against the TPU compiler's own output, on both of the
program's GEMM paths.

The data are the compiled HLO of one WeatherMixer train step (d_emb 512,
d_tok 1024, d_ch 512, a 128 x 256 x 69 grid, batch 2, bf16 with fp32
masters, remat), compiled for a TPU v5e with ``kernel="xla"`` and with
``kernel="pallas"``.  A device op in a trace is named by the text of its
instruction, so each executed instruction here is an op the rule sees.
"""
import gzip
import os
import re

import pytest

import _paths
from harness import flops, gemm, peaks, spec
from harness.xtrace import Op

DATA = os.path.join(_paths.BENCH, "tests", "data")
CALLS_RE = re.compile(r"calls=(%[\w.\-]+)")


def _computations(path):
    comps, cur = {}, None
    with gzip.open(path, "rt") as f:
        for line in f:
            line = line.rstrip("\n")
            if line and not line.startswith(" "):
                m = re.match(r"^(?:ENTRY )?(%[\w.\-]+) ", line)
                cur = m.group(1) if m else None
                if cur:
                    comps[cur] = []
            elif cur and line.startswith("  "):
                comps[cur].append(line.strip())
    return comps


def _holds_gemm(comps, ins, seen=()):
    if re.search(r" (dot|convolution)\(", ins) \
            or 'custom_call_target="tpu_custom_call"' in ins:
        return True
    return any(_holds_gemm(comps, sub, seen + (c,))
               for c in CALLS_RE.findall(ins) if c not in seen
               and "fused" in c for sub in comps.get(c, []))


def _executed(comps):
    """Instructions of the computations that run op by op (the entry, loop
    bodies and conditions), not of the computations fused into them."""
    for name, body in comps.items():
        if "fused" in name:
            continue
        for ins in body:
            if " = " in ins and not ins.startswith("ROOT %tuple"):
                yield ins.removeprefix("ROOT ")


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_rule_picks_exactly_the_ops_that_hold_a_gemm(kernel):
    comps = _computations(os.path.join(
        DATA, f"train-step-small-{kernel}.hlo.txt.gz"))
    picked = wrong = 0
    for ins in _executed(comps):
        op = Op(ins, 0.0, 1.0, 0)
        holds = _holds_gemm(comps, ins)
        if gemm.is_gemm(op) != holds:
            wrong += 1
        picked += holds
    assert wrong == 0
    assert picked >= 18


def test_counted_work_does_not_depend_on_the_path():
    cfg = spec.config("wm-zoo-4t", _paths.ROOT)
    peak = peaks.peaks_for("TPU v5 lite")
    by_path = [gemm.least_time(flops.train_gemms(dict(cfg, kernel=k)), peak)
               for k in ("xla", "pallas")]
    assert by_path[0] == by_path[1] > 0
