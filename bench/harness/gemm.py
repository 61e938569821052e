"""Roofline share of the local GEMMs: the least time of the GEMM work the
window did, over the device time of the device ops that did it.

The work is priced from the model's shapes (``flops.py``), never from
the shapes an op ran at, so an XLA dot, a Pallas kernel or a kernel
added later count the same work, and padding or a recomputed epilogue
costs time without adding work.  Each GEMM's least time is the larger
of its operations at the chip's bf16 peak and its bytes at the HBM
bandwidth; the reader multiplies a step's GEMMs by what the window ran
(samples trained, batch slots stepped).

The time is that of every op the rule picks.  The rule, worked out from
TPU v5e traces of the training step on both GEMM paths read by hand:
each ``XLA Ops`` event is named by its instruction,
``%name = type opcode(operands), attributes``.  An op does GEMM work if

  * it is a ``custom-call`` to ``tpu_custom_call``: a Pallas kernel.
    Every Pallas kernel on these paths is a GEMM (``block_matmul`` and the
    Jigsaw ring and Cannon kernels), whatever its name (``matmul.154``,
    ``jvp_jit_matmul__.4``, ``transpose_jvp_jit_matmul___.3``);
  * or its opcode is ``dot`` or ``convolution``;
  * or it is a ``fusion`` of ``kind=kOutput``, XLA's fusion around a dot
    or a convolution, or of ``kind=kCustom``, XLA's fusion of operand
    ops into a Pallas kernel.

On both paths every instruction of a compiled step that holds a dot, a
convolution or a Pallas kernel is picked and no other
(``tests/test_bench_gemm_paths.py``).
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional

from harness import flops


def is_gemm(op) -> bool:
    code = op.opcode
    if code == "custom-call":
        return 'custom_call_target="tpu_custom_call"' in op.name
    if code in ("dot", "convolution"):
        return True
    return code == "fusion" and ("kind=kOutput" in op.name
                                 or "kind=kCustom" in op.name)


def least_time(gemms: Iterable[flops.Gemm], peak: Dict[str, float]
               ) -> float:
    return sum(flops.least_time(flops.gemm_flops(g), flops.gemm_bytes(g),
                                peak) for g in gemms)


def roofline_share(trace, peak: Dict[str, float],
                   gemms: Iterable[flops.Gemm], times: float
                   ) -> Optional[float]:
    """Percent: ``times`` runs of ``gemms`` at their least time, over the
    device time of the ops the rule picks; None where nothing ran."""
    spent = trace.op_time(is_gemm) * trace.chips
    if spent <= 0 or times <= 0:
        return None
    return 100.0 * times * least_time(gemms, peak) / spent
