"""Roofline share of the training step's local GEMMs
(``kernels/block_matmul.py`` through ``kernels/ops.py``): the GEMM work of
the samples whose update finished in the window, priced from the model's
shapes with the ``remat`` re-forward, at its least time, over the device
time of the ops that did GEMM work.  The rule that picks those ops is
``harness/gemm.py``'s, shared with ``gemm_roofline.serve``."""
from harness import flops, gemm


def read(run):
    if run.trace is None:
        return None
    return gemm.roofline_share(run.trace, run.peak,
                               flops.train_gemms(run.config),
                               run.counts.get("samples", 0))
