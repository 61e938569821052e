"""Roofline share of the forecast rollout step's local GEMMs
(``kernels/block_matmul.py`` through ``kernels/ops.py``): one forward's
GEMM work, priced from the model's shapes, for each batch slot of each
rollout step the window ran (a padded slot runs the GEMMs too), at its
least time, over the device time of the ops that did GEMM work.  The
rule that picks those ops is ``harness/gemm.py``'s, shared with
``gemm_roofline.train``."""
from harness import flops, gemm


def read(run):
    if run.trace is None:
        return None
    slots = sum(int(b) * n for b, n in
                run.counts.get("bucket_steps", {}).items())
    return gemm.roofline_share(run.trace, run.peak,
                               flops.forward_gemms(run.config), slots)
