"""Model FLOP/s utilization of training: model FLOPs per sample (forward
plus backward, three times the forward; recomputed work does not count)
times the samples whose update finished in the window, over the window
times the chips times each chip's bf16 peak."""
from harness import flops


def read(run):
    done = run.counts.get("samples", 0)
    if not done:
        return None
    work = flops.train_flops(run.config) * done
    return 100.0 * work / (run.window_s * run.chips
                           * run.peak["bf16_flops_per_s"])
