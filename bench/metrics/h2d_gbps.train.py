"""Host-to-device rate of the training input pipeline: the bytes of the
batches whose ``pipeline.h2d`` span (``InputPipeline`` on its prefetch
thread, from the start of a batch's transfer to ``block_until_ready`` of
its arrays) lies wholly in the window, over those spans' total time.
The bytes are computed from tensor sizes, not counted by the program:
fields and target, float32, batch x lat x lon x channels each (1.157 GB
a batch at wm-zoo-4t's batch 2)."""

EDGE_S = 1e-6
KEYS = 2                  # fields, target
F32_BYTES = 4


def read(run):
    spans = [d for n, s, d, _ in run.spans if n == "pipeline.h2d"
             and s > EDGE_S and s + d < run.window_s - EDGE_S]
    if not spans or sum(spans) <= 0:
        return None
    cfg = run.config
    batch_bytes = (KEYS * run.res["cell"]["batch"] * cfg["lat"] * cfg["lon"]
                   * cfg["channels"] * F32_BYTES)
    return batch_bytes * len(spans) / sum(spans) / 1e9
