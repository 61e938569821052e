"""Mean time a forecast request waited between ``submit`` and its
admission at a step boundary: the program's ``serve.queue`` spans, one
per request, over the requests whose span lies wholly in the window
(the spans reach the reader clipped to it)."""

EDGE_S = 1e-6


def read(run):
    waits = [d for n, s, d, _ in run.spans if n == "serve.queue"
             and s > EDGE_S and s + d < run.window_s - EDGE_S]
    if not waits:
        return None
    return sum(waits) / len(waits)
