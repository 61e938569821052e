"""Share of the serving window the engine's thread spent at the step
boundary moving fields across the host link: the program's
``serve.admit``, ``serve.peel`` and ``serve.grow`` spans
(``ForecastEngine.step_once``)."""

SPANS = ("serve.admit", "serve.peel", "serve.grow")


def read(run):
    if not any(n in SPANS for n, *_ in run.spans):
        return None
    return 100.0 * run.span_total(*SPANS) / run.window_s
