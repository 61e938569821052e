"""Share of the serving window in which the device had work to wait for
and ran none: no operation ran on it and the engine's thread was not in
``serve.idle`` (``ForecastEngine.step_once``: no request queued or in
flight), averaged over the cell's chips.  This is the part of
``device_idle_share.serve`` the step boundary's host work causes; the
rest is the open loop's own lack of requests."""
from harness import xtrace


def read(run):
    if run.trace is None or not run.trace.busy_by_chip:
        return None
    idle = xtrace.union([(s, s + d) for name, s, d, main in run.spans
                         if name == "serve.idle" and main])
    if not idle:
        return None
    window = [(0.0, run.trace.window_s)]
    chips = run.trace.chips
    busy = list(run.trace.busy_by_chip.values())
    busy += [[]] * (chips - len(busy))         # a chip that ran nothing
    starved = sum(xtrace.length(xtrace.subtract(
        xtrace.subtract(window, b), idle)) for b in busy)
    return 100.0 * starved / (chips * run.trace.window_s)
