"""Share of the traced serving window (from the first request's due time
to the last delivery) in which no operation ran on the device:
1 - (union of op intervals) / window."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * run.trace.idle_share()
