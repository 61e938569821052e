"""Share of the serving window the engine's thread spent copying
delivered forecasts to the host once the device had them ready: the
program's ``serve.peel.fetch`` spans (``np.asarray`` of the peeled
slice inside ``serve.peel``: the device-to-host copy and the host's
delinearize of the TPU's tiled layout)."""


def read(run):
    if not any(n == "serve.peel.fetch" for n, *_ in run.spans):
        return None
    return 100.0 * run.span_total("serve.peel.fetch") / run.window_s
