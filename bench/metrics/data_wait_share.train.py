"""Share of the window the training loop spent blocked on the input
pipeline: the program's ``data_wait`` spans (``TrainEngine.run``)."""


def read(run):
    if not any(n == "data_wait" for n, *_ in run.spans):
        return None
    return 100.0 * run.span_total("data_wait") / run.window_s
