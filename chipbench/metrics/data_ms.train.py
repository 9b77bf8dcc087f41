"""Mean host time of the program's ``train/phase/data`` span (the batch
feed of ``train.loop.run_loop``) per step of the traced window, in ms."""


def read(ctx):
    mean = ctx.get("data_span_mean_s")
    return None if mean is None else 1e3 * mean
