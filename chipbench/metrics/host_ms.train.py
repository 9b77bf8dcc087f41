"""Mean host time of the program's ``train/phase/host`` span (the loop's
work between one step's sync and the next step: metric transfers, the
callback, the non-finite, checkpoint and preemption checks) per traced
step, in ms: the span's annotations in the trace, on the device's clock.
The registry's mean would also hold the profiler's stop, which the
harness's callback makes inside the last traced step's span."""


def read(ctx):
    mean = ctx.get("host_span_mean_s")
    return None if mean is None else 1e3 * mean
