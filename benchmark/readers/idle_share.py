"""1 - (union of device-op intervals) / (traced span), in percent."""


def read(ctx):
    from benchmark import trace_reduce

    if ctx.trace is None:
        return None
    b = trace_reduce.busy(ctx.trace)
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
