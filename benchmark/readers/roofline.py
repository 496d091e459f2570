"""Share of a peak: the least time the chip could take for the counted
work (``<count>`` of the configuration's own counts module,
``family.load_counts``, on its ``hparams``, over ``peaks.json``'s
``<peak>``) over the device time the trace shows for the matched events.
``bound`` says which roof it is (bytes or flops). ``executions_per_count``
is how many matched executions do one counted unit of work."""


def read(ctx, *, line: str, pattern: str, count: str, peak: str,
         bound: str, work_per_execution=1):
    from benchmark import family, trace_reduce

    if ctx.trace is None:
        return None
    total_s, n = trace_reduce.total_and_count(ctx.trace, line, pattern)
    per = ctx.hp[work_per_execution] if isinstance(work_per_execution, str) \
        else work_per_execution
    work = getattr(family.load_counts(ctx.conf), count)(ctx.hp) * n * per
    return 100.0 * (work / ctx.peaks[peak]) / total_s
