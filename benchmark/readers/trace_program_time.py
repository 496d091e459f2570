"""Device time of a program's (or a kernel's) executions in the trace, in
milliseconds, over their count times ``per_execution`` (a literal, or the
name of a configuration size such as ``fused_chain``)."""


def read(ctx, *, line: str, pattern: str, per_execution=1):
    from benchmark import trace_reduce

    if ctx.trace is None:
        return None
    total_s, n = trace_reduce.total_and_count(ctx.trace, line, pattern)
    div = ctx.hp[per_execution] if isinstance(per_execution, str) \
        else per_execution
    return 1e3 * total_s / (n * div)
