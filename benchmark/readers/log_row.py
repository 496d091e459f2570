"""A key of the log rows inside the window: the program's own rows in a
``train_distributed`` cell, the driver's rows of the same schema in a
learner-only cell. ``agg``: ``mean`` over the rows, or ``last``."""


def read(ctx, *, key: str, agg: str = "mean"):
    vals = [r[key] for r in ctx.result["rows"] if key in r]
    if not vals:
        return None
    return vals[-1] if agg == "last" else sum(vals) / len(vals)
