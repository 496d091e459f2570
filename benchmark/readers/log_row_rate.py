"""Growth of a cumulative counter between the first and the last log row
inside the window, per second of the rows' own clock. Fewer than
``min_rows`` rows: nothing to read."""


def read(ctx, *, key: str, min_rows: int = 3):
    rows = [r for r in ctx.result["rows"] if key in r]
    if len(rows) < min_rows:
        return None
    return (rows[-1][key] - rows[0][key]) / (rows[-1]["t"] - rows[0]["t"])
