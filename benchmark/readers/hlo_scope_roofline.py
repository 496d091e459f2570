"""A kernel's share of its roofline, the kernel found by the scope it was
traced under (``hlo_scope_time``): the least time the chip could take for
the counted work of ONE unit (``count`` of the configuration's counts
module on its ``hparams`` over ``peaks.json``'s ``peak``) over the device
time of the matched kernel events per unit. A unit is an execution of
``module`` divided by ``per_execution`` (``fused_chain``: a grad step).
``bound`` says which roof it is. ``scale`` (optional) corrects a count
made from an EXPECTED quantity by what the program counted: the work is
multiplied by the mean of log-row key ``row_key`` over the traced steps
(``result["traced_steps"]``; all rows where none falls inside) over
``expected`` — the NAME of a function of the counts module on the
configuration's ``hparams``, looked up as ``count`` is, so one metric file
serves cells whose expectation differs (a number is taken as it is). The
expert layer's count assumes even routing, and its counter says what
share of the token-slots really came."""

from __future__ import annotations


def measured_over_expected(ctx, counts, row_key: str, expected) -> float:
    if isinstance(expected, str):
        expected = getattr(counts, expected)(ctx.hp)
    rows = [r for r in ctx.result["rows"] if row_key in r]
    lo, hi = ctx.result.get("traced_steps") or (0, 0)
    inside = [r for r in rows if lo < r["step"] <= hi] or rows
    return sum(r[row_key] for r in inside) / len(inside) / expected


def read(ctx, *, module: str, scopes: list[str], pattern: str, count: str,
         peak: str, bound: str, per_execution=1, scale: dict | None = None):
    from benchmark import family
    from benchmark.readers import hlo_scope_time

    seconds_per_unit = hlo_scope_time.seconds_per_unit(
        ctx, module, scopes, per_execution, pattern)
    if seconds_per_unit is None:
        return None
    counts = family.load_counts(ctx.conf)
    work = getattr(counts, count)(ctx.hp)
    if scale:
        work *= measured_over_expected(ctx, counts, **scale)
    return 100.0 * (work / ctx.peaks[peak]) / seconds_per_unit
