"""Who owns the device's idle time: every idle gap of the traced span,
given to the program's span that was open on the learner thread.

The learner thread is the host line that carries ``ddq/learner_chunk``.
Its spans are flattened into segments, each owned by the INNERMOST span
open there (a parent owns only what its children leave), and every gap
between device operations — all of them, not the longest few — gives its
seconds to the segments it overlaps. What no span covers is unowned. The
value is unowned idle over all idle, in percent; the table (seconds and
share of idle per owner) and the thread's own coverage — the share of the
traced span inside any span, and inside spans that enclose no other —
are printed as one line before the result line. A trace without any
``ddq/`` event (``host_span_time``) reads 100: nothing owns anything.
"""

from __future__ import annotations

from benchmark.common import emit
from benchmark.readers import host_span_time as hst

UNOWNED = "(no span)"


def idle_gaps(trace: dict) -> list[tuple[float, float]]:
    """Every ``(start, end)`` in ns between device operations on the first
    device plane, inside the traced span."""
    from benchmark import trace_reduce as tr

    p = tr.device_planes(trace)[0]
    evs = tr.events(trace, p, tr.OP_LINE) or tr.events(
        trace, p, tr.MODULE_LINE)
    gaps, end = [], None
    for s, e in sorted((s, s + d) for _, s, d in evs):
        if end is not None and s > end:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    return gaps


def segments(line: list[tuple]) -> list[tuple]:
    """``[(start, end, owner, is_leaf), ...]``, disjoint and in order: the
    line's spans cut so that each stretch belongs to the innermost span
    open over it. ``is_leaf`` says the owner encloses no other span."""
    out: list[tuple] = []
    stack: list[list] = []          # [name, end, cursor, has_child]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor, has_child = stack.pop()
            if end > cursor:
                out.append((cursor, end, name, not has_child))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, s, e in line:         # sorted by start, enclosing first
        close(s)
        if stack:
            top = stack[-1]
            if s > top[2]:
                out.append((top[2], s, top[0], False))
            top[2], top[3] = s, True
            e = min(e, top[1])      # a child never outlives its parent
        stack.append([name, e, s, False])
    close(float("inf"))
    return sorted(out)


def owners(gaps: list[tuple], segs: list[tuple]) -> dict[str, float]:
    """Idle ns per owner; ``UNOWNED`` takes what no segment overlaps."""
    acc: dict[str, float] = {}
    i = 0
    for g0, g1 in gaps:
        while i < len(segs) and segs[i][1] <= g0:
            i += 1
        owned, j = 0.0, i
        while j < len(segs) and segs[j][0] < g1:
            ov = min(segs[j][1], g1) - max(segs[j][0], g0)
            if ov > 0:
                acc[segs[j][2]] = acc.get(segs[j][2], 0.0) + ov
                owned += ov
            j += 1
        acc[UNOWNED] = acc.get(UNOWNED, 0.0) + (g1 - g0) - owned
    return acc


def coverage(segs: list[tuple], t0: float, t1: float) -> tuple[float, float]:
    """Share of ``[t0, t1]`` inside any span, and inside leaf spans."""
    inside = leaves = 0.0
    for s, e, _, leaf in segs:
        ov = max(0.0, min(e, t1) - max(s, t0))
        inside += ov
        leaves += ov if leaf else 0.0
    return inside / (t1 - t0), leaves / (t1 - t0)


def learner_line(lines: list[list[tuple]]) -> list[tuple] | None:
    """The line with (the most) ``learner_chunk`` events, if any has one."""
    def chunks(line):
        return sum(1 for ev in line if ev[0] == hst.CHUNK)
    best = max(lines, key=chunks, default=None)
    return best if best is not None and chunks(best) else None


def read(ctx):
    lines = hst.lines_of(ctx)
    if lines is None:
        return None
    if not lines:       # a program without the spans: nothing is owned
        return 100.0
    line = learner_line(lines)
    if line is None:
        return None
    gaps = idle_gaps(ctx.trace)
    idle = sum(g1 - g0 for g0, g1 in gaps)
    if idle <= 0:
        return None
    segs = segments(line)
    acc = owners(gaps, segs)
    t0, t1 = hst.device_span(ctx.trace)
    any_share, leaf_share = coverage(segs, t0, t1)
    emit(idle_owners={k: {"s": v / 1e9, "share_of_idle": v / idle}
                      for k, v in sorted(acc.items(), key=lambda kv: -kv[1])},
         idle_s=idle / 1e9, idle_gaps=len(gaps), traced_s=(t1 - t0) / 1e9,
         learner_thread_in_spans=any_share,
         learner_thread_in_leaf_spans=leaf_share)
    return 100.0 * acc.get(UNOWNED, 0.0) / idle
