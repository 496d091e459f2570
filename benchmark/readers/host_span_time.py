"""Host time of the program's own spans, from the profiler's trace.

While ``profiling.TraceWindow`` captures, every ``tracing`` span of the
process that holds the chip is also written into the ``.xplane.pb`` as an
event named ``ddq/<span>`` on its thread's line of the host plane, on the
device operations' clock (``distributed_deep_q_tpu/tracing.py``). This
reader sums the durations of the events named by ``spans``, optionally
only those that lie inside an event named ``within`` on the SAME line
(thread), and divides by ``per``:

- ``"count"``: the number of matched events (ms per event);
- ``"chunks"``: the number of ``ddq/learner_chunk`` events (ms per chunk);
- ``"steps"``: chunks x the configuration's ``fused_chain`` (ms per step);
- ``"traced_span"``: the device's traced span, first operation start to
  last operation end, the events clipped to it (a share in percent; it
  passes 100 where several threads are inside the span at once).

The lines are read here, apart: ``trace_reduce.load`` keys a plane's lines
by name, and the runtime gives every Python thread's line the same one.

Two ways to find nothing. A trace with ``ddq/`` events in which the NAMED
span (or the chunk count) is missing: ``None``, and the harness fails the
run as for any listed metric — a span was renamed or lost. A trace with
no ``ddq/`` event at all: the program does not write its spans into the
trace (every commit before PR 24; the driver runs this file against the
parent too, and ``run.py`` cannot leave a listed metric out). The time
such a trace shows inside any span is 0, and that is what is returned,
after one line that says so.
"""

from __future__ import annotations

PREFIX = "ddq/"
CHUNK = "learner_chunk"


def load_lines(path: str) -> list[list[tuple]]:
    """One list per line of every host plane that carries a ``ddq/``
    event: ``[(span name, start_ns, end_ns), ...]`` sorted by start, the
    enclosing span before the enclosed."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(ev.name[len(PREFIX):], float(ev.start_ns),
                    float(ev.start_ns) + float(ev.duration_ns))
                   for ev in line.events if ev.name.startswith(PREFIX)]
            if evs:
                out.append(sorted(evs, key=lambda e: (e[1], -e[2])))
    return out


def lines_of(ctx) -> list[list[tuple]] | None:
    """The traced run's span lines, read once per run; ``None`` where
    there is no trace (the CPU rehearsal)."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "span_lines"):
        from benchmark import trace_reduce
        from benchmark.common import emit

        ctx.span_lines = load_lines(
            trace_reduce.find_xplane(ctx.result["trace_dir"]))
        if not ctx.span_lines:
            emit(program_spans="none in the trace: span times read 0, all "
                               "device idle reads unowned")
    return ctx.span_lines


def device_span(trace: dict) -> tuple[float, float]:
    """First operation start and last operation end on the first device
    plane, in ns: the span ``trace_reduce.busy`` divides by."""
    from benchmark import trace_reduce as tr

    p = tr.device_planes(trace)[0]
    evs = tr.events(trace, p, tr.OP_LINE) or tr.events(
        trace, p, tr.MODULE_LINE)
    if not evs:
        raise tr.NothingMatched(f"no device operation on {p}")
    return (min(s for _, s, _ in evs), max(s + d for _, s, d in evs))


def matched(line: list[tuple], spans, within: str | None = None
            ) -> list[tuple]:
    """The events of one line named in ``spans``; with ``within``, only
    those inside an event of that name on this line."""
    evs = [e for e in line if e[0] in spans]
    if within is None:
        return evs
    parents = [(s, e) for name, s, e in line if name == within]
    return [ev for ev in evs
            if any(ps <= ev[1] and ev[2] <= pe for ps, pe in parents)]


def clipped_ns(evs: list[tuple], t0: float, t1: float) -> float:
    return sum(max(0.0, min(e, t1) - max(s, t0)) for _, s, e in evs)


def span_time(lines: list[list[tuple]], spans, within: str | None,
              per: str, *, chain: int = 1,
              traced: tuple[float, float] | None = None) -> float | None:
    """The arithmetic, on lines alone (``test_host_spans.py``)."""
    evs = [ev for line in lines for ev in matched(line, spans, within)]
    if not evs:
        return None
    if per == "traced_span":
        t0, t1 = traced
        return 100.0 * clipped_ns(evs, t0, t1) / (t1 - t0)
    total_ms = sum(e - s for _, s, e in evs) / 1e6
    if per == "count":
        return total_ms / len(evs)
    chunks = sum(1 for line in lines for ev in line if ev[0] == CHUNK)
    if not chunks:
        return None
    if per == "chunks":
        return total_ms / chunks
    if per == "steps":
        return total_ms / (chunks * chain)
    raise SystemExit(f"host_span_time: unknown divisor {per!r}")


def read(ctx, *, spans: list[str], per: str, within: str | None = None):
    lines = lines_of(ctx)
    if lines is None:
        return None
    if not lines:
        return 0.0
    traced = device_span(ctx.trace) if per == "traced_span" else None
    return span_time(lines, set(spans), within, per,
                     chain=ctx.hp["fused_chain"], traced=traced)
