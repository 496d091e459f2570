"""From a ``jax.profiler`` trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` and nothing else. A trace has
planes; a device plane (``/device:TPU:<n>``) has lines — ``XLA Modules``
(one event per program execution), ``XLA Ops`` (one per HLO op or kernel)
— and host planes have one line per thread. Every event has a name, a
start and a duration in nanoseconds on one clock.

Everything here is arithmetic on ``(name, start_ns, dur_ns)`` triples, so
``test_trace_reduce.py`` checks it on the recorded fixture.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
# ops that only contain other ops of the same line (their time is the sum)
CONTAINER = re.compile(r"^%(while|conditional|call)[.\d]* = ")
NAME_CHARS = 160        # XLA prints an op as its whole HLO line


class NothingMatched(LookupError):
    """A name pattern found no event: the metric cannot be read."""


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise NothingMatched(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> dict:
    """``{plane name: {line name: [(event name, start_ns, dur_ns), ...]}}``"""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for ev in line.events:
                evs.append((ev.name, float(ev.start_ns),
                            float(ev.duration_ns)))
    return out


def device_planes(trace: dict) -> list[str]:
    names = sorted(p for p in trace if DEVICE_PLANE.match(p))
    if not names:
        raise NothingMatched(f"no device plane among {sorted(trace)}")
    return names


def events(trace: dict, plane: str, line: str) -> list[tuple]:
    return trace[plane].get(line, [])


def union_ns(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy(trace: dict) -> dict:
    """Device busy seconds (union of op intervals; module intervals where a
    plane has no op line) and the traced span (first op start to last op
    end), averaged over the device planes."""
    busy_s = span_s = 0.0
    planes = device_planes(trace)
    for p in planes:
        evs = events(trace, p, OP_LINE) or events(trace, p, MODULE_LINE)
        if not evs:
            raise NothingMatched(f"no device operation on {p}")
        iv = [(s, s + d) for _, s, d in evs]
        busy_s += union_ns(iv) / 1e9
        span_s += (max(e for _, e in iv) - min(s for s, _ in iv)) / 1e9
    return {"busy_s": busy_s / len(planes), "window_s": span_s / len(planes)}


def matching(trace: dict, line: str, pattern: str) -> list[tuple]:
    """Events of ``line`` on the first device plane whose name matches."""
    rx = re.compile(pattern)
    evs = [e for e in events(trace, device_planes(trace)[0], line)
           if rx.search(e[0])]
    if not evs:
        names = sorted({e[0] for e in events(
            trace, device_planes(trace)[0], line)})[:40]
        raise NothingMatched(
            f"pattern {pattern!r} matches nothing on line {line!r}; "
            f"names there: {names}")
    return evs


def total_and_count(trace: dict, line: str, pattern: str):
    evs = matching(trace, line, pattern)
    return sum(d for _, _, d in evs) / 1e9, len(evs)


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """The device operations that took most time, by printed name."""
    acc: dict[str, float] = {}
    p = device_planes(trace)[0]
    for name, _, d in events(trace, p, OP_LINE) or events(
            trace, p, MODULE_LINE):
        if not CONTAINER.match(name):
            key = name[:NAME_CHARS]
            acc[key] = acc.get(key, 0.0) + d / 1e9
    return [[k, v] for k, v in sorted(acc.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 5) -> list[list]:
    """The longest device idle gaps, each named by the host event that
    covers most of it in the trace itself, else ``unattributed``."""
    p = device_planes(trace)[0]
    evs = events(trace, p, OP_LINE) or events(trace, p, MODULE_LINE)
    iv = sorted((s, s + d) for _, s, d in evs)
    gaps, end = [], None
    for s, e in iv:
        if end is not None and s > end:
            gaps.append((s - end, end, s))
        end = e if end is None else max(end, e)
    gaps.sort(reverse=True)
    host = [(name, s, s + d) for plane, lines in trace.items()
            if not DEVICE_PLANE.match(plane) and plane.startswith("/host")
            for evl in lines.values() for name, s, d in evl if d > 0]
    out = []
    for length, g0, g1 in gaps[:n]:
        best, cover = "unattributed", 0.0
        for name, s, e in host:
            ov = min(e, g1) - max(s, g0)
            # the innermost event that covers most of the gap
            if ov > 0.5 * length and (best == "unattributed"
                                      or e - s < cover):
                best, cover = name, e - s
        out.append([best[:NAME_CHARS], length / 1e9])
    return out
