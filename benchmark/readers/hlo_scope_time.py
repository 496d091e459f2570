"""Device time of the operations a program traced under a named scope.

XLA names a device event by its HLO instruction, not by the
``jax.named_scope`` it was traced under: a fusion is ``%fusion.N``, a
Mosaic kernel takes its kernel's name. The scope is in the compiled
module's text (the instruction's ``op_name`` keeps the name stack), so the
driver of a cell hands the table over as ``result["hlo_scopes"] = {program
name: {instruction name: innermost ddq.* scope}}`` and this reader gives
each ``XLA Ops`` event inside an execution of that program (``XLA
Modules`` events whose name starts with ``module``) to its instruction's
scope. Containers (``while``, ``conditional``, ``call``) are left out:
their time is their bodies'. ``pattern`` narrows to instructions whose
printed line matches it (a kernel's custom-call). The value is the picked
events' summed time in ms over executions x ``per_execution`` (a literal
or the name of a configuration size such as ``fused_chain``).

No trace (the CPU rehearsal), no table in the result (a driver or a
program that gives none), or no event under the scope: ``None``.
"""

from __future__ import annotations

import bisect
import re

INSTR = re.compile(r"^%(\S+) = ")


def picked(ctx, module: str, scopes, pattern: str | None = None):
    """``(events, executions)``: the ``(name, start_ns, dur_ns)`` events
    of ``scopes`` inside whole executions of ``module``, and how many
    executions the trace holds; ``None`` where it cannot be read."""
    from benchmark import trace_reduce as tr

    table = (ctx.result.get("hlo_scopes") or {}).get(module)
    if ctx.trace is None or not table:
        return None
    plane = tr.device_planes(ctx.trace)[0]
    runs = sorted((s, s + d) for name, s, d in
                  tr.events(ctx.trace, plane, tr.MODULE_LINE)
                  if re.match(re.escape(module) + r"\b", name))
    if not runs:
        return None
    begins = [s for s, _ in runs]
    rx = re.compile(pattern) if pattern else None
    wanted = set(scopes)
    out = []
    for name, s, d in tr.events(ctx.trace, plane, tr.OP_LINE):
        m = INSTR.match(name)
        if not m or tr.CONTAINER.match(name) \
                or table.get(m.group(1)) not in wanted \
                or (rx and not rx.search(name)):
            continue
        i = bisect.bisect_right(begins, s) - 1
        if i >= 0 and s + d <= runs[i][1]:
            out.append((name, s, d))
    return (out, len(runs)) if out else None


def seconds_per_unit(ctx, module: str, scopes, per_execution=1,
                     pattern: str | None = None) -> float | None:
    """The picked events' device seconds per unit of work: an execution of
    ``module`` over ``per_execution``."""
    got = picked(ctx, module, scopes, pattern)
    if got is None:
        return None
    evs, runs = got
    div = ctx.hp[per_execution] if isinstance(per_execution, str) \
        else per_execution
    return sum(d for _, _, d in evs) / 1e9 / (runs * div)


def read(ctx, *, module: str, scopes: list[str], per_execution=1,
         pattern: str | None = None):
    s = seconds_per_unit(ctx, module, scopes, per_execution, pattern)
    return None if s is None else 1e3 * s
