"""Device time of a program's operations by the ``ddq.*`` scope they were
traced under, from the table the PROGRAM writes beside its trace.

``profiling.TraceWindow`` leaves ``ddq_scopes.json`` in the trace directory
it captured into: ``{"programs": {module name: {"scopes": {HLO instruction:
[ddq.* scopes, outermost first]}, "mixed": {...}, "inherited": {...}}}}``
(``distributed_deep_q_tpu/profiling.scope_table``). This reader picks the
program whose name matches ``program`` (a pattern, as ``train_ms_per_step``
finds it), gives every ``XLA Ops`` event inside one of its executions to
its instruction's INNERMOST scope — so the scopes of one program partition
its time — and returns the events of ``scopes`` in ms over executions x
``per_execution`` (a literal or a configuration size such as
``fused_chain``). The sum is ``hlo_scope_time``'s own, by import.

Three ways to find nothing, kept apart as ``host_span_time`` keeps them:

- no trace (the CPU rehearsal): ``None``, and the harness skips the metric;
- no ``ddq_scopes.json`` in the trace directory: the program does not
  write one (every commit before PR 36; the driver runs this file against
  the parent too, and ``run.py`` cannot leave a listed metric out). Such a
  trace gives no time to any scope: ``0.0``, after one line that says so;
- a file that lacks the program, a program that lacks the scope, or a scope
  that names no event of the trace: ``None`` — a scope was renamed or
  lost, and the harness fails the run as for any listed metric.

The first metric read of a program also prints, once, where that program's
device time went: by innermost scope, in fusions whose instructions lie
under more than one scope (``mixed``; the largest by name, with the
scope each is counted under and the scopes it fused), in instructions the
compiler made
that took a neighbour's scope (``inherited``), and in operations the table
has no scope for (``unscoped``) — all in ms per execution, beside their
total.
"""

from __future__ import annotations

import json
import os
import re
import types

SCOPES_FILE = "ddq_scopes.json"
UNSCOPED = "(unscoped)"
MIXED_SHOWN = 6         # the mixed fusions printed by name, largest first


def tables_of(ctx) -> dict | None:
    """The traced run's ``programs``, read once per run; ``{}`` where the
    program wrote no file, ``None`` where there is no trace."""
    if ctx.trace is None:
        return None
    if not hasattr(ctx, "scope_tables"):
        from benchmark.common import emit

        path = os.path.join(ctx.result["trace_dir"], SCOPES_FILE)
        ctx.scope_tables = {}
        if os.path.isfile(path):
            with open(path) as fh:
                wrote = json.load(fh)
            ctx.scope_tables = wrote["programs"]
            emit(scope_table_s=wrote["scope_table_s"],
                 scope_table_programs={k: len(v["scopes"]) for k, v in
                                       ctx.scope_tables.items()},
                 scope_table_unavailable=wrote["unavailable"])
        else:
            emit(program_scopes="none written: scope times read 0")
    return ctx.scope_tables


def innermost(table: dict) -> dict[str, str]:
    return {instr: stack[-1] for instr, stack in table["scopes"].items()}


def _shim(ctx, module: str, labels: dict[str, str]):
    """What ``hlo_scope_time`` reads of a run, with this table."""
    return types.SimpleNamespace(trace=ctx.trace, hp=ctx.hp,
                                 result={"hlo_scopes": {module: labels}})


def partition(ctx, module: str, table: dict) -> dict | None:
    """``{label: ms per execution}`` over EVERY operation inside the
    program's executions, and the shares no single name owns."""
    from benchmark import trace_reduce as tr
    from benchmark.readers import hlo_scope_time as hst

    labels = innermost(table)
    plane = tr.device_planes(ctx.trace)[0]
    for name, _, _ in tr.events(ctx.trace, plane, tr.OP_LINE):
        m = hst.INSTR.match(name)
        if m:
            labels.setdefault(m.group(1), UNSCOPED)
    got = hst.picked(_shim(ctx, module, labels), module,
                     set(labels.values()))
    if got is None:
        return None
    evs, runs = got
    by: dict[str, float] = {}
    mixed: dict[str, float] = {}
    inherited = 0.0
    for name, _, d in evs:
        instr = hst.INSTR.match(name).group(1)
        ms = d / 1e6 / runs
        by[labels[instr]] = by.get(labels[instr], 0.0) + ms
        if instr in table["mixed"]:
            mixed[instr] = mixed.get(instr, 0.0) + ms
        inherited += ms if instr in table["inherited"] else 0.0
    top = sorted(mixed.items(), key=lambda kv: -kv[1])[:MIXED_SHOWN]
    return {"by_scope": dict(sorted(by.items(), key=lambda kv: -kv[1])),
            "total": sum(by.values()), "mixed": sum(mixed.values()),
            "mixed_top": [[i, ms, labels[i], table["mixed"][i]]
                          for i, ms in top],
            "inherited": inherited, "executions": runs}


def read(ctx, *, program: str, scopes: list[str], per_execution=1):
    from benchmark.common import emit
    from benchmark.readers import hlo_scope_time as hst

    tables = tables_of(ctx)
    if tables is None:
        return None
    if not tables:
        return 0.0
    rx = re.compile(program)
    module = next((name for name in tables if rx.search(name)), None)
    if module is None:
        return None
    table = tables[module]
    if not hasattr(ctx, "scope_partitions"):
        ctx.scope_partitions = set()
    if module not in ctx.scope_partitions:
        ctx.scope_partitions.add(module)
        emit(scope_partition_ms_per_execution={
            module: partition(ctx, module, table)})
    s = hst.seconds_per_unit(_shim(ctx, module, innermost(table)), module,
                             scopes, per_execution)
    return None if s is None else 1e3 * s
