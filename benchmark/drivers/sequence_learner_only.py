"""Traffic kind ``sequence_learner_only``: the SEQUENCE learner catching up
on a full ring of stored windows — Q-learning over long stored rollouts at
a high replay ratio.

Fill the ring to capacity with seeded windows, then call
``FusedStepStream(solver, ring, chain).next(...)`` per grad step, as the
program's own sequence train loop does: the sequence solver dispatches the
sample program and the train program of ``fused_chain`` steps, priorities
are written back on the device. No actors, nothing is
written to the ring inside the window. Closed loop.

Traffic parameters (``traffic/<name>.json``): as ``learner_only`` —
``prefill``, ``overrides``, ``warmup_steps``, ``row_every`` (steps per log
row: the driver's clock per step and the family's counters of that step),
``trace_start_step`` / ``trace_num_steps``.

The driver names no family. What belongs to one it asks of the
configuration's ``check`` module (``family.load_check``), beside
``build_checked``; both names are optional:

- ``ROW_COUNTERS`` and ``log_row(counters) -> dict``: the keys of a step's
  metrics a log row carries, and the row's keys made from their values
  once the window has closed;
- ``hlo_scope_tables(solver, replay, chain) -> {program: {HLO instruction:
  innermost ddq.* scope}}``: what the ``hlo_scope_*`` readers need to give
  device time to a scope. A traced run returns it as ``hlo_scopes``, with
  ``traced_steps``, the window steps the trace covers.
"""

from __future__ import annotations

import math
import os
import time

from benchmark import family, program
from benchmark.common import CompileClock, emit, fence, memory_peak_bytes

BIG = 10 ** 9       # steps_left: never clamp a chunk


def run(ctx) -> dict:
    import jax

    from distributed_deep_q_tpu.profiling import TraceWindow

    conf, traffic = ctx.conf, ctx.traffic
    cfg = program.make_cfg(conf, ctx.seed, ctx.backend,
                           traffic.get("overrides", []))
    chain = cfg.replay.fused_chain
    marks: dict[str, float] = {}

    def mark(name: str) -> None:    # cumulative seconds since process start
        marks[name] = time.perf_counter() - ctx.t_start

    mark("imports_config")
    check = family.load_check(conf)
    counters = getattr(check, "ROW_COUNTERS", ())
    solver, replay, stream, mirror, rec = check.build_checked(
        conf, cfg, ctx.seed, traffic["prefill"], traffic["episode"],
        mark=mark)
    emit(ring_capacity_windows=replay.capacity, ring_windows=len(replay),
         window_steps=replay.seq_len, chain=chain,
         batch=cfg.replay.batch_size)
    for _ in range(max(traffic["warmup_steps"] - rec["driven_steps"], 0)
                   // chain * chain):
        stream.next(BIG)

    trace_dir = os.path.join(ctx.out_dir, "trace") if ctx.trace else ""
    tracer = TraceWindow(trace_dir, traffic["trace_start_step"],
                         traffic["trace_num_steps"])
    clock: CompileClock = ctx.clock
    compiles0 = clock.backend_compiles
    starts, losses, rows = [], [], []
    steps, in_next, row_next = 0, 0.0, 0.0
    row_every = traffic["row_every"]
    t_open = fence()
    setup_s = t_open - ctx.t_start
    emit(setup_marks_s=marks)
    compile_s_at_open = clock.compile_s
    deadline = t_open + ctx.seconds
    while True:
        t = time.perf_counter()
        if steps % chain == 0:
            if t >= deadline:
                break
            starts.append(t)
            tracer.on_step(steps)
        m = stream.next(BIG)
        dt = time.perf_counter() - t
        in_next += dt
        row_next += dt
        steps += 1
        if steps % chain == 0:
            losses.append(m["loss"])
        if steps % row_every == 0:
            # the step's counters stay on the device until the window has
            # closed: reading one here would hold the loop for its chunk
            rows.append({"step": steps, "t": time.perf_counter() - t_open,
                         "time_step_ms": 1e3 * row_next / row_every,
                         **{k: m[k] for k in counters}})
            row_next = 0.0
    tracer.close()
    t_close = fence()
    compiles_in_window = clock.backend_compiles - compiles0
    peak = memory_peak_bytes()
    failed = sum(1 for x in jax.device_get(losses)
                 if not math.isfinite(float(x)))
    if counters:
        for row in rows:
            row.update(check.log_row({k: float(row.pop(k))
                                      for k in counters}))
    emit(window_s=t_close - t_open, steps=steps, chunks=len(losses),
         loss_open=float(losses[0]), loss_close=float(losses[-1]),
         host_in_next_share=in_next / (t_close - t_open),
         last_row=rows[-1] if rows else None)
    scopes = None
    if ctx.trace and hasattr(check, "hlo_scope_tables"):
        t0 = time.perf_counter()
        scopes = check.hlo_scope_tables(solver, replay, chain)
        emit(hlo_scope_table_s=time.perf_counter() - t0,
             instructions_in_scopes={k: len(v) for k, v in scopes.items()})
    del stream, replay, solver          # the ring goes before the reference
    return dict(
        t_open=t_open, t_close=t_close, setup_s=setup_s, steps=steps,
        chunk_starts=starts, attempted=len(losses), failed=failed,
        rows=rows, memory_peak_bytes=peak,
        compiles_in_window=compiles_in_window,
        trace_dir=trace_dir or None,
        compile_s_at_open=compile_s_at_open,
        mirror=mirror, rec=rec, program_flops_per_step=None,
        hlo_scopes=scopes,
        traced_steps=(traffic["trace_start_step"],
                      traffic["trace_start_step"]
                      + traffic["trace_num_steps"]))
