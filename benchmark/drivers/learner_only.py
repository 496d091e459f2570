"""Traffic kind ``learner_only``: the learner catching up on a full buffer.

Fill the ring to capacity with seeded rows, then call
``FusedStepStream(solver, replay, chain).next(...)`` per grad step, as both
of the program's train loops do — no actors, no server, no lock. Closed
loop: the next chunk is dispatched when the host gets to it.

Traffic parameters (``traffic/<name>.json``): ``prefill`` (a row count or
``"capacity"``), ``overrides`` (settings of the program that belong to the
traffic: nothing is written inside this window, so the fill may use a
wider write program than the fleet's 64 rows), ``episode`` length,
``warmup_steps``, ``row_every`` (steps per synthetic log row),
``trace_start_step`` / ``trace_num_steps`` (traced runs).
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
    solver, replay, stream, mirror, rec = family.load_check(
        conf).build_checked(conf, cfg, ctx.seed, traffic["prefill"],
                            traffic["episode"], mark=mark)
    emit(ring_capacity=replay.capacity, ring_rows_written=len(replay),
         streams=replay.num_streams, slot_cap=replay.slot_cap,
         chain=chain, batch=cfg.replay.batch_size)
    for _ in range(max(traffic["warmup_steps"] - rec["driven_steps"], 0)
                   // chain * chain):
        stream.next(BIG)

    # the program's own trace helper, as train_distributed drives it
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
            rows.append({"step": steps, "t": time.perf_counter() - t_open,
                         "time_step_ms": 1e3 * row_next / row_every})
            row_next = 0.0
    tracer.close()
    t_close = fence()
    compiles_in_window = clock.backend_compiles - compiles0
    peak = memory_peak_bytes()
    failed = sum(1 for x in jax.device_get(losses)
                 if not math.isfinite(float(x)))
    emit(window_s=t_close - t_open, steps=steps, chunks=len(losses),
         loss_open=float(losses[0]), loss_close=float(losses[-1]),
         host_in_next_share=in_next / (t_close - t_open))
    census = (program.program_flops_per_step(solver, replay, chain)
              if ctx.trace else None)
    del stream, replay, solver          # the ring goes before the reference
    return dict(
        t_open=t_open, t_close=t_close, setup_s=setup_s, steps=steps,
        chunk_starts=starts, attempted=len(losses), failed=failed,
        rows=rows, memory_peak_bytes=peak,
        compiles_in_window=compiles_in_window,
        trace_dir=trace_dir or None,
        compile_s_at_open=compile_s_at_open,
        mirror=mirror, rec=rec, program_flops_per_step=census)
