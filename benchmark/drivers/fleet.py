"""Traffic kind ``fleet``: the path users run.

The benchmark calls ``train_distributed(cfg, metrics=<its sink>)`` — what
``main.main(["train", ..., "--distributed"])`` calls, default ``log_every``
— with real CPU actor processes on the seeded signal env. Closed loop: each
actor sends its next flush when the last returned; the learner runs free.

The sink is a ``Metrics`` subclass: it timestamps every
``count("grad_steps")``, keeps every ``log`` row, opens the window on a
chunk boundary after ``warmup_steps`` (fenced), and when the window's
closing fence has been taken it raises ``StopRun`` out of ``count``;
``train_distributed``'s ``finally`` tears the fleet down as on Ctrl-C.

The solver and the ring that train are the ones the check drove first:
they are built here as ``train_distributed`` builds them, the solver given
the seed's weights, the ring filled to capacity with seeded rows (a
deployment's ring is full; the fleet alone would need 37 minutes for
that), driven through their first chunks, and handed to
``train_distributed`` by standing in for the ``Solver`` and
``DevicePERFrameReplay`` constructors it calls (the program has no
parameter for either). The actors then overwrite the oldest rows, as they
do in a deployment. A full ring is ready at once, so ``learn_start`` is
kept by the sink: it holds the learner at its first counted step until the
fleet has delivered that many rows of its own, and every actor is
streaming when the window opens.

Traffic parameters: ``num_actors``, ``learn_start``, ``warmup_steps``,
``prefill``, ``episode``, ``trace_start_step``, ``trace_num_steps``.
"""

from __future__ import annotations

import gc
import math
import multiprocessing
import os
import time

from benchmark import family, program
from benchmark.common import emit, fence, memory_peak_bytes

TOTAL_STEPS = 50_000_000        # far beyond any window


class StopRun(Exception):
    """The window has closed."""


def _make_sink(ctx, chain: int, warmup: int, fleet_rows):
    from distributed_deep_q_tpu.metrics import Metrics

    class Sink(Metrics):
        def __init__(self):
            super().__init__(None)
            self.t_open = self.t_close = None
            self.open_step = self.close_step = 0
            self.starts: list[float] = []
            self.rows: list[tuple[float, dict]] = []
            self.compiles0 = self.compiles1 = 0
            self.peak = 0

        def count(self, name: str, inc: int = 1) -> None:
            super().count(name, inc)
            if name != "grad_steps":
                return
            n = self._counters[name]
            if self.t_open is None:
                while n == 1 and fleet_rows() < ctx.traffic["learn_start"]:
                    time.sleep(0.05)    # learn_start, on a full ring
                if n >= warmup and n % chain == 0:
                    self.t_open = fence()
                    self.open_step = n
                    self.compiles0 = ctx.clock.backend_compiles
                    self.compile_s_at_open = ctx.clock.compile_s
                return
            k = n - self.open_step
            if k % chain == 1:      # first count after a chunk's dispatch
                self.starts.append(time.perf_counter())
            if k % chain == 0 and \
                    time.perf_counter() >= self.t_open + ctx.seconds:
                self.t_close = fence()
                self.close_step = n
                self.compiles1 = ctx.clock.backend_compiles
                self.peak = memory_peak_bytes()
                raise StopRun

        def log(self, step: int, **scalars) -> None:
            super().log(step, **scalars)
            self.rows.append((time.perf_counter(), {
                "step": int(step), **{k: v for k, v in scalars.items()
                                      if isinstance(v, (int, float))}}))

    return Sink()


def run(ctx) -> dict:
    conf, traffic = ctx.conf, ctx.traffic
    sets = [f"actors.num_actors={traffic['num_actors']}",
            f"replay.learn_start={traffic['learn_start']}",
            f"train.total_steps={TOTAL_STEPS}"]
    trace_dir = None
    if ctx.trace:
        trace_dir = os.path.join(ctx.out_dir, "trace")
        sets += [f"train.profile_dir={trace_dir}",
                 f"train.profile_start_step={traffic['trace_start_step']}",
                 f"train.profile_num_steps={traffic['trace_num_steps']}"]
    cfg = program.make_cfg(conf, ctx.seed, ctx.backend, sets)
    chain = cfg.replay.fused_chain
    marks: dict[str, float] = {}

    def mark(name: str) -> None:    # cumulative seconds since process start
        marks[name] = time.perf_counter() - ctx.t_start

    mark("imports_config")
    # the check's chunks, on the solver and the ring that will train
    solver, ring, stream, mirror, rec = family.load_check(
        conf).build_checked(conf, cfg, ctx.seed, traffic["prefill"],
                            traffic["episode"],
                            beta_steps=cfg.train.total_steps, mark=mark)
    rows0 = ring.steps_added
    emit(ring_capacity=ring.capacity, ring_rows_written=len(ring),
         streams=ring.num_streams, slot_cap=ring.slot_cap, chain=chain,
         batch=cfg.replay.batch_size, actors=cfg.actors.num_actors,
         learn_start=cfg.replay.learn_start)
    del stream

    import distributed_deep_q_tpu.replay.device_per as per_mod
    import distributed_deep_q_tpu.solver as solver_mod
    from distributed_deep_q_tpu.actors.supervisor import train_distributed

    real_solver, real_ring = solver_mod.Solver, per_mod.DevicePERFrameReplay

    class HandedRing(real_ring):
        """Stands where ``train_distributed`` constructs its ring and hands
        back the filled one, after seeing that it asked for the same."""

        def __new__(cls, rcfg, mesh, *a, write_chunk, num_streams, **kw):
            if (rcfg, write_chunk, num_streams) != (
                    ring._cfg, ring.write_chunk, ring.num_streams):
                raise SystemExit("train_distributed asked for another ring "
                                 "than the one the benchmark filled")
            return ring

        def __init__(self, *a, **kw):
            pass

    ring.__class__ = HandedRing
    sink = _make_sink(ctx, chain, traffic["warmup_steps"],
                      lambda: ring.steps_added - rows0)
    solver_mod.Solver = lambda *a, **kw: solver
    per_mod.DevicePERFrameReplay = HandedRing
    try:
        train_distributed(cfg, metrics=sink)
        raise SystemExit("train_distributed returned before the window "
                         "closed")
    except StopRun:
        pass
    finally:
        solver_mod.Solver = real_solver
        per_mod.DevicePERFrameReplay = real_ring
        ring.__class__ = real_ring
        for p in multiprocessing.active_children():
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
                p.join(timeout=10)
    marks["window_open"] = sink.t_open - ctx.t_start
    emit(setup_marks_s=marks, fleet_rows_in_run=ring.steps_added - rows0)
    del solver, ring
    gc.collect()

    rows = [dict(r, t=t - sink.t_open) for t, r in sink.rows
            if sink.t_open <= t <= sink.t_close]
    errs = ("rpc/dispatch_errors", "rpc/checksum_errors", "actor_restarts")
    grown = 0
    if len(rows) >= 2:
        grown = int(sum(rows[-1].get(k, 0) - rows[0].get(k, 0)
                        for k in errs))
    bad_rows = sum(1 for r in rows if not math.isfinite(r.get("loss", 0.0)))
    emit(window_s=sink.t_close - sink.t_open,
         steps=sink.close_step - sink.open_step, rows_in_window=len(rows),
         loss_open=rows[0].get("loss") if rows else None,
         loss_close=rows[-1].get("loss") if rows else None,
         errors_grown=grown,
         row_steps_per_s=[r.get("grad_steps_per_s") for r in rows],
         row_env_steps=[r.get("env_steps") for r in rows],
         row_t=[r["t"] for r in rows])
    return dict(
        t_open=sink.t_open, t_close=sink.t_close,
        setup_s=sink.t_open - ctx.t_start,
        steps=sink.close_step - sink.open_step, chunk_starts=sink.starts,
        attempted=(sink.close_step - sink.open_step) // chain,
        failed=bad_rows + grown, rows=rows, memory_peak_bytes=sink.peak,
        compiles_in_window=sink.compiles1 - sink.compiles0,
        trace_dir=trace_dir, mirror=mirror, rec=rec,
        compile_s_at_open=sink.compile_s_at_open,
        program_flops_per_step=None)
