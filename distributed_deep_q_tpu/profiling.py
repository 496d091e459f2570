"""Tracing / profiling subsystem (SURVEY.md §5.1).

The reference has nothing beyond Caffe layer timing and prints [R]; the
rebuild gets two first-class tools:

- ``StepTimer`` — cheap host-side wall-time breakdown of the train loop's
  phases (``sample`` / ``host_compose`` / ``dispatch`` / ``device``),
  accumulated per step and emitted through ``Metrics`` as
  ``time_<phase>_ms`` scalars, plus per-phase ``time_<phase>_p50_ms`` /
  ``time_<phase>_p99_ms`` percentiles from a streaming histogram — the
  mean hides the stall spikes (GC, lock contention, an actor flush
  landing mid-sample) that the p99 exists to expose. Dispatch is what the host pays to enqueue
  the XLA program (µs when the pipeline is healthy); ``device`` is measured
  by blocking on the step's outputs, so it's recorded only on logging
  steps — blocking every step would serialize the pipeline the timer
  exists to protect.

- ``TraceWindow`` — a ``jax.profiler`` trace capture over a step window
  (e.g. steps 100–120), plus ``start_profiler_server`` for live
  TensorBoard-connected profiling. Enabled with
  ``TrainConfig.profile_dir`` / ``profile_port``. While the window is
  open every ``tracing`` span of this process is also written into the
  capture as ``ddq/<name>`` on its thread's line, on the device
  operations' clock (``tracing.profile_start``).
"""

from __future__ import annotations

import contextlib
import json
import os
import re
import time
import weakref
from collections import defaultdict
from typing import Any, Callable, Iterator

import jax
import numpy as np

from distributed_deep_q_tpu import tracing
from distributed_deep_q_tpu.metrics import Histogram


# -- compiled-HLO op census (what tests/test_op_count.py pins) -------------

# NB: the param list may hold nested parens (tuple-typed while-body
# params), so the body is matched greedily; op-definition lines can't
# collide — they carry " = " and never end with "{".
_HLO_COMP_RE = re.compile(
    r"^\s*(?:ENTRY\s+)?(%[\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_HLO_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*[^=]*?\s([a-z][\w\-]*)\(")
_HLO_CALLS_RE = re.compile(
    r"(?:calls|to_apply|body|condition|true_computation|"
    r"false_computation)=(%[\w.\-]+)")
_HLO_CALLS_SET_RE = re.compile(
    r"(?:calls|called_computations|branch_computations)=\{([^}]*)\}")
# opcodes whose referenced computation runs INSIDE the one dispatched
# kernel (fused/applied elementwise) — its ops are not scheduled
_HLO_WRAPPER_OPS = frozenset({
    "fusion", "reduce", "reduce-window", "reduce-scatter", "all-reduce",
    "scatter", "select-and-scatter", "sort", "map", "reduce-precision",
})


def hlo_op_census(hlo_text: str,
                  ops: tuple[str, ...] = ("fusion", "convolution", "copy"),
                  ) -> dict[str, int]:
    """Count SCHEDULED ops in a compiled HLO module's text.

    "Scheduled" = ops the runtime dispatches: everything in the entry
    computation plus control-flow computations (while/conditional bodies
    and outlined ``call`` targets — their ops run when the loop/branch
    does), EXCLUDING the sub-computations that fusions and reducers
    merely wrap (their ops execute inside the one fused kernel). A
    ``calls=``/``to_apply=`` reference excludes its target only when the
    referencing op is a fusion/reduction-style wrapper — a ``call``'s
    target (XLA outlines scan bodies this way on CPU) stays counted.

    Returns ``{op: count for op in ops}`` plus ``"scheduled_total"``
    (all scheduled ops except parameter/constant declarations).
    """
    bodies, fused, _ = _parse_hlo_computations(hlo_text)
    counts = {op: 0 for op in ops}
    counts["scheduled_total"] = 0
    for name, opcodes in bodies.items():
        if name in fused:
            continue
        _count_into(counts, opcodes)
    return counts


def hlo_scan_body_census(
    hlo_text: str,
    ops: tuple[str, ...] = ("fusion", "convolution", "copy"),
) -> dict[str, int]:
    """``hlo_op_census`` of the LARGEST scheduled non-entry computation
    plus everything it reaches through call/while/conditional references
    — for a chained (``lax.scan``-over-grad-steps) train program that is
    the loop body, i.e. the ops run PER GRAD STEP (CPU XLA outlines e.g.
    each threaded convolution into its own ``call``-referenced
    computation, which executes per iteration and must count). Falls
    back to the whole-module census when no substantial non-entry
    computation exists (unchained programs)."""
    bodies, fused, refs = _parse_hlo_computations(hlo_text)
    best: str | None = None
    for name, opcodes in bodies.items():
        if name in fused or name.startswith("%ENTRY"):
            continue
        if best is None or len(opcodes) > len(bodies[best]):
            best = name
    counts = {op: 0 for op in ops}
    counts["scheduled_total"] = 0
    if best is None or len(bodies[best]) < 8:
        return hlo_op_census(hlo_text, ops)
    seen: set[str] = set()
    frontier = [best]
    while frontier:
        name = frontier.pop()
        if name in seen or name in fused or name not in bodies:
            continue
        seen.add(name)
        _count_into(counts, bodies[name])
        frontier.extend(refs.get(name, ()))
    return counts


def _parse_hlo_computations(hlo_text: str) -> tuple[
        dict[str, list[str]], set[str], dict[str, set[str]]]:
    """→ (ops per computation, fusion-wrapped computation names,
    call-style references per computation)."""
    bodies: dict[str, list[str]] = {}
    fused: set[str] = set()
    refs: dict[str, set[str]] = {}
    current: list[str] | None = None
    cur_name = ""
    for line in hlo_text.splitlines():
        comp = _HLO_COMP_RE.match(line)
        if comp and line.rstrip().endswith("{"):
            entry = line.lstrip().startswith("ENTRY")
            cur_name = ("%ENTRY" if entry else "") + comp.group(1)
            current = bodies.setdefault(cur_name, [])
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _HLO_OP_RE.match(line)
        if m is None or current is None:
            continue
        opcode = m.group(1)
        current.append(opcode)
        targets: list[str] = list(_HLO_CALLS_RE.findall(line))
        for group in _HLO_CALLS_SET_RE.findall(line):
            targets.extend(ref.strip() for ref in group.split(",")
                           if ref.strip().startswith("%"))
        if opcode in _HLO_WRAPPER_OPS:
            fused.update(targets)
        elif targets:
            refs.setdefault(cur_name, set()).update(targets)
    return bodies, fused, refs


def _count_into(counts: dict[str, int], opcodes: list[str]) -> None:
    for op in opcodes:
        if op not in ("parameter", "constant"):
            counts["scheduled_total"] += 1
        if op in counts:
            counts[op] += 1


# -- the scope table: which device operation belongs to which ddq.* name ----
#
# A device trace names every event by its HLO instruction (``%fusion.319``),
# and the numbering moves with every change to a program. The name a piece
# of work was TRACED under (``jax.named_scope("ddq.conv_in")``) is kept in
# the compiled module's text, in the instruction's ``op_name``; this turns
# that text into the table a reader needs to give a trace's device time to
# the program's own names. ``TraceWindow`` writes it beside every trace.

SCOPES_FILE = "ddq_scopes.json"
_SCOPE_RE = re.compile(r"ddq\.[a-z_]+")
_HLO_INSTR_RE = re.compile(
    r"^\s*(ROOT )?%(\S+) = (?:\(.*?\)|\S+) ([a-z][\w\-]*)\(", re.M)
_HLO_HEADER_RE = re.compile(_HLO_COMP_RE.pattern, re.M)
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_FUSION_CALLS_RE = re.compile(r"\bcalls=%([\w.\-]+)")
_HLO_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_HLO_MODULE_RE = re.compile(r"^HloModule ([\w.\-]+)")
# their device time is their bodies': a reader that summed them too would
# count every operation of a scan twice
_HLO_CONTAINERS = frozenset({"while", "conditional", "call"})
_HLO_NO_EVENT = _HLO_CONTAINERS | {"parameter", "constant"}


def _scope_stack(body: str) -> list[str] | None:
    """The ``ddq.*`` names of an instruction's ``op_name``, outermost
    first; ``None`` for an instruction the COMPILER made (no ``op_name``
    of the traced program: none at all, or an expander's own, such as
    ``reduce_window_sum``). Transform wrappers
    (``transpose(jvp(ddq.conv_in))``) keep the name inside them; a name
    nested in itself is given once."""
    m = _OP_NAME_RE.search(body)
    if m is None or not m.group(1).startswith(("jit(", "pjit(")):
        return None
    stack: list[str] = []
    for name in _SCOPE_RE.findall(m.group(1)):
        if not stack or stack[-1] != name:
            stack.append(name)
    return stack


def scope_table(hlo_text: str) -> dict[str, dict]:
    """From a compiled module's text::

        {"scopes": {instruction: [ddq.* scopes, outermost first]},
         "mixed": {fusion: [the innermost scopes of its fused instructions]},
         "inherited": {instruction: the neighbour its scopes are from}}

    An instruction's text runs to the next one's (a Mosaic call's
    metadata spans lines). A ``fusion`` carries one event for everything
    it fused: it takes its own ``op_name`` (XLA copies the root's), where
    that has no scope the scopes of the last scoped instruction of the
    computation it ``calls``; and where its fused instructions lie under
    MORE than one innermost scope it is listed under ``mixed`` too, so a
    reader can say how much time sits in fusions that no single name
    owns. An instruction the compiler made (a relayout ``copy``, the
    ``reduce-window`` passes a ``cumsum`` expands to, an asynchronous
    ``slice-start``) has no name stack of its own: it takes the scopes of
    its first operand that has any, else of its first user's, and is
    listed under ``inherited``. What the program traced under no
    ``ddq.*`` scope stays out (the window gather's Mosaic call), as do
    containers (``while`` / ``conditional`` / ``call``), parameters and
    constants: none of them is a device event of its own."""
    starts = list(_HLO_INSTR_RE.finditer(hlo_text))
    headers = [(m.start(), m.group(1).lstrip("%"))
               for m in _HLO_HEADER_RE.finditer(hlo_text)]
    headers.append((len(hlo_text), ""))
    stacks: dict[str, list[str] | None] = {}
    operands: dict[str, list[str]] = {}
    members: dict[str, list[str]] = {}     # computation -> its instructions
    fusions: dict[str, str] = {}           # fusion -> computation it calls
    no_event: set[str] = set()
    comp, h = "", 0
    for m, nxt in zip(starts, starts[1:] + [None]):
        while headers[h][0] < m.start():    # a computation opened above
            comp = headers[h][1]
            h += 1
        end = nxt.start() if nxt else len(hlo_text)
        body = hlo_text[m.end():min(end, headers[h][0])]
        name, opcode = m.group(2), m.group(3)
        members.setdefault(comp, []).append(name)
        operands[name] = _HLO_OPERAND_RE.findall(body[:body.find(")") + 1])
        stacks[name] = _scope_stack(body)
        if opcode in _HLO_NO_EVENT:
            no_event.add(name)      # a neighbour to inherit from, no more
        elif opcode == "fusion":
            called = _FUSION_CALLS_RE.search(body)
            if called:
                fusions[name] = called.group(1)
    mixed: dict[str, list[str]] = {}
    for name, called in fusions.items():
        inside = [stacks[i] for i in members.get(called, ())
                  if stacks.get(i)]
        if not stacks[name] and inside:
            stacks[name] = inside[-1]
        innermost = sorted({st[-1] for st in inside})
        if len(innermost) > 1:
            mixed[name] = innermost
    inherited: dict[str, str] = {}
    for names in members.values():
        users: dict[str, list[str]] = {}
        for name in names:                  # operands come first in a text
            for op in operands[name]:
                users.setdefault(op, []).append(name)
            _inherit(name, operands[name], stacks, inherited)
        for name in reversed(names):
            _inherit(name, users.get(name, ()), stacks, inherited)
    return {"scopes": {k: v for k, v in stacks.items()
                       if v and k not in no_event},
            "mixed": mixed,
            "inherited": {k: v for k, v in inherited.items()
                          if k not in no_event}}


def _inherit(name: str, neighbours, stacks: dict, inherited: dict) -> None:
    if name in stacks and stacks[name] is None:
        for other in neighbours:
            if stacks.get(other):
                stacks[name], inherited[name] = stacks[other], other
                return


def hlo_module_name(hlo_text: str) -> str:
    """``jit_tree_train_fn``: the name a trace's ``XLA Modules`` line
    gives the program's executions."""
    m = _HLO_MODULE_RE.match(hlo_text)
    return m.group(1) if m else "unknown"


# Whoever owns device programs says so once, where it is built — never on
# a step's path: ``owner`` is kept weakly, and ``source(owner)`` answers
# ``{label: executable}`` for the programs it runs, without executing or
# compiling any (``ran_executable``). ``source`` must not hold the owner.
_PROGRAM_SOURCES: "weakref.WeakKeyDictionary[Any, Callable[[Any], dict]]" \
    = weakref.WeakKeyDictionary()


def register_programs(owner: Any, source: Callable[[Any], dict]) -> None:
    _PROGRAM_SOURCES[owner] = source


def ran_executable(jitted, *args):
    """The executable ``jitted`` already holds for these arguments (arrays,
    or avals with the shardings the call had): lowering finds the traced
    program, ``compile`` the executable of the call that RAN — nothing is
    compiled again and nothing executes. Arguments the program never met
    are lowered and compiled like any first call."""
    return jitted.lower(*args).compile()


def outputs_as_avals(compiled):
    """A program's outputs as the next program's arguments: shapes with
    the shardings the executable gives them."""
    return jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        compiled.out_info, compiled.output_shardings)


def write_scope_tables(logdir: str) -> str:
    """``<logdir>/ddq_scopes.json``: the scope table of every program the
    registered loops run, by module name; a program no table could be
    made for is named under ``unavailable`` with the reason."""
    t0 = time.perf_counter()
    programs: dict[str, Any] = {}
    unavailable: dict[str, str] = {}
    sources = list(_PROGRAM_SOURCES.items())
    for owner, source in sources:
        try:
            for exe in source(owner).values():
                text = exe.as_text()
                programs[hlo_module_name(text)] = scope_table(text)
        except Exception as e:  # noqa: BLE001 — the reason is the record
            unavailable[source.__qualname__] = f"{type(e).__name__}: {e}"
    if not sources:
        unavailable["*"] = ("no loop registered its programs "
                            "(profiling.register_programs)")
    path = os.path.join(logdir, SCOPES_FILE)
    os.makedirs(logdir, exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"programs": programs, "unavailable": unavailable,
                   "scope_table_s": time.perf_counter() - t0}, fh)
    return path


class StepTimer:
    """Accumulates per-phase wall time across train-loop steps.

    Usage::

        with timer.phase("sample"):
            batch = replay.sample(n)
        with timer.phase("dispatch"):
            m = solver.train_step(batch)
        ...
        timer.step_done()
        if logging:
            metrics.log(step, **timer.summary())

    ``summary()`` returns mean milliseconds per phase since the last call
    (keys ``time_<phase>_ms``) plus ``time_step_ms`` (mean wall time per
    step, measured step_done→step_done, covering phases AND everything
    between them).
    """

    def __init__(self) -> None:
        self._acc: dict[str, float] = defaultdict(float)
        self._hists: dict[str, Histogram] = {}
        self._steps = 0
        self._last_step_t: float | None = None
        self._step_total = 0.0

    @contextlib.contextmanager
    def phase(self, name: str) -> Iterator[None]:
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._acc[name] += dt
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = Histogram()
            h.observe(1e3 * dt)

    def measure_device(self, outputs) -> None:
        """Block until ``outputs`` (the step's device results) are done and
        attribute the wait to the ``device`` phase. Call on logging steps
        only — this synchronizes the pipeline."""
        with self.phase("device"):
            jax.block_until_ready(outputs)

    def step_done(self) -> None:
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_total += now - self._last_step_t
        self._last_step_t = now
        self._steps += 1

    def summary(self, reset: bool = True) -> dict[str, float]:
        n = max(self._steps, 1)
        out = {f"time_{k}_ms": 1e3 * v / n for k, v in self._acc.items()}
        # device is measured once per summary window, not per step
        if "time_device_ms" in out:
            out["time_device_ms"] = 1e3 * self._acc["device"]
        if self._steps > 1:
            out["time_step_ms"] = 1e3 * self._step_total / (self._steps - 1)
        for name, h in self._hists.items():
            if h.count:
                out[f"time_{name}_p50_ms"] = h.percentile(0.50)
                out[f"time_{name}_p99_ms"] = h.percentile(0.99)
        if reset:
            self._acc.clear()
            self._hists.clear()
            self._steps = 0
            self._step_total = 0.0
            # drop the carried timestamp too: each window then averages
            # exactly (steps−1) intra-window intervals over (steps−1),
            # keeping windows mutually consistent
            self._last_step_t = None
        return out


# -- flops-per-step census (feeds the live ``train/mfu`` gauge) ------------

# bf16 peak FLOP/s by device_kind prefix (public spec sheets)
PEAK_FLOPS = {
    "TPU v6 lite": 918e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,      # v5p
    "TPU v4": 275e12,
    "TPU v3": 123e12,      # per chip (2 cores)
}


def peak_flops_for(device=None, backend: str = "cpu") -> float | None:
    """Spec-sheet bf16 peak for ``device`` (default: the first local
    device). On behalf of ``backend="tpu"`` a device that is not in the
    table is an ERROR — a utilization divided by a guessed or missing peak
    must not be reported from the chip path. On the CPU test path a
    device with no published peak gives None: MFU is then absent rather
    than invented."""
    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    for prefix, peak in sorted(PEAK_FLOPS.items(),
                               key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return peak
    if backend == "tpu":
        raise ValueError(
            f"no published peak FLOP/s for device_kind {kind!r} — add it "
            "to profiling.PEAK_FLOPS with its source")
    return None


def _cost_flops(compiled) -> float | None:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    return flops if flops > 0 else None


def fused_executables(solver, replay, chain: int) -> dict[str, Any]:
    """The fused step's ``sample`` and ``train`` executables for
    ``replay``'s geometry, from avals alone: no device sample execution,
    no sampling-key-stream side effect. The arguments have the types and
    shardings the train loop's have, so where the loop has run these ARE
    its executables (``ran_executable``); before it the pair is built and
    compiled."""
    sample, train = solver.learner.device_per_programs(
        solver.device_per_spec(replay), chain)
    cursors, sizes = replay.device_inputs()
    betas = np.full(chain, 0.5, np.float32)
    keys = np.zeros((replay.num_shards, chain, 2), np.uint32)
    rows = replay.dstate
    sampled = ran_executable(
        sample, keys, rows.frames, rows.action, rows.reward, rows.done,
        rows.boundary, rows.prio, np.asarray(cursors), np.asarray(sizes),
        betas)
    metas, win, idx = outputs_as_avals(sampled)
    return {"sample": sampled, "train": ran_executable(
        train, solver.state, metas, win, idx, rows.prio, rows.maxp)}


def compile_fused_train(solver, replay, chain: int):
    """The fused TRAIN program's executable — the one artifact the flops
    census, the op census and the chip smoke's all-reduce check read."""
    return fused_executables(solver, replay, chain)["train"]


def fused_train_flops(solver, replay, chain: int) -> float | None:
    """Per-grad-step FLOPs of the FUSED train program — the same program
    the MFU denominator times. XLA's cost model counts a ``lax.scan`` body
    ONCE (verified against the analytic count: the batch-512 chained
    program reports ~44.8 GF regardless of chain), so the figure is
    already per-step. Fails loudly on ``backend="tpu"``; the CPU test
    path answers None when the census cannot be taken."""
    try:
        return _cost_flops(compile_fused_train(solver, replay, chain))
    except Exception:
        if solver.backend == "tpu":
            raise
        return None


class MFUMeter:
    """Live model-FLOPs-utilization gauge (health plane, ISSUE 13).

    MFU = flops-per-step (from the compiled fused train program's cost
    analysis, ``fused_train_flops``) × measured steps/s ÷ the device's
    peak. The learner calls ``update(gstep)`` on its logging cadence,
    the meter converts the grad-step delta over the wall-clock window
    into steps/s and emits ``train/steps_per_s`` + ``train/mfu`` in the
    health tick (and, fed the flow plane's rates,
    ``train/ingest_utilization`` — the fraction of ingested rows the
    learner actually consumes). ``peak_flops`` is None on devices with
    no published peak (CPU containers): MFU is then simply absent from
    the gauges rather than a made-up number.
    """

    def __init__(self, flops_per_step: float | None,
                 peak_flops: float | None):
        self.flops_per_step = (float(flops_per_step)
                               if flops_per_step else None)
        self.peak_flops = float(peak_flops) if peak_flops else None
        self._last_t: float | None = None
        self._last_step = 0

    def update(self, gstep: int, t: float | None = None,
               ingest_rate: float | None = None,
               consume_rate: float | None = None) -> dict[str, float]:
        """One window: gauges for the steps/s since the previous call
        (empty on the first call — no window yet)."""
        if t is None:
            t = time.monotonic()
        if self._last_t is None:
            self._last_t, self._last_step = t, int(gstep)
            return {}
        dt = max(t - self._last_t, 1e-9)
        rate = max(int(gstep) - self._last_step, 0) / dt
        self._last_t, self._last_step = t, int(gstep)
        out = {"train/steps_per_s": round(rate, 3)}
        if self.flops_per_step and self.peak_flops:
            out["train/mfu"] = round(
                self.flops_per_step * rate / self.peak_flops, 4)
        if ingest_rate is not None and consume_rate is not None:
            util = (min(consume_rate / ingest_rate, 1.0)
                    if ingest_rate > 1e-9 else 0.0)
            out["train/ingest_utilization"] = round(util, 4)
        return out


class TraceWindow:
    """Capture a ``jax.profiler`` trace over a contiguous step window.

    ``on_step(step)`` is called once per train-loop step; the trace starts
    when ``step == start_step`` and stops after ``num_steps`` steps (or at
    ``close()``) — and, where the caller gives ``min_seconds``, not before
    that much wall time: a loop with threads around it (the RPC plane of
    ``train_distributed``) works in periods a fast learner's step count
    no longer spans. Output is a TensorBoard-loadable trace directory.

    The capture runs WITHOUT the Python tracer: the program's own spans
    (``ddq/<name>``) name what each thread is doing, and a hook on every
    Python call slows exactly the Python-heavy threads being measured
    (PERF.md §6, PR 24). Host-side runtime events (TraceMe) stay on.
    """

    def __init__(self, logdir: str, start_step: int = 100,
                 num_steps: int = 20, min_seconds: float = 0.0):
        self.logdir = logdir
        self.start_step = int(start_step)
        self.num_steps = int(num_steps)
        self.min_seconds = float(min_seconds)
        self._active = False
        self._done = False

    def on_step(self, step: int) -> None:
        if self._done or not self.logdir:
            return
        if not self._active and step >= self.start_step:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.logdir, profiler_options=options)
            tracing.profile_start(jax.profiler.TraceAnnotation)
            self._active = True
            self._stop_at = step + self.num_steps
            self._not_before = time.perf_counter() + self.min_seconds
        elif (self._active and step >= self._stop_at
              and time.perf_counter() >= self._not_before):
            self.stop()

    def stop(self) -> None:
        if self._active:
            # the loop runs ahead of the device (``FusedStepStream``: up
            # to two chunks): let the device finish what the window's
            # steps dispatched, or the trace cuts its last program short
            # and every per-execution device time read from it is low
            _drain_device()
            tracing.profile_stop()
            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            # after the capture, so outside the traced span: which device
            # operation of the trace belongs to which ddq.* name
            write_scope_tables(self.logdir)

    close = stop


def _drain_device() -> None:
    """Wait for every device array this process holds. Needs no handle
    into the train loop and compiles nothing; an array an ingest thread
    donates between the listing and the wait is skipped."""
    for a in jax.live_arrays():
        try:
            if not a.is_deleted():
                a.block_until_ready()
        except RuntimeError:        # donated between the two lines
            pass


def start_profiler_server(port: int) -> None:
    """Live profiling endpoint (TensorBoard "capture profile" target)."""
    jax.profiler.start_server(int(port))
