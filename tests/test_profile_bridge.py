"""The tracer's bridge into the profiler's trace (ISSUE 24): while a
``TraceWindow`` captures, the program's spans land in the ``.xplane.pb``
as ``ddq/<name>`` on their thread's line; the two sinks (rings, trace) are
independent; the learner loop's spans nest as the benchmark's readers
expect; the programs carry their ``ddq.*`` scopes."""

import contextlib
import glob
import os
import re
import sys
import threading

import numpy as np
import pytest

from distributed_deep_q_tpu import tracing

pytestmark = [pytest.mark.tracing]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHAIN = 2


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.reset()
    yield
    tracing.disable()
    tracing.profile_stop()
    tracing.reset()


class _Recorder:
    """Stands where ``jax.profiler.TraceAnnotation`` does: the names of
    the annotations opened, in order, and the depth each opened at."""

    def __init__(self):
        self.opened: list[tuple[str, int]] = []
        self._depth = threading.local()

    @contextlib.contextmanager
    def __call__(self, name: str):
        d = getattr(self._depth, "d", 0)
        self.opened.append((name, d))
        self._depth.d = d + 1
        try:
            yield
        finally:
            self._depth.d = d

    def names(self) -> list[str]:
        return [n for n, _ in self.opened]


class _StubSolver:
    """``FusedStepStream`` needs only this of a solver."""

    def train_steps_device_per(self, replay, chain):
        with tracing.span("sample"):
            pass
        with tracing.span("train_step"):
            pass
        return {"loss": np.arange(chain, dtype=np.float32)}


@pytest.fixture(scope="module")
def toy(toy_fused_pair):
    """A real fused pair at toy size: the solver, its filled ring."""
    return toy_fused_pair(CHAIN)


# -- the two flags ----------------------------------------------------------
def test_both_flags_off_is_the_null_path():
    assert not tracing.ENABLED and not tracing.PROFILING
    lock = threading.Lock()
    assert tracing.span("learner_chunk") is tracing._NULL
    assert tracing.span_sampled("env_step") is tracing._NULL
    assert tracing.locked(lock) is lock
    assert tracing.instant("shed") is None


def test_profiling_alone_annotates_and_records_nothing():
    rec = _Recorder()
    tracing.profile_start(rec)
    with tracing.span("rpc_handle"):
        with tracing.locked(threading.Lock()):
            with tracing.span("ring_insert"):
                tracing.instant("shed")
    tracing.profile_stop()
    assert rec.opened == [("ddq/rpc_handle", 0), ("ddq/lock_wait", 1),
                          ("ddq/lock_hold", 1), ("ddq/ring_insert", 2),
                          ("ddq/shed", 3)]
    assert tracing.drain() == []
    assert tracing.wire_context() == {}      # the ring plane stays off
    assert tracing.span("sample") is tracing._NULL      # and so does this


def test_enabled_alone_writes_no_annotation():
    rec = _Recorder()
    tracing.profile_start(rec)
    tracing.profile_stop()
    tracing.configure(enabled=True, sample_rate=1.0)
    with tracing.span("learner_slice"):
        pass
    assert rec.opened == []
    assert [e["name"] for e in tracing.drain()] == ["learner_slice"]


def test_both_on_feed_both_sinks_and_a_flip_keeps_the_exit_balanced():
    rec = _Recorder()
    tracing.configure(enabled=True, sample_rate=1.0)
    tracing.profile_start(rec)
    with tracing.span("learner_chunk"):
        tracing.profile_stop()          # TraceWindow.stop, mid-span
        with tracing.span("sample"):
            pass
    assert rec.names() == ["ddq/learner_chunk"]
    assert sorted(e["name"] for e in tracing.drain()) == [
        "learner_chunk", "sample"]


# -- the learner loop's spans ----------------------------------------------
@pytest.mark.parametrize("lock", [None, threading.RLock()],
                         ids=["learner_only", "with_replay_lock"])
def test_fused_stream_spans_and_the_lock(lock):
    from distributed_deep_q_tpu.solver import FusedStepStream

    rec = _Recorder()
    stream = FusedStepStream(_StubSolver(), object(), chain=3,
                             dispatch_lock=lock)
    tracing.profile_start(rec)
    for left in range(6, 0, -1):
        stream.next(left)
    tracing.profile_stop()
    # the run-ahead wait comes first: before the chunk's span, the lock
    # and its spans (ISSUE 28)
    chunk = [("ddq/learner_wait", 0), ("ddq/learner_chunk", 0)]
    if lock is not None:
        chunk += [("ddq/lock_wait", 1), ("ddq/lock_hold", 1)]
    inner = 1 if lock is None else 2
    chunk += [("ddq/sample", inner), ("ddq/train_step", inner)]
    assert rec.opened == 2 * (chunk + 3 * [("ddq/learner_slice", 0)])
    if lock is None:
        assert "ddq/lock_wait" not in rec.names()


def test_every_stage_has_a_call_site_and_every_site_a_stage():
    """A name in ``STAGES`` that nothing opens is dead weight in the
    closed table; ``lock_wait`` / ``lock_hold`` are opened by ``locked``."""
    from distributed_deep_q_tpu.analysis import metric_keys

    assert metric_keys.check(ROOT) == []
    rx = re.compile(r"tracing\.span(?:_sampled)?\(\s*\"(\w+)\"")
    used = {"lock_wait", "lock_hold"}
    paths = glob.glob(os.path.join(ROOT, "distributed_deep_q_tpu", "**",
                                   "*.py"), recursive=True)
    for path in paths:
        with open(path) as fh:
            used.update(rx.findall(fh.read()))
    assert used == set(tracing.STAGES)


# -- the real thing: a TraceWindow over a tiny fused loop ------------------
def _span_lines(logdir):
    """Through the reader the benchmark's metrics use."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark.readers.host_span_time import load_lines

    paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    return load_lines(paths[0])


def test_trace_window_puts_the_loop_spans_on_the_device_clock(toy, tmp_path):
    from distributed_deep_q_tpu.profiling import TraceWindow
    from distributed_deep_q_tpu.solver import FusedStepStream

    solver, dev = toy
    stream = FusedStepStream(solver, dev, CHAIN)
    stream.next(10 ** 6), stream.next(10 ** 6)      # compile outside
    steps = 3 * CHAIN
    trace = TraceWindow(str(tmp_path / "t"), start_step=0, num_steps=steps)
    for step in range(steps + 1):
        trace.on_step(step)         # starts at 0, stops itself at `steps`
        if step < steps:
            stream.next(10 ** 6)
    assert trace._done and not tracing.PROFILING
    assert tracing.drain() == []    # PROFILING alone: nothing in the rings

    lines = _span_lines(str(tmp_path / "t"))
    assert len(lines) == 1          # one thread ran the loop
    evs = lines[0]

    def named(name):
        return [(s, e) for n, s, e in evs if n == name]

    chunks = named("learner_chunk")
    assert len(chunks) == 3
    assert len(named("learner_slice")) == steps      # one a step
    assert len(named("learner_wait")) == 3           # one a chunk
    assert not named("lock_wait")                    # no lock was given
    for name in ("sample", "train_step", "learner_flush", "learner_feed",
                 "learner_adopt"):
        inside = [any(cs <= s and e <= ce for cs, ce in chunks)
                  for s, e in named(name)]
        assert inside and all(inside), name
    assert len(named("sample")) == len(named("train_step")) == 3
    # slices and the run-ahead wait sit between the chunks, never inside
    assert not any(cs < s < ce
                   for s, _ in named("learner_slice") + named("learner_wait")
                   for cs, ce in chunks)


@pytest.fixture
def profiler_calls(monkeypatch):
    """``jax.profiler``'s start and stop replaced by a log of the calls."""
    import jax

    calls: list[str] = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    return calls


def test_trace_window_stop_waits_for_the_device_first(
        monkeypatch, tmp_path, profiler_calls):
    """The loop runs up to two chunks ahead of the device (ISSUE 28): a
    window that stopped the trace at once would cut its last program."""
    import jax

    from distributed_deep_q_tpu.profiling import TraceWindow

    order = profiler_calls

    class _Held:
        def __init__(self, deleted=False, donated=False):
            self._deleted, self._donated = deleted, donated

        def is_deleted(self):
            return self._deleted

        def block_until_ready(self):
            if self._donated:
                raise RuntimeError("Array has been deleted.")
            order.append("waited")

    monkeypatch.setattr(jax, "live_arrays", lambda: [
        _Held(), _Held(deleted=True), _Held(donated=True), _Held()])
    trace = TraceWindow(str(tmp_path), start_step=0, num_steps=2)
    trace.on_step(0), trace.on_step(1)
    assert order == ["start"]
    trace.on_step(2)
    assert order == ["start", "waited", "waited", "stop"]
    assert trace._done and not tracing.PROFILING


def test_trace_window_min_seconds_holds_the_stop_back(
        monkeypatch, tmp_path, profiler_calls):
    """``train_distributed`` asks for a least wall time: the step count
    alone no longer spans the RPC plane's bursts (ISSUE 28)."""
    from distributed_deep_q_tpu import profiling

    calls, now = profiler_calls, [100.0]
    monkeypatch.setattr(profiling.time, "perf_counter", lambda: now[0])
    trace = profiling.TraceWindow(str(tmp_path), start_step=0, num_steps=2,
                                  min_seconds=1.0)
    trace.on_step(0)
    for step in (1, 2, 3):          # the steps are over at 2, the time not
        now[0] += 0.3
        trace.on_step(step)
    assert calls == ["start"]
    now[0] += 0.3                   # 1.2 s after the start
    trace.on_step(4)
    assert calls == ["start", "stop"] and trace._done


# -- jax.named_scope on the four program bodies ----------------------------
def _lowered(fn, seen, key):
    def spy(*args):
        seen[key] = fn.lower(*args).as_text(debug_info=True)
        return fn(*args)
    return spy


def test_programs_carry_their_scopes(toy):
    from distributed_deep_q_tpu.models.policy import BatchedPolicy

    solver, dev = toy
    seen: dict[str, str] = {}
    key = (solver.device_per_spec(dev), CHAIN)
    sample, train = solver.learner.device_per_programs(*key)
    solver.learner._device_per_steps[key] = (
        _lowered(sample, seen, "sample"), _lowered(train, seen, "train"))
    write = dev._write_full
    dev._write_full = _lowered(write, seen, "write")
    try:
        for i in range(16):
            dev.add(np.full((36, 36), i, np.uint8), 1, 0.5, done=False)
        dev.flush()
        solver.train_steps_device_per(dev, chain=CHAIN)
    finally:
        solver.learner._device_per_steps[key] = (sample, train)
        dev._write_full = write
    assert "ddq.sample" in seen["sample"]
    assert "ddq.train" in seen["train"]
    assert "ddq.write" in seen["write"]
    assert "ddq.scatter_rows" in seen["write"]
    # the names the benchmark's patterns read are the functions' own
    for k, name in (("sample", "sample_fn"), ("write", "write"),
                    ("train", "train_fn")):
        assert re.search(rf"module @jit_\w*{name}", seen[k]), k

    policy = BatchedPolicy(solver.config.net, obs_dim=4)
    text = policy._fwd.lower(
        policy.params, np.zeros((8, 36, 36, 4), np.uint8)
    ).as_text(debug_info=True)
    assert "ddq.infer" in text
