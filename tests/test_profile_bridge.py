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
from distributed_deep_q_tpu.config import Config, NetConfig, ReplayConfig

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
def toy():
    """A real fused pair at toy size: the solver, its filled ring."""
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay
    from distributed_deep_q_tpu.solver import Solver

    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36))
    cfg.replay = ReplayConfig(capacity=512, batch_size=16, n_step=2,
                              prioritized=True, device_per=True,
                              write_chunk=16, fused_chain=CHAIN)
    solver = Solver(cfg)
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, (36, 36), stack=4,
                               gamma=0.99, seed=0, write_chunk=16)
    rng = np.random.default_rng(0)
    for i in range(300):
        dev.add(rng.integers(0, 255, (36, 36), dtype=np.uint8),
                int(rng.integers(4)), float(rng.standard_normal()),
                done=(i % 9 == 8))
    dev.flush()
    return solver, dev


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
    chunk = [("ddq/learner_chunk", 0)]
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
    for path in [*paths, os.path.join(ROOT, "bench.py")]:
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
    assert not named("lock_wait")                    # no lock was given
    for name in ("sample", "train_step", "learner_flush", "learner_feed",
                 "learner_adopt"):
        inside = [any(cs <= s and e <= ce for cs, ce in chunks)
                  for s, e in named(name)]
        assert inside and all(inside), name
    assert len(named("sample")) == len(named("train_step")) == 3
    # slices sit between the chunks, never inside one
    assert not any(cs < s < ce for s, _ in named("learner_slice")
                   for cs, ce in chunks)


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
