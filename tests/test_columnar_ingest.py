"""Columnar ingest plane (ISSUE 8): staging equivalence + drain + lock shape.

Pins, in order of load-bearing-ness:

- The columnar staging path (``ColumnStage`` + device-side
  ``insert_meta_pack``) produces BIT-IDENTICAL ring state to the legacy
  per-flush FIFO it replaced, for both device replay tiers. This is the
  invariant that lets ``staging_columnar`` default on while the legacy
  path stays the semantic reference.
- The native ``staged_append`` memcpy and the numpy slice-assign
  fallback agree byte-for-byte across growth and partial FIFO takes.
- ``IngestDrain`` moves flushes off the writer thread and strands no
  rows on shutdown.
- ``_add_transitions`` keeps request parsing OUTSIDE the replay lock and
  ring mutation INSIDE it (ISSUE 8 satellite: shrunken hold).
"""

import threading
import time

import numpy as np
import pytest

from distributed_deep_q_tpu import tracing
from distributed_deep_q_tpu.config import MeshConfig, ReplayConfig
from distributed_deep_q_tpu.parallel.mesh import make_mesh
from distributed_deep_q_tpu.replay.columnar import ColumnStage


def _stream(replay, n_steps, episode_len=13, seed=0, frame_shape=(8, 8)):
    """Same transition stream as test_device_per: episode cuts plus
    truncation-only boundaries every 29 steps."""
    rng = np.random.default_rng(seed)
    t = 0
    for i in range(n_steps):
        frame = rng.integers(0, 255, frame_shape, dtype=np.uint8)
        a, r = int(rng.integers(0, 4)), float(rng.standard_normal())
        t += 1
        done = t % episode_len == 0
        trunc = (not done) and (t % 29 == 0)
        replay.add(frame, a, r, done, boundary=done or trunc)
        if done or trunc:
            t = 0


# -- ColumnStage: native == numpy reference ---------------------------------
def test_column_stage_native_matches_numpy():
    """Random-size appends (forcing growth) interleaved with random
    partial takes: the C memcpy path and the numpy fallback must hold
    identical buffers, cursors, and drained planes throughout."""
    cols = [((), np.int32), ((17,), np.uint8), ((), np.float32)]
    a = ColumnStage(cols, depth=8, use_native=True)
    b = ColumnStage(cols, depth=8, use_native=False)
    if a._lib is None:
        pytest.skip("native replay_core unavailable")
    rng = np.random.default_rng(7)
    for _ in range(37):
        n = int(rng.integers(1, 50))
        seg = (rng.integers(0, 2 ** 31 - 1, n).astype(np.int32),
               rng.integers(0, 255, (n, 17), dtype=np.uint8),
               rng.standard_normal(n).astype(np.float32))
        a.append(*seg)
        b.append(*seg)
        assert len(a) == len(b)
        if rng.random() < 0.4 and len(a):
            k = int(rng.integers(1, len(a) + 1))
            outs_a = [np.zeros((1, k) + tail, dt) for tail, dt in cols]
            outs_b = [np.zeros((1, k) + tail, dt) for tail, dt in cols]
            assert a.take(k, outs_a, 0) == b.take(k, outs_b, 0)
            for oa, ob in zip(outs_a, outs_b):
                np.testing.assert_array_equal(oa, ob)
    # drain everything and compare the final planes too
    k = len(a)
    outs_a = [np.zeros((1, k) + tail, dt) for tail, dt in cols]
    outs_b = [np.zeros((1, k) + tail, dt) for tail, dt in cols]
    assert a.take(k, outs_a, 0) == b.take(k, outs_b, 0) == k
    for oa, ob in zip(outs_a, outs_b):
        np.testing.assert_array_equal(oa, ob)
    assert len(a) == len(b) == 0


# -- columnar staging ≡ legacy FIFO, both replay tiers ----------------------
def _pair(cls, cfg_kw, mesh, **kw):
    out = []
    for columnar in (True, False):
        cfg = ReplayConfig(staging_columnar=columnar, **cfg_kw)
        out.append(cls(cfg, mesh, (8, 8), stack=4, gamma=0.99, seed=0,
                       write_chunk=16, **kw))
    return out


def test_device_per_columnar_bitwise_equals_legacy():
    """DevicePERFrameReplay: raw-u8 columnar staging + jit'd
    ``insert_meta_pack`` (pad→bitcast→priority-seed on device) must
    reproduce the legacy host-padded path's DeviceReplayState exactly —
    every frame byte, every metadata lane, every seeded priority."""
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    col, ref = _pair(
        DevicePERFrameReplay,
        dict(capacity=512, batch_size=32, n_step=3, prioritized=True,
             device_per=True, write_chunk=16),
        mesh, num_streams=2)
    assert col._columnar and not ref._columnar
    for r in (col, ref):
        _stream(r, 300)
        r.flush()
    assert col.pending_rows() == ref.pending_rows() == 0
    for field in ("frames", "action", "reward", "done", "boundary",
                  "prio", "maxp"):
        np.testing.assert_array_equal(
            np.asarray(getattr(col.dstate, field)),
            np.asarray(getattr(ref.dstate, field)), err_msg=field)


def test_device_ring_columnar_bitwise_equals_legacy():
    """DeviceFrameReplay (the base class's own uint8 HBM ring): columnar
    staging must leave the pixel ring byte-identical to the legacy FIFO
    path."""
    from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    col, ref = _pair(
        DeviceFrameReplay,
        dict(capacity=512, batch_size=32, n_step=3, prioritized=True,
             write_chunk=16),
        mesh, num_streams=2)
    for r in (col, ref):
        _stream(r, 300)
        r.flush()
    np.testing.assert_array_equal(np.asarray(col.ring),
                                  np.asarray(ref.ring))


# -- shard-aware drain (ISSUE 10): prepare_rounds ≡ inline assembly --------
def test_prepare_rounds_then_flush_bitwise_equals_direct_flush():
    """The multi-host drain's work unit pre-assembles flush planes
    host-side (``prepare_rounds``) and the next ``flush()`` dispatches
    them before assembling fresh rounds. Splitting assembly from
    dispatch must not change a single ring byte, metadata lane, or
    seeded priority versus the inline flush — otherwise the multi-host
    drain would diverge from the single-host semantics it offloads."""
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=2))
    cfg_kw = dict(capacity=512, batch_size=32, n_step=3, prioritized=True,
                  device_per=True, write_chunk=16)
    pre = DevicePERFrameReplay(ReplayConfig(**cfg_kw), mesh, (8, 8),
                               stack=4, gamma=0.99, seed=0, write_chunk=16,
                               num_streams=2)
    ref = DevicePERFrameReplay(ReplayConfig(**cfg_kw), mesh, (8, 8),
                               stack=4, gamma=0.99, seed=0, write_chunk=16,
                               num_streams=2)
    for r in (pre, ref):
        _stream(r, 300)
    # pre: assemble every full round host-side, then dispatch; a second
    # prepare_rounds must find nothing full left to assemble
    assert pre.prepare_rounds() > 0
    assert pre.prepare_rounds() == 0
    assert pre.pending_rows() == ref.pending_rows()  # prepared still pend
    pre.flush()
    ref.flush()
    assert pre.pending_rows() == ref.pending_rows() == 0
    for field in ("frames", "action", "reward", "done", "boundary",
                  "prio", "maxp"):
        np.testing.assert_array_equal(
            np.asarray(getattr(pre.dstate, field)),
            np.asarray(getattr(ref.dstate, field)), err_msg=field)


# -- drain thread -----------------------------------------------------------
def test_ingest_drain_flushes_off_thread():
    """Writers stage + notify; the drain owns the flush. After the
    writer stops, the staged backlog reaches the ring without any
    caller-side flush, and stop_drain() strands nothing."""
    from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay

    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=8, dp=1))
    cfg = ReplayConfig(capacity=256, batch_size=32, n_step=3,
                       prioritized=False, write_chunk=16)
    replay = DeviceFrameReplay(cfg, mesh, (8, 8), stack=4, gamma=0.99,
                               seed=0, write_chunk=16)
    lock = threading.Lock()
    drain = replay.start_drain(lock)
    assert drain is not None
    assert replay.start_drain(lock) is drain  # idempotent attach
    try:
        rng = np.random.default_rng(0)
        with lock:
            for i in range(64):
                replay.add(rng.integers(0, 255, (8, 8), dtype=np.uint8),
                           int(rng.integers(4)), 0.0, done=(i % 9 == 8))
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            with lock:
                if replay.pending_rows() == 0:
                    break
            time.sleep(0.01)
        with lock:
            assert replay.pending_rows() == 0
            assert len(replay) == 64
        c = drain.counters()
        assert c["rows"] == 64 and c["flushes"] >= 1
        # a sub-chunk remainder is drained by shutdown, not stranded
        with lock:
            replay.add(rng.integers(0, 255, (8, 8), dtype=np.uint8),
                       0, 0.0, done=False)
    finally:
        replay.stop_drain()
    assert replay.pending_rows() == 0
    assert len(replay) == 65
    assert replay._drain is None


# -- _add_transitions lock shape --------------------------------------------
@pytest.fixture
def _clean_tracer():
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def test_add_transitions_lock_shape(_clean_tracer):
    """Parsing happens OUTSIDE the replay lock, ring mutation inside:
    ``ingest_parse`` must complete before the ``lock_hold`` opens and
    must not be its child, while ``ring_insert`` must be nested under
    the hold. Guards the ISSUE 8 satellite that shrank the critical
    section — anyone who drags the parse back under the lock reparents
    the span and fails here."""
    from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
    from distributed_deep_q_tpu.rpc.replay_server import ReplayFeedServer

    tracing.configure(enabled=True, sample_rate=1.0, lineage_rate=1.0)
    replay = ReplayMemory(32, (2,), np.float32, seed=0)
    server = ReplayFeedServer(replay)
    try:
        n = 4
        obs = np.zeros((n, 2), np.float32)
        resp = server._add_transitions(
            {"obs": obs, "next_obs": obs,
             "action": np.zeros(n, np.int32),
             "reward": np.zeros(n, np.float32),
             "discount": np.ones(n, np.float32),
             "ep_returns": np.ones(2, np.float32), "episodes": 2,
             "flush_seq": 0, tracing.KEY_BIRTH: np.full(n, tracing.now()),
             tracing.KEY_SENT_AT: tracing.now()}, 0)
        assert resp["ok"]
    finally:
        server.close()
    spans = {}
    for e in tracing.drain():
        spans.setdefault(e["name"], e)
    assert {"ingest_parse", "lock_hold", "ring_insert"} <= set(spans)
    hold = spans["lock_hold"]["args"]["span"]
    assert spans["ring_insert"]["args"]["parent"] == hold
    parse = spans["ingest_parse"]
    assert parse["args"]["parent"] != hold
    # parse finished before the hold opened (strictly off-lock)
    assert parse["ts"] + parse["dur"] <= spans["lock_hold"]["ts"]
