"""Durability-plane tests (ISSUE 6): CRC-32C correctness, atomic writes,
the generational snapshot store's torn-write matrix, warm-boot fallback
and quarantine semantics, the non-blocking snapshot path, and the
durability telemetry.

Everything is CPU-only and fast; the raw ``open``/``np.savez`` calls in
this file are test fixtures damaging or forging snapshot files on
purpose — ``analysis/atomic_writes.py`` scans the package, not tests.
"""

from __future__ import annotations

import contextlib
import ctypes
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from distributed_deep_q_tpu import native
from distributed_deep_q_tpu.rpc import faultinject
from distributed_deep_q_tpu.rpc.protocol import (
    HEADER_SIZE, WIRE_VERSION, ChecksumError, encode, recv_msg_sized)
from distributed_deep_q_tpu.rpc.replay_server import (
    ReplayFeedClient, ReplayFeedServer)
from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
from distributed_deep_q_tpu.utils.durability import (
    GEN_PREFIX, MANIFEST_NAME, QUARANTINE_PREFIX, GenerationStore,
    IntegrityError, atomic_write, crc32c, crc_backend, savez_bytes)


@pytest.fixture(autouse=True)
def _no_chaos_leak(monkeypatch):
    monkeypatch.delenv(faultinject.ENV_VAR, raising=False)
    faultinject.uninstall()
    yield
    faultinject.uninstall()


@pytest.fixture
def feed_server():
    created = []

    def make(replay=None, **kw):
        if replay is None:
            replay = ReplayMemory(256, (2,))
        s = ReplayFeedServer(replay, **kw)
        created.append(s)
        return s

    yield make
    for s in created:
        s.close()


def _vector_batch(n: int, base: float = 0.0) -> dict:
    ids = base + np.arange(n, dtype=np.float32)
    obs = np.stack([ids, ids], axis=1)
    return dict(obs=obs, action=np.zeros(n, np.int32),
                reward=np.zeros(n, np.float32), next_obs=obs,
                discount=np.ones(n, np.float32))


# ---------------------------------------------------------------------------
# CRC-32C — one algorithm, two implementations (ISSUE 30): the native
# core's ``crc32c_update`` where it loads, else numpy, the reference
# ---------------------------------------------------------------------------

BACKENDS = ("native", "numpy")


@contextlib.contextmanager
def crc_backend_forced(name: str):
    """Run the body with ``crc32c`` on the named implementation: numpy is
    forced the way a host without g++ gets it (``native.load()`` answers
    None); native needs the library this host built."""
    with pytest.MonkeyPatch.context() as mp:
        if name == "numpy":
            mp.setattr(native, "load", lambda: None)
        elif native.load() is None:
            pytest.skip("the native core does not build on this host")
        assert crc_backend() == name
        yield


@pytest.fixture(params=BACKENDS)
def backend(request):
    with crc_backend_forced(request.param):
        yield request.param


def _numpy_crc32c(data, value: int = 0) -> int:
    with crc_backend_forced("numpy"):
        return crc32c(data, value)


def _random_bytes(n: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, 256, size=n,
                                                dtype=np.uint8)


def _check_known_vectors():
    # RFC 3720 §B.4 test vectors
    assert crc32c(b"") == 0
    assert crc32c(b"123456789") == 0xE3069283
    assert crc32c(b"\x00" * 32) == 0x8A9136AA
    assert crc32c(b"\xff" * 32) == 0x62A8AB43


def test_crc32c_known_vectors(backend):
    _check_known_vectors()


def test_crc32c_chunked_matches_streaming_small_path(backend):
    """The numpy-chunked large-buffer path must agree with the ≤512-byte
    pure-python path for every size around the chunking boundaries —
    streamed 256 bytes at a time, only the small path runs, so the two
    implementations cross-check each other. (Native: one call against
    many continued ones.)"""
    rng = np.random.default_rng(0)
    for n in (1, 2, 511, 512, 513, 1000, 4096, 65537, 100003):
        data = rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        whole = crc32c(data)
        streamed = 0
        for i in range(0, n, 256):
            streamed = crc32c(data[i:i + 256], streamed)
        assert whole == streamed, f"n={n}"


def test_crc32c_streaming_split_invariance(backend):
    data = bytes(range(256)) * 20
    whole = crc32c(data)
    for cut in (0, 1, 100, len(data) // 2, len(data) - 1, len(data)):
        assert crc32c(data[cut:], crc32c(data[:cut])) == whole


def test_crc32c_ndarray_equals_bytes(backend):
    arr = np.linspace(0, 1, 1000, dtype=np.float64).reshape(10, 100)
    assert crc32c(arr) == crc32c(arr.tobytes())


def test_crc32c_detects_single_bit_flips(backend):
    rng = np.random.default_rng(5)
    data = bytearray(rng.integers(0, 256, size=2048, dtype=np.uint8))
    ref = crc32c(bytes(data))
    for _ in range(64):
        i = int(rng.integers(len(data)))
        data[i] ^= 1 << int(rng.integers(8))
        got = crc32c(bytes(data))
        assert got != ref
        ref = got  # keep the flip: the next one must differ again


@pytest.mark.parametrize(
    "n", (0, 1, 7, 8, 9, 511, 512, 513, 4095, 451_584, 6_744_720))
def test_crc32c_native_equals_numpy(n):
    """Both native paths — the one ``crc32c`` takes on this CPU and the
    portable table loop — against the numpy reference, at the sizes
    around the 8-byte step and the numpy path's chunking, a 64-row flush
    (451 584 B) and a θ frame (6 744 720 B)."""
    buf = _random_bytes(n, seed=n)
    want = _numpy_crc32c(buf)
    with crc_backend_forced("native"):
        assert crc32c(buf) == want
        assert crc32c(buf.tobytes()) == want
    assert native.load().crc32c_update_portable(
        0, buf.ctypes.data, n) == want


def test_crc32c_native_equals_numpy_on_views_offsets_and_continuation():
    """What a pointer can get wrong: unaligned starts (odd offsets into a
    larger buffer), a memoryview and a bytearray, a non-contiguous
    ndarray (checksummed in C order, as ``tobytes`` lays it out), and a
    non-zero ``value`` to continue from."""
    big = _random_bytes(70_001, seed=30)
    raw = big.tobytes()
    with crc_backend_forced("native"):
        portable = native.load().crc32c_update_portable
        for off in (1, 3, 5, 7, 13):
            for n in (0, 1, 9, 64, 4097, 65_537 - off):
                want = _numpy_crc32c(raw[off:off + n])
                assert crc32c(memoryview(raw)[off:off + n]) == want
                assert crc32c(big[off:off + n]) == want
                assert portable(0, big.ctypes.data + off, n) == want
        assert crc32c(bytearray(raw[:1000])) == _numpy_crc32c(raw[:1000])
        grid = big[:69_696].reshape(264, 264)
        for view in (grid[::2, 1::3], grid.T, grid[5:, :-7],
                     big[:4000].view(np.float32)[::-1]):
            assert not view.flags.c_contiguous
            assert crc32c(view) == _numpy_crc32c(view.tobytes())
        for value in (1, 0xDEADBEEF, 0xFFFFFFFF):
            assert crc32c(raw[:5000], value) \
                == _numpy_crc32c(raw[:5000], value)
            assert portable(value, big.ctypes.data, 5000) \
                == _numpy_crc32c(raw[:5000], value)


def test_crc32c_falls_back_to_numpy_without_the_library():
    """A host without g++: ``native.load()`` answers None, ``crc32c``
    takes the numpy path, says so, and every vector still holds."""
    with crc_backend_forced("numpy"):
        assert crc_backend() == "numpy"
        _check_known_vectors()
        buf = _random_bytes(10_000, seed=3)
        assert crc32c(buf[5000:], crc32c(buf[:5000])) == crc32c(buf)


def test_native_core_is_loaded_without_the_interpreter_lock():
    """``ctypes.CDLL`` gives the interpreter lock up around every foreign
    call; ``PyDLL`` (a CDLL subclass) keeps it. The checksum moved into
    the library so that a serve thread's verify no longer holds the
    learner thread — which only a plain CDLL delivers."""
    with crc_backend_forced("native"):
        lib = native.load()
    assert isinstance(lib, ctypes.CDLL)
    assert not isinstance(lib, ctypes.PyDLL)
    assert not lib.crc32c_update._flags_ & ctypes._FUNCFLAG_PYTHONAPI


def test_actor_says_which_crc_backend_it_runs(backend, caplog):
    """Once at start, and loudly only when it fell back."""
    import logging

    from distributed_deep_q_tpu.actors import supervisor
    with caplog.at_level(logging.INFO, logger=supervisor.__name__):
        supervisor._log_crc_backend(3)
    (rec,) = [r for r in caplog.records if "crc32c backend" in r.message]
    assert rec.getMessage() == f"actor 3: crc32c backend {backend}"
    assert rec.levelno == (logging.INFO if backend == "native"
                           else logging.WARNING)


def test_generations_cross_verify_between_crc_backends(tmp_path):
    """A snapshot generation written under one implementation (the
    parent tree's numpy, this tree's native) verifies under the other."""
    files = {"a.bin": _random_bytes(100_000, seed=1).tobytes(),
             "b.bin": b"x" * 17}
    for i, (writer, reader) in enumerate((BACKENDS, BACKENDS[::-1])):
        store = GenerationStore(str(tmp_path / f"s{i}"))
        with crc_backend_forced(writer):
            gen = store.commit(files)
        with crc_backend_forced(reader):
            paths, _ = store.verify(gen)  # raises IntegrityError if not
        assert sorted(paths) == sorted(files)


# ---------------------------------------------------------------------------
# atomic_write + torn chaos verb
# ---------------------------------------------------------------------------


def test_atomic_write_lands_content_and_leaves_no_tmp(tmp_path):
    p = str(tmp_path / "blob.bin")
    atomic_write(p, b"first")
    with open(p, "rb") as f:
        assert f.read() == b"first"
    atomic_write(p, b"second version")  # overwrite is atomic too
    with open(p, "rb") as f:
        assert f.read() == b"second version"
    assert [n for n in os.listdir(tmp_path) if n.endswith(".tmp")] == []


def test_torn_chaos_verb_damages_the_final_file(tmp_path):
    plan = faultinject.install("torn=1.0,seed=3")
    p = str(tmp_path / "torn.bin")
    data = bytes(range(256)) * 16
    atomic_write(p, data)
    assert plan.counters.get("file/torn", 0) == 1
    with open(p, "rb") as f:
        got = f.read()
    assert got != data  # truncated or garbage-filled, as a real tear
    assert crc32c(got) != crc32c(data)  # and the CRC catches it


def test_store_never_serves_torn_generations_under_chaos(tmp_path):
    """With torn= chaos active on every other write, latest_valid must
    still only ever return a generation that verifies clean."""
    faultinject.install("torn=0.5,seed=11")
    rng = np.random.default_rng(1)
    store = GenerationStore(str(tmp_path / "store"), keep=8)
    for _ in range(6):
        blob = rng.integers(0, 256, size=1500, dtype=np.uint8).tobytes()
        store.commit({"server.npz": blob}, meta={"n": len(blob)})
    faultinject.uninstall()
    pick = store.latest_valid()
    if pick is not None:
        gen, paths, meta = pick
        with open(paths["server.npz"], "rb") as f:
            assert len(f.read()) == meta["n"]  # verified == intact


# ---------------------------------------------------------------------------
# GenerationStore: commit / verify / retention
# ---------------------------------------------------------------------------


def test_store_commit_verify_roundtrip(tmp_path):
    store = GenerationStore(str(tmp_path / "s"), keep=3)
    gen = store.commit({"a.npz": b"AAAA", "b.npz": b"BBBBBB"},
                       meta={"env_steps": 7})
    assert gen == 0
    paths, meta = store.verify(0)
    assert set(paths) == {"a.npz", "b.npz"}
    assert meta == {"env_steps": 7}
    assert store.latest_valid()[0] == 0


def test_store_retention_prunes_oldest(tmp_path):
    store = GenerationStore(str(tmp_path / "s"), keep=2)
    for i in range(5):
        store.commit({"f": bytes([i])})
    assert store.generations() == [3, 4]
    assert store.latest_valid()[0] == 4


def test_store_missing_root_is_cold_boot(tmp_path):
    store = GenerationStore(str(tmp_path / "never"))
    assert store.generations() == []
    assert store.latest_valid() is None
    assert store.quarantined == 0


def _two_gen_store(root: str) -> GenerationStore:
    """gen 0 and gen 1, two payload files each, distinct contents."""
    store = GenerationStore(root, keep=4)
    for i in range(2):
        store.commit({"server.npz": bytes([i]) * 900,
                      "replay.npz": bytes([10 + i]) * 1700},
                     meta={"env_steps": 100 + i})
    return store


def test_torn_write_matrix_truncation_every_boundary(tmp_path):
    """Truncating either payload file of the newest generation at any
    boundary — empty, one byte, half, all-but-one — must quarantine it
    and fall back to the previous generation."""
    case = 0
    for name, size in (("server.npz", 900), ("replay.npz", 1700)):
        for cut in (0, 1, size // 2, size - 1):
            root = str(tmp_path / f"m{case}")
            case += 1
            store = _two_gen_store(root)
            victim = os.path.join(root, f"{GEN_PREFIX}00000001", name)
            with open(victim, "rb") as f:
                pristine = f.read()
            with open(victim, "wb") as f:
                f.write(pristine[:cut])
            with pytest.raises(IntegrityError, match="torn write"):
                store.verify(1)
            gen, _, meta = store.latest_valid()
            assert gen == 0 and meta["env_steps"] == 100
            assert store.quarantined == 1
            assert any(n.startswith(QUARANTINE_PREFIX)
                       for n in os.listdir(root))


def test_torn_write_matrix_garbage_span_same_size(tmp_path):
    """A garbage-filled span (size unchanged — the tear fsync cannot see)
    is caught by the checksum, not the size field."""
    root = str(tmp_path / "g")
    store = _two_gen_store(root)
    victim = os.path.join(root, f"{GEN_PREFIX}00000001", "server.npz")
    with open(victim, "r+b") as f:
        f.seek(300)
        f.write(b"\xde\xad\xbe\xef" * 8)
    with pytest.raises(IntegrityError, match="corrupt"):
        store.verify(1)
    assert store.latest_valid()[0] == 0


def test_torn_write_matrix_manifest_damage(tmp_path):
    """Manifest damage of every kind — truncated JSON, schema drift, a
    flipped checksum digest, a drifted size — invalidates the generation
    without crashing the walk."""
    def damaged(mutate):
        root = str(tmp_path / f"mf{damaged.n}")
        damaged.n += 1
        store = _two_gen_store(root)
        mpath = os.path.join(root, f"{GEN_PREFIX}00000001", MANIFEST_NAME)
        with open(mpath, encoding="utf-8") as f:
            text = f.read()
        with open(mpath, "w", encoding="utf-8") as f:
            f.write(mutate(text))
        with pytest.raises(IntegrityError):
            store.verify(1)
        assert store.latest_valid()[0] == 0

    damaged.n = 0
    server_digest = '"%08x"' % crc32c(b"\x01" * 900)  # gen 1's server.npz
    damaged(lambda t: t[: len(t) // 2])                     # torn JSON
    damaged(lambda t: t.replace('"schema": 1', '"schema": 99'))
    damaged(lambda t: t.replace(server_digest, '"00000000"'))
    damaged(lambda t: t.replace('"size": 900', '"size": 901'))


def test_uncommitted_generation_is_invisible(tmp_path):
    """A directory without a manifest (crash before the commit point)
    is quarantined by the walk and never considered committed."""
    root = str(tmp_path / "u")
    store = _two_gen_store(root)
    partial = os.path.join(root, f"{GEN_PREFIX}00000002")
    os.makedirs(partial)
    with open(os.path.join(partial, "server.npz"), "wb") as f:
        f.write(b"\x00" * 100)  # payload landed, manifest never did
    gen, _, meta = store.latest_valid()
    assert gen == 1 and meta["env_steps"] == 101
    assert store.quarantined == 1
    # the next commit number continues past the quarantined attempt
    assert store.commit({"server.npz": b"x"}) == 2


def test_quarantine_disk_use_is_bounded(tmp_path):
    root = str(tmp_path / "q")
    store = GenerationStore(root, keep=2)
    for _ in range(4):  # repeatedly: commit a pair, tear both, quarantine
        for _ in range(2):
            g = store.commit({"f": b"x" * 64})
            with open(os.path.join(store._gen_dir(g), "f"), "wb") as f:
                f.write(b"")
        assert store.latest_valid() is None
    assert store.quarantined == 8
    quars = [n for n in os.listdir(root) if n.startswith(QUARANTINE_PREFIX)]
    # _prune (run at each commit) bounds quarantine dirs to keep=2, plus
    # at most the pair quarantined after the final commit
    assert len(quars) <= 4


# ---------------------------------------------------------------------------
# Server warm boot: fallback, quarantine counters, legacy layout
# ---------------------------------------------------------------------------


def test_warm_boot_falls_back_to_older_generation(feed_server, tmp_path):
    snap = str(tmp_path / "fb")
    replay = ReplayMemory(64, (2,))
    server = feed_server(replay)
    host, port = server.address
    c = ReplayFeedClient(host, port, actor_id=1)
    try:
        c.call("add_transitions", flush_seq=1, **_vector_batch(2))
        assert server.snapshot(snap) == 0
        c.call("add_transitions", flush_seq=2, **_vector_batch(2, base=50))
        assert server.snapshot(snap) == 1
    finally:
        c.close()
    server.close()
    # tear the newest generation after the fact (corrupt at rest)
    victim = os.path.join(snap, f"{GEN_PREFIX}00000001", "server.npz")
    with open(victim, "r+b") as f:
        f.truncate(40)

    replay2 = ReplayMemory(64, (2,))
    server2 = feed_server(replay2, snapshot_path=snap)
    assert server2._restored_generation == 0  # fell back one generation
    assert server2.env_steps == 2 and len(replay2) == 2
    assert server2.telemetry.snapshot_quarantined == 1
    assert server2.telemetry.robustness_counters()["snapshot_quarantined"] == 1


def test_warm_boot_cold_boots_when_every_generation_is_torn(
        feed_server, tmp_path):
    snap = str(tmp_path / "cb")
    replay = ReplayMemory(64, (2,))
    server = feed_server(replay)
    host, port = server.address
    c = ReplayFeedClient(host, port, actor_id=1)
    try:
        c.call("add_transitions", flush_seq=1, **_vector_batch(4))
    finally:
        c.close()
    server.snapshot(snap)
    server.snapshot(snap)
    server.close()
    for gen in (0, 1):
        with open(os.path.join(snap, f"{GEN_PREFIX}{gen:08d}",
                               MANIFEST_NAME), "w") as f:
            f.write("{ not json")

    replay2 = ReplayMemory(64, (2,))
    server2 = feed_server(replay2, snapshot_path=snap)
    assert server2._restored_generation == -1  # cold boot, not a crash
    assert server2.env_steps == 0 and len(replay2) == 0
    assert server2.telemetry.snapshot_quarantined == 2


def test_warm_boot_without_replay_file_restores_counters(
        feed_server, tmp_path):
    """A generation whose manifest lists only server.npz (replay tier
    without persistence support) warm-boots the counters and dedup map;
    the replay simply starts empty."""
    snap = str(tmp_path / "nr")
    replay = ReplayMemory(64, (2,))
    server = feed_server(replay)
    host, port = server.address
    c = ReplayFeedClient(host, port, actor_id=3)
    try:
        c.call("add_transitions", flush_seq=9, **_vector_batch(3))
    finally:
        c.close()
    server.snapshot(snap)
    server.close()
    gdir = os.path.join(snap, f"{GEN_PREFIX}00000000")
    mpath = os.path.join(gdir, MANIFEST_NAME)
    with open(mpath, encoding="utf-8") as f:
        manifest = json.load(f)
    del manifest["files"]["replay.npz"]
    atomic_write(mpath, json.dumps(manifest).encode())
    os.unlink(os.path.join(gdir, "replay.npz"))

    replay2 = ReplayMemory(64, (2,))
    server2 = feed_server(replay2, snapshot_path=snap)
    assert server2._restored_generation == 0
    assert server2.env_steps == 3 and len(replay2) == 0
    assert server2._flush_seq == {3: 9}  # dedup map survived


def test_legacy_flat_snapshot_still_warm_boots(feed_server, tmp_path):
    snap = str(tmp_path / "legacy")
    np.savez(f"{snap}.server.npz", schema=1, env_steps=5, episodes=2,
             returns=np.array([1.5, 2.5]), flush_ids=np.array([7], np.int64),
             flush_seqs=np.array([3], np.int64), params_version=0,
             params_wire=np.zeros(0, np.uint8))
    server = feed_server(snapshot_path=snap)
    assert server.env_steps == 5 and server.episodes == 2
    assert server._flush_seq == {7: 3}
    assert server.telemetry.snapshot_quarantined == 0


def test_legacy_flat_corrupt_snapshot_cold_boots_loudly(
        feed_server, tmp_path):
    snap = str(tmp_path / "legacy-bad")
    with open(f"{snap}.server.npz", "wb") as f:
        f.write(b"PK\x03\x04 definitely not a zip" * 3)  # torn npz
    server = feed_server(snapshot_path=snap)
    assert server.env_steps == 0  # cold boot, no crash
    assert server.telemetry.snapshot_quarantined == 1
    assert server.telemetry.robustness_counters()["snapshot_quarantined"] == 1


# ---------------------------------------------------------------------------
# Non-blocking snapshots (the tentpole's perf half)
# ---------------------------------------------------------------------------


@pytest.fixture
def blocked_commit(monkeypatch):
    """GenerationStore.commit that parks on a gate — models a slow disk
    so 'does the dump block serving?' is deterministic, not timing-based."""
    gate = threading.Event()
    entered = threading.Event()
    real = GenerationStore.commit

    def slow_commit(self, files, meta=None):
        entered.set()
        assert gate.wait(20), "test never opened the gate"
        return real(self, files, meta)

    monkeypatch.setattr(GenerationStore, "commit", slow_commit)
    yield entered, gate
    gate.set()


def test_snapshot_async_never_blocks_ingest(feed_server, tmp_path,
                                            blocked_commit):
    entered, gate = blocked_commit
    snap = str(tmp_path / "nb")
    replay = ReplayMemory(256, (2,))
    server = feed_server(replay)
    host, port = server.address
    c = ReplayFeedClient(host, port, actor_id=1, timeout=10.0)
    try:
        c.call("add_transitions", flush_seq=1, **_vector_batch(2))
        assert server.snapshot_async(snap) is True
        assert entered.wait(10)  # writer thread is parked inside commit
        # a dump is in flight: a second cadence tick skips, never piles up
        assert server.snapshot_async(snap) is False
        assert server.telemetry.snapshot_skipped == 1
        # ingest proceeds while the dump is stuck on "disk"
        t0 = time.monotonic()
        r = c.call("add_transitions", flush_seq=2, **_vector_batch(2, 50))
        assert r["ok"] and time.monotonic() - t0 < 5.0
        assert len(replay) == 4
    finally:
        gate.set()
        c.close()
    with server._snap_lock:  # join the background writer
        pass
    gen, _, meta = GenerationStore(snap).latest_valid()
    assert gen == 0
    assert meta["env_steps"] == 2  # captured BEFORE the second flush
    assert server.telemetry.snapshot_count == 1


def test_sync_snapshot_releases_replay_lock_during_dump(
        feed_server, tmp_path, blocked_commit):
    """Satellite 1 regression: snapshot() used to hold replay_lock across
    the whole serialize+write. Now the lock must be free while the dump
    is mid-write."""
    entered, gate = blocked_commit
    replay = ReplayMemory(256, (2,))
    server = feed_server(replay)
    done = []
    t = threading.Thread(
        target=lambda: done.append(server.snapshot(str(tmp_path / "s"))))
    t.start()
    try:
        assert entered.wait(10)  # dump in flight...
        assert server.replay_lock.acquire(timeout=5.0)  # ...lock is free
        server.replay_lock.release()
    finally:
        gate.set()
        t.join(timeout=20)
    assert done == [0]


def test_snapshot_durability_telemetry_lands_in_summary(
        feed_server, tmp_path):
    server = feed_server(ReplayMemory(64, (2,)))
    host, port = server.address
    c = ReplayFeedClient(host, port, actor_id=1)
    try:
        c.call("add_transitions", flush_seq=1, **_vector_batch(2))
    finally:
        c.close()
    server.snapshot(str(tmp_path / "t"))
    s = server.telemetry_summary()
    assert s["durability/snapshot_count"] == 1
    assert s["durability/snapshot_bytes"] > 0
    assert s["durability/snapshot_capture_ms"] >= 0.0
    assert s["durability/snapshot_write_ms"] > 0.0
    assert s["durability/generations"] == 1
    assert s["durability/quarantined"] == 0
    assert s["rpc/checksum_errors"] == 0


# ---------------------------------------------------------------------------
# Wire v4 CRC at the server boundary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sender, receiver",
                         (BACKENDS, BACKENDS[::-1]), ids="->".join)
def test_wire_frames_cross_verify_between_crc_backends(sender, receiver):
    """A frame encoded under one implementation verifies under the
    other through ``encode`` / ``recv_msg_sized`` — a v4 peer of the
    parent tree (numpy) and this tree (native) interoperate both ways —
    and a bit flipped in transit is still refused."""
    msg = {"method": "add_transitions", "flush_seq": 7,
           "obs": _random_bytes(64 * 7056, seed=2).reshape(64, 7056),
           "reward": np.linspace(-1, 1, 64, dtype=np.float32)}
    with crc_backend_forced(sender):
        frame = encode(msg)
    assert frame[1] == WIRE_VERSION == 4
    torn = bytearray(frame)
    torn[HEADER_SIZE + len(frame) // 2] ^= 0x04
    for wire, ok in ((frame, True), (bytes(torn), False)):
        a, b = socket.socketpair()
        tx = threading.Thread(target=a.sendall, args=(wire,))
        tx.start()
        try:
            with crc_backend_forced(receiver):
                if ok:
                    got, size = recv_msg_sized(b)
                    assert size == len(frame) - HEADER_SIZE - 4
                    assert got["flush_seq"] == 7
                    np.testing.assert_array_equal(got["obs"], msg["obs"])
                else:
                    with pytest.raises(ChecksumError):
                        recv_msg_sized(b)
        finally:
            tx.join(timeout=10)
            a.close()
            b.close()
        assert not tx.is_alive()


def test_server_counts_checksum_errors_and_keeps_serving(feed_server,
                                                         backend):
    server = feed_server()
    assert server.telemetry.crc_native == int(backend == "native")
    assert server.telemetry_summary()["rpc/crc_native"] \
        == server.telemetry.crc_native
    host, port = server.address
    frame = bytearray(encode({"method": "heartbeat", "actor_id": 0}))
    frame[HEADER_SIZE + 2] ^= 0x10  # payload flip in transit
    raw = socket.create_connection((host, port))
    try:
        raw.sendall(bytes(frame))
        raw.settimeout(5)
        try:
            assert raw.recv(1) == b""  # server dropped the connection
        except ConnectionResetError:
            pass
    finally:
        raw.close()
    deadline = time.monotonic() + 5
    while server.telemetry.checksum_errors == 0 \
            and time.monotonic() < deadline:
        time.sleep(0.01)
    assert server.telemetry.checksum_errors == 1
    assert server.telemetry.dispatch_errors == 0  # classified, not generic
    assert server.telemetry.robustness_counters()["checksum_errors"] == 1
    c = ReplayFeedClient(host, port, actor_id=0)
    try:
        assert c.call("heartbeat")["ok"]  # service unharmed
    finally:
        c.close()


def test_params_frame_corrupt_at_rest_fails_warm_boot_to_older_gen(
        feed_server, tmp_path):
    """The persisted θ frame carries its own wire CRC: a generation whose
    files all verify clean (the damage predates the commit, so the
    manifest checksums the poisoned bytes as written) still fails restore
    at reframe(), and the boot falls back instead of handing actors a
    poisoned frame."""
    snap = str(tmp_path / "theta")

    def state(wire: bytes, version: int) -> dict:
        return dict(schema=1, env_steps=0, episodes=0,
                    returns=np.zeros(0), flush_ids=np.zeros(0, np.int64),
                    flush_seqs=np.zeros(0, np.int64),
                    params_version=version,
                    params_wire=np.frombuffer(wire, np.uint8))

    good = encode({"version": 1, "w0": np.arange(8, dtype=np.float32)})
    bad = bytearray(encode({"version": 2,
                            "w0": np.arange(8, dtype=np.float32) * 2}))
    bad[HEADER_SIZE + 5] ^= 0x20  # flip INSIDE the stored θ frame
    store = GenerationStore(snap)
    store.commit({"server.npz": savez_bytes(**state(good, 1))})
    store.commit({"server.npz": savez_bytes(**state(bytes(bad), 2))})
    assert store.verify(1)  # file-level integrity is clean by design

    server = feed_server(ReplayMemory(64, (2,)), snapshot_path=snap)
    assert server._restored_generation == 0  # poisoned gen quarantined
    assert server._params_version == 1
    assert server.telemetry.snapshot_quarantined == 1
