"""``FusedStepStream``'s rows are views and its run-ahead has a bound
(ISSUE 28): a row is the chunk's own slice, bit for bit, behaves as a
read-only dict, and costs nothing until a key is read; chunk k+1 is
dispatched only after chunk k-1 has finished, and that wait holds no
lock."""

import threading
from collections.abc import Mapping

import numpy as np
import pytest

from distributed_deep_q_tpu.solver import FusedStepStream

CHAIN = 2
KEYS = ("loss", "q_mean", "grad_norm")


@pytest.fixture(scope="module")
def toy(toy_fused_pair):
    """A real fused pair at toy size: the solver, its filled ring."""
    return toy_fused_pair(CHAIN)


class _Recording:
    """Hands on a solver's chunks and keeps them, in order."""

    def __init__(self, solver):
        self._solver = solver
        self.chunks: list[dict] = []

    def train_steps_device_per(self, replay, chain):
        self.chunks.append(self._solver.train_steps_device_per(replay, chain))
        return self.chunks[-1]


# -- (a) a row is the chunk's slice, and a read-only dict -------------------
def test_rows_are_the_chunks_slices_bitwise_tail_included(toy):
    solver, dev = toy
    rec = _Recording(solver)
    stream = FusedStepStream(rec, dev, CHAIN)
    total = 2 * CHAIN + 1               # two whole chunks and a tail of one
    rows = [stream.next(total - i) for i in range(total)]
    assert [len(c["loss"]) for c in rec.chunks] == [CHAIN, CHAIN, 1]
    for step, row in enumerate(rows):
        chunk, i = rec.chunks[step // CHAIN], step % CHAIN
        assert set(row) == set(chunk) >= set(KEYS)
        for k in row:
            got, want = np.asarray(row[k]), np.asarray(chunk[k][i])
            assert got.dtype == want.dtype and got.shape == want.shape
            assert got.tobytes() == want.tobytes(), (step, k)
        assert np.isfinite(float(row["loss"]))


def test_a_row_behaves_as_a_read_only_dict(toy):
    solver, dev = toy
    rec = _Recording(solver)
    row = FusedStepStream(rec, dev, CHAIN).next(CHAIN)
    chunk = rec.chunks[0]
    assert isinstance(row, Mapping)
    assert list(row) == list(row.keys()) == list(chunk)
    assert len(row) == len(chunk)
    assert "loss" in row and "learn_plane" not in row and 0 not in row
    as_dict = dict(row)
    assert list(as_dict) == list(chunk)
    assert [k for k, _ in row.items()] == list(chunk)
    for k, v in as_dict.items():
        assert np.asarray(v).tobytes() == np.asarray(chunk[k][0]).tobytes()
    assert row.get("nope") is None
    with pytest.raises(KeyError):
        row["nope"]
    with pytest.raises(TypeError):
        row["loss"] = 0.0               # a view: nothing to assign into


# -- (b) handing out rows launches nothing ----------------------------------
def _held(lock) -> bool | None:
    """Whether ``lock`` is held right now (by any thread)."""
    if lock is None:
        return None
    if lock.acquire(blocking=False):
        lock.release()
        return False
    return True


class _StubSolver:
    """``FusedStepStream`` needs only this of a solver. Its log has every
    dispatch, slice and wait in order, the dispatches and waits with
    whether ``lock`` was held while they ran."""

    def __init__(self, lock=None):
        self.log: list = []
        self.lock = lock
        self._n = 0

    def train_steps_device_per(self, replay, chain):
        self.log.append(("dispatch", self._n, _held(self.lock)))
        chunk = {k: _StubArray(self, self._n, chain) for k in KEYS}
        self._n += 1
        return chunk


class _StubArray:
    """A chunk's stacked metric: logs what is asked of it."""

    def __init__(self, solver: _StubSolver, chunk_no: int, chain: int):
        self._solver, self._no = solver, chunk_no
        self._data = np.arange(chain, dtype=np.float32) + 10 * chunk_no

    def __getitem__(self, i):
        self._solver.log.append(("slice", self._no, i))
        return self._data[i]

    def block_until_ready(self):
        self._solver.log.append(("wait", self._no, _held(self._solver.lock)))
        return self


def test_handing_out_rows_launches_nothing():
    solver = _StubSolver()
    stream = FusedStepStream(solver, object(), chain=4)
    rows = [stream.next(10 ** 6) for _ in range(8)]
    # nothing between two dispatches: no slice, and no wait yet
    assert solver.log == [("dispatch", 0, None), ("dispatch", 1, None)]
    # contains / len / iteration ask the chunk's dict, not its arrays
    assert all("loss" in r and len(r) == 3 and list(r) == list(KEYS)
               for r in rows)
    assert len(solver.log) == 2
    # reading one key slices that key of that row, once
    assert float(rows[5]["q_mean"]) == 11.0
    assert solver.log[2:] == [("slice", 1, 1)]


# -- (c) the run-ahead bound: two chunks, waited for outside the lock -------
@pytest.mark.parametrize("make_lock", [None, threading.Lock],
                         ids=["learner_only", "with_replay_lock"])
def test_chunk_k_plus_1_waits_for_chunk_k_minus_1_outside_the_lock(make_lock):
    lock = make_lock() if make_lock else None
    solver = _StubSolver(lock)
    stream = FusedStepStream(solver, object(), chain=2, dispatch_lock=lock)
    for left in range(9, 0, -1):            # 4 chunks of 2 and a tail of 1
        stream.next(left)
    under = None if lock is None else True  # a dispatch holds the lock,
    free = None if lock is None else False  # a wait never does
    assert solver.log == [
        ("dispatch", 0, under),             # the first two wait for nothing
        ("dispatch", 1, under),
        ("wait", 0, free), ("dispatch", 2, under),
        ("wait", 1, free), ("dispatch", 3, under),
        ("wait", 2, free), ("dispatch", 4, under)]
    assert FusedStepStream.RUN_AHEAD_CHUNKS == 2
