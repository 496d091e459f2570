"""The two span readers' arithmetic (``readers/host_span_time.py``,
``readers/idle_owner.py``) on synthetic lines and on a small recorded
trace.

``fixtures/ddqn_per_b512_fleet4_spans_3chunks.xplane.pb`` is three chunks cut
out of a traced chip run of ``ddqn_per_b512.fleet4`` (my chip run, PR 24; TPU
v5 lite; Python tracer off): the device plane's ``XLA Modules`` and ``XLA
Ops`` lines and every host line's ``ddq/`` events, per-event stats dropped.
Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_host_spans.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402
from benchmark.readers import host_span_time as hst  # noqa: E402
from benchmark.readers import idle_owner as io  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "ddqn_per_b512_fleet4_spans_3chunks.xplane.pb")
MS = 1e6    # ns


def line(*evs):
    """``(name, start_ms, end_ms)`` -> a sorted line in ns."""
    return sorted(((n, s * MS, e * MS) for n, s, e in evs),
                  key=lambda e: (e[1], -e[2]))


# two chunks of a learner thread: lock wait, hold (flush, feed, the two
# dispatches), then the slices; a serve thread with two requests
LEARNER = line(
    ("learner_chunk", 0, 10), ("lock_wait", 0, 3), ("lock_hold", 3, 10),
    ("learner_flush", 3, 4), ("learner_feed", 4, 5), ("sample", 5, 7),
    ("train_step", 7, 9.5),
    ("learner_slice", 10, 14), ("learner_slice", 14, 18),
    ("learner_chunk", 20, 26), ("lock_wait", 20, 21), ("lock_hold", 21, 26),
    ("sample", 22, 23), ("train_step", 23, 25),
    ("learner_slice", 26, 30), ("learner_slice", 30, 36))
SERVE = line(
    ("crc_verify", 1, 5), ("rpc_handle", 6, 16), ("lock_wait", 6, 12),
    ("lock_hold", 12, 15), ("ring_insert", 12, 14),
    ("crc_verify", 18, 21), ("rpc_handle", 22, 40), ("lock_wait", 23, 25))
LINES = [LEARNER, SERVE]


@pytest.mark.parametrize("spans,within,per,want", [
    (["learner_slice"], None, "steps", (4 + 4 + 4 + 6) / (2 * 2)),
    (["sample", "train_step"], "learner_chunk", "chunks",
     (2 + 2.5 + 1 + 2) / 2),
    # the serve thread's lock waits lie inside the learner's chunk BY TIME
    # but on another line: they are not the learner's
    (["lock_wait"], "learner_chunk", "chunks", (3 + 1) / 2),
    (["lock_wait"], "rpc_handle", "count", (6 + 2) / 2),
    (["lock_wait"], None, "count", (3 + 1 + 6 + 2) / 4),
    (["learner_flush"], None, "chunks", 1 / 2),
    (["learner_publish"], None, "chunks", None),        # never opened
])
def test_span_time_within_and_divisors(spans, within, per, want):
    got = hst.span_time(LINES, set(spans), within, per, chain=2)
    assert got == (want if want is None else pytest.approx(want))


def test_share_of_the_traced_span_clips_and_may_pass_100():
    # traced span 2..20 ms: the first CRC loses 1 ms, the second 1 ms
    got = hst.span_time(LINES, {"crc_verify"}, None, "traced_span",
                        traced=(2 * MS, 20 * MS))
    assert got == pytest.approx(100 * (3 + 2) / 18)
    both = [SERVE, SERVE]           # two threads inside the span at once
    got = hst.span_time(both, {"rpc_handle"}, None, "traced_span",
                        traced=(6 * MS, 16 * MS))
    assert got == pytest.approx(200.0)


def test_a_chunk_divisor_without_chunks_reads_nothing():
    assert hst.span_time([SERVE], {"lock_wait"}, None, "chunks") is None


def test_segments_give_each_stretch_to_the_innermost_span():
    segs = io.segments(LEARNER[:7])     # the first chunk alone
    assert [(s / MS, e / MS, n, leaf) for s, e, n, leaf in segs] == [
        (0, 3, "lock_wait", True), (3, 4, "learner_flush", True),
        (4, 5, "learner_feed", True), (5, 7, "sample", True),
        (7, 9.5, "train_step", True), (9.5, 10, "lock_hold", False)]
    # a parent owns what its children leave, before and between them too
    segs = io.segments(line(("learner_chunk", 0, 10), ("sample", 2, 4),
                            ("train_step", 6, 7)))
    assert [(s / MS, e / MS, n, leaf) for s, e, n, leaf in segs] == [
        (0, 2, "learner_chunk", False), (2, 4, "sample", True),
        (4, 6, "learner_chunk", False), (6, 7, "train_step", True),
        (7, 10, "learner_chunk", False)]


def test_gaps_go_to_owners_by_overlap_and_the_rest_is_unowned():
    segs = io.segments(LEARNER)
    # idle 1..4 (lock_wait 2, flush 1), 9..12 (train .5, hold .5, slice 2),
    # 17..22 (slice 1, nothing 2, lock_wait 1, hold 1), 40..41 (nothing)
    gaps = [(1 * MS, 4 * MS), (9 * MS, 12 * MS), (17 * MS, 22 * MS),
            (40 * MS, 41 * MS)]
    acc = {k: v / MS for k, v in io.owners(gaps, segs).items()}
    assert acc == pytest.approx({
        "lock_wait": 3, "learner_flush": 1, "train_step": 0.5,
        "lock_hold": 1.5, "learner_slice": 3, io.UNOWNED: 3})
    assert sum(acc.values()) == pytest.approx(3 + 3 + 5 + 1)


def test_coverage_any_span_and_leaf_spans():
    segs = io.segments(LEARNER)
    any_share, leaf_share = io.coverage(segs, 0, 40 * MS)
    # spans cover 0..18 and 20..36; non-leaf: hold 9.5..10, 21..22, 25..26
    assert any_share == pytest.approx((18 + 16) / 40)
    assert leaf_share == pytest.approx((18 + 16 - 0.5 - 1 - 1) / 40)


def test_the_learner_line_is_the_one_with_the_chunks():
    assert io.learner_line([SERVE, LEARNER]) is LEARNER
    assert io.learner_line([SERVE]) is None


# -- the recorded trace ------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    import types

    trace = tr.load(FIXTURE)
    return types.SimpleNamespace(
        trace=trace, hp={"fused_chain": 8},
        result={"trace_dir": None}, span_lines=hst.load_lines(FIXTURE))


def test_fixture_lines_stay_apart_where_trace_reduce_merges_them(recorded):
    lines = recorded.span_lines
    host = [p for p in recorded.trace if p.startswith("/host")]
    merged = sum(len(recorded.trace[p]) for p in host)
    assert len(lines) > merged      # same-named thread lines, kept apart
    assert sum(1 for ln in lines
               if any(e[0] == hst.CHUNK for e in ln)) == 1


def test_fixture_reads(recorded):
    read = hst.read
    chunks = sum(1 for e in io.learner_line(recorded.span_lines)
                 if e[0] == hst.CHUNK)
    assert chunks == 3
    slices = [e for e in io.learner_line(recorded.span_lines)
              if e[0] == "learner_slice"]
    assert len(slices) == 3 * 8
    per_step = read(recorded, spans=["learner_slice"], per="steps")
    assert per_step == pytest.approx(
        sum(e - s for _, s, e in slices) / 24 / MS)
    assert read(recorded, spans=["lock_wait"], within="learner_chunk",
                per="chunks") > 0
    assert read(recorded, spans=["lock_wait"], within="rpc_handle",
                per="count") > 0
    assert 0 < read(recorded, spans=["crc_verify"], per="traced_span")
    # a span the window never opened: nothing, not zero
    assert read(recorded, spans=["learner_checkpoint"], per="count") is None


def test_fixture_idle_owners_add_up(recorded, capsys):
    value = io.read(recorded)
    gaps = io.idle_gaps(recorded.trace)
    idle = sum(g1 - g0 for g0, g1 in gaps)
    b = tr.busy(recorded.trace)
    assert idle / 1e9 == pytest.approx(b["window_s"] - b["busy_s"])
    acc = io.owners(gaps, io.segments(io.learner_line(recorded.span_lines)))
    assert sum(acc.values()) == pytest.approx(idle)
    assert value == pytest.approx(100 * acc[io.UNOWNED] / idle)
    assert 0 <= value < 100
    assert '"idle_owners"' in capsys.readouterr().out


def test_a_trace_without_any_span_reads_zero_time_and_all_idle_unowned():
    """PR 23's fixture: a program that writes no spans into the trace."""
    import types

    old = os.path.join(ROOT, "benchmark", "fixtures",
                       "ddqn_per_b512_learner_only_3chunks.xplane.pb")
    ctx = types.SimpleNamespace(trace=tr.load(old), hp={"fused_chain": 8},
                                span_lines=hst.load_lines(old))
    assert ctx.span_lines == []
    assert hst.read(ctx, spans=["learner_slice"], per="steps") == 0.0
    assert io.read(ctx) == 100.0
