"""``trace_reduce.py`` on a small recorded trace.

``fixtures/ddqn_per_b512_learner_only_3chunks.xplane.pb`` is three chunks cut
out of the first traced chip run of ``ddqn_per_b512.learner_only`` (my chip
run, PR 23; TPU v5 lite; device plane whole, host plane cut to the Python
and main threads' events of 20 us or more, per-event stats dropped). Run
by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_trace_reduce.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(ROOT, "benchmark", "fixtures",
                       "ddqn_per_b512_learner_only_3chunks.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return tr.load(FIXTURE)


def test_union_of_intervals():
    assert tr.union_ns([(0, 10), (5, 12), (20, 30), (21, 22)]) == 22
    assert tr.union_ns([]) == 0


def test_planes_and_lines(trace):
    assert tr.device_planes(trace) == ["/device:TPU:0"]
    assert len(tr.events(trace, "/device:TPU:0", tr.MODULE_LINE)) == 150
    assert len(tr.events(trace, "/device:TPU:0", tr.OP_LINE)) == 7629


def test_busy_union_and_span(trace):
    b = tr.busy(trace)
    assert b["busy_s"] == pytest.approx(0.039793818, rel=1e-9)
    assert b["window_s"] == pytest.approx(0.067731948, rel=1e-9)
    assert 0 < b["busy_s"] < b["window_s"]


@pytest.mark.parametrize("line,pattern,total_s,count", [
    (tr.MODULE_LINE, "^jit_sample_fn", 0.01104955, 3),
    (tr.MODULE_LINE, "^jit_(tree|plane)_train_fn", 0.028729414, 3),
    (tr.OP_LINE, r"^%sample_fn\S* = \S+ custom-call\(", 0.002393698, 3),
    (tr.MODULE_LINE, "^jit_dynamic_slice", None, 72),
])
def test_per_program_time(trace, line, pattern, total_s, count):
    total, n = tr.total_and_count(trace, line, pattern)
    assert n == count
    if total_s is not None:
        assert total == pytest.approx(total_s, rel=1e-9)


def test_a_pattern_that_matches_nothing_raises(trace):
    with pytest.raises(tr.NothingMatched):
        tr.total_and_count(trace, tr.MODULE_LINE, "^jit_no_such_program")
    with pytest.raises(tr.NothingMatched):
        tr.busy({"/host:CPU": {}})


def test_top_ops_leave_out_containers(trace):
    ops = tr.top_ops(trace, 10)
    assert len(ops) == 10
    assert not any(name.startswith("%while") for name, _ in ops)
    assert ops[0][0].startswith("%broadcast.518")
    assert ops[0][1] == pytest.approx(0.004910524, rel=1e-9)
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))


def test_idle_gaps_are_named_by_the_trace_itself(trace):
    gaps = tr.idle_gaps(trace, 5)
    assert [round(s, 9) for _, s in gaps] == [
        0.0019922, 0.001939506, 0.001895493, 0.001729641, 0.001631944]
    assert gaps[0][0] == "CommonPjRtLoadedExecutable::ExecutePrepare"
    # without a host plane nothing can be attributed
    bare = {p: l for p, l in trace.items() if p.startswith("/device")}
    assert {name for name, _ in tr.idle_gaps(bare, 5)} == {"unattributed"}
