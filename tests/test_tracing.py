"""Tracing plane (ISSUE 7): context propagation, skew math, ring
accounting, the disabled fast path, Perfetto export schema, and the
lineage → time_to_learn pipeline."""

import importlib.util
import json
import os
import socket
import threading
import time

import numpy as np
import pytest

from distributed_deep_q_tpu import tracing
from distributed_deep_q_tpu.rpc import protocol

pytestmark = [pytest.mark.tracing]


@pytest.fixture(autouse=True)
def _clean_tracer():
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


def _enable(**kw):
    kw.setdefault("sample_rate", 1.0)
    kw.setdefault("lineage_rate", 1.0)
    tracing.configure(enabled=True, **kw)


# -- wire context round trip ------------------------------------------------
def test_wire_context_roundtrip_over_socketpair():
    """tr_* context keys survive the real wire encode/decode, and
    activate() parents server-side spans under the client's span."""
    _enable()
    a, b = socket.socketpair()
    try:
        with tracing.span("rpc_call"):
            ctx = tracing.wire_context()
            assert ctx[tracing.KEY_TRACE] and ctx[tracing.KEY_SPAN]
            protocol.send_msg(a, {"method": "add_transitions",
                                  "action": np.zeros(3, np.int32), **ctx})
        req = protocol.recv_msg(b)
    finally:
        a.close()
        b.close()
    assert int(req[tracing.KEY_TRACE]) == ctx[tracing.KEY_TRACE]
    assert int(req[tracing.KEY_SPAN]) == ctx[tracing.KEY_SPAN]
    assert abs(float(req[tracing.KEY_SENT_AT])
               - ctx[tracing.KEY_SENT_AT]) < 1e-6

    with tracing.activate(req):
        with tracing.span("ring_insert"):
            pass
    events = {e["name"]: e for e in tracing.drain()}
    child = events["ring_insert"]
    assert child["args"]["parent"] == ctx[tracing.KEY_SPAN]
    assert child["args"]["trace"] == ctx[tracing.KEY_TRACE]


def test_activate_without_context_is_noop():
    _enable()
    assert tracing.activate({"method": "add_transitions"}) is tracing._NULL


# -- skew math --------------------------------------------------------------
def test_estimate_skew_symmetric_path():
    # server clock = client + 5.0 s, 0.1 s each network leg, 0.1 s serve
    offset, rtt = tracing.estimate_skew(10.0, 15.1, 15.2, 10.3)
    assert offset == pytest.approx(5.0)
    assert rtt == pytest.approx(0.2)


def test_record_skew_keeps_min_rtt_estimate():
    tracing.record_skew(5.0, 0.2)
    tracing.record_skew(7.0, 1.0)   # noisier sample must not win
    assert tracing.skew_s() == pytest.approx(5.0)
    tracing.record_skew(4.9, 0.1)   # tighter RTT wins
    assert tracing.skew_s() == pytest.approx(4.9)
    # to_server_clock is elementwise on the lineage birth arrays
    shifted = tracing.to_server_clock(np.zeros(3))
    assert np.allclose(shifted, 4.9)
    assert tracing.counters()["trace/skew_samples"] == 3


# -- ring overflow accounting ----------------------------------------------
def test_ring_overflow_drops_oldest_and_counts():
    _enable(buffer_spans=8)
    # ring capacity is fixed at a thread's FIRST touch — a fresh thread
    # is the only way to observe the configured cap deterministically
    def burst():
        for i in range(20):
            tracing.instant("retry", i=i)

    t = threading.Thread(target=burst)
    t.start()
    t.join()
    events = [e for e in tracing.drain() if e["name"] == "retry"]
    assert len(events) == 8                      # newest `cap` survive
    assert [e["args"]["i"] for e in events] == list(range(12, 20))
    assert tracing.drop_count() == 12
    assert tracing.counters()["trace/spans_dropped"] == 12.0
    # drain cleared the rings but the drop counter must survive
    assert tracing.drain() == []
    assert tracing.drop_count() == 12


# -- disabled fast path -----------------------------------------------------
def test_disabled_path_allocates_nothing():
    assert not tracing.ENABLED and not tracing.PROFILING
    lock = threading.Lock()
    # singletons / passthroughs: no per-call object on the disabled path
    assert tracing.span("env_step") is tracing._NULL
    assert tracing.span("train_step") is tracing._NULL
    assert tracing.span_sampled("env_step") is tracing._NULL
    assert tracing.locked(lock) is lock
    assert tracing.activate({tracing.KEY_TRACE: 1}) is tracing._NULL
    assert tracing.wire_context() == {}
    assert tracing.lineage_sample() is False
    with tracing.span("sample"):
        tracing.instant("shed")
    assert tracing.drain() == []
    assert tracing.export() is None


def test_sampling_is_counter_based():
    _enable(sample_rate=0.25)

    def worker():
        for _ in range(8):
            with tracing.span_sampled("env_step"):
                pass

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert len(tracing.drain()) == 2  # every 4th per thread, exactly


# -- Perfetto export schema -------------------------------------------------
def test_export_schema(tmp_path):
    _enable(export_dir=str(tmp_path))
    with tracing.span("flush"):
        with tracing.span("rpc_call"):
            tracing.instant("retry", attempt=1)
    path = tracing.export()
    assert path == str(tmp_path / f"trace-{os.getpid()}.json")
    doc = json.load(open(path))
    assert set(doc) >= {"traceEvents", "displayTimeUnit", "otherData"}
    assert doc["displayTimeUnit"] == "ms"
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert any(m["name"] == "thread_name" for m in meta)
    spans = {e["name"]: e for e in doc["traceEvents"] if e["ph"] == "X"}
    assert set(spans) == {"flush", "rpc_call"}
    for ev in spans.values():
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(ev)
        assert ev["pid"] == os.getpid()
        assert ev["dur"] >= 0
        assert {"trace", "span", "parent"} <= set(ev["args"])
    # causality: child under parent, instant under child, one trace id
    assert spans["rpc_call"]["args"]["parent"] == \
        spans["flush"]["args"]["span"]
    inst = next(e for e in doc["traceEvents"] if e["ph"] == "i")
    assert inst["s"] == "t"
    assert inst["args"]["parent"] == spans["rpc_call"]["args"]["span"]
    assert inst["args"]["attempt"] == 1
    assert len({e["args"]["trace"] for e in doc["traceEvents"]
                if e["ph"] != "M"}) == 1
    other = doc["otherData"]
    assert {"pid", "skew_s", "spans_dropped", "anchored_at"} <= set(other)
    # rings were drained into the shard: a second export has nothing
    assert tracing.export() is None


def test_self_times_subtracts_direct_children():
    mk = lambda name, ts, dur, span, parent: {
        "name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1, "tid": 1,
        "args": {"trace": 1, "span": span, "parent": parent}}
    events = [mk("flush", 0, 100, 1, 0), mk("rpc_call", 10, 30, 2, 1),
              mk("wire_recv", 50, 20, 3, 1)]
    st = tracing.self_times(events)[(1, 1)]
    assert st["stages"]["flush"] == pytest.approx(50)   # 100 - 30 - 20
    assert st["stages"]["rpc_call"] == pytest.approx(30)
    assert st["wall_us"] == pytest.approx(100)
    table = tracing.attribution_table(events, wall_s=100e-6)
    assert "flush" in table and "untraced" in table


# -- trace_report merge + orphan detection ---------------------------------
def _load_trace_report():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "trace_report.py")
    spec = importlib.util.spec_from_file_location("_trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_merges_shards_and_finds_orphans(tmp_path):
    tr = _load_trace_report()
    ev = lambda span, parent, pid, ts: {
        "name": "flush", "ph": "X", "ts": ts, "dur": 5.0, "pid": pid,
        "tid": 1, "args": {"trace": 9, "span": span, "parent": parent}}
    shard_a = {"traceEvents": [ev(1, 0, 100, 1000.0)],
               "otherData": {"pid": 100, "skew_s": 2.0}}
    shard_b = {"traceEvents": [ev(2, 1, 200, 500.0), ev(3, 77, 200, 600.0)],
               "otherData": {"pid": 200, "skew_s": 0.0,
                             "spans_dropped": 4}}
    pa, pb = tmp_path / "trace-100.json", tmp_path / "trace-200.json"
    pa.write_text(json.dumps(shard_a))
    pb.write_text(json.dumps(shard_b))
    docs = tr.load_shards([str(pa), str(pb)])
    events, info = tr.merge_shards(docs)
    # shard A's clock shifted onto the server's by its skew estimate
    a_ev = next(e for e in events if e["pid"] == 100)
    assert a_ev["ts"] == pytest.approx(1000.0 + 2.0 * 1e6)
    assert sum(row["spans_dropped"] for row in info) == 4
    orphans = tr.orphan_spans(events)
    assert len(orphans) == 1 and orphans[0]["args"]["parent"] == 77
    # CLI end to end: merged file written, non-strict exit 0
    rc = tr.main([str(pa), str(pb), "--out", str(tmp_path / "m.json")])
    assert rc == 0
    merged = json.load(open(tmp_path / "m.json"))
    assert merged["otherData"]["orphan_spans"] == 1
    assert tr.main([str(pa), str(pb), "--strict",
                    "--out", str(tmp_path / "m2.json")]) == 1


# -- lineage → time_to_learn ------------------------------------------------
def test_lineage_time_to_learn_monotonic():
    from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
    from distributed_deep_q_tpu.rpc.replay_server import ReplayFeedServer

    _enable()
    replay = ReplayMemory(16, (2,), np.float32, seed=0)
    server = ReplayFeedServer(replay)
    try:
        n = 8
        obs = np.zeros((n, 2), np.float32)
        births = np.full(n, tracing.now() - 0.5)
        resp = server._add_transitions(
            {"obs": obs, "next_obs": obs,
             "action": np.zeros(n, np.int32),
             "reward": np.zeros(n, np.float32),
             "discount": np.ones(n, np.float32),
             "flush_seq": 0, tracing.KEY_BIRTH: births,
             tracing.KEY_SENT_AT: tracing.now()}, 0)
        assert resp["ok"]
        # the NTP reply stamps ride the traced reply
        assert resp[tracing.KEY_DONE_AT] >= resp[tracing.KEY_RECV_AT]
        ages1 = server.lineage_ages(np.arange(n))
        assert ages1.size == n
        assert np.all(ages1 >= 0.5)
        time.sleep(0.02)
        ages2 = server.lineage_ages(np.arange(n))
        # time_to_learn grows monotonically while consumption waits
        assert np.all(ages2 > ages1)
        # flush-level ingest lag landed in the telemetry histogram
        assert server.telemetry.ingest_lag.count == n
        assert server.telemetry.ingest_lag.vmin >= 500.0  # ms
        # ring wrap invalidates stamps: 2× capacity of fresh rows later,
        # the old slots describe younger data and must not report ages
        for seq in range(1, 5):
            server._add_transitions(
                {"obs": obs, "next_obs": obs,
                 "action": np.zeros(n, np.int32),
                 "reward": np.zeros(n, np.float32),
                 "discount": np.ones(n, np.float32),
                 "flush_seq": seq}, 0)
        assert server.lineage_ages(np.arange(n)).size == 0
    finally:
        server.close()


def test_lineage_disabled_returns_empty():
    from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
    from distributed_deep_q_tpu.rpc.replay_server import ReplayFeedServer

    replay = ReplayMemory(16, (2,), np.float32, seed=0)
    server = ReplayFeedServer(replay)
    try:
        assert server.lineage_ages(np.arange(4)).size == 0
    finally:
        server.close()
