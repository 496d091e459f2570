"""Telemetry spine (PR 1, observability): streaming-histogram math, the
``stats`` RPC round trip against a live ``ReplayFeedServer`` (server-side
counters must match what the actor fleet sent), the telemetry_report CLI,
and the tier-1 JSONL contract — every ``Metrics.log`` record is valid JSON
with a monotonic step."""

import json
import math
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from distributed_deep_q_tpu.metrics import Histogram, Metrics

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "scripts"))

from telemetry_report import (  # noqa: E402
    load_records, render_report, slo_problems, validate_records)


# -- histogram math ---------------------------------------------------------


def test_histogram_single_value_is_exact():
    h = Histogram()
    h.observe(7.3)
    s = h.summary("lat")
    assert s["lat_count"] == 1
    assert s["lat_mean"] == pytest.approx(7.3)
    assert s["lat_max"] == pytest.approx(7.3)
    # percentile clamps to observed min/max → single value reports exactly
    for q in (0.5, 0.95, 0.99):
        assert h.percentile(q) == pytest.approx(7.3)


def test_histogram_percentiles_uniform_within_bucket_resolution():
    h = Histogram(lo=1e-3, hi=1e5, per_decade=10)
    for v in range(1, 1001):
        h.observe(float(v))
    assert h.count == 1000
    assert h.mean == pytest.approx(500.5)
    # log buckets at 10/decade have edge ratio 10^0.1 ≈ 1.26 — estimates
    # must land within a bucket of the true percentile
    assert h.percentile(0.50) == pytest.approx(500, rel=0.30)
    assert h.percentile(0.99) == pytest.approx(990, rel=0.30)
    assert (h.percentile(0.50) <= h.percentile(0.95)
            <= h.percentile(0.99) <= h.vmax == 1000.0)


def test_histogram_under_overflow_clamped():
    h = Histogram(lo=1.0, hi=100.0, per_decade=5)
    h.observe(1e-6)   # underflow bucket
    h.observe(1e9)    # overflow bucket
    assert h.count == 2
    assert h.percentile(0.0) >= 1e-6
    assert h.percentile(1.0) == pytest.approx(1e9)
    h.observe(float("nan"))  # NaN is skipped, not propagated
    assert h.count == 2


def test_histogram_empty_and_reset():
    h = Histogram()
    assert h.summary("x") == {}
    assert math.isnan(h.percentile(0.5))
    h.observe(3.0)
    assert h.summary("x") != {}
    h.reset()
    assert h.summary("x") == {}
    assert h.count == 0


def test_histogram_merge_equals_single_stream():
    """Merging shard-local histograms with identical geometry is bitwise
    equal to one histogram that observed every value — percentiles of
    the merge are IDENTICAL to single-stream, not merely close."""
    rng = np.random.default_rng(3)
    streams = [rng.lognormal(1.0, 1.5, 400) for _ in range(3)]
    shards = []
    for vals in streams:
        h = Histogram()
        h.observe_many(vals)
        shards.append(h)
    merged = shards[0].snapshot()
    merged.merge(shards[1]).merge(shards[2])
    single = Histogram()
    for vals in streams:
        single.observe_many(vals)
    assert merged._counts == single._counts
    assert merged.count == single.count
    assert merged.total == pytest.approx(single.total)
    assert merged.vmin == single.vmin and merged.vmax == single.vmax
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        assert merged.percentile(q) == single.percentile(q)
    assert merged.summary("x") == pytest.approx(single.summary("x"))


def test_histogram_merge_under_overflow_and_extremes():
    a = Histogram(lo=1.0, hi=100.0, per_decade=5)
    b = Histogram(lo=1.0, hi=100.0, per_decade=5)
    a.observe(1e-6)          # a's underflow
    a.observe(5.0)
    b.observe(1e9)           # b's overflow
    b.observe(0.5)           # b's underflow
    a.merge(b)
    assert a.count == 4
    assert a._counts[0] == 2 and a._counts[-1] == 1   # under/overflow add
    assert a.vmin == 1e-6 and a.vmax == 1e9           # min/max of both
    assert a.percentile(1.0) == pytest.approx(1e9)


def test_histogram_merge_and_delta_reject_geometry_mismatch():
    a = Histogram(lo=1e-3, hi=1e5, per_decade=10)
    for bad in (Histogram(lo=1e-2, hi=1e5, per_decade=10),
                Histogram(lo=1e-3, hi=1e5, per_decade=5),
                Histogram(lo=1e-3, hi=1e6, per_decade=10)):
        with pytest.raises(ValueError, match="geometry"):
            a.merge(bad)
        with pytest.raises(ValueError, match="geometry"):
            a.delta(bad)


def test_histogram_snapshot_is_independent():
    h = Histogram()
    h.observe(2.0)
    snap = h.snapshot()
    h.observe(50.0)
    assert snap.count == 1 and h.count == 2
    assert snap.vmax == pytest.approx(2.0) and h.vmax == pytest.approx(50.0)


def test_histogram_delta_windows_a_cumulative_stream():
    h = Histogram()
    for _ in range(100):
        h.observe(1.0)
    prev = h.snapshot()
    for _ in range(100):
        h.observe(500.0)
    win = h.delta(prev)
    # the window holds ONLY the second batch: p50 sits at ~500, while
    # the cumulative histogram's p50 still straddles both batches
    assert win.count == 100
    assert win.percentile(0.5) == pytest.approx(500.0, rel=0.30)
    assert win.mean == pytest.approx(500.0)
    # documented conservatism: vmin/vmax keep the CUMULATIVE extremes
    # (window extrema are unrecoverable from bucket counts)
    assert win.vmin == pytest.approx(1.0) and win.vmax == pytest.approx(500.0)


def test_histogram_delta_reset_fallback():
    h = Histogram()
    for _ in range(10):
        h.observe(4.0)
    prev = h.snapshot()
    h.reset()
    h.observe(7.0)           # source reset since prev: count went backwards
    win = h.delta(prev)
    assert win.count == 1    # full current state, not a negative window
    assert win.percentile(0.5) == pytest.approx(7.0)


def test_metrics_gauges_histograms_flatten(tmp_path):
    jsonl = tmp_path / "m.jsonl"
    m = Metrics(jsonl_path=str(jsonl))
    m.gauge("queue/depth", 17)
    m.observe("lat_ms", 4.0)
    m.observe("lat_ms", 8.0)
    tele = m.telemetry()
    assert tele["queue/depth"] == 17.0
    assert tele["lat_ms_count"] == 2
    assert tele["lat_ms_max"] == pytest.approx(8.0)
    m.log(1, **tele)
    m.close()
    (rec,) = [json.loads(l) for l in jsonl.read_text().splitlines()]
    assert rec["step"] == 1 and rec["queue/depth"] == 17.0


# -- stats RPC round trip ---------------------------------------------------


def test_stats_rpc_matches_actor_sent_counters():
    from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
    from distributed_deep_q_tpu.rpc.replay_server import (
        ReplayFeedClient, ReplayFeedServer)

    replay = ReplayMemory(256, (4,), np.float32)
    server = ReplayFeedServer(replay)
    host, port = server.address
    client = ReplayFeedClient(host, port, actor_id=5)
    try:
        server.publish_params([np.ones(3, np.float32)])
        version, weights = client.get_params()
        assert weights is not None
        client.call("heartbeat")

        n = 16
        pull_ms = np.asarray([1.5, 2.5], np.float32)
        hb_ms = np.asarray([0.7], np.float32)
        step_ms = np.asarray([0.1, 0.2, 0.3, 0.4], np.float32)
        client.add_transitions(
            obs=np.ones((n, 4), np.float32),
            action=np.zeros(n, np.int32),
            reward=np.ones(n, np.float32),
            next_obs=np.ones((n, 4), np.float32),
            discount=np.full(n, 0.99, np.float32),
            episodes=1, ep_returns=np.asarray([12.0], np.float32),
            tm_param_pull_ms=pull_ms, tm_heartbeat_rtt_ms=hb_ms,
            tm_env_step_ms=step_ms)

        stats = client.call("stats")
        # server-side aggregates match exactly what this actor sent
        assert stats["env_steps"] == n
        assert stats["fleet/param_pull_ms_count"] == len(pull_ms)
        assert stats["fleet/param_pull_ms_max"] == pytest.approx(2.5)
        assert stats["fleet/heartbeat_rtt_ms_count"] == len(hb_ms)
        assert stats["fleet/env_step_ms_count"] == len(step_ms)
        assert stats["fleet/env_step_ms_p50"] <= 0.4
        np.testing.assert_array_equal(stats["actor_ids"], [5])
        np.testing.assert_array_equal(stats["actor_env_steps"], [n])
        # per-method RPC accounting: latency + payload-size histograms
        assert stats["rpc/add_transitions_calls"] == 1
        assert stats["rpc/add_transitions_ms_count"] == 1
        assert stats["rpc/add_transitions_ms_p99"] > 0
        assert stats["rpc/add_transitions_bytes_max"] > n * 4 * 4 * 2
        assert stats["rpc/heartbeat_calls"] == 1
        assert stats["rpc/get_params_calls"] == 1
        # queue gauges: replay depth + params-version lag (this actor has
        # the latest θ, so the fleet lag is zero)
        assert stats["queue/replay_size"] == len(replay) == n
        assert stats["queue/params_version"] == version
        assert stats["queue/params_version_lag"] == 0
        assert stats["fleet/actors_seen"] == 1
        # shard gauges must land for host-RAM replays too (no
        # pending_rows): the server's replay IS the shard, owner 0
        assert stats["shard/rows"] == n
        assert stats["shard/owner_host"] == 0
        assert "shard/ingest_rate" in stats
    finally:
        client.close()
        server.close()


def test_stats_rpc_version_lag_counts_stale_actor():
    from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
    from distributed_deep_q_tpu.rpc.replay_server import (
        ReplayFeedClient, ReplayFeedServer)

    replay = ReplayMemory(64, (4,), np.float32)
    server = ReplayFeedServer(replay)
    host, port = server.address
    client = ReplayFeedClient(host, port, actor_id=0)
    try:
        server.publish_params([np.zeros(2, np.float32)])
        client.get_params()                              # pulled v1
        server.publish_params([np.ones(2, np.float32)])  # now v2
        stats = client.call("stats")
        assert stats["queue/params_version"] == 2
        assert stats["queue/params_version_lag"] == 1
    finally:
        client.close()
        server.close()


# -- telemetry_report -------------------------------------------------------


def _synthetic_records():
    return [
        {"step": 100, "t": 1.0, "loss": 0.5, "grad_steps_per_s": 90.0,
         "env_steps": 400, "time_sample_ms": 1.2, "time_sample_p50_ms": 1.0,
         "time_sample_p99_ms": 3.0, "rpc/add_transitions_calls": 4,
         "rpc/add_transitions_ms_p50": 0.4, "rpc/add_transitions_ms_p95": 0.9,
         "queue/replay_size": 1000, "fleet/param_pull_ms_count": 3,
         "fleet/param_pull_ms_p95": 2.0},
        {"step": 200, "t": 2.0, "loss": 0.4, "grad_steps_per_s": 95.0,
         "env_steps": 800, "queue/replay_size": 2000},
    ]


def test_report_renders_synthetic_jsonl(tmp_path):
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text("".join(json.dumps(r) + "\n"
                             for r in _synthetic_records()))
    records = load_records(str(jsonl))
    assert validate_records(records) == []
    report = render_report(records)
    for needle in ("run overview", "step phases", "rpc methods",
                   "add_transitions", "queue gauges", "queue/replay_size",
                   "fleet", "anomalies (0)"):
        assert needle in report, f"missing section {needle!r}\n{report}"


def test_report_flags_anomalies(tmp_path):
    recs = [{"step": 100, "t": 1.0}, {"step": 50, "t": 2.0},
            {"step": 150, "t": 3.0, "loss": float("nan")}]
    problems = validate_records(recs)
    assert any("non-monotonic" in p for p in problems)
    assert any("nan" in p for p in problems)
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"step": 1}\nnot json at all\n')
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        load_records(str(bad))


def test_report_cli_smoke(tmp_path):
    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text("".join(json.dumps(r) + "\n"
                             for r in _synthetic_records()))
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "telemetry_report.py"),
         str(jsonl)], capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    assert "run overview" in proc.stdout
    # a missing file is a clean error, not a traceback
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "telemetry_report.py"),
         str(tmp_path / "nope.jsonl")], capture_output=True, text=True,
        timeout=60)
    assert proc.returncode == 1 and "error:" in proc.stderr


def _verdict(status, rules=()):
    return {"status": status, "ok": status == "ok", "t": 1.0,
            "findings": [{"rule": r, "key": "k", "severity": status,
                          "kind": "slo"} for r in rules]}


def test_slo_problems_gate_semantics():
    ok = {"step": 2, "t": 2.0, "health/verdict": _verdict("ok")}
    deg = {"step": 1, "t": 1.0,
           "health/verdict": _verdict("degraded", ["wire_integrity"])}
    # transient degraded window that RECOVERS passes — that is the
    # health plane working, not an SLO violation
    assert slo_problems([deg, ok]) == []
    # a run that ENDS degraded fails, naming the violated rule
    (p,) = slo_problems([ok, deg])
    assert "degraded" in p and "wire_integrity" in p
    # any CRITICAL verdict fails even if the run recovers
    crit = {"step": 1, "t": 1.0,
            "health/verdict": _verdict("critical", ["oom"])}
    assert any("CRITICAL" in p for p in slo_problems([crit, ok]))
    # no health plane in the run → nothing to gate
    assert slo_problems([{"step": 1, "t": 1.0}]) == []


def test_report_renders_health_section_and_strict_gates(tmp_path):
    recs = [
        {"step": 1, "t": 1.0, "health/members": 2, "health/findings": 0,
         "train/steps_per_s": 120.0, "train/mfu": 0.31,
         "health/verdict": _verdict("ok")},
        {"step": 2, "t": 2.0, "health/members": 2, "health/findings": 1,
         "health/verdict": _verdict("degraded", ["wire_integrity"])},
    ]
    report = render_report(recs)
    for needle in ("health & efficiency", "train/mfu", "fleet verdict",
                   "final status        degraded", "wire_integrity"):
        assert needle in report, f"missing {needle!r}\n{report}"

    jsonl = tmp_path / "run.jsonl"
    jsonl.write_text("".join(json.dumps(r) + "\n" for r in recs))
    cli = [sys.executable, str(REPO / "scripts" / "telemetry_report.py"),
           str(jsonl)]
    # non-strict: the degraded tail is reported but does not gate
    proc = subprocess.run(cli, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr
    # strict: run ends degraded → convention line on stderr, exit 1
    proc = subprocess.run(cli + ["--strict"], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 1
    assert "strict: FAILED" in proc.stderr
    assert "wire_integrity" in proc.stderr
    # strict over a healthy run passes
    jsonl.write_text(json.dumps(
        {"step": 1, "t": 1.0, "health/verdict": _verdict("ok")}) + "\n")
    proc = subprocess.run(cli + ["--strict"], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stderr


# -- tier-1 JSONL contract over a real run (satellite g) --------------------


@pytest.mark.slow
def test_distributed_run_jsonl_carries_rpc_and_fleet_telemetry(tmp_path):
    """Full loopback topology: the learner's JSONL must carry the server's
    per-method RPC latency histograms, the fleet counters the actors
    flushed back, and the queue gauges — and the report must render it."""
    from distributed_deep_q_tpu.actors.supervisor import train_distributed
    from distributed_deep_q_tpu.config import cartpole_config

    jsonl = tmp_path / "m.jsonl"
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 2
    cfg.train.total_steps = 150
    cfg.replay.learn_start = 200
    cfg.replay.batch_size = 32
    cfg.actors.num_actors = 2
    cfg.actors.send_batch = 16
    cfg.actors.param_sync_period = 50
    train_distributed(cfg, metrics=Metrics(jsonl_path=str(jsonl)),
                      log_every=50)
    records = load_records(str(jsonl))
    assert validate_records(records) == []
    merged: dict = {}
    for r in records:
        merged.update(r)
    assert merged.get("rpc/add_transitions_calls", 0) > 0
    assert merged.get("rpc/add_transitions_ms_p99", 0) > 0
    assert merged.get("rpc/get_params_ms_count", 0) > 0
    assert merged.get("fleet/param_pull_ms_count", 0) > 0
    assert merged.get("fleet/env_step_ms_count", 0) > 0
    assert merged.get("queue/replay_size", 0) > 0
    assert "queue/params_version" in merged
    assert merged.get("fleet/actors_seen", 0) == 2
    report = render_report(records)
    assert "rpc methods" in report and "fleet" in report


def test_train_run_jsonl_valid_monotonic_with_telemetry(tmp_path):
    from distributed_deep_q_tpu.config import cartpole_config
    from distributed_deep_q_tpu.train import train_single_process

    jsonl = tmp_path / "m.jsonl"
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.train.total_steps = 700
    cfg.train.train_every = 4
    cfg.train.grad_steps_per_train = 1
    cfg.train.eval_every = 0
    cfg.replay.learn_start = 200
    train_single_process(cfg, metrics=Metrics(jsonl_path=str(jsonl)),
                         log_every=25)
    records = load_records(str(jsonl))  # raises on any invalid-JSON line
    assert records, "run produced no metrics records"
    assert validate_records(records) == []  # monotonic steps, finite values
    timed = [r for r in records if "time_sample_p99_ms" in r]
    assert timed, "no streaming-histogram summary in the JSONL"
    gauged = [r for r in records if "queue/replay_size" in r]
    assert gauged, "no queue-depth gauge in the JSONL"
    assert gauged[-1]["queue/replay_size"] > 0
    render_report(records)  # must not raise on a real run's file
