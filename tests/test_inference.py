"""Batched inference plane tests (ISSUE 9).

Covers the ``BatchedPolicy`` bucket machinery (program bound, padding
hygiene), the ``infer`` RPC round trip, remote-vs-local action parity on
both feed-forward torsos (the acceptance bar: bitwise identical actions,
so remote inference can replace the per-actor CPU forward without
touching reproducibility), microbatch coalescing across concurrent
clients, the shed/admission path against a deliberately wedged forward,
and the ``_RemoteInference`` actor-side source end to end.
"""

import threading
import time

import numpy as np
import pytest

from distributed_deep_q_tpu.config import Config, NetConfig
from distributed_deep_q_tpu.models.policy import BatchedPolicy
from distributed_deep_q_tpu.models.qnet import QNet
from distributed_deep_q_tpu.rpc.flowcontrol import FlowConfig
from distributed_deep_q_tpu.rpc.inference_server import (
    InferenceClient, InferenceServer)

MLP = NetConfig(kind="mlp", hidden=(32, 32), num_actions=5)


# ---------------------------------------------------------------------------
# BatchedPolicy: bucket math + padding hygiene
# ---------------------------------------------------------------------------


def test_bucket_for_and_program_bound():
    p = BatchedPolicy(MLP, seed=0, obs_dim=6, buckets=(4, 16))
    assert p.bucket_for(1) == 4
    assert p.bucket_for(4) == 4
    assert p.bucket_for(5) == 16
    assert p.bucket_for(16) == 16
    assert p.bucket_for(999) == 16  # oversized → largest-bucket chunks
    rng = np.random.default_rng(0)
    for n in (1, 3, 4, 9, 16, 33, 50):
        a, q = p.forward(rng.standard_normal((n, 6)).astype(np.float32))
        assert a.shape == (n,)
        assert q.shape == (n, 5)
    # the whole sweep — including the 33- and 50-row oversized batches —
    # may only ever compile the declared bucket shapes
    assert set(p.compiled_buckets()) <= {4, 16}


def test_rejects_r2d2():
    with pytest.raises(ValueError, match="r2d2|recurrent"):
        BatchedPolicy(NetConfig(kind="r2d2"), seed=0)


def test_padding_rows_never_leak():
    """A row's action/Q must not depend on which bucket it rode in or on
    its zero-padded neighbors."""
    p = BatchedPolicy(MLP, seed=1, obs_dim=6, buckets=(2, 8))
    obs = np.random.default_rng(2).standard_normal((7, 6)).astype(np.float32)
    a_all, q_all = p.forward(obs)          # pads 7 → bucket 8
    for i in range(7):
        a_one, q_one = p.forward(obs[i:i + 1])  # pads 1 → bucket 2
        assert int(a_one[0]) == int(a_all[i])
        np.testing.assert_allclose(q_one[0], q_all[i], rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Wire round trip
# ---------------------------------------------------------------------------


def test_infer_wire_roundtrip():
    policy = BatchedPolicy(MLP, seed=3, obs_dim=6, buckets=(4,))
    server = InferenceServer(policy, cutoff_us=500)
    host, port = server.address
    client = InferenceClient(host, port, actor_id=0)
    try:
        obs = np.random.default_rng(4).standard_normal(
            (3, 6)).astype(np.float32)
        want_a, want_q = policy.forward(obs)
        version = server.set_params(policy.get_weights(), version=7)
        assert version == 7

        resp = client.infer(obs, seq=11)
        assert "error" not in resp
        np.testing.assert_array_equal(resp["actions"], want_a)
        np.testing.assert_allclose(resp["q"], want_q, rtol=1e-6)
        assert resp["version"] == 7
        assert resp["seq"] == 11
        assert resp["credits"] > 0

        assert client.call("heartbeat")["ok"] is True
        stats = client.call("stats")
        assert stats["params_version"] == 7
        assert 4 in np.asarray(stats["compiled_buckets"]).tolist()
        unknown = client.call("get_params")
        assert "error" in unknown  # replay-plane verb, wrong server
    finally:
        client.close()
        server.close()


# ---------------------------------------------------------------------------
# Action parity: remote == local CPU forward, both torsos (satellite 3)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["mlp", "nature_cnn"])
def test_action_parity_remote_vs_local(kind):
    """The reproducibility bar for remote_inference mode: with identical
    θ, the server's bucket-padded batched forward must return bitwise the
    SAME action the actor's own ``QNet.argmax_action`` would have picked
    for every observation — otherwise flipping ``inference.enabled``
    changes the trajectory stream."""
    if kind == "mlp":
        net = NetConfig(kind="mlp", hidden=(24,), num_actions=4)
        obs_dim = 6
        rng = np.random.default_rng(5)
        make = lambda: rng.standard_normal(obs_dim).astype(np.float32)  # noqa: E731
    else:
        net = NetConfig(kind="nature_cnn", num_actions=4,
                        frame_shape=(36, 36), stack=2)
        obs_dim = 4  # unused by conv torsos
        rng = np.random.default_rng(6)
        make = lambda: rng.integers(  # noqa: E731
            0, 256, (36, 36, 2), dtype=np.uint8)

    local = QNet(net, seed=9, obs_dim=obs_dim)
    policy = BatchedPolicy(net, seed=0, obs_dim=obs_dim, buckets=(4,))
    policy.set_weights(local.get_weights())  # identical θ by construction

    server = InferenceServer(policy, cutoff_us=500)
    host, port = server.address
    client = InferenceClient(host, port, actor_id=0)
    try:
        for _ in range(16):
            obs = make()
            resp = client.infer(obs[None])
            remote_a = int(np.asarray(resp["actions"])[0])
            assert remote_a == local.argmax_action(np.asarray(obs))
    finally:
        client.close()
        server.close()


# ---------------------------------------------------------------------------
# Microbatching across concurrent clients
# ---------------------------------------------------------------------------


def test_microbatch_coalesces_concurrent_clients():
    """Requests from distinct clients landing inside one cutoff window
    ride ONE forward — and every client still gets its own row back."""
    policy = BatchedPolicy(MLP, seed=7, obs_dim=6, buckets=(8,))
    # generous cutoff so all four 1-row requests land in one window
    server = InferenceServer(policy, max_batch=8, cutoff_us=200_000)
    host, port = server.address
    num = 4
    obs = np.random.default_rng(8).standard_normal(
        (num, 6)).astype(np.float32)
    want_a, want_q = policy.forward(obs)
    start = threading.Barrier(num)
    failures: list[str] = []

    def worker(i: int) -> None:
        c = InferenceClient(host, port, actor_id=i)
        try:
            start.wait(10)
            resp = c.infer(obs[i:i + 1], seq=i)
            if int(np.asarray(resp["actions"])[0]) != int(want_a[i]) \
                    or not np.allclose(resp["q"][0], want_q[i], rtol=1e-6):
                failures.append(f"client {i}: crossed or wrong reply")
        except Exception as e:  # noqa: BLE001 — surfaced via failures
            failures.append(f"client {i}: {type(e).__name__}: {e}")
        finally:
            c.close()

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(num)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    biggest = server.telemetry.batch_rows.vmax
    server.close()
    assert not failures, failures
    # all four rows inside one 200ms window must coalesce (≥2 proves the
    # batcher crossed a client boundary; usually all 4 ride together)
    assert biggest >= 2


# ---------------------------------------------------------------------------
# Shed / admission against a wedged forward
# ---------------------------------------------------------------------------


class _GatedPolicy:
    """Stub with an event-gated forward so the test controls exactly when
    the batcher is busy — makes the shed decision deterministic."""

    def __init__(self, num_actions: int = 3):
        self.gate = threading.Event()
        self.in_forward = threading.Event()
        self.num_actions = num_actions

    def forward(self, obs):
        self.in_forward.set()
        assert self.gate.wait(30)
        n = obs.shape[0]
        return (np.zeros(n, np.int64),
                np.zeros((n, self.num_actions), np.float32))

    def compiled_buckets(self):
        return []


def test_shed_reply_and_retry():
    policy = _GatedPolicy()
    server = InferenceServer(
        policy, max_batch=256, cutoff_us=1_000,
        flow=FlowConfig(staged_high_watermark=8, shed_policy="all",
                        flush_credit_floor=4))
    host, port = server.address
    obs6 = np.zeros((6, 2), np.float32)
    replies: dict[str, dict] = {}

    def send(name: str) -> None:
        c = InferenceClient(host, port, actor_id=hash(name) % 100)
        try:
            replies[name] = c.call("infer", obs=obs6)
        finally:
            c.close()

    ta = threading.Thread(target=send, args=("a",))
    ta.start()
    assert policy.in_forward.wait(10)  # batcher took A, wedged in forward
    tb = threading.Thread(target=send, args=("b",))
    tb.start()
    deadline = time.monotonic() + 10
    while server.queued_rows() < 6 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert server.queued_rows() == 6  # B staged behind the wedged forward

    # C: 6 staged + 6 new > watermark 8 → explicit shed, never queued
    c = InferenceClient(host, port, actor_id=99)
    try:
        resp = c.call("infer", obs=obs6)
        assert resp.get("shed") is True
        assert resp["retry_after_ms"] >= 0
        assert "credits" in resp

        policy.gate.set()  # unwedge; A then B drain
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            resp = c.call("infer", obs=obs6)
            if not resp.get("shed"):
                break
            time.sleep(resp["retry_after_ms"] / 1e3)
        assert not resp.get("shed"), "retry never admitted after drain"
        assert len(resp["actions"]) == 6
    finally:
        c.close()
        ta.join(timeout=10)
        tb.join(timeout=10)
        summary = server.telemetry_summary()
        server.close()
    assert len(replies["a"]["actions"]) == 6
    assert len(replies["b"]["actions"]) == 6
    assert summary["inference/sheds"] >= 1


# ---------------------------------------------------------------------------
# Actor-side source (_RemoteInference) + zero steady-state param pulls
# ---------------------------------------------------------------------------


def test_remote_inference_actor_source():
    from distributed_deep_q_tpu.actors.supervisor import _RemoteInference

    net = NetConfig(kind="mlp", hidden=(24,), num_actions=3)
    local = QNet(net, seed=2, obs_dim=4)
    policy = BatchedPolicy(net, seed=0, obs_dim=4, buckets=(4,))
    policy.set_weights(local.get_weights())
    server = InferenceServer(policy, cutoff_us=500)

    cfg = Config()
    cfg.net = net
    cfg.inference.enabled = True
    cfg.inference.host, cfg.inference.port = server.address
    server.set_params(local.get_weights(), version=5)

    remote = _RemoteInference(cfg, threading.Event(), actor_id=0, gid=0)
    try:
        rng = np.random.default_rng(10)
        for _ in range(8):
            obs = rng.standard_normal(4).astype(np.float32)
            assert remote.action(obs) == local.argmax_action(obs)
        assert remote.version == 5
        assert remote.sheds == 0
    finally:
        remote.close()
        server.close()


@pytest.mark.slow
def test_distributed_remote_inference_end_to_end():
    """Full topology with the inference plane on: actor processes pull
    actions (not parameters) from the learner host. The replay server's
    method ledger proves the mode's point — zero ``get_params`` traffic
    after the initial bring-up."""
    from distributed_deep_q_tpu.actors.supervisor import train_distributed
    from distributed_deep_q_tpu.config import cartpole_config

    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.num_fake_devices = 2
    cfg.train.total_steps = 150
    cfg.replay.learn_start = 200
    cfg.replay.batch_size = 32
    cfg.actors.num_actors = 2
    cfg.actors.send_batch = 16
    cfg.actors.param_sync_period = 50
    cfg.inference.enabled = True
    summary = train_distributed(cfg, log_every=50)
    assert summary["solver"].step == 150
    assert np.isfinite(summary["loss"])
    assert summary["inference_requests"] > 0
    assert summary["inference_param_pulls"] == 0


# ---------------------------------------------------------------------------
# Multi-tenant serving (ISSUE 20): per-tenant θ, A/B split, shadow mirror
# ---------------------------------------------------------------------------

from distributed_deep_q_tpu.rpc.inference_server import (  # noqa: E402
    TENANT_PRIMARY, arm_for)


def _rigged(weights, v: int, num_actions: int = 5):
    """All-zero θ except the final Q bias, one-hot at ``v % A``: with
    zero kernels every layer's contribution dies, so argmax action ==
    v % A for ANY observation — a reply's actions spell out which θ
    generation computed them, which is what lets the swap-race test
    check (actions, version) consistency without reading server state."""
    out = []
    for w in weights:
        z = np.zeros_like(np.asarray(w))
        if z.ndim == 1 and z.shape[0] == num_actions:
            z[v % num_actions] = 1.0
        out.append(z)
    return out


def test_arm_split_deterministic_and_covers_arms():
    arms = (TENANT_PRIMARY, "ab:cand")
    picks = [arm_for(a, arms) for a in range(64)]
    assert picks == [arm_for(a, arms) for a in range(64)]  # pure
    assert set(picks) == set(arms)  # both arms actually get traffic
    assert arm_for(3, ()) == TENANT_PRIMARY


def test_tenants_serve_distinct_generations():
    policy = BatchedPolicy(MLP, seed=11, obs_dim=6, buckets=(8,))
    server = InferenceServer(policy, max_batch=8, cutoff_us=300,
                             tenants=("ab:cand",))
    host, port = server.address
    base = policy.get_weights()
    server.set_params(_rigged(base, 2), version=2)
    server.set_params(_rigged(base, 3), version=3, tenant="ab:cand")
    client = InferenceClient(host, port, actor_id=0)
    try:
        obs = np.random.default_rng(0).standard_normal(
            (4, 6)).astype(np.float32)
        rp = client.infer(obs, tenant=TENANT_PRIMARY)
        ra = client.infer(obs, tenant="ab:cand")
        assert rp["version"] == 2 and rp["tenant"] == TENANT_PRIMARY
        assert ra["version"] == 3 and ra["tenant"] == "ab:cand"
        assert all(int(a) == 2 for a in np.asarray(rp["actions"]))
        assert all(int(a) == 3 for a in np.asarray(ra["actions"]))
        tm = server.telemetry_summary()
        assert tm["tenant/served"] >= 2.0
        assert tm["tenant/ab:cand/requests"] == 1.0
    finally:
        client.close()
        server.close()


def test_shadow_is_mirror_only_and_counts_divergence():
    policy = BatchedPolicy(MLP, seed=12, obs_dim=6, buckets=(8,))
    server = InferenceServer(policy, max_batch=8, cutoff_us=300,
                             tenants=("shadow:next",))
    host, port = server.address
    base = policy.get_weights()
    server.set_params(_rigged(base, 1), version=1)
    # shadow θ rigged to a DIFFERENT action: every mirrored row diverges
    server.set_params(_rigged(base, 4), version=4, tenant="shadow:next")
    client = InferenceClient(host, port, actor_id=5)
    try:
        rej = client.infer(np.zeros((2, 6), np.float32),
                           tenant="shadow:next")
        assert "mirror-only" in str(rej.get("error", ""))
        for i in range(4):
            r = client.infer(np.random.default_rng(i).standard_normal(
                (4, 6)).astype(np.float32))
            assert r["tenant"] == TENANT_PRIMARY  # never a shadow reply
            assert all(int(a) == 1 for a in np.asarray(r["actions"]))
        # the mirror runs after the primary waiters are released, so the
        # last reply can arrive before its rows are counted
        deadline = time.monotonic() + 5.0
        while True:
            tm = server.telemetry_summary()
            if (tm["tenant/shadow:next/shadow_diverged"] >= 16.0
                    or time.monotonic() > deadline):
                break
            time.sleep(0.01)
        assert tm["tenant/shadow:next/shadow_requests"] >= 16.0
        assert tm["tenant/shadow:next/shadow_diverged"] >= 16.0
        assert tm["tenant/shadow:next/requests"] == 0.0  # served nobody
    finally:
        client.close()
        server.close()


def test_mid_batch_swap_keeps_reply_consistent():
    """set_params racing _run_batch (ISSUE 20 satellite): every reply's
    (actions, version) pair must come from ONE θ generation per tenant —
    the rigged weights make any torn capture visible as an action that
    contradicts the reply's own version stamp."""
    policy = BatchedPolicy(MLP, seed=13, obs_dim=6, buckets=(8,))
    server = InferenceServer(policy, max_batch=8, cutoff_us=2000,
                             tenants=("ab:cand",))
    host, port = server.address
    base = policy.get_weights()
    server.set_params(_rigged(base, 0), version=0)
    server.set_params(_rigged(base, 1), version=1, tenant="ab:cand")
    stop = threading.Event()
    problems: list[str] = []

    def swapper() -> None:
        v = 2
        while not stop.is_set():
            server.set_params(_rigged(base, v), version=v)
            server.set_params(_rigged(base, v + 1), version=v + 1,
                              tenant="ab:cand")
            v += 2
            time.sleep(0.002)

    def drive(aid: int, tenant: str) -> None:
        rng = np.random.default_rng(aid)
        c = InferenceClient(host, port, actor_id=aid)
        try:
            done = 0
            while done < 40 and not problems:
                obs = rng.standard_normal(
                    (int(rng.integers(1, 6)), 6)).astype(np.float32)
                r = c.infer(obs, seq=done, tenant=tenant)
                if r.get("shed"):
                    time.sleep(r.get("retry_after_ms", 10) / 1e3)
                    continue
                if "error" in r:
                    problems.append(f"aid {aid}: {r['error']}")
                    return
                acts = np.asarray(r["actions"])
                want = int(r["version"]) % 5
                if r["tenant"] != tenant:
                    problems.append(
                        f"aid {aid}: tenant {r['tenant']} != {tenant}")
                if not all(int(a) == want for a in acts):
                    problems.append(
                        f"aid {aid}: actions {acts.tolist()} vs version "
                        f"{r['version']} (torn θ capture)")
                done += 1
        finally:
            c.close()

    sw = threading.Thread(target=swapper, daemon=True)
    sw.start()
    drivers = ([threading.Thread(target=drive, args=(a, TENANT_PRIMARY))
                for a in (0, 1, 2)]
               + [threading.Thread(target=drive, args=(a, "ab:cand"))
                  for a in (3, 4)])
    for t in drivers:
        t.start()
    for t in drivers:
        t.join(timeout=60)
    stop.set()
    sw.join(timeout=10)
    server.close()
    assert problems == []
    assert not any(t.is_alive() for t in drivers)
