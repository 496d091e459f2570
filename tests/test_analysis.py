"""Static-analysis suite tests — each rule catches its synthetic bad
module, suppression works (and unsuppressed findings still fail), and
the self-hosting gate holds the real tree at zero findings."""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from distributed_deep_q_tpu.analysis import repo_root, run_all
from distributed_deep_q_tpu.analysis import (
    atomic_writes, blocking, config_keys, locks, metric_keys,
    protocol_drift, purity, threads)
from distributed_deep_q_tpu.analysis.core import Source


def src(text: str, path: str = "synthetic.py") -> Source:
    return Source.parse(textwrap.dedent(text), path)


def rules(findings) -> set[str]:
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# lock discipline
# ---------------------------------------------------------------------------

LOCK_REG = locks.LockRegistry(
    attrs={"count": locks.Guard("lock", "Server", ("self", "server"))},
    globals={"mod.py": {"g_state": "g_lock"}},
)


def test_locks_unguarded_access_caught():
    findings = locks.check_sources([src("""
        class Server:
            def bump(self):
                self.count += 1
    """)], LOCK_REG)
    assert rules(findings) == {locks.RULE_UNGUARDED}
    assert findings[0].line == 4


def test_locks_guarded_access_clean():
    findings = locks.check_sources([src("""
        class Server:
            def bump(self):
                with self.lock:
                    self.count += 1
    """)], LOCK_REG)
    assert findings == []


def test_locks_lambda_inside_with_counts_as_held():
    findings = locks.check_sources([src("""
        class Server:
            def drain(self):
                with self.lock:
                    wait(lambda: self.count == 0)
    """)], LOCK_REG)
    assert findings == []


def test_locks_init_exempt_but_other_methods_not():
    findings = locks.check_sources([src("""
        class Server:
            def __init__(self):
                self.count = 0
            def peek(self):
                return self.count
    """)], LOCK_REG)
    assert [f.line for f in findings] == [6]


def test_locks_foreign_receiver_checked_unrelated_skipped():
    findings = locks.check_sources([src("""
        def loop(server, cfg):
            x = server.count          # guarded receiver: finding
            y = cfg.count             # unrelated object: skipped
            with server.lock:
                z = server.count      # held: clean
    """)], LOCK_REG)
    assert [f.line for f in findings] == [3]


def test_locks_module_global_guard():
    findings = locks.check_sources([src("""
        import threading
        g_lock = threading.Lock()
        g_state = None

        def bad():
            global g_state
            g_state = 1

        def good():
            global g_state
            with g_lock:
                g_state = 2
    """, path="mod.py")], LOCK_REG)
    assert rules(findings) == {locks.RULE_UNGUARDED}
    assert all(f.line in (7, 8) for f in findings)


def test_locks_order_cycle_detected():
    findings = locks.check_sources([src("""
        class A:
            def one(self):
                with self.lock:
                    with self.other:
                        pass
            def two(self):
                with self.other:
                    with self.lock:
                        pass
    """)], locks.LockRegistry(attrs={
        "x": locks.Guard("lock", "A"), "y": locks.Guard("other", "A")}))
    assert rules(findings) == {locks.RULE_CYCLE}


def test_locks_consistent_order_no_cycle():
    findings = locks.check_sources([src("""
        class A:
            def one(self):
                with self.lock:
                    with self.other:
                        pass
            def two(self):
                with self.lock:
                    with self.other:
                        pass
    """)], locks.LockRegistry(attrs={
        "x": locks.Guard("lock", "A"), "y": locks.Guard("other", "A")}))
    assert findings == []


# ---------------------------------------------------------------------------
# purity
# ---------------------------------------------------------------------------


def test_purity_impure_jit_body_caught():
    findings = purity.check_sources([src("""
        import jax, time, numpy as np

        stats = {}

        def step(state, batch):
            print("tracing")
            t = time.time()
            host = np.asarray(batch)
            stats["calls"] = t          # captured-module-state mutation
            return state

        train = jax.jit(step)
    """)])
    assert rules(findings) == {"purity.print", "purity.time",
                               "purity.host-sync", "purity.captured-write"}


def test_purity_nested_body_may_store_to_the_enclosing_kernels_refs():
    """A Pallas kernel's ``@pl.when`` body stores to the kernel's own ref
    arguments: names of the traced function it is nested in, not captured
    state. The same body writing module state is still caught."""
    findings = purity.check_sources([src("""
        from jax.experimental import pallas as pl

        seen = {}

        def kernel(x_ref, o_ref, acc_ref):
            @pl.when(pl.program_id(0) == 0)
            def _():
                acc_ref[...] = x_ref[...]
                seen["first"] = True      # module state: still a finding

            o_ref[...] = acc_ref[...]

        def run(x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """)])
    assert [f.rule for f in findings] == ["purity.captured-write"]
    assert "'seen'" in findings[0].message


def test_purity_non_jitted_function_not_flagged():
    findings = purity.check_sources([src("""
        import numpy as np

        def feed(batch):
            print("host side")
            return np.asarray(batch)
    """)])
    assert findings == []


def test_purity_callee_expansion_and_partial_wrapper():
    findings = purity.check_sources([src("""
        import functools, jax
        import numpy as np

        def helper(x):
            return x.item()

        def kernel(ref, o_ref):
            o_ref[0] = helper(ref[0])

        jax.experimental.pallas.pallas_call(
            functools.partial(kernel, 3))
    """)])
    assert rules(findings) == {"purity.host-sync"}


def test_purity_local_alias_resolves_to_kernel():
    findings = purity.check_sources([src("""
        import functools, random

        def build(pl):
            def kernel(ref):
                ref[0] = random.random()
            k = functools.partial(kernel, 1)
            return pl.pallas_call(k)
    """)])
    assert "purity.host-rng" in rules(findings)


def test_purity_gated_alias_lints_both_branches():
    """``train_fn = plane_fn if gate else tree_fn`` (the stacked/donated
    step builders' static gate) must make BOTH candidate bodies roots."""
    findings = purity.check_sources([src("""
        import jax, time
        import numpy as np

        def build(use_plane):
            def plane_fn(x):
                return np.asarray(x)
            def tree_fn(x):
                return x + time.time()
            train_fn = plane_fn if use_plane else tree_fn
            return jax.jit(train_fn)
    """)])
    assert rules(findings) == {"purity.host-sync", "purity.time"}


def test_purity_rng_and_item_decorated():
    findings = purity.check_sources([src("""
        import jax, numpy as np

        @jax.jit
        def step(x):
            noise = np.random.normal()
            return (x + noise).item()
    """)])
    assert rules(findings) == {"purity.host-rng", "purity.host-sync"}


def test_purity_local_writes_allowed():
    findings = purity.check_sources([src("""
        import jax

        @jax.jit
        def step(batch):
            batch = dict(batch)
            batch["x"] = 1
            acc = {}
            acc["y"] = 2
            return batch, acc
    """)])
    assert findings == []


# ---------------------------------------------------------------------------
# protocol drift
# ---------------------------------------------------------------------------

SERVER_SRC = """
    class ReplayFeedServer:
        def _dispatch(self, req):
            method = req.get("method")
            if method == "ping":
                return {"ok": True}
            if method == "orphaned":
                return {"ok": True}
"""

PROTO_SRC = """
    _KIND_A, _KIND_B = range(2)

    def encode(msg):
        return [_KIND_A, _KIND_B]

    def _decode(payload):
        return [_KIND_A]
"""


def test_protocol_orphan_and_unhandled_and_wire_skew():
    findings = protocol_drift.check_sources(
        src(SERVER_SRC, "server.py"), src(PROTO_SRC, "proto.py"),
        [src("""
            def go(client):
                client.call("ping")
                client.call("renamed_method")
        """, "client.py")])
    by_rule = {f.rule: f for f in findings}
    assert by_rule["protocol.unhandled-method"].path == "client.py"
    assert "renamed_method" in by_rule["protocol.unhandled-method"].message
    assert "orphaned" in by_rule["protocol.orphan-handler"].message
    assert "_KIND_B" in by_rule["protocol.wire-skew"].message


def test_protocol_clean_when_paired():
    findings = protocol_drift.check_sources(
        src(SERVER_SRC, "server.py"),
        src("""
            _KIND_A = 0
            def encode(m):
                return _KIND_A
            def _decode(p):
                return _KIND_A
        """, "proto.py"),
        [src("""
            def go(c):
                c.call("ping")
                c.call_once("orphaned")
        """, "client.py")])
    assert findings == []


# ---------------------------------------------------------------------------
# config keys
# ---------------------------------------------------------------------------

SCHEMA = {"train": {"lr", "total_steps"}, "net": {"kind"}}


def test_config_unknown_key_caught():
    findings = config_keys.check_sources(SCHEMA, [src("""
        def run(cfg):
            cfg.train.lr = 1e-3
            return cfg.train.total_stepz
    """)])
    assert rules(findings) == {config_keys.RULE}
    assert "train.total_stepz" in findings[0].message


def test_config_non_config_roots_skipped():
    findings = config_keys.check_sources(SCHEMA, [src("""
        def run(solver, cfg):
            solver.train.whatever()   # root not a config expr
            cfg.optimizer.zero_grad() # unknown section: skipped
            return cfg.net.kind
    """)])
    assert findings == []


def test_config_schema_parsed_from_real_config():
    cfg_src = Source.load(
        os.path.join(repo_root(), config_keys.CONFIG_FILE),
        config_keys.CONFIG_FILE)
    schema = config_keys.config_schema(cfg_src)
    assert set(schema) == {"net", "replay", "train", "env", "actors",
                           "mesh", "trace", "inference", "health",
                           "autoscale"}
    assert "num_actions" in schema["net"]
    assert "server_snapshot_path" in schema["train"]
    assert "cutoff_us" in schema["inference"]
    assert "fast_window_s" in schema["health"]
    assert "recover_ticks" in schema["autoscale"]


# ---------------------------------------------------------------------------
# atomic-write discipline
# ---------------------------------------------------------------------------


def test_atomic_writes_raw_binary_sinks_caught():
    findings = atomic_writes.check_sources([src("""
        import pickle
        import numpy as np

        def dump(path, arr, state):
            with open(path, "wb") as f:          # raw binary write
                f.write(arr.tobytes())
            np.savez(path, **state)              # savez to a real path
            arr.tofile(path)                     # unbuffered raw write
            with open(path, "wb") as f:
                pickle.dump(state, f)            # banned on persisted paths
    """)])
    assert rules(findings) == {atomic_writes.RULE}
    assert len(findings) == 5  # two opens, savez, tofile, pickle.dump


def test_atomic_writes_reads_text_and_memory_sinks_clean():
    findings = atomic_writes.check_sources([src("""
        import io
        import numpy as np

        def fine(path, state, log_line):
            with open(path, "rb") as f:          # binary READ
                blob = f.read()
            with open(path + ".jsonl", "a") as f:  # text append (metrics)
                f.write(log_line)
            np.savez(io.BytesIO(), **state)      # in-memory serialize
            buf = io.BytesIO()
            np.savez(buf, **state)               # named memory sink
            return blob
    """)])
    assert findings == []


def test_atomic_writes_nonliteral_mode_skipped_pragma_works():
    findings = atomic_writes.check_sources([src("""
        def edge(path, mode, blob):
            with open(path, mode) as f:          # non-literal mode: skipped
                f.write(blob)
            with open(path, "wb") as f:  # ddq: allow(durability.raw-write)
                f.write(blob)
    """)])
    assert findings == []


def test_atomic_writes_durability_module_is_exempt():
    bad = """
        def primitive(path, blob):
            with open(path, "wb") as f:
                f.write(blob)
    """
    assert atomic_writes.check_sources(
        [src(bad, atomic_writes.EXEMPT_FILES[0])]) == []
    assert len(atomic_writes.check_sources(
        [src(bad, "distributed_deep_q_tpu/other.py")])) == 1


# ---------------------------------------------------------------------------
# suppression pragma
# ---------------------------------------------------------------------------


def test_pragma_suppresses_exact_rule():
    findings = locks.check_sources([src("""
        class Server:
            def peek(self):
                return self.count  # ddq: allow(locks.unguarded)
    """)], LOCK_REG)
    assert findings == []


def test_pragma_pass_prefix_and_star():
    base = """
        class Server:
            def peek(self):
                return self.count  {pragma}
    """
    for pragma in ("# ddq: allow(locks)", "# ddq: allow(*)"):
        findings = locks.check_sources(
            [src(base.format(pragma=pragma))], LOCK_REG)
        assert findings == [], pragma


def test_unsuppressed_finding_still_fails():
    """The pragma is line- and rule-scoped: a wrong rule name or a
    different line must NOT silence the finding."""
    findings = locks.check_sources([src("""
        class Server:  # ddq: allow(locks.unguarded)
            def peek(self):
                return self.count  # ddq: allow(purity.print)
    """)], LOCK_REG)
    assert rules(findings) == {locks.RULE_UNGUARDED}


# ---------------------------------------------------------------------------
# metric keys
# ---------------------------------------------------------------------------


def _tracing_src() -> Source:
    return Source.load(os.path.join(
        repo_root(), "distributed_deep_q_tpu", "tracing.py"))


def test_metric_keys_typo_caught():
    findings = metric_keys.check_sources([src("""
        metrics.gauge("queue/replay_sise", 1)
        self.metrics.count("grad_stepz")
    """)], _tracing_src())
    assert [f.rule for f in findings] == [metric_keys.RULE_METRIC] * 2


def test_metric_keys_known_and_dynamic_names_clean():
    findings = metric_keys.check_sources([src("""
        metrics.gauge("queue/replay_size", 1)
        metrics.count("grad_steps")
        out[f"rpc/{m}_calls"] = 1            # dynamic: out of static reach
        h.summary(prefix="trace/ingest_lag_ms")
    """)], _tracing_src())
    assert findings == []


def test_metric_keys_span_names_checked_against_tracer_tables():
    findings = metric_keys.check_sources([src("""
        from distributed_deep_q_tpu import tracing
        with tracing.span("env_step"):
            tracing.instant("shed")
        with tracing.span("env_stepp"):
            tracing.instant("shedd")
    """)], _tracing_src())
    assert [f.rule for f in findings] == [metric_keys.RULE_SPAN] * 2
    assert all("tracing." in f.message for f in findings)


def test_metric_keys_pragma_suppresses():
    findings = metric_keys.check_sources([src("""
        metrics.gauge("queue/oops", 1)  # ddq: allow(metric_keys.unknown-metric)
    """)], _tracing_src())
    assert findings == []


def test_metric_keys_gate_fails_on_seeded_typo():
    """Un-declaring a really-emitted name makes the REAL tree fail —
    i.e. a typo'd emit site (name not in the registry) fails the gate."""
    culled = frozenset(metric_keys.REGISTRY - {"queue/replay_size"})
    findings = metric_keys.check(repo_root(), registry=culled)
    assert any(f.rule == metric_keys.RULE_METRIC
               and "queue/replay_size" in f.message for f in findings)


# ---------------------------------------------------------------------------
# self-hosting gate
# ---------------------------------------------------------------------------


def test_self_hosting_zero_findings():
    """The shipped tree passes every analyzer — the gate ratchets from
    here: any new unguarded access / impure jit body / protocol or
    config drift fails tier-1."""
    findings = run_all()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_gate_cli_exits_zero():
    proc = subprocess.run(
        [sys.executable, os.path.join(repo_root(), "scripts",
                                      "analysis_gate.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_gate_cli_fails_on_broken_invariant(tmp_path):
    """Deliberately breaking a lock invariant in a COPY of the tree
    makes the gate exit non-zero with a file:line finding."""
    import shutil
    root = repo_root()
    for d in ("distributed_deep_q_tpu", "scripts", "tests"):
        shutil.copytree(os.path.join(root, d), tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    target = tmp_path / "distributed_deep_q_tpu/rpc/replay_server.py"
    text = target.read_text().replace(
        'if method == "reset_stream":', 'if method == "reset_streamz":')
    target.write_text(text)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "analysis_gate.py"),
         "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "protocol." in proc.stdout
    # findings carry file:line
    assert any(line.split(":")[1].isdigit()
               for line in proc.stdout.splitlines() if ":" in line)
    # --json: one parseable object per finding on stdout, verdict on
    # stderr; --rule narrows to the protocol pass
    import json
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "scripts", "analysis_gate.py"),
         "--root", str(tmp_path), "--json", "--rule", "protocol"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    objs = [json.loads(line) for line in proc.stdout.splitlines()]
    assert objs and all(
        set(o) == {"rule", "path", "line", "message"} for o in objs)
    assert all(o["rule"].startswith("protocol.") for o in objs)
    assert "FAILED" in proc.stderr


def test_chaos_smoke_preflight_passes_on_clean_tree():
    sys.path.insert(0, os.path.join(repo_root(), "scripts"))
    try:
        import chaos_smoke
        chaos_smoke._require_clean_gate()  # must not SystemExit
    finally:
        sys.path.pop(0)

# ---------------------------------------------------------------------------
# thread-lifecycle registry
# ---------------------------------------------------------------------------

THREAD_REG = threads.ThreadRegistry(
    specs={
        ("mod.py", "_run"): threads.ThreadSpec(
            name="worker", owner="W", stop=("event", "_stop"),
            joined_in="close"),
    },
    files=("mod.py",),
)

GOOD_THREAD_SRC = """
    import threading

    class W:
        def __init__(self):
            self._stop = threading.Event()
            self._t = threading.Thread(
                target=self._run, name="worker", daemon=True)
            self._t.start()

        def _run(self):
            while not self._stop.wait(0.1):
                pass

        def close(self):
            self._stop.set()
            self._t.join()
"""


def test_threads_registered_lifecycle_clean():
    findings = threads.check_sources(
        [src(GOOD_THREAD_SRC, "mod.py")], THREAD_REG)
    assert findings == []


def test_threads_unregistered_spawn_caught():
    findings = threads.check_sources([src("""
        import threading

        class W:
            def go(self):
                threading.Thread(target=self._other, daemon=True).start()
    """, "mod.py")], THREAD_REG)
    assert rules(findings) == {threads.RULE_UNREGISTERED}
    assert "_other" in findings[0].message


def test_threads_name_mismatch_and_missing_join_caught():
    findings = threads.check_sources([src("""
        import threading

        class W:
            def __init__(self):
                self._stop = threading.Event()
                self._t = threading.Thread(
                    target=self._run, name="wrong-name", daemon=True)

            def _run(self):
                pass

            def close(self):
                self._stop.set()  # no join on self._t
    """, "mod.py")], THREAD_REG)
    assert rules(findings) == {threads.RULE_MISMATCH, threads.RULE_NO_JOIN}


def test_threads_unset_stop_event_caught():
    """A stop event nobody ever .set()s is an unstoppable thread."""
    findings = threads.check_sources([src("""
        import threading

        class W:
            def __init__(self):
                self._stop = threading.Event()
                self._t = threading.Thread(
                    target=self._run, name="worker", daemon=True)

            def _run(self):
                while not self._stop.wait(0.1):
                    pass

            def close(self):
                self._t.join()
    """, "mod.py")], THREAD_REG)
    assert rules(findings) == {threads.RULE_NO_STOP}


FLAG_REG = threads.ThreadRegistry(
    specs={
        ("mod.py", "_run"): threads.ThreadSpec(
            name="drain", owner="D", stop=("flag", "_closed", "_cv"),
            joined_in="close"),
    },
    files=("mod.py",),
)

FLAG_SRC = """
    import threading

    class D:
        def __init__(self):
            self._cv = threading.Condition()
            self._closed = False
            self._t = threading.Thread(
                target=self._run, name="drain", daemon=True)

        def _run(self):
            with self._cv:
                while not self._closed:
                    self._cv.wait()

        def close(self):
            {shutdown}
            self._t.join()
"""


def test_threads_stop_flag_write_outside_guard_caught():
    findings = threads.check_sources([src(
        FLAG_SRC.format(shutdown="self._closed = True"), "mod.py")],
        FLAG_REG)
    assert rules(findings) == {threads.RULE_STOP_UNGUARDED}
    # the __init__ seed write is exempt (single-threaded construction)
    assert len(findings) == 1


def test_threads_stop_flag_write_under_guard_clean():
    shutdown = ("with self._cv:\n"
                "                self._closed = True\n"
                "                self._cv.notify_all()")
    findings = threads.check_sources([src(
        FLAG_SRC.format(shutdown=shutdown), "mod.py")], FLAG_REG)
    assert findings == []


def test_threads_daemon_without_join_needs_reason():
    reg = threads.ThreadRegistry(
        specs={
            ("mod.py", "_run"): threads.ThreadSpec(
                name="w", owner="W", stop=("event", "_stop"),
                joined_in=None),  # no why_no_join rationale
        },
        files=("mod.py",),
    )
    findings = threads.check_sources([src("""
        import threading

        class W:
            def go(self):
                self._t = threading.Thread(
                    target=self._run, name="w", daemon=True)

            def _run(self):
                pass

            def close(self):
                self._stop.set()
    """, "mod.py")], reg)
    assert rules(findings) == {threads.RULE_NO_JOIN}
    assert "why_no_join" in findings[0].message


# ---------------------------------------------------------------------------
# blocking-while-locked
# ---------------------------------------------------------------------------

BLOCK_LOCKS = {"replay_lock", "_cv"}


def blocking_findings(text: str, path: str = "mod.py"):
    return blocking.check_sources([src(text, path)],
                                  lock_names=BLOCK_LOCKS,
                                  unlocked=frozenset({"__init__"}))


def test_blocking_sleep_under_lock_caught():
    findings = blocking_findings("""
        import time

        class S:
            def flush(self):
                with self.replay_lock:
                    time.sleep(0.1)
    """)
    assert rules(findings) == {blocking.RULE}
    assert "time.sleep()" in findings[0].message


def test_blocking_off_lock_clean():
    findings = blocking_findings("""
        import time

        class S:
            def flush(self):
                with self.replay_lock:
                    rows = self.pop()
                time.sleep(0.1)
    """)
    assert findings == []


def test_blocking_interprocedural_callee_expansion():
    """The fsync lives two calls away from the lock: the finding lands
    on the blocking line, with the lock-entry site in the message."""
    findings = blocking_findings("""
        import os

        class S:
            def snapshot(self):
                with self.replay_lock:
                    self._persist()

            def _persist(self):
                self._sync()

            def _sync(self):
                os.fsync(self.fd)
    """)
    assert rules(findings) == {blocking.RULE}
    [f] = findings
    assert "os.fsync()" in f.message and "entered from mod.py:" in f.message


def test_blocking_cv_wait_on_held_lock_exempt_foreign_wait_caught():
    """Condition.wait on the HELD condition releases it (not blocking-
    under-lock); waiting on a foreign event under the lock is."""
    findings = blocking_findings("""
        class S:
            def take(self):
                with self._cv:
                    while not self.ready:
                        self._cv.wait()

            def bad(self):
                with self._cv:
                    self.other_event.wait()
    """)
    assert [f.rule for f in findings] == [blocking.RULE]
    assert "foreign event" in findings[0].message


def test_blocking_pragma_suppresses():
    findings = blocking_findings("""
        class C:
            def call(self):
                with self.replay_lock:
                    return recv_msg(self.sock)  # ddq: allow(blocking.under-lock)
    """)
    assert findings == []


def test_blocking_init_is_not_a_lock_root():
    findings = blocking_findings("""
        import time

        class S:
            def __init__(self):
                with self.replay_lock:
                    time.sleep(0.1)
    """)
    assert findings == []


# ---------------------------------------------------------------------------
# condition-variable discipline
# ---------------------------------------------------------------------------

CV_REG = locks.LockRegistry(
    attrs={}, globals={}, conditions=frozenset({"_cv"}))


def test_cv_wait_without_while_caught():
    findings = locks.check_sources([src("""
        class S:
            def take(self):
                with self._cv:
                    if not self.ready:
                        self._cv.wait()
    """)], CV_REG)
    assert rules(findings) == {locks.RULE_CV_WAIT}


def test_cv_wait_in_while_and_wait_for_clean():
    findings = locks.check_sources([src("""
        class S:
            def take(self):
                with self._cv:
                    while not self.ready:
                        self._cv.wait()

            def take2(self):
                with self._cv:
                    self._cv.wait_for(lambda: self.ready)
    """)], CV_REG)
    assert findings == []


def test_cv_notify_without_lock_caught():
    findings = locks.check_sources([src("""
        class S:
            def put(self, row):
                self.rows.append(row)
                self._cv.notify_all()
    """)], CV_REG)
    assert rules(findings) == {locks.RULE_CV_NOTIFY}


def test_cv_notify_under_lock_clean():
    findings = locks.check_sources([src("""
        class S:
            def put(self, row):
                with self._cv:
                    self.rows.append(row)
                    self._cv.notify_all()
    """)], CV_REG)
    assert findings == []


# ---------------------------------------------------------------------------
# wire-verb idempotence classes
# ---------------------------------------------------------------------------

PROTO_OK = """
    _KIND_A = 0

    def encode(m):
        return _KIND_A

    def _decode(p):
        return _KIND_A
"""


def test_protocol_unclassified_and_stale_verb_caught():
    findings = protocol_drift.check_sources(
        src(SERVER_SRC, "server.py"), src(PROTO_OK, "proto.py"),
        [src("""
            def go(c):
                c.call("ping")
                c.call_once("orphaned")
        """, "client.py")],
        verb_classes={"ping": protocol_drift.IDEMPOTENT,
                      "gone": protocol_drift.DEDUP_KEYED})
    by_rule = {f.rule: f for f in findings}
    assert set(by_rule) == {"protocol.unclassified-verb",
                            "protocol.stale-verb-class"}
    assert "orphaned" in by_rule["protocol.unclassified-verb"].message
    assert "gone" in by_rule["protocol.stale-verb-class"].message


def test_protocol_unsafe_verb_on_retry_path_caught():
    """.call() retries on failure — an unsafe verb must not ride it;
    call_once (single attempt) is the sanctioned escape hatch."""
    findings = protocol_drift.check_sources(
        src(SERVER_SRC, "server.py"), src(PROTO_OK, "proto.py"),
        [src("""
            def go(c):
                c.call("ping")
                c.call_once("orphaned")
        """, "client.py")],
        verb_classes={"ping": protocol_drift.UNSAFE,
                      "orphaned": protocol_drift.UNSAFE})
    assert rules(findings) == {"protocol.unsafe-resend"}
    [f] = findings
    assert "'ping'" in f.message and f.path == "client.py"


def test_protocol_every_real_verb_is_classified():
    """Every verb in the live VERB_CLASSES table names a known class —
    the table itself cannot drift to a typo'd class name."""
    valid = {protocol_drift.IDEMPOTENT, protocol_drift.DEDUP_KEYED,
             protocol_drift.UNSAFE}
    assert protocol_drift.VERB_CLASSES
    assert set(protocol_drift.VERB_CLASSES.values()) <= valid


# ---------------------------------------------------------------------------
# new-pass self-host ratchets + gate CLI surface
# ---------------------------------------------------------------------------


def test_threads_and_blocking_self_host_zero():
    """The live tree satisfies the thread-lifecycle and blocking
    ratchets pass-by-pass (run_all covers the union; these keep the
    attribution obvious when one regresses)."""
    root = repo_root()
    assert threads.check(root) == []
    assert blocking.check(root) == []


def test_gate_cli_rule_filter_json_and_list_rules():
    gate = os.path.join(repo_root(), "scripts", "analysis_gate.py")
    proc = subprocess.run(
        [sys.executable, gate, "--rule", "locks", "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # --json keeps stdout machine-parseable: findings only (none on a
    # clean tree); the human verdict goes to stderr
    assert proc.stdout.strip() == ""
    assert "clean" in proc.stderr

    proc = subprocess.run(
        [sys.executable, gate, "--list-rules"],
        capture_output=True, text=True, timeout=120)
    listed = proc.stdout.split()
    assert proc.returncode == 0
    for rule in ("threads.unregistered", "blocking.under-lock",
                 "locks.cv-wait-no-loop", "protocol.unsafe-resend"):
        assert rule in listed


def test_gate_cli_unknown_rule_prefix_exits_2():
    gate = os.path.join(repo_root(), "scripts", "analysis_gate.py")
    proc = subprocess.run(
        [sys.executable, gate, "--rule", "nonsense"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "unknown rule prefix" in proc.stderr
