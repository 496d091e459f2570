"""Profiling subsystem (SURVEY §5.1): StepTimer breakdown keys exist, phase
sums track the measured step time, and the train loop emits them."""

import json
import time

import numpy as np

from distributed_deep_q_tpu.config import cartpole_config
from distributed_deep_q_tpu.metrics import Metrics
from distributed_deep_q_tpu.profiling import StepTimer, TraceWindow
from distributed_deep_q_tpu.train import train_single_process


def test_step_timer_phases_sum_to_step_time():
    timer = StepTimer()
    for _ in range(6):
        with timer.phase("sample"):
            time.sleep(0.01)
        with timer.phase("dispatch"):
            time.sleep(0.005)
        timer.step_done()
    s = timer.summary()
    assert set(s) >= {"time_sample_ms", "time_dispatch_ms", "time_step_ms"}
    assert s["time_sample_ms"] >= 9.0
    assert s["time_dispatch_ms"] >= 4.0
    # phases account for (almost all of) the measured step wall time
    phase_sum = s["time_sample_ms"] + s["time_dispatch_ms"]
    assert phase_sum <= s["time_step_ms"] * 1.25
    assert s["time_step_ms"] <= phase_sum + 5.0  # loop overhead bound
    # summary resets the accumulators
    assert timer.summary() == {}


def test_step_timer_measure_device_blocks_and_records():
    import jax.numpy as jnp
    timer = StepTimer()
    x = jnp.ones((256, 256)) @ jnp.ones((256, 256))
    timer.step_done()
    timer.measure_device(x)
    timer.step_done()
    s = timer.summary()
    assert "time_device_ms" in s and s["time_device_ms"] >= 0.0


def test_trace_window_writes_profile(tmp_path):
    trace = TraceWindow(str(tmp_path / "trace"), start_step=2, num_steps=3)
    import jax.numpy as jnp
    for step in range(1, 8):
        _ = jnp.square(jnp.arange(8.0)).sum()
        trace.on_step(step)
    trace.close()
    assert trace._done
    produced = list((tmp_path / "trace").rglob("*"))
    assert produced, "jax.profiler trace produced no files"


def test_train_loop_emits_time_breakdown(tmp_path):
    jsonl = tmp_path / "m.jsonl"
    cfg = cartpole_config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.train.total_steps = 1_200
    cfg.train.train_every = 4
    cfg.train.grad_steps_per_train = 1
    cfg.replay.learn_start = 200
    train_single_process(cfg, metrics=Metrics(jsonl_path=str(jsonl)),
                         log_every=100)
    recs = [json.loads(l) for l in jsonl.read_text().splitlines()]
    timed = [r for r in recs if "time_sample_ms" in r]
    assert timed, "no per-step time breakdown logged"
    for r in timed:
        assert "time_dispatch_ms" in r and "time_device_ms" in r
        assert np.isfinite(r["time_sample_ms"])
