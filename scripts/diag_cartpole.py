"""Diagnostic: CartPole learning curve under cartpole_config().

Runs training with periodic eval to find where/why the run lands at ~120
instead of >=475 (VERDICT weak #1). Not part of the package.
"""
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")
from distributed_deep_q_tpu.parallel.mesh import set_cpu_device_count
set_cpu_device_count(8)

import numpy as np

from distributed_deep_q_tpu.config import cartpole_config
from distributed_deep_q_tpu.train import train_single_process, evaluate

cfg = cartpole_config()
cfg.mesh.backend = "cpu"
cfg.train.eval_every = 2_000
cfg.train.eval_episodes = 5

from distributed_deep_q_tpu.config import apply_overrides

apply_overrides(cfg, sys.argv[1:])
for arg in sys.argv[1:]:
    print(f"override {arg}")

import tempfile

from distributed_deep_q_tpu.metrics import Metrics

jsonl = tempfile.mktemp(suffix=".jsonl")
t0 = time.time()
out = train_single_process(cfg, metrics=Metrics(jsonl_path=jsonl),
                           log_every=2_000)
for line in open(jsonl):
    print(line.strip())
solver = out.pop("solver")
final = evaluate(solver, cfg, episodes=10)
print(f"\nwall={time.time()-t0:.0f}s final10={final:.1f} summary={ {k: v for k, v in out.items()} }")
