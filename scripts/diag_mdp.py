"""Numerics sanity: solver must recover analytic Q* on a tiny known MDP.

2-state MDP, one-hot obs. State 0: action 0 -> stay s0 r=0; action 1 -> s1
r=1. State 1: any action -> terminal r=0 ... make it simple:

Chain: s0 -a1-> s1 (r=1), s1 -a1-> terminal (r=1); a0 stays with r=0.
gamma=0.9.
Q*(s1,a1)=1, Q*(s1,a0)=0.9*V(s1)=0.9*1=0.9? V(s1)=max(Q)=1 => Q*(s1,a0)=0+0.9*1=0.9
Q*(s0,a1)=1+0.9*V(s1)=1.9 ; Q*(s0,a0)=0+0.9*V(s0)=0.9*1.9=1.71
"""
import jax

jax.config.update("jax_platforms", "cpu")
from distributed_deep_q_tpu.parallel.mesh import set_cpu_device_count
set_cpu_device_count(8)

import numpy as np

from distributed_deep_q_tpu.config import Config
from distributed_deep_q_tpu.replay.replay_memory import ReplayMemory
from distributed_deep_q_tpu.solver import Solver

cfg = Config()
cfg.mesh.backend = "cpu"
cfg.net.kind = "mlp"
cfg.net.num_actions = 2
cfg.net.hidden = (64, 64)
cfg.train.lr = 1e-3
cfg.train.gamma = 0.9
cfg.train.target_update_period = 100

solver = Solver(cfg, obs_dim=2)
replay = ReplayMemory(1024, (2,), np.float32, seed=0)

s0 = np.array([1, 0], np.float32)
s1 = np.array([0, 1], np.float32)
g = 0.9
# transitions: (obs, a, r, next_obs, discount)
replay.add(s0, 0, 0.0, s0, g)
replay.add(s0, 1, 1.0, s1, g)
replay.add(s1, 0, 0.0, s1, g)
replay.add(s1, 1, 1.0, s1, 0.0)  # terminal

for i in range(4000):
    solver.train_step(replay.sample(64))

q0, q1 = solver.q_values(s0)[0], solver.q_values(s1)[0]
print("Q(s0):", q0, "expected [1.71, 1.9]")
print("Q(s1):", q1, "expected [0.9, 1.0]")
ok = (np.allclose(q0, [1.71, 1.9], atol=0.05)
      and np.allclose(q1, [0.9, 1.0], atol=0.05))
print("NUMERICS", "OK" if ok else "BROKEN")
