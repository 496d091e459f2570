"""Pallas fused loss ≡ the jnp reference path (value and gradient)."""

import jax
import jax.numpy as jnp
import numpy as np

from distributed_deep_q_tpu.config import Config
from distributed_deep_q_tpu.ops.losses import dqn_loss
from distributed_deep_q_tpu.ops.pallas_kernels import fused_dqn_loss
from distributed_deep_q_tpu.solver import Solver


def _random_batch(rng, b=32, a=6):
    return (
        jnp.asarray(rng.normal(size=(b, a)), jnp.float32),
        jnp.asarray(rng.integers(0, a, size=b), jnp.int32),
        jnp.asarray(rng.normal(size=b), jnp.float32),
        jnp.asarray(rng.uniform(0.2, 1.0, size=b), jnp.float32),
    )


def test_fused_loss_matches_reference_value_and_td():
    rng = np.random.default_rng(0)
    q, actions, targets, weights = _random_batch(rng)
    for delta in (0.5, 1.0, 2.0):
        loss_p, td_p = fused_dqn_loss(q, actions, targets, weights, delta,
                                      interpret=True)
        loss_j, td_j = dqn_loss(q, actions, targets, weights, delta)
        np.testing.assert_allclose(loss_p, loss_j, rtol=1e-6)
        np.testing.assert_allclose(td_p, td_j, rtol=1e-6)


def test_fused_loss_gradient_matches_reference():
    rng = np.random.default_rng(1)
    q, actions, targets, weights = _random_batch(rng, b=16, a=4)

    def f_pallas(qq):
        return fused_dqn_loss(qq, actions, targets, weights, 1.0,
                              interpret=True)[0]

    def f_jnp(qq):
        return dqn_loss(qq, actions, targets, weights, 1.0)[0]

    gp = jax.grad(f_pallas)(q)
    gj = jax.grad(f_jnp)(q)
    np.testing.assert_allclose(gp, gj, rtol=1e-5, atol=1e-7)


def test_solver_with_pallas_loss_trains():
    """use_pallas_loss end-to-end: identical trajectories vs the jnp path."""
    rng = np.random.default_rng(2)
    batches = []
    for _ in range(5):
        obs = rng.normal(size=(64, 4)).astype(np.float32)
        batches.append({
            "obs": obs,
            "action": rng.integers(0, 2, size=64).astype(np.int32),
            "reward": rng.normal(size=64).astype(np.float32),
            "next_obs": rng.normal(size=(64, 4)).astype(np.float32),
            "discount": np.full(64, 0.99, np.float32),
            "weight": np.ones(64, np.float32),
        })

    def run(use_pallas):
        cfg = Config()
        cfg.mesh.backend = "cpu"
        cfg.mesh.dp = 1
        cfg.train.use_pallas_loss = use_pallas
        solver = Solver(cfg, obs_dim=4)
        losses = [float(solver.train_step(dict(b))["loss"]) for b in batches]
        return losses

    lp, lj = run(True), run(False)
    np.testing.assert_allclose(lp, lj, rtol=1e-5, atol=1e-6)
