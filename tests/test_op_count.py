"""What the compiled train programs are made of (compiled-HLO census).

Pinned here, because each is a property of the PROGRAM and not of the
backend that schedules it:

- **conv chains per train program**: exactly 8 scheduled convolutions
  (three forward, three filter-gradient, two input-gradient) in the b32
  host-batch step, the fused chain's per-grad-step scan body and the
  R2D2 sequence program — ONE conv chain each, which is what the stacked
  θ/θ⁻ forward and the time-batched torso (models/qnet.py
  ``stacked_r2d2_features``) bought over one chain per net and per
  burn/train window;
- **T-independence**: the R2D2 conv count does not change with the
  sequence length — T is a shape, not an op;
- **zero host-communication ops** in the Anakin superstep, with and
  without ``train.learn_metrics``;
- **the device-side meta pack** stays a couple of fusions (it runs on
  every flush).

NOT pinned: XLA:CPU fusion and copy counts of the train programs. They
were budgets here on the premise that every surviving op is one
dispatch paid per grad step. The chip refuted it: PR 25 took the fused
body from 81 to 105 CPU fusions while ``dqn_b32.learner_only`` went
86.775 → 0.80826 ms a train step and 11.349 → 396.97 grad-steps/s
(PERF_LEDGER.jsonl, PR 25). What a train step costs is read on the chip
(``train_ms_per_step``, ``sample_ms_per_chunk``, ``write_ms_per_flush``).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_deep_q_tpu.config import (
    ActorConfig, Config, EnvConfig, MeshConfig, NetConfig, ReplayConfig,
    TrainConfig)
from distributed_deep_q_tpu.profiling import (
    compile_fused_train, hlo_op_census, hlo_scan_body_census)

TRAIN_PROGRAM_CONVS = 8  # 3 forward + 3 filter-grad + 2 input-grad
# (fusions, convolutions, copies); census must be <= elementwise
META_PACK_BUDGET = (4, 0, 2)        # measured 2/0/0 (ISSUE 8)


def _assert_within(census, budget, label):
    got = (census["fusion"], census["convolution"], census["copy"])
    assert got[0] <= budget[0] and got[1] <= budget[1] \
        and got[2] <= budget[2], (
            f"{label}: scheduled-op census {got} exceeds "
            f"(fusions, convolutions, copies) <= {budget}")


def _transition_config():
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.net = NetConfig(kind="nature_cnn", num_actions=6, dueling=True,
                        compute_dtype="bfloat16", frame_shape=(84, 84))
    cfg.train = TrainConfig(double_dqn=True, target_update_period=2500)
    cfg.replay = ReplayConfig(capacity=1024, batch_size=32, n_step=3,
                              prioritized=True, device_per=True,
                              write_chunk=64, fused_chain=2)
    return cfg


@pytest.fixture(scope="module")
def transition_solver():
    """Flagship-shaped transition solver (84×84, bf16, dueling, double,
    batch 32) shared by the plain-step and fused-chain censuses."""
    from distributed_deep_q_tpu.solver import Solver

    return Solver(_transition_config())


def _b32_step_census(request):
    """Plain host-batch b32 step: whole-module census."""
    solver = request.getfixturevalue("transition_solver")
    B = 32
    batch = {
        "obs": jnp.zeros((B, 84, 84, 4), jnp.uint8),
        "next_obs": jnp.zeros((B, 84, 84, 4), jnp.uint8),
        "action": jnp.zeros((B,), jnp.int32),
        "reward": jnp.zeros((B,), jnp.float32),
        "discount": jnp.zeros((B,), jnp.float32),
        "weight": jnp.ones((B,), jnp.float32),
    }
    return hlo_op_census(solver.learner._train_step.lower(
        solver.state, batch).compile().as_text())


def _fused_chain_body_census(request):
    """Fused flagship chain: census of the per-grad-step scan body.
    The program is built (not executed), so the census pays one
    compile."""
    from distributed_deep_q_tpu.replay.device_per import DevicePERFrameReplay

    solver = request.getfixturevalue("transition_solver")
    cfg = solver.config
    replay = DevicePERFrameReplay(cfg.replay, solver.mesh, (84, 84),
                                  stack=4, gamma=cfg.train.gamma, seed=0,
                                  write_chunk=64)
    rng = np.random.default_rng(0)
    for i in range(300):
        replay.add(rng.integers(0, 255, (84, 84), dtype=np.uint8),
                   int(rng.integers(6)), float(rng.standard_normal()),
                   done=(i % 9 == 8))
    replay.flush()
    return hlo_scan_body_census(
        compile_fused_train(solver, replay, chain=2).as_text())


def _r2d2_train_census(solver, batch):
    """Census of the compiled R2D2 host-batch train program (whole
    module: the program is unchained, so that IS the per-step count)."""
    from distributed_deep_q_tpu.parallel.multihost import global_batch

    return hlo_op_census(solver.learner._train_step.lower(
        solver.state,
        global_batch(solver.learner._batch_sharding, solver._strip(batch)),
    ).compile().as_text())


def _r2d2_program_census(request):
    solver = request.getfixturevalue("r2d2_solver")
    return _r2d2_train_census(solver, _r2d2_batch(solver, seq_len=16))


@pytest.mark.parametrize("census_of", [
    pytest.param(_b32_step_census, id="b32_step"),
    pytest.param(_fused_chain_body_census, id="fused_chain_body"),
    pytest.param(_r2d2_program_census, id="r2d2_program"),
])
def test_train_program_conv_count(census_of, request):
    """Each train program runs ONE conv chain forward and backward: the
    stacked θ/θ⁻ forward (and, for R2D2, the time-batched torso) put
    every frame of both nets through the conv stack in one pass."""
    assert census_of(request)["convolution"] == TRAIN_PROGRAM_CONVS


def test_insert_meta_pack_budget():
    """Device-side meta pack (columnar ingest, ISSUE 8): the pad +
    bitcast + priority-seed program that replaced the per-row host
    numpy pack must stay a couple of fusions — it runs on EVERY flush,
    so any op that creeps in here is paid at ingest rate, not grad
    rate."""
    import functools

    from distributed_deep_q_tpu.ops.ring_gather import padded_row_bytes
    from distributed_deep_q_tpu.replay.device_per import insert_meta_pack

    k, row_len = 64, 84 * 84 + 11  # flagship-row-shaped, not special
    rowb = padded_row_bytes(row_len)
    fn = jax.jit(functools.partial(insert_meta_pack, k=k, row_len=row_len,
                                   rowb=rowb, alpha=0.6))
    text = fn.lower(jnp.zeros((k, row_len), jnp.uint8),
                    jnp.float32(1.0)).compile().as_text()
    _assert_within(hlo_op_census(text), META_PACK_BUDGET,
                   "insert meta pack")


@pytest.fixture(scope="module")
def anakin_superstep_hlo():
    """Compiled HLO of one whole Anakin superstep (ISSUE 11) — act scan,
    ring insert, fused sample, plane train scan in ONE program — on the
    tiny mlp/signal shape the anakin tests use."""
    from distributed_deep_q_tpu.parallel.anakin import AnakinRunner

    cfg = Config(
        env=EnvConfig(id="signal", kind="signal_atari",
                      frame_shape=(10, 10), stack=2),
        net=NetConfig(kind="mlp", num_actions=4, hidden=(32, 32),
                      frame_shape=(10, 10), stack=2),
        replay=ReplayConfig(capacity=256, batch_size=16, fused_chain=2,
                            n_step=1, learn_start=0, device_resident=True,
                            write_chunk=32),
        train=TrainConfig(optimizer="adam", seed=3, stack_forwards="on"),
        actors=ActorConfig(anakin_envs=16, anakin_ticks=8),
        mesh=MeshConfig(backend="cpu", num_fake_devices=8),
    )
    runner = AnakinRunner(cfg)
    keys = runner.solver._next_sample_keys(runner.num_shards, runner.chain)
    betas = np.asarray(runner.replay.next_betas(runner.chain), np.float32)
    return runner._fn.lower(runner._carry, runner._eps, keys,
                            betas).compile().as_text()


def test_anakin_superstep_zero_host_transfers(anakin_superstep_hlo):
    """The Anakin acceptance pin: the compiled superstep contains NO
    host-communication ops — acting, insert, sampling, and training all
    stay on-device; the host's steady-state job is re-dispatching. Keys
    and β ride in as ordinary (tiny) program arguments, which is not a
    transfer op; nothing is read back."""
    census = hlo_op_census(
        anakin_superstep_hlo,
        ops=("infeed", "outfeed", "send", "recv", "copy-start"))
    hot = {k: v for k, v in census.items()
           if k != "scheduled_total" and v != 0}
    assert not hot, (
        f"Anakin superstep schedules host-communication ops {hot} — the "
        "zero-steady-state-transfer contract is broken")


@pytest.fixture(scope="module")
def anakin_superstep_lm_hlo():
    """Same superstep, ``cfg.train.learn_metrics`` on: the plane rides
    the train-scan carry and is finalized with the chunk's collectives,
    so it must not change the zero-host-comm contract."""
    from distributed_deep_q_tpu.parallel.anakin import AnakinRunner

    cfg = Config(
        env=EnvConfig(id="signal", kind="signal_atari",
                      frame_shape=(10, 10), stack=2),
        net=NetConfig(kind="mlp", num_actions=4, hidden=(32, 32),
                      frame_shape=(10, 10), stack=2),
        replay=ReplayConfig(capacity=256, batch_size=16, fused_chain=2,
                            n_step=1, learn_start=0, device_resident=True,
                            write_chunk=32),
        train=TrainConfig(optimizer="adam", seed=3, stack_forwards="on",
                          learn_metrics=True),
        actors=ActorConfig(anakin_envs=16, anakin_ticks=8),
        mesh=MeshConfig(backend="cpu", num_fake_devices=8),
    )
    runner = AnakinRunner(cfg)
    keys = runner.solver._next_sample_keys(runner.num_shards, runner.chain)
    betas = np.asarray(runner.replay.next_betas(runner.chain), np.float32)
    return runner._fn.lower(runner._carry, runner._eps, keys,
                            betas).compile().as_text()


def test_anakin_superstep_lm_zero_host_transfers(anakin_superstep_lm_hlo):
    """ISSUE 16 acceptance pin: the metrics plane is accumulated with
    plain jnp in the scan body and leaves as an ordinary program output
    — enabling it must add ZERO infeed/outfeed/send/recv ops."""
    census = hlo_op_census(
        anakin_superstep_lm_hlo,
        ops=("infeed", "outfeed", "send", "recv", "copy-start"))
    hot = {k: v for k, v in census.items()
           if k != "scheduled_total" and v != 0}
    assert not hot, (
        f"learn_metrics superstep schedules host-communication ops {hot} "
        "— the plane must stay a plain program output")


@pytest.fixture(scope="module")
def r2d2_solver():
    from distributed_deep_q_tpu.parallel.sequence_learner import (
        SequenceSolver)

    hw, stack, lstm = (36, 36), 4, 16
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.net = NetConfig(kind="r2d2", num_actions=6, frame_shape=hw,
                        stack=stack, lstm_size=lstm,
                        compute_dtype="float32")
    cfg.replay = ReplayConfig(batch_size=8, sequence_length=16, burn_in=4)
    cfg.train = TrainConfig(double_dqn=True, target_update_period=2500)
    return SequenceSolver(cfg, obs_dim=int(np.prod(hw)))


def _r2d2_batch(solver, seq_len):
    cfg = solver.config
    b, lstm = cfg.replay.batch_size, cfg.net.lstm_size
    hw, stack = tuple(cfg.net.frame_shape), cfg.net.stack
    T = seq_len + cfg.replay.burn_in
    return {
        "obs": jnp.zeros((b, T + 1) + hw + (stack,), jnp.uint8),
        "action": jnp.zeros((b, T), jnp.int32),
        "reward": jnp.zeros((b, T), jnp.float32),
        "discount": jnp.zeros((b, T), jnp.float32),
        "mask": jnp.ones((b, T), jnp.float32),
        "weight": jnp.ones((b,), jnp.float32),
        "init_c": jnp.zeros((b, lstm), jnp.float32),
        "init_h": jnp.zeros((b, lstm), jnp.float32),
    }


def test_r2d2_conv_count_independent_of_t(r2d2_solver):
    """Halving the train window must not change the scheduled conv
    count — the torso is time-batched, so T is a shape, not an op."""
    c16 = _r2d2_train_census(r2d2_solver, _r2d2_batch(r2d2_solver, 16))
    c8 = _r2d2_train_census(r2d2_solver, _r2d2_batch(r2d2_solver, 8))
    assert c16["convolution"] == c8["convolution"], (
        "R2D2 scheduled conv count changed with sequence length: "
        f"T=20 -> {c16['convolution']}, T=12 -> {c8['convolution']}")
