"""The fused train program's pixel unpack by BYTE PLANES (PERF.md §6, PR 32)
against the ``u8 [B, H, W, stack]`` entry decoded in numpy.

A ring row packs four pixels to a little-endian int32; where the frame
width is a multiple of 4, byte ``k`` of every word is the image at columns
``≡ k (mod 4)``, and the four planes side by side are the image: the train
program takes them by shift and mask instead of a bitcast to uint8. Same
bytes, same masks — so on the SAME packed windows ``window_to_obs`` must
give the pixels bit for bit, the fused step must give the host-batch
step's loss, Q-values and gradients on numpy-decoded pixels (float32
compute here, so the comparison is tight), a width off the grid of 4 must
take the bitcast and say so in ``train/unpack_planes``, and the parameter
tree must be what it was.

The windows reach the train program as the sample program hands them over
since PR 35: ``[chain, batch, window, rowp // 128, 128]``, a row's words in
the two tiled dims (``ops/ring_gather.tile_rows``) — word for word the
ring's rows, which the last test here holds the sample program to.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_deep_q_tpu.config import Config, NetConfig, ReplayConfig
from distributed_deep_q_tpu.ops.ring_gather import (
    LANES, flat_rows, padded_row_bytes, tile_rows)
from distributed_deep_q_tpu.replay.device_per import (
    DevicePERFrameReplay, window_to_obs)
from distributed_deep_q_tpu.solver import Solver

STACK, N_STEP, CAP = 4, 3, 256
BATCH = 128     # a shard's batch fills the lanes: the plane path's size
WINDOW = STACK + N_STEP
ALPHA, EPS = 0.6, 1e-6


def _solver(frame, kind="nature_cnn", stack_forwards="auto", double=True,
            batch=BATCH, n_step=N_STEP):
    cfg = Config()
    cfg.mesh.backend = "cpu"
    cfg.mesh.dp = 1
    cfg.net = NetConfig(kind=kind, num_actions=4, frame_shape=frame,
                        compute_dtype="float32", hidden=(32,))
    cfg.train.stack_forwards = stack_forwards
    cfg.train.double_dqn = double
    cfg.replay = ReplayConfig(capacity=CAP, batch_size=batch, n_step=n_step,
                              prioritized=True, device_per=True)
    return Solver(cfg, obs_dim=frame[0] * frame[1] * STACK)   # mlp's input


def _spec(frame, batch):
    row_len = frame[0] * frame[1]
    return (CAP, CAP + WINDOW - 1, padded_row_bytes(row_len), row_len, STACK,
            N_STEP, 0.99, tuple(frame), batch, ALPHA, EPS, 1, True)


def _windows(frame, chain, seed=0, batch=BATCH):
    """Seeded packed windows as the sample program hands them over, with
    invalid frames in BOTH masks, and the pixels they hold."""
    rng = np.random.default_rng(seed)
    row_len = frame[0] * frame[1]
    pix = rng.integers(0, 256, (chain, batch, WINDOW, row_len),
                       dtype=np.uint8)
    padded = np.zeros((chain, batch, WINDOW, padded_row_bytes(row_len)),
                      np.uint8)
    padded[..., :row_len] = pix
    ovalid = np.ones((chain, batch, STACK), np.uint8)
    nvalid = np.ones((chain, batch, STACK), np.uint8)
    ovalid[:, 0, :2] = 0        # episode start two frames before the anchor
    nvalid[:, 1, :1] = 0
    ovalid[:, 2, :3] = 0        # both masks cut on one row
    nvalid[:, 2, :2] = 0
    metas = {
        "action": rng.integers(0, 4, (chain, batch)).astype(np.int32),
        "reward": rng.standard_normal((chain, batch)).astype(np.float32),
        "discount": np.full((chain, batch), 0.99 ** N_STEP, np.float32),
        "weight": rng.uniform(0.3, 1.0, (chain, batch)).astype(np.float32),
        "ovalid": ovalid, "nvalid": nvalid}
    idxs = np.stack([rng.permutation(CAP)[:batch] for _ in range(chain)]
                    ).astype(np.int32)
    win = tile_rows(padded.view(np.int32).reshape(-1), chain, batch, WINDOW)
    return pix, win, metas, idxs


def _pixels(frame, pix, first, valid):
    """Frames ``first..`` of one step's windows as ``u8 [B, H, W, stack]``,
    invalid frames zeroed: the model's entry, decoded in numpy."""
    rows = pix[:, first:first + STACK] * valid[..., None]
    return np.moveaxis(rows.reshape(rows.shape[:2] + tuple(frame)), 1, -1)


def _pixel_batch(frame, pix, metas, step):
    return {"obs": _pixels(frame, pix[step], 0, metas["ovalid"][step]),
            "next_obs": _pixels(frame, pix[step], N_STEP,
                                metas["nvalid"][step]),
            **{k: metas[k][step]
               for k in ("action", "reward", "discount", "weight")}}


def _run_fused(solver, frame, chain, win, metas, idxs):
    _, train = solver.learner._build_device_per_step(
        _spec(frame, idxs.shape[1]), chain, donate=False)
    prio = jnp.ones((CAP,), jnp.float32)
    return train(solver.state, metas, win, idxs, prio,
                 jnp.ones((), jnp.float32))


def _assert_trees_close(a, b, rtol=2e-5, atol=1e-7):
    la, lb = jax.tree_util.tree_leaves_with_path(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for (path, x), y in zip(la, lb):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("first", [0, N_STEP], ids=["obs", "next_obs"])
@pytest.mark.parametrize("frame", [(36, 36), (84, 84), (8, 12)],
                         ids=["36", "84", "8x12"])
def test_window_to_obs_is_the_pixels(frame, first):
    """Shift-and-mask planes of the packed words, laid side by side, are
    the window's pixels bit for bit, masked frames zero."""
    pix, win, metas, _ = _windows(frame, 1, seed=3, batch=6)
    valid = metas["nvalid" if first else "ovalid"][0]
    got = window_to_obs(flat_rows(jnp.asarray(win[0])), first,
                        jnp.asarray(valid), frame[0] * frame[1], frame)
    assert got.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got),
                                  _pixels(frame, pix[0], first, valid))


@pytest.mark.parametrize("double", [True, False], ids=["double", "single"])
@pytest.mark.parametrize("body", ["tree", "plane"])
@pytest.mark.parametrize(
    "frame,batch,planes",
    [((36, 36), BATCH, 1), ((84, 84), BATCH, 1), ((38, 38), BATCH, 0),
     ((36, 36), 6, 0)],
    ids=["36", "84", "width-38-bitcast", "batch-6-bitcast"])
def test_fused_step_matches_host_batch_step(frame, batch, planes, body,
                                            double):
    """Two chained steps of the fused train program on packed windows
    against two host-batch steps on the numpy-decoded pixels of the same
    windows: per-step loss, mean Q and gradient norm, every leaf of θ, θ⁻
    and Adam's moments (after step one the first moment IS the clipped
    gradient), and the written-back priorities. A width of 38 is off the
    grid of 4, and a shard's batch of 6 leaves the lanes empty: the same
    numbers by the bitcast, and the gauge reads 0."""
    chain = 2
    solver = _solver(frame, double=double, batch=batch,
                     stack_forwards="off" if body == "tree" else "on")
    pix, win, metas, idxs = _windows(frame, chain, batch=batch)
    ref = jax.tree.map(jnp.copy, solver.state)
    state, prio, _, m = _run_fused(solver, frame, chain, win, metas, idxs)
    assert solver.fused_gauges() == {"train/unpack_planes": planes}
    for step in range(chain):
        ref, ref_m, td_abs = solver.learner.train_step(
            ref, _pixel_batch(frame, pix, metas, step))
        for key in ("loss", "q_mean", "grad_norm"):
            np.testing.assert_allclose(float(m[key][step]),
                                       float(ref_m[key]), rtol=2e-5,
                                       err_msg=f"{key} step {step}")
    assert np.all(np.isfinite(np.asarray(m["loss"])))
    _assert_trees_close(state, ref)
    np.testing.assert_allclose(
        np.asarray(prio)[idxs[-1]],
        (np.abs(np.asarray(td_abs)) + EPS) ** ALPHA, rtol=2e-5)


def test_any_net_gets_the_same_pixels():
    """What is handed over is the model's uint8 entry, so the unpack asks
    nothing of the net: an MLP on 36x36 frames takes the plane path too."""
    frame = (36, 36)
    solver = _solver(frame, kind="mlp")
    pix, win, metas, idxs = _windows(frame, 1, seed=5)
    ref = jax.tree.map(jnp.copy, solver.state)
    state, _, _, m = _run_fused(solver, frame, 1, win, metas, idxs)
    assert solver.fused_gauges() == {"train/unpack_planes": 1}
    ref, ref_m, _ = solver.learner.train_step(
        ref, _pixel_batch(frame, pix, metas, 0))
    np.testing.assert_allclose(float(m["loss"][0]), float(ref_m["loss"]),
                               rtol=2e-5)
    _assert_trees_close(state.params, ref.params)


def test_gauge_is_silent_before_a_fused_step_is_built():
    assert _solver((36, 36)).fused_gauges() == {}


def test_parameter_tree_is_what_it_was():
    """Leaf names and shapes of the Nature CNN: the unpack changed, the
    model did not."""
    leaves = {jax.tree_util.keystr(p): x.shape for p, x in
              jax.tree_util.tree_leaves_with_path(
                  _solver((84, 84)).state.params)}
    assert leaves == {
        "['_Head_0']['q']['bias']": (4,),
        "['_Head_0']['q']['kernel']": (512, 4),
        "['torso']['conv1']['bias']": (32,),
        "['torso']['conv1']['kernel']": (8, 8, STACK, 32),
        "['torso']['conv2']['bias']": (64,),
        "['torso']['conv2']['kernel']": (4, 4, 32, 64),
        "['torso']['conv3']['bias']": (64,),
        "['torso']['conv3']['kernel']": (3, 3, 64, 64),
        "['torso']['fc4']['bias']": (512,),
        "['torso']['fc4']['kernel']": (3136, 512),
    }


@pytest.mark.parametrize("window,batch", [(7, 128), (5, 6)],
                         ids=["window7-b128", "window5-b6"])
def test_sample_program_hands_over_the_ring_rows_word_for_word(window,
                                                               batch):
    """The sample program's windows (the DMA kernel in interpret mode),
    their last two dims flattened, are ``[chain, batch, window, rowp]`` of
    the ring's own words: row ``k`` of sample ``b`` is ring row
    ``start(b) + k``, padding included — what the program returned before
    the view changed (PR 35). Both batch sizes, both windows: the view
    depends on neither."""
    frame, chain, n_step = (36, 36), 2, window - STACK
    solver = _solver(frame, batch=batch, n_step=n_step)
    cfg = solver.config
    dev = DevicePERFrameReplay(cfg.replay, solver.mesh, frame, stack=STACK,
                               gamma=0.99, seed=0, write_chunk=16)
    rng = np.random.default_rng(window)
    for i in range(CAP + 40):       # past one lap: the ghost rows matter
        dev.add(rng.integers(0, 256, frame, dtype=np.uint8),
                int(rng.integers(4)), float(rng.standard_normal()),
                done=(i % 11 == 10))
    dev.flush()
    sample, _ = solver.learner.device_per_programs(
        solver.device_per_spec(dev), chain)
    cursors, sizes = dev.device_inputs()
    keys = rng.integers(0, 2**32, (dev.num_shards, chain, 2), np.uint32)
    rows = dev.dstate
    _, win, idx = sample(keys, rows.frames, rows.action, rows.reward,
                         rows.done, rows.boundary, rows.prio, cursors,
                         sizes, np.full(chain, 0.4, np.float32))
    rowp = dev.rowb // 4
    assert win.shape == (chain, batch, window, rowp // LANES, LANES)
    ring = np.asarray(rows.frames).reshape(-1, rowp)
    sub, local = np.divmod(np.asarray(idx), dev.slot_cap)
    start = sub * dev.slot_pad + (local - (STACK - 1)) % dev.slot_cap
    want = ring[start[..., None] + np.arange(window)]
    assert want.shape == (chain, batch, window, rowp) and want.any()
    np.testing.assert_array_equal(np.asarray(flat_rows(win)), want)
