"""Plain reference for the DQN-family learner cells (the yardstick).

A straightforward ``jax.numpy`` / numpy implementation of what one grad
step of the configurations in ``benchmark/configs/`` means: Nature-DQN CNN
(Mnih et al. 2015: conv 32x8x8/4, 64x4x4/2, 64x3x3/1, FC 512, FC |A|),
n-step (Double-)DQN targets, importance-weighted Huber loss, global-norm
clip, Adam, hard target refresh, proportional prioritized replay weights
(Schaul et al. 2016). No Pallas, no stacked forwards, no parameter planes,
no scan; it imports nothing from ``distributed_deep_q_tpu`` and takes
nothing the program made: weights come from the seed (``init_weights``,
which the benchmark also installs into the program), batches are composed
here from the host mirror of the rows the benchmark wrote (``compose``).

Precision: the configuration states ``compute_dtype`` (bfloat16 for both
configurations today): every conv/dense takes bf16 operands and yields
bf16, as the configuration says; loss, gradients' reduction, clip and Adam
are float32 under ``jax.default_matmul_precision("highest")``. ``quant``
selects the CONTROL: the same mathematics with every matmul operand
rounded to float8 (e4m3 forward, e5m2 for the cotangents, per-tensor
scaled), the nearest precision below bf16 — the step that would tempt a
later PR. The control must come out
not correct (``benchmark/test_control.py``; chip readings in PERF.md §2).

``EXACT_LIMITS`` holds the exact comparisons; every other limit is the
configuration file's own (no defaults here).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ADAM_B1, ADAM_B2 = 0.9, 0.999

# name, kernel HWIO (I filled from the stack), stride
CONVS = (("conv1", (8, 8, None, 32), 4), ("conv2", (4, 4, 32, 64), 2),
         ("conv3", (3, 3, 64, 64), 1))
FC_OUT = 512

# The exact comparisons, which hold for every configuration. Every other
# compared number (per-step loss, gradient norm and mean Q gaps, Adam's
# first moment, the parameter change, the IS weights of rewritten rows)
# takes its limit from the configuration file's ``limits``, read on the
# chip at that configuration's sizes (``benchmark/control.py``; the
# readings stand beside them in the file and in PERF.md §2). A
# configuration without them fails the check: there is no default.
EXACT_LIMITS = {
    # bit-for-bit: the window DMA copies bytes, the meta pack copies values
    "window_pixels_mismatch": 0,
    "action_mismatch": 0,
    "validity_mismatch": 0,
    "illegal_draws": 0,
    # f32 sums of <= 3 products in another order
    "reward_max_abs": 1e-5,
    "discount_max_abs": 1e-6,
}


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def weight_shapes(num_actions: int, stack: int,
                  frame_shape=(84, 84)) -> dict[str, tuple]:
    out = {}
    h, w = frame_shape
    for name, (kh, kw, cin, cout), stride in CONVS:
        out[f"{name}_w"] = (kh, kw, cin or stack, cout)
        out[f"{name}_b"] = (cout,)
        h, w = (h - kh) // stride + 1, (w - kw) // stride + 1
    out["fc4_w"], out["fc4_b"] = (h * w * 64, FC_OUT), (FC_OUT,)
    out["q_w"], out["q_b"] = (FC_OUT, num_actions), (num_actions,)
    return out


def init_weights(seed: int, num_actions: int, stack: int = 4,
                 frame_shape=(84, 84)):
    """(θ, θ⁻) as dicts of float32 arrays, made on the device in ONE jitted
    call from the seed. Kernels ~ N(0, 1/fan_in), biases ~ N(0, 0.05²);
    θ⁻ = θ + a quarter-scale perturbation, so a step that confused the two
    nets would not pass."""
    shapes = weight_shapes(num_actions, stack, frame_shape)

    @jax.jit
    def make(key):
        theta, target = {}, {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k1, k2 = jax.random.split(jax.random.fold_in(key, i))
            fan_in = int(np.prod(shape[:-1])) if len(shape) > 1 else 0
            scale = (1.0 / np.sqrt(fan_in)) if fan_in else 0.05
            theta[name] = scale * jax.random.normal(k1, shape, jnp.float32)
            target[name] = theta[name] + 0.25 * scale * jax.random.normal(
                k2, shape, jnp.float32)
        return theta, target

    # the seed may pass 2**31: fold it into a 32-bit key in two halves
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return make(key)


# ---------------------------------------------------------------------------
# forward, loss, one optimizer step
# ---------------------------------------------------------------------------


def _scaled_round(x, dtype8, top):
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))), 1e-30)
    s = (amax / top).astype(x.dtype)
    return (x / s).astype(dtype8).astype(x.dtype) * s


@jax.custom_vjp
def _qdq8(x):
    """The control's rounding of a matmul operand: float8_e4m3 with a
    per-tensor scale going forward, and the cotangent that comes back
    through it rounded to float8_e5m2 with its own scale — the usual fp8
    training recipe (e4m3 forward, e5m2 backward, delayed-free scaling)."""
    return _scaled_round(x, jnp.float8_e4m3fn, 448.0)


def _qdq8_fwd(x):
    return _qdq8(x), None


def _qdq8_bwd(_, g):
    return (_scaled_round(g, jnp.float8_e5m2, 57344.0),)


_qdq8.defvjp(_qdq8_fwd, _qdq8_bwd)


def q_forward(p, obs_u8, dtype, quant: str | None = None):
    """Q(s, ·) [B, A] float32 for uint8 observations [B, H, W, stack]."""
    rnd = _qdq8 if quant == "fp8" else (lambda x: x)
    h = obs_u8.astype(dtype) / np.asarray(255.0, dtype)
    for name, _, stride in CONVS:
        h = lax.conv_general_dilated(
            rnd(h), rnd(p[f"{name}_w"].astype(dtype)), (stride, stride),
            "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        h = jax.nn.relu(h + p[f"{name}_b"].astype(dtype))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(rnd(h) @ rnd(p["fc4_w"].astype(dtype))
                    + p["fc4_b"].astype(dtype))
    q = rnd(h) @ rnd(p["q_w"].astype(dtype)) + p["q_b"].astype(dtype)
    return q.astype(jnp.float32)


def huber(x, delta):
    a = jnp.abs(x)
    quad = jnp.minimum(a, delta)
    return 0.5 * quad * quad + delta * (a - quad)


def make_step(hp: dict, quant: str | None = None):
    """One jitted grad step ``(state, batch) -> (state, metrics, |TD|)``.
    ``state`` = dict(theta, target, m, v, count, step); ``hp`` = the
    configuration file's ``hparams``."""
    dtype = jnp.dtype(hp["compute_dtype"])
    lr, eps, clip = hp["lr"], hp["adam_eps"], hp["grad_clip_norm"]
    period, delta = hp["target_update_period"], hp["huber_delta"]
    double = hp["double_dqn"]

    def loss_fn(theta, target, b):
        q = q_forward(theta, b["obs"], dtype, quant)
        qn_t = q_forward(target, b["next_obs"], dtype, quant)
        if double:
            qn_o = lax.stop_gradient(
                q_forward(theta, b["next_obs"], dtype, quant))
            a_star = jnp.argmax(qn_o, axis=-1)
        else:
            a_star = jnp.argmax(qn_t, axis=-1)
        boot = jnp.take_along_axis(qn_t, a_star[:, None], axis=-1)[:, 0]
        y = lax.stop_gradient(b["reward"] + b["discount"] * boot)
        q_sa = jnp.take_along_axis(q, b["action"][:, None], axis=-1)[:, 0]
        td = q_sa - y
        loss = jnp.mean(b["weight"] * huber(td, delta))
        return loss, (jnp.abs(td), jnp.mean(q))

    def step(state, b):
        with jax.default_matmul_precision("highest"):
            (loss, (td_abs, q_mean)), g = jax.value_and_grad(
                loss_fn, has_aux=True)(state["theta"], state["target"], b)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            scale = (jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
                     if clip > 0 else 1.0)
            count = state["count"] + 1
            c = count.astype(jnp.float32)
            theta, m, v = {}, {}, {}
            for k, gk in g.items():
                gk = gk * scale
                m[k] = ADAM_B1 * state["m"][k] + (1 - ADAM_B1) * gk
                v[k] = ADAM_B2 * state["v"][k] + (1 - ADAM_B2) * gk * gk
                upd = (m[k] / (1 - ADAM_B1 ** c)) / (
                    jnp.sqrt(v[k] / (1 - ADAM_B2 ** c)) + eps)
                theta[k] = state["theta"][k] - lr * upd
            nstep = state["step"] + 1
            refresh = nstep % period == 0
            target = {k: jnp.where(refresh, theta[k], state["target"][k])
                      for k in theta}
        new = dict(theta=theta, target=target, m=m, v=v, count=count,
                   step=nstep)
        return new, dict(loss=loss, grad_norm=gnorm, q_mean=q_mean), td_abs

    return jax.jit(step)


def init_state(theta, target):
    zeros = {k: jnp.zeros_like(x) for k, x in theta.items()}
    return dict(theta=dict(theta), target=dict(target), m=zeros,
                v=dict(zeros), count=jnp.zeros((), jnp.int32),
                step=jnp.zeros((), jnp.int32))


# ---------------------------------------------------------------------------
# replay semantics on the host mirror (numpy)
# ---------------------------------------------------------------------------


class Mirror:
    """The rows the benchmark wrote, by ring row: ``frames`` [N, H·W] u8,
    ``action`` i32, ``reward`` f32, ``done`` bool, in write order, with
    ``gidx`` [N] the ring row each landed on and ``segments`` the
    contiguous single-writer runs ``(first ring row, rows written)`` (one
    per sub-ring that was written, none wrapped). A boundary is an episode
    end (``done``); the seeded content has no truncation-only boundary."""

    def __init__(self, frames, action, reward, done, gidx, segments,
                 capacity: int):
        self.frames, self.action, self.reward, self.done = (
            frames, action, reward, done)
        self.row_of = np.full(capacity + 1, -1, np.int64)
        self.row_of[gidx] = np.arange(len(gidx))
        self.segments = segments
        self._gidx = gidx
        self.fresh_priorities()

    def fresh_priorities(self) -> None:
        """p^alpha by ring row as the ring starts: written rows at the
        running max 1.0, the rest 0."""
        self.prio = np.zeros(len(self.row_of), np.float64)
        self.prio[self._gidx] = 1.0


def valid_rows(mirror: Mirror, stack: int, n_step: int) -> np.ndarray:
    """Ring rows that may be drawn: the [i-stack+1, i+n] window lies inside
    the written run (a part-filled single-writer sub-ring)."""
    ok = np.zeros(len(mirror.row_of), bool)
    for first, n in mirror.segments:
        ok[first + stack - 1: first + n - n_step] = True
    return ok


def compose(mirror: Mirror, idx: np.ndarray, hp: dict) -> dict:
    """Batches for sampled ring rows ``idx`` [chain, B]: obs / next_obs
    [chain, B, H, W, stack] u8 (frames before an episode end zeroed),
    n-step return, bootstrap discount, action."""
    stack, n, gamma = hp["stack"], hp["n_step"], hp["gamma"]
    h, w = hp["frame_shape"]
    row = mirror.row_of[idx]                       # write-order row
    assert (row >= 0).all(), "a drawn row was never written"

    def stacked(anchor):
        offs = np.arange(-(stack - 1), 1)
        rows = anchor[..., None] + offs            # oldest first
        # frame at p valid iff no episode end in rows p .. anchor-1
        ends = mirror.done[rows[..., :-1]]
        later = np.flip(np.cumsum(np.flip(ends, -1), -1), -1) > 0
        valid = np.concatenate(
            [~later, np.ones(anchor.shape + (1,), bool)], -1)
        fr = mirror.frames[rows] * valid[..., None].astype(np.uint8)
        fr = fr.reshape(anchor.shape + (stack, h, w))
        return np.moveaxis(fr, -3, -1), valid

    obs, ovalid = stacked(row)
    nobs, nvalid = stacked(row + n)
    ks = np.arange(n)
    d = mirror.done[row[..., None] + ks]
    cont = np.ones(d.shape, bool)
    cont[..., 1:] = ~(np.cumsum(d[..., :-1], -1) > 0)
    gam = np.float32(gamma) ** np.arange(n + 1, dtype=np.float32)
    r = (mirror.reward[row[..., None] + ks] * cont * gam[:n]).sum(
        -1, dtype=np.float32)
    any_done = (d & cont).any(-1)
    disc = np.where(any_done, 0.0, gam[n]).astype(np.float32)
    return dict(obs=obs, next_obs=nobs, ovalid=ovalid, nvalid=nvalid,
                action=mirror.action[row].astype(np.int32),
                reward=r.astype(np.float32), discount=disc)


def is_weights(mirror: Mirror, idx: np.ndarray, betas: np.ndarray,
               hp: dict) -> np.ndarray:
    """Importance weights [chain, B] for draws ``idx`` against the
    reference's own priority table as of the chunk's start:
    w = (N · p_i / Σp)^-β, normalized by the row's max (one shard)."""
    ok = valid_rows(mirror, hp["stack"], hp["n_step"])
    pm = mirror.prio * ok
    prob = np.maximum(pm[idx] / max(pm.sum(), 1e-12), 1e-12)
    w = (ok.sum() * prob) ** (-betas[:, None].astype(np.float64))
    return (w / np.maximum(w.max(axis=1, keepdims=True), 1e-12)).astype(
        np.float32)


def update_priorities(mirror: Mirror, idx: np.ndarray, td_abs: np.ndarray,
                      hp: dict) -> None:
    """p_i^α ← (|TD| + ε)^α for one step's draws (later duplicates win, as
    a sequential write would have it)."""
    mirror.prio[idx] = (np.abs(td_abs).astype(np.float64)
                        + hp["priority_eps"]) ** hp["priority_alpha"]


def betas_for(first_sample: int, k: int, hp: dict) -> np.ndarray:
    """β for sample calls first_sample+1 .. first_sample+k (annealed
    linearly β₀ → 1 over ``priority_beta_steps`` calls)."""
    s = first_sample + 1 + np.arange(k)
    frac = np.minimum(s / max(hp["priority_beta_steps"], 1), 1.0)
    return (hp["priority_beta0"] + frac * (1 - hp["priority_beta0"])
            ).astype(np.float32)
