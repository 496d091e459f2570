"""Device-resident prioritized replay — sampling fused INTO the train step.

The host-PER data path (``replay/prioritized.py`` sum-tree + index batches)
pays two host round trips per grad step: the sampled-index upload and the
per-sample |TD| readback for priority updates — a device→host sync in
the step loop — and the host-side sum-tree walk at batch 512 over a 1M
ring is itself per-step host work (neither cost is measured on today's
code). This module moves the WHOLE prioritized loop into HBM
(SURVEY §7.3 item 2, redesigned TPU-first instead of host-first):

- per-row metadata rings (action, reward, done, boundary) and a priority
  row ``p^α`` live on device, sharded ``P('dp')`` exactly like the frame
  ring; the flush scatter writes all of them in one program, with fresh
  rows initialized to the running max-priority device scalar.
- each train step, per shard: build the validity mask from the (tiny,
  host-shipped) per-slot cursors/sizes, draw ``B/D`` indices by inverse-CDF
  over the masked priorities (``cumsum`` + ``searchsorted`` — the sum-tree's
  job, done as one memory-bound pass at HBM bandwidth), compose frame
  stacks and n-step returns from the device rings, compute IS weights
  for the REALIZED stratified distribution (each shard contributes
  exactly ``B/D`` draws, proportional within the shard, so
  ``P(i) = p_i / (D · mass_shard(i))`` over the shard's sampleable rows
  and ``w_i = (N · P(i))^-β / max w`` — ``stratified_is_weights``; the
  global mass would bias the weights wherever shard masses differ), run
  the DQN step, and scatter ``(|TD|+ε)^α`` straight back into the priority
  row — zero-step-stale, no D2H anywhere.

The per-device layout mirrors ``device_ring.py``: a shard holds
``subs_per_shard`` sub-rings (slots) of ``slot_cap`` rows; all mask/window
math reshapes ``[cap_local] → [subs, slot_cap]`` so ring wraps stay inside
a sub-ring. Host-side slot bookkeeping (cursors/sizes/boundaries) is
unchanged — the device copies exist so composition never needs the host.
"""

from __future__ import annotations

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributed_deep_q_tpu.parallel.mesh import AXIS_DP
from distributed_deep_q_tpu.profiling import (
    ran_executable, register_programs)
from distributed_deep_q_tpu.replay.device_ring import DeviceFrameReplay


class DeviceReplayState(flax.struct.PyTreeNode):
    """Device twin of the replay ring: pixels + metadata + priorities.

    All arrays are global (mesh-sharded over their leading axis); ``maxp``
    is the replicated running max |TD| priority (pre-α), used to seed
    fresh rows optimistically.
    """

    frames: jax.Array     # [capacity, H·W] uint8
    action: jax.Array     # [capacity] int32
    reward: jax.Array     # [capacity] float32
    done: jax.Array       # [capacity] uint8 (cuts bootstrap)
    boundary: jax.Array   # [capacity] uint8 (any episode end)
    prio: jax.Array       # [capacity] float32, p^α (0 = never written)
    maxp: jax.Array       # [] float32, running max pre-α priority


# every plane sharded over its leading axis; the running max replicated
STATE_SPEC = DeviceReplayState(
    frames=P(AXIS_DP), action=P(AXIS_DP), reward=P(AXIS_DP),
    done=P(AXIS_DP), boundary=P(AXIS_DP), prio=P(AXIS_DP), maxp=P())


def valid_mask(done: jax.Array, boundary: jax.Array, cursors: jax.Array,
               sizes: jax.Array, slot_cap: int, stack: int,
               n_step: int) -> jax.Array:
    """Per-row sampleability for one shard — device twin of
    ``FrameStackReplay._invalid`` vectorized over the shard's sub-rings.

    ``done``/``boundary`` are the shard's rows ``[cap_local]``; ``cursors``
    and ``sizes`` are ``[subs]`` per-sub write cursors / fill counts. A row
    is sampleable iff its ``[i-stack+1, i+n]`` window neither crosses the
    write cursor nor falls off the filled region, and its n-step window
    crosses no truncation-only boundary.
    """
    L = slot_cap
    d = done.reshape(-1, L).astype(bool)
    b = boundary.reshape(-1, L).astype(bool)
    subs = d.shape[0]
    idx = jnp.arange(L)[None, :]                        # [1, L]
    size = sizes[:, None]                               # [subs, 1]
    cur = cursors[:, None]
    partial = (idx < stack - 1) | (idx + n_step >= size)
    back = (idx - cur) % L
    full = (back >= L - n_step) | (back < stack - 1)
    bad = jnp.where(size < L, partial, full)
    trunc = b & ~d
    cross = jnp.zeros((subs, L), bool)
    for k in range(n_step):
        cross = cross | jnp.roll(trunc, -k, axis=1)
    return (~(bad | cross)).reshape(-1)                 # [cap_local]


def build_cdf(prio_masked: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(inclusive CDF, total mass) over a shard's masked priorities. ONE
    ``cumsum`` over the shard (memory-bound, HBM rate) replaces the host
    sum-tree descent. Capacity-scaled (O(cap_local) passes) — so the
    chained path builds it ONCE per chunk: sampling is defined against
    the priorities as of chunk start, making the CDF scan-invariant (the
    in-scan version cost ~1.7 ms/step extra at 1M rows, measured)."""
    cdf = jnp.cumsum(prio_masked)
    return cdf, cdf[-1]


def draw_from_cdf(key: jax.Array, cdf: jax.Array, prio_masked: jax.Array,
                  mass: jax.Array, num: int,
                  ) -> tuple[jax.Array, jax.Array]:
    """``num`` inverse-CDF draws ∝ p: (indices [num], p_i/mass [num]).
    [B]-scale only — safe inside a scan."""
    u = jax.random.uniform(key, (num,)) * mass
    idx = jnp.searchsorted(cdf, u, side="right")
    idx = jnp.clip(idx, 0, prio_masked.shape[0] - 1)
    p = prio_masked[idx] / jnp.maximum(mass, 1e-12)
    return idx, p


def _stack_window(boundary: jax.Array, local: jax.Array, sub: jax.Array,
                  slot_cap: int, stack: int) -> tuple[jax.Array, jax.Array]:
    """(shard-local frame indices [B, stack] oldest-first, validity mask) —
    device twin of ``FrameStackReplay._stack_indices``."""
    L = slot_cap
    offs = jnp.arange(stack - 1, -1, -1)                # stack-1 .. 0
    loc = (local[:, None] - offs[None, :]) % L          # [B, stack]
    flat = sub[:, None] * L + loc
    prev_b = boundary[sub[:, None] * L + (loc - 1) % L].astype(bool)
    # valid right-to-left, unrolled (stack is tiny and static): newest
    # frame always valid, older frames valid while no boundary sits
    # between them and the anchor
    valid_cols = [jnp.ones(local.shape[0], bool)]
    for j in range(stack - 2, -1, -1):
        valid_cols.append(valid_cols[-1] & ~prev_b[:, j + 1])
    valid = jnp.stack(valid_cols[::-1], axis=1)         # [B, stack]
    return flat.astype(jnp.int32), valid


def stack_rows_to_obs(rows: jax.Array,
                      frame_shape: tuple[int, int]) -> jax.Array:
    """[B, stack, H·W] gathered rows → [B, H, W, stack] CNN input.

    Kept OUT of the sampling program on purpose: the transpose propagates
    the consumer's preferred layout backwards onto the frame-ring gather
    operand during XLA layout assignment, which materializes a relayout
    copy of the ENTIRE ring per step (7 GB at 1M capacity, ~29 ms
    measured). The sampling program returns gather-natural flat rows; the
    train program does this (14 MB) rearrangement instead.
    """
    rows = rows.reshape(rows.shape[:2] + tuple(frame_shape))
    return jnp.moveaxis(rows, 1, -1)


def window_to_obs(win: jax.Array, first: int, valid: jax.Array,
                  row_len: int, frame_shape: tuple[int, int]) -> jax.Array:
    """Frames ``first .. first+stack-1`` of [B, window, rowp] packed ring
    rows → [B, H, W, stack] uint8 CNN input, by BYTE PLANES: for frame
    widths that are a multiple of 4. Frames whose ``valid`` [B, stack] is
    0 are zeroed as ``gather_rows`` zeroes them. ``win`` is one scan
    step's share of what crossed from the sample program, its rows
    flattened back (``ops/ring_gather.flat_rows``): between the programs
    the windows travel as ``[..., window, rowp // 128, 128]``, because 7
    rows in a tiled dim would be padded to 8 (``tile_rows``).

    A row is H·W pixel bytes packed little-endian four to an int32, so
    every image row starts on a word and word ``(W/4)·h + j`` holds the
    pixels at columns ``4j .. 4j+3``: byte ``k`` of every word,
    ``(word >> 8k) & 0xff``, is the image sub-sampled at columns
    ``≡ k (mod 4)`` — a plane, by an elementwise shift and mask. The four
    planes stacked behind ``j`` ARE the image (``[..., W/4, 4]`` is
    ``[..., W]``). ``lax.bitcast_convert_type`` to uint8 gives the same
    bytes, but the TPU compiler lowers it by broadcasting every word four
    times into a ``u32[..., 4]`` copy of the whole window and shifting
    that (0.51 of the b512 step's 1.20 ms, PERF.md §6, PR 32). Here it
    keeps the batch in the lanes and ``k`` a major dimension, so stacking
    the planes moves no pixel: each is written once, in the layout the
    first convolution reads."""
    h, w = frame_shape
    stack = valid.shape[1]
    words = win[:, first:first + stack, :row_len // 4]
    words = words * valid[..., None].astype(words.dtype)
    words = words.reshape(words.shape[:2] + (h, w // 4))
    planes = jnp.stack([(words >> (8 * k)) & 0xFF for k in range(4)],
                       axis=-1).astype(jnp.uint8)
    return jnp.moveaxis(planes.reshape(planes.shape[:3] + (w,)), 1, -1)


def gather_rows(frames: jax.Array, flat_idx: jax.Array,
                valid: jax.Array) -> jax.Array:
    """``frames[flat_idx]`` with invalid stack positions zeroed — the ONE
    place the pixel plane is touched. Kept OUT of any ``lax.scan``: a
    gather inside a scan body makes XLA materialize a ring-sized temp per
    iteration (measured: the compiled chained sample program carried a
    471 MB temp ≈ one full 462 MB ring copy per step, ~2.5 ms/step at
    batch 512 vs ~0.04 ms of actual gathered bytes). Batched over the
    chunk, the leading dims of ``flat_idx`` are free."""
    f = frames[flat_idx.reshape(-1)].reshape(flat_idx.shape + (-1,))
    return f * valid[..., None].astype(jnp.uint8)


def compose_meta(state_rows: dict[str, jax.Array], local: jax.Array,
                 sub: jax.Array, slot_cap: int, stack: int,
                 n_step: int, gamma: float):
    """Device twin of ``FrameStackReplay.gather_meta``: from sampled
    (sub, local) rows build the n-step return, bootstrap discount, action,
    and the obs/next_obs WINDOW INDICES + validity masks (the pixel gather
    itself happens outside, ``gather_rows``). Returns
    (meta dict, oflat, ovalid, nflat, nvalid)."""
    L = slot_cap
    action = state_rows["action"]
    reward, done, boundary = (state_rows["reward"], state_rows["done"],
                              state_rows["boundary"])

    oflat, ovalid = _stack_window(boundary, local, sub, L, stack)
    nflat, nvalid = _stack_window(boundary, (local + n_step) % L, sub, L,
                                  stack)
    ks = jnp.arange(n_step)
    win = sub[:, None] * L + (local[:, None] + ks[None, :]) % L  # [B, n]
    d = done[win].astype(bool)
    continuing = jnp.ones(d.shape, bool)
    if n_step > 1:
        continuing = continuing.at[:, 1:].set(
            ~jnp.cumsum(d[:, :-1], axis=1).astype(bool))
    gammas = gamma ** jnp.arange(n_step + 1, dtype=jnp.float32)
    r = (reward[win] * continuing * gammas[None, :n_step]).sum(axis=1)
    any_done = (d & continuing).any(axis=1)
    discount = jnp.where(any_done, 0.0, gammas[n_step]).astype(jnp.float32)
    flat = sub * L + local
    meta = {
        "action": action[flat],
        "reward": r.astype(jnp.float32),
        "discount": discount,
    }
    return meta, oflat, ovalid, nflat, nvalid


@jax.named_scope("ddq.sample_prep")
def fused_sample_prep(shard_rows: dict[str, jax.Array],
                      cursors: jax.Array, sizes: jax.Array,
                      slot_cap: int, stack: int, n_step: int):
    """The CAPACITY-SCALED part of a fused prioritized sample, built once
    per chunk (scan-invariant: the chained path samples against the
    priorities as of chunk start): validity mask → masked priorities →
    CDF/mass → global sampleable count. Returns (pm, cdf, mass, n_glob).
    """
    from jax import lax

    mask = valid_mask(shard_rows["done"], shard_rows["boundary"], cursors,
                      sizes, slot_cap, stack, n_step)
    pm = shard_rows["prio"] * mask
    cdf, mass = build_cdf(pm)
    n_glob = lax.psum(jnp.sum(mask.astype(jnp.float32)), "dp")
    return pm, cdf, mass, n_glob


def fused_sample_draw_many(keys: jax.Array,
                           shard_rows: dict[str, jax.Array],
                           pm: jax.Array, cdf: jax.Array, mass: jax.Array,
                           n_glob: jax.Array, per_shard: int, slot_cap: int,
                           stack: int, n_step: int, gamma: float,
                           betas: jax.Array, num_shards: int):
    """All ``chain`` draws of a chunk in one straight-line vectorized
    block (no scan: the draw has no carry — sampling is defined against
    chunk-start priorities — and scanned bodies re-touch capacity-sized
    operands per iteration).

    THE reference of the production ``fused_sample_draw_packed``, not a
    path the learner runs: this composes meta through ``compose_meta``'s
    window gathers (clear, tile-amplified); the packed path composes the
    same values from ``build_meta_pack`` row lanes.
    ``test_packed_draw_matches_reference_draw`` (tests/test_device_per.py)
    holds the two together, and the zero-mass unit test drives this one.

    Per-step key semantics: row i draws ``uniform(keys[i], (per_shard,))``
    — the vmap computes the same Threefry bits as ``chain`` separate
    calls, so a chain=k chunk byte-matches k single-step dispatches
    (``test_chained_fused_steps_match_sequential_alpha0``).

    ``keys`` is [chain, 2] uint32, ``betas`` [chain]. Returns (meta dict
    incl. ``weight``, oflat, ovalid, nflat, nvalid, sampled shard-local
    indices), a leading [chain] axis everywhere; the pixel gather is the
    caller's (``gather_rows`` on the window indices).
    """
    from jax import lax

    chain = keys.shape[0]
    idx, p = jax.vmap(
        lambda k: draw_from_cdf(k, cdf, pm, mass, per_shard))(keys)
    sub, local = idx // slot_cap, idx % slot_cap
    meta, oflat, ovalid, nflat, nvalid = compose_meta(
        shard_rows, local.reshape(-1), sub.reshape(-1), slot_cap, stack,
        n_step, gamma)
    lead = (chain, per_shard)
    meta = {k: v.reshape(lead + v.shape[1:]) for k, v in meta.items()}
    oflat, ovalid, nflat, nvalid = (
        x.reshape(lead + x.shape[1:])
        for x in (oflat, ovalid, nflat, nvalid))
    meta["weight"] = stratified_is_weights(p, mass, n_glob, betas,
                                           num_shards)
    idx = jnp.where(mass > 0, idx, pm.shape[0])
    return meta, oflat, ovalid, nflat, nvalid, idx.astype(jnp.int32)


def stratified_is_weights(p: jax.Array, mass: jax.Array,
                          n_glob: jax.Array, betas: jax.Array,
                          num_shards: int) -> jax.Array:
    """IS weights for the realized per-shard stratified draw, normalized
    per chain row — THE single copy of this math, shared by the
    transition samplers (reference and packed) and the fused sequence
    sampler. ``p`` [chain, B] draw probabilities (p_i/mass),
    ``betas`` [chain]; runs inside shard_map (``lax.pmax`` over 'dp').

    P(i) = p_i/(D·mass_s) — each shard contributes exactly B/D draws;
    N = global sampleable count (``n_glob``, psum'd once per chunk).

    A shard whose masked priority mass is zero (e.g. its only sampleable
    slot sealed away post-warmup) would otherwise compose garbage rows
    with extreme weights: zero those weights (the caller points its
    priority scatter out of bounds), so the degenerate shard contributes
    nothing and the step stays total.
    Masking must precede the pmax: a dead shard's floored p=1e-12 blows
    w up to ~1e4, and normalizing live shards by THAT w_max would crush
    the whole batch's learning signal."""
    from jax import lax

    pr = jnp.maximum(p / num_shards, 1e-12)
    w = (n_glob * pr) ** (-betas[:, None])
    w = jnp.where(mass > 0, w, 0.0)
    w_max = lax.pmax(jnp.max(w, axis=1), "dp")             # [chain]
    return (w / jnp.maximum(w_max[:, None], 1e-12)).astype(jnp.float32)


@jax.named_scope("ddq.meta_pack")
def build_meta_pack(action: jax.Array, reward: jax.Array, done: jax.Array,
                    boundary: jax.Array, slot_cap: int, stack: int,
                    n_step: int, gamma: float) -> jax.Array:
    """Per-row composed sample metadata for ALL rows at once — the roll
    twin of ``compose_meta``. Returns ``[cap_local, 3 + stack]`` float32:
    lane 0 action, 1 n-step return, 2 bootstrap discount, 3.. the obs
    stack-validity bits of the row as anchor (oldest-first).

    Why: per-sample element gathers from the [cap_local] metadata rows
    read a full (8,128)/(32,128) tile per element on TPU — measured
    ~42 ms per 32-step chunk at 1M capacity (scripts/sample_ablate.py).
    Rolls compose the same windows for every row in a handful of
    sequential passes at HBM bandwidth, and the sampler then needs just
    TWO row gathers per sample (anchor and anchor+n) from this pack.
    ``jnp.roll`` wraps within each sub-ring after the ``[subs, L]``
    reshape — exactly the mod-``L`` window math of ``compose_meta``.
    """
    L = slot_cap
    a2 = action.reshape(-1, L).astype(jnp.float32)
    r2 = reward.reshape(-1, L).astype(jnp.float32)
    d2 = done.reshape(-1, L).astype(bool)
    b2 = boundary.reshape(-1, L).astype(bool)
    # n-step return / discount: row i's window rows are roll(-k)[i]
    rn = r2
    any_done = d2
    cont = ~d2
    for k in range(1, n_step):
        dk = jnp.roll(d2, -k, axis=1)
        rn = rn + jnp.roll(r2, -k, axis=1) * cont * (gamma ** k)
        any_done = any_done | (dk & cont)
        cont = cont & ~dk
    disc = jnp.where(any_done, 0.0, gamma ** n_step).astype(jnp.float32)
    # obs stack-validity bits (right-to-left like _stack_window): the
    # anchor frame is always valid; older frames stay valid while no
    # boundary sits between them and the anchor
    vs: list = [None] * stack
    vs[stack - 1] = jnp.ones_like(d2)
    for j in range(stack - 2, -1, -1):
        pb = jnp.roll(b2, stack - 1 - j, axis=1)
        vs[j] = vs[j + 1] & ~pb
    lanes = [a2, rn, disc] + [v.astype(jnp.float32) for v in vs]
    return jnp.stack(lanes, axis=-1).reshape(-1, 3 + stack)


@jax.named_scope("ddq.draw")
def fused_sample_draw_packed(keys: jax.Array, pack: jax.Array,
                             pm: jax.Array, cdf: jax.Array, mass: jax.Array,
                             n_glob: jax.Array, per_shard: int,
                             slot_cap: int, slot_pad: int, stack: int,
                             n_step: int, betas: jax.Array,
                             num_shards: int):
    """The production draw for the padded-ring path: inverse-CDF draws for
    all ``chain`` steps, metadata from TWO row gathers per sample off the
    ``build_meta_pack`` pack, and the frame-window START rows for the
    Pallas DMA gather (``ops/ring_gather.py``).

    Returns (meta dict [chain, B] incl. ``weight`` and the obs/next-obs
    validity bit-planes ``ovalid``/``nvalid`` [chain, B, stack] u8;
    window-start rows ``ws`` [chain, B] in PADDED shard coords; sampled
    row indices [chain, B] in real coords, OOB-masked for dead shards).
    """
    from jax import lax

    chain = keys.shape[0]
    idx, p = jax.vmap(
        lambda k: draw_from_cdf(k, cdf, pm, mass, per_shard))(keys)
    sub, local = idx // slot_cap, idx % slot_cap
    anchor2 = sub * slot_cap + (local + n_step) % slot_cap
    lanes = pack.shape[-1]
    mp = pack[idx.reshape(-1)].reshape(chain, per_shard, lanes)
    mp2 = pack[anchor2.reshape(-1)].reshape(chain, per_shard, lanes)
    meta = {
        "action": mp[..., 0].astype(jnp.int32),
        "reward": mp[..., 1],
        "discount": mp[..., 2],
        "ovalid": mp[..., 3:3 + stack].astype(jnp.uint8),
        "nvalid": mp2[..., 3:3 + stack].astype(jnp.uint8),
    }
    meta["weight"] = stratified_is_weights(p, mass, n_glob, betas,
                                           num_shards)
    # window start (padded coords): rows [local-stack+1 .. local+n_step]
    # are contiguous there thanks to the ghost rows — always in bounds
    # (slot_pad = slot_cap + window - 1)
    ws = sub * slot_pad + (local - (stack - 1)) % slot_cap
    idx = jnp.where(mass > 0, idx, pm.shape[0])
    return meta, ws.astype(jnp.int32), idx.astype(jnp.int32)


@jax.named_scope("ddq.priority_writeback")
def scatter_priorities(prio: jax.Array, maxp: jax.Array, idx: jax.Array,
                       td_abs: jax.Array, alpha: float, eps: float,
                       ) -> tuple[jax.Array, jax.Array]:
    """Same-step priority write-back (one shard): ``p[idx] ← (|TD|+ε)^α``
    and the running pre-α max. No staleness window exists — sampling and
    update happen in one XLA program, so no write can interleave."""
    from jax import lax

    td = jnp.abs(td_abs) + eps
    prio = prio.at[idx].set(td ** alpha)
    maxp = jnp.maximum(maxp, lax.pmax(jnp.max(td), "dp"))
    return prio, maxp


def insert_meta_pack(staged_u8: jax.Array, maxp: jax.Array, *, k: int,
                     row_len: int, rowb: int,
                     alpha: float) -> tuple[jax.Array, jax.Array]:
    """Device-side insert pack for one staged chunk (ISSUE 8 tentpole
    part 3): runs per shard inside the fused write program.

    The host used to pad every staged frame row to the DMA stride
    (``rowb`` bytes, a ``np.zeros`` + slice copy per segment) and view
    the result as packed int32 — per-row host byte churn on the ingest
    hot path. Here the raw staged bytes arrive as-is and the program:

    - pads ``[k, row_len]`` u8 rows to the ``rowb`` DMA stride,
    - packs pixel bytes 4-per-int32 (``bitcast_convert_type`` — on a
      little-endian host this is bit-identical to the reference's
      ``padded.view(np.int32)``, which tests pin),
    - seeds the fresh-row priority from the device running max
      (``maxp ** α``, the scalar every inserted row shares).

    Returns (flat packed rows ``[k · rowb/4]`` int32, priority seed).
    """
    rows = staged_u8.reshape(k, row_len)
    rows = jnp.pad(rows, ((0, 0), (0, rowb - row_len)))
    packed = jax.lax.bitcast_convert_type(
        rows.reshape(k, rowb // 4, 4), jnp.int32)
    return packed.reshape(-1), maxp ** alpha


def make_write_fn(*, k: int, rowb: int, row_len: int, alpha: float,
                  columnar: bool, interpret: bool):
    """The per-shard flush program body (``shard_map``ped and jitted by
    ``DevicePERFrameReplay``; module-level so the chip's compiler can be
    asked about it for a described device, without a live ring): metadata
    scatters in real coordinates, fresh-row priorities seeded from the
    device max, and the frame-row DMA plane aliased in place."""
    from distributed_deep_q_tpu.ops.ring_gather import scatter_rows

    def write(rows, midx, act, rew, dn, bnd, sidx, didx, staged):
        if columnar:
            # device-side meta pack (ISSUE 8 tentpole part 3): raw
            # staged bytes → padded/packed DMA rows + priority seed
            staged, new_p = insert_meta_pack(
                staged, rows.maxp, k=k, row_len=row_len, rowb=rowb,
                alpha=alpha)
        else:
            new_p = rows.maxp ** alpha
        with jax.named_scope("ddq.scatter_rows"):
            frames = scatter_rows(sidx, didx, staged, rows.frames,
                                  n=2 * k, rowb=rowb, interpret=interpret)
        return DeviceReplayState(
            frames=frames,
            action=rows.action.at[midx].set(act, mode="drop"),
            reward=rows.reward.at[midx].set(rew, mode="drop"),
            done=rows.done.at[midx].set(dn, mode="drop"),
            boundary=rows.boundary.at[midx].set(bnd, mode="drop"),
            prio=rows.prio.at[midx].set(new_p, mode="drop"),
            maxp=rows.maxp,
        )

    # keeps the name: the program stays ``jit_write`` in a trace
    return jax.named_scope("ddq.write")(write)


# ---------------------------------------------------------------------------
# The replay object: DeviceFrameReplay + device metadata/priority twin
# ---------------------------------------------------------------------------


class DevicePERFrameReplay(DeviceFrameReplay):
    """Frame ring + metadata + priorities all device-resident; sampling
    and priority updates happen inside the fused learner step
    (``Learner.train_step_device_per``), so per step the host ships only
    per-slot cursors/sizes (~a few hundred bytes) and reads back nothing.

    Frame-plane layout (round 5 — built for the Pallas row-DMA kernels in
    ``ops/ring_gather.py``; see that module's docstring for the measured
    XLA gather pathology this replaces):

    - frames live in ONE flat int32 array per mesh (pixel bytes packed
      4-per-element — Mosaic's 32-bit index arithmetic caps u8-element
      offsets below the flagship's 8 GB plane), sharded ``P('dp')``; each
      frame row is padded to ``rowb`` bytes (a multiple of the 4 KB 1-D
      tile) so any row range is DMA-alignable.
    - each sub-ring holds ``slot_pad = slot_cap + window - 1`` rows where
      ``window = stack + n_step``: the last ``window - 1`` rows are GHOST
      rows mirroring rows ``0..window-2`` (the flush writes wrap rows
      twice), so every sample's combined obs+next-obs window is ONE
      contiguous ``window``-row DMA — no wrap handling on device.
    - one extra SCRATCH row per shard at the end absorbs the flush's
      padding lanes (the DMA scatter has no out-of-bounds drop).

    Metadata/priority rows stay in REAL (unpadded) coordinates
    ``[capacity]`` — only the pixel plane is padded/ghosted.

    Subclasses ``DeviceFrameReplay`` for all host-side slot bookkeeping
    (stream→slot routing, seal-on-restart, ready gating, the generic
    chunked flush); the overrides pad staged frame rows, widen the
    staging pipeline with metadata columns, and route writes to the
    fused meta-scatter + frame-DMA program.
    """

    def __init__(self, cfg, mesh, frame_shape=(84, 84), stack: int = 4,
                 gamma: float = 0.99, seed: int = 0, write_chunk: int = 64,
                 num_streams: int = 1):
        from jax import shard_map
        from jax.sharding import NamedSharding

        super().__init__(cfg, mesh, frame_shape, stack, gamma, seed,
                         write_chunk, num_streams)
        # the priorities live on the device, whatever ``cfg.prioritized``
        # says: a loop must not start a host write-back for this ring
        self.prioritized = True
        self.n_step, self.gamma = cfg.n_step, gamma
        # frame column: the columnar path stages RAW rows — padding to
        # the DMA stride and the 4-per-int32 byte pack happen inside the
        # jit'd insert program (``insert_meta_pack``), so the host-side
        # stage is a pure memcpy of the wire payload. The legacy
        # reference path stages PADDED rows (host zero-fill + .view),
        # which the device pack is pinned bit-identical against.
        self._stage_columns[0] = (
            ((self._row_len,), np.uint8) if self._columnar
            else ((self.rowb,), np.uint8))
        self._stage_columns += [
            ((), np.int32), ((), np.float32), ((), np.uint8), ((), np.uint8)]
        self._di_cache: tuple[np.ndarray, np.ndarray] | None = None
        self._pending_seals: list[tuple[int, int]] = []
        # to_global assembles a contiguous local block per process
        assert self.local_shards == list(range(
            self.local_shards[0], self.local_shards[0]
            + len(self.local_shards))), (
            "mesh device order must group each process's shards "
            "contiguously for P('dp') local-block assembly")

        sharded = NamedSharding(mesh, P(AXIS_DP))
        replicated = NamedSharding(mesh, P())
        cap = self.capacity

        # metadata/priority rings allocated directly on the mesh; the frame
        # ring is ADOPTED from the base allocation (NOT closed over in a
        # jit — a captured multi-GB device array would be lowered constant)
        def init_meta():
            return (jnp.zeros(cap, jnp.int32), jnp.zeros(cap, jnp.float32),
                    jnp.zeros(cap, jnp.uint8), jnp.zeros(cap, jnp.uint8),
                    jnp.zeros(cap, jnp.float32), jnp.ones((), jnp.float32))

        action, reward, done, boundary, prio, maxp = jax.jit(
            init_meta, out_shardings=(sharded, sharded, sharded, sharded,
                                      sharded, replicated))()
        self.dstate = DeviceReplayState(
            frames=self.ring, action=action, reward=reward, done=done,
            boundary=boundary, prio=prio, maxp=maxp)
        self.ring = None  # the frames now live in dstate (single owner)

        # boundary-only scatter for reset_stream: the device boundary ring
        # must mirror the host seal or the fused sampler would compose
        # windows across a dead writer's seam (frames can't be re-written
        # here — they aren't stored host-side — so this touches ONE column)
        def seal(boundary, idx):
            return boundary.at[idx].set(1, mode="drop")

        self._seal_writer = jax.jit(
            shard_map(seal, mesh=mesh,
                      in_specs=(P(AXIS_DP), P(AXIS_DP)),
                      out_specs=P(AXIS_DP), check_vma=False),
            donate_argnums=0)

        write = make_write_fn(
            k=self.write_chunk, rowb=self.rowb, row_len=self._row_len,
            alpha=float(cfg.priority_alpha), columnar=self._columnar,
            interpret=self._interpret)
        # entry/exit layouts pinned to the live arrays' formats: XLA's
        # auto layout assignment may otherwise pick a transposed entry
        # layout for a metadata plane and relayout-copy it every flush
        state_fmt = jax.tree.map(lambda x: x.format, self.dstate)
        self._write_full = jax.jit(
            shard_map(write, mesh=mesh,
                      in_specs=(STATE_SPEC,) + (P(AXIS_DP),) * 8,
                      out_specs=STATE_SPEC,
                      check_vma=False),
            in_shardings=(state_fmt,) + (None,) * 8,
            out_shardings=state_fmt,
            donate_argnums=0)
        register_programs(self, DevicePERFrameReplay._write_executable)

    # -- padded frame plane --------------------------------------------------

    def _alloc_ring(self) -> None:
        """Flat padded u8 ring (see class docstring) instead of the base's
        ``[capacity, H·W]`` scatter ring. Runs inside ``super().__init__``;
        geometry derives from attributes the base set before the call."""
        from jax.sharding import NamedSharding

        from distributed_deep_q_tpu.ops.ring_gather import padded_row_bytes
        from distributed_deep_q_tpu.parallel.mesh import pallas_interpret

        cfg = self._cfg
        self.window = self.stack + int(cfg.n_step)
        assert self.slot_cap >= self.window, (
            f"slot capacity {self.slot_cap} must hold one sample window "
            f"(stack {self.stack} + n_step {cfg.n_step})")
        self.slot_pad = self.slot_cap + self.window - 1
        self.rowb = padded_row_bytes(self._row_len)   # bytes per frame row
        self.rowp = self.rowb // 4                    # int32 per frame row
        self.cap_local_pad = self.subs_per_shard * self.slot_pad
        self.shard_rows = self.cap_local_pad + 1  # +1 scratch row
        # Mosaic scalar index arithmetic is 32-bit: per-shard ELEMENT
        # offsets must stay below 2^31 (ops/ring_gather.py docstring) —
        # 1M frames of 84x84 sit at 2.048e9, inside by 4.6%
        assert self.shard_rows * self.rowp < 2**31, (
            f"per-shard frame plane ({self.shard_rows} rows x {self.rowp} "
            "int32) exceeds Mosaic's 32-bit index range — shard over more "
            "devices/processes or shrink capacity")
        self._interpret = pallas_interpret(self.mesh)
        shape = (self.num_shards * self.shard_rows * self.rowp,)
        self.ring = jax.jit(
            lambda: jnp.zeros(shape, jnp.int32),
            out_shardings=NamedSharding(self.mesh, P(AXIS_DP)))()
        self._write = None  # frames flush through _write_full's DMA plane

    # -- overridden write plumbing ------------------------------------------

    def _stage(self, slot: int, local, frames_arr) -> None:
        """Stage (rows, frames, action, reward, done, boundary) — the
        metadata comes from the host slot arrays the rows were just
        written to, gathered vectorized (fancy indexing copies).
        Columnar staging takes the frame rows RAW (pad/pack moved into
        the device insert program); the legacy reference pads here."""
        m = self.slots[slot]
        shard, base_off = self._slot_base(slot)
        k = len(local)
        if self._columnar:
            frames_col = frames_arr
        else:
            frames_col = np.zeros((k, self.rowb), np.uint8)
            frames_col[:, :self._row_len] = frames_arr
        self._stage_rows(shard, (base_off + local).astype(np.int32), (
            frames_col, m.action[local], m.reward[local],
            m.done[local].astype(np.uint8),
            m.boundary[local].astype(np.uint8)))
        self._di_cache = None  # cursors/sizes moved

    def _write_args(self, idx, cols) -> tuple:
        """Each padded chunk ([local_shards, k] planes) as the fused
        write's arguments: metadata scatters (real coords, fresh-row
        priorities seeded from the device max) + the frame-row DMA plane
        (padded coords, ghost duplicates, padding lanes → the scratch
        row). Multi-host:
        every plane assembles this process's local rows into the global
        P('dp') arrays; every process enters this program in lockstep
        (``flush``'s agreed round count)."""
        k = self.write_chunk
        i2 = idx  # [dl, k], in-shard real coords
        ok = i2 < self.cap_local
        sub = np.where(ok, i2 // self.slot_cap, 0)
        local = np.where(ok, i2 % self.slot_cap, 0)
        scratch = self.cap_local_pad
        main = np.where(ok, sub * self.slot_pad + local, scratch)
        ghost = np.where(ok & (local < self.window - 1),
                         sub * self.slot_pad + self.slot_cap + local,
                         scratch)
        dl = i2.shape[0]
        src = np.tile(np.arange(k, dtype=np.int32), (dl, 1))
        sidx = np.concatenate([src, src], axis=1)
        didx = np.concatenate([main, ghost], axis=1).astype(np.int32)
        if self._columnar:
            # raw u8 rows; insert_meta_pack pads + packs them on device
            staged = np.ascontiguousarray(cols[0]).reshape(dl, -1)
        else:
            staged = np.ascontiguousarray(cols[0]).reshape(dl, -1).view(
                np.int32)
        return (self.to_global(idx.reshape(-1)),
                *(self.to_global(c.reshape((dl * k,) + t))
                  for c, (t, _) in zip(cols[1:], self._stage_columns[1:])),
                self.to_global(sidx.reshape(-1)),
                self.to_global(didx.reshape(-1)),
                self.to_global(staged.reshape(-1)))

    def _apply_write(self, idx, cols) -> None:
        self.dstate = self._write_full(self.dstate,
                                       *self._write_args(idx, cols))

    def _write_executable(self) -> dict:
        """The flush program's executable, found again from an all-padding
        round's arguments (what a ``TraceWindow`` writes the scope table
        of): nothing is written."""
        dl, k = len(self.local_shards), self.write_chunk
        idx = np.full((dl, k), self.cap_local, np.int32)
        cols = [np.zeros((dl, k) + tail, dt)
                for tail, dt in self._stage_columns]
        return {"write": ran_executable(
            self._write_full, self.dstate, *self._write_args(idx, cols))}

    # -- multi-host plumbing -------------------------------------------------

    def to_global(self, local: np.ndarray):
        """Assemble a per-process local plane (this process's contiguous
        block of a ``P('dp')``-sharded array, dim 0) into the global jax
        array; identity on a single process."""
        if self._pc == 1:
            return local
        from jax.sharding import NamedSharding

        spec = P(*((AXIS_DP,) + (None,) * (local.ndim - 1)))
        factor = self.num_shards // len(self.local_shards)
        gshape = (local.shape[0] * factor,) + local.shape[1:]
        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, spec), np.ascontiguousarray(local),
            global_shape=gshape)

    def to_replicated(self, arr: np.ndarray):
        """Replicate a host value onto the (possibly multi-host) mesh."""
        if self._pc == 1:
            return arr
        from jax.sharding import NamedSharding

        return jax.make_array_from_process_local_data(
            NamedSharding(self.mesh, P()), np.ascontiguousarray(arr),
            global_shape=arr.shape)

    def reset_stream(self, stream: int) -> None:
        """Seal the stream's current slot on HOST AND DEVICE: the fused
        sampler reads the device boundary ring, so a host-only seal would
        let sampled windows straddle the dead writer's seam.

        Multi-host the device seal DEFERS to the next lockstep flush (the
        seal program runs on global arrays — a per-process immediate
        dispatch would deadlock the collective); the sealed row's
        position is fixed at request time, so later ingest into the same
        slot (which appends past it) cannot invalidate it within a
        chunk."""
        if not (0 <= stream < self.num_streams):
            return
        if self._pc == 1:
            # flush FIRST: rows still staged carry their pre-seal boundary
            # values and a later flush would scatter them over the seal
            self.flush()
        cycle = self._slot_cycle[stream]
        slot = cycle[self._stream_pos[stream] % len(cycle)]
        m = self.slots[slot]
        super().reset_stream(stream)
        if len(m) == 0:
            return
        local = (m._cursor - 1) % self.slot_cap
        shard, base_off = self._slot_base(slot)
        if self._pc > 1:
            self._pending_seals.append((shard, base_off + local))
            return
        # one lane per shard; non-owners carry an OOB index the scatter drops
        idx = np.full(self.num_shards, self.cap_local, np.int32)
        idx[shard] = base_off + local
        self.dstate = self.dstate.replace(
            boundary=self._seal_writer(self.dstate.boundary, idx))

    def flush(self) -> None:
        """Base flush (agreed round count multi-host) + deferred device
        seals (one lockstep seal program per agreed seal round). Seals
        drain AFTER the staged rows so pre-seal rows cannot scatter over
        the seal — the single-process ordering, preserved."""
        super().flush()
        if self._pc == 1:
            return
        from distributed_deep_q_tpu.parallel.multihost import global_max_int

        per_shard: dict[int, list[int]] = {}
        for shard, row in self._pending_seals:
            per_shard.setdefault(shard, []).append(row)
        self._pending_seals.clear()
        rounds = global_max_int(max((len(v) for v in per_shard.values()),
                                    default=0))
        dl = len(self.local_shards)
        for r in range(rounds):
            idx = np.full(dl, self.cap_local, np.int32)
            for li, s in enumerate(self.local_shards):
                rows = per_shard.get(s, [])
                if r < len(rows):
                    idx[li] = rows[r]
            self.dstate = self.dstate.replace(
                boundary=self._seal_writer(self.dstate.boundary,
                                           self.to_global(idx)))

    # -- learner-side inputs -------------------------------------------------
    # (β comes from the inherited ``beta`` property; next_betas is what
    # advances the anneal)

    def next_betas(self, k: int) -> np.ndarray:
        """β values for the next ``k`` fused steps, advancing the anneal
        BEFORE each read — the ordering of the host tier's
        ``PrioritizedReplay.sample``, which counts the draw before it
        computes the weights."""
        out = np.empty(k, np.float32)
        for i in range(k):
            self._samples += 1
            out[i] = self.beta
        return out

    def device_inputs(self):
        """(cursors, sizes) int32 host arrays for this process's LOCAL
        shards, shard-major ``[dl·subs]`` — the local block of the global
        ``P('dp')`` plane (``to_global`` assembles it; single-process the
        local block IS the plane).

        Cached between writes: the idle hot loop (no ingest since the last
        step) pays one ``is None`` check instead of a Python pass over all
        slots — at the apex preset's 256 streams that pass is real per-step
        host time (VERDICT r3 weak #3)."""
        if self._di_cache is None:
            d, subs = self.num_shards, self.subs_per_shard
            dl = len(self.local_shards)
            cursors = np.zeros(dl * subs, np.int32)
            sizes = np.zeros(dl * subs, np.int32)
            for li, s in enumerate(self.local_shards):
                for sub in range(subs):
                    m = self.slots[sub * d + s]
                    cursors[li * subs + sub] = m._cursor
                    sizes[li * subs + sub] = len(m)
            self._di_cache = (cursors, sizes)
        return self._di_cache


def pixel_device_ring(cfg, mesh, frame_shape, stack: int, gamma: float,
                      seed: int, num_streams: int = 1):
    """The ring a pixel run with ``replay.device_resident=true`` trains
    from — both train loops ask here, and the fused ring is the only
    answer. ``cfg`` is the run's ``ReplayConfig``. Uniform replay is the
    fused sampler at α = 0 (the ``pong`` preset's way), so a run that
    asks for an unprioritized device ring is told what to set: nothing
    rewrites its config for it.

    ``DevicePERFrameReplay`` is looked up when this is called, and takes
    ``write_chunk`` and ``num_streams`` by name: the benchmark's fleet
    driver stands in for the class to hand the loop a ring it filled."""
    if not cfg.prioritized:
        raise ValueError(
            "a pixel run with replay.device_resident=true samples inside "
            "the fused step, which draws by priority: for uniform replay "
            "set replay.prioritized=true replay.priority_alpha=0 (constant "
            "priorities: uniform draws on the device), or set "
            "replay.device_resident=false for the host replay tier")
    return DevicePERFrameReplay(
        cfg, mesh, frame_shape, stack, gamma, seed=seed,
        write_chunk=cfg.write_chunk, num_streams=num_streams)
