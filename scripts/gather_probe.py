"""Diagnose the 1M-ring gather slowness (VERDICT r4 missing #1 / PERF §3).

An isolated 65k-row gather from the 1M-row uint8 frame ring measures
~73 ms for a 462 MB output — far off the ~1.1 ms HBM copy bound. This
probe separates the candidate causes before a kernel is designed:

- capacity scaling: is the cost O(output) or O(ring)?
- dtype tiling: uint8 rows live in (32,128) HBM tiles, so a row-gather
  may read 32x its bytes; an int32 view ([cap, 1764]) amplifies only 8x.
- index order: XLA's gather may have a fast path for sorted indices.
- Pallas row-DMA: per-row async copies straight HBM->HBM, no tiles read
  beyond the row's own granules.

Every timed window here ends with a D2H read of a scalar that
data-depends on every gather.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

ROW = 7056          # 84*84
N_OUT = 65_536      # rows per gather (= chain 32 x batch 512 x stack 4 / 2)
K = 8               # gathers per timed program


def timed(fn, *args, reps=3) -> float:
    """Median seconds per call of jitted fn returning a scalar, fenced."""
    r = fn(*args)
    int(jax.device_get(r))  # compile + first run
    outs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        int(jax.device_get(fn(*args)))
        outs.append(time.perf_counter() - t0)
    return float(np.median(outs))


def probe_xla(frames: jax.Array, idxs: jax.Array) -> float:
    """K gathers in one program; returns s per gather."""

    @jax.jit
    def run(frames, idxs):
        acc = jnp.zeros((), jnp.int32)
        for i in range(K):
            out = frames[idxs[i]]
            acc = acc + jnp.sum(out[:, :1].astype(jnp.int32))
        return acc

    return timed(run, frames, idxs) / K


def main() -> None:
    rng = np.random.default_rng(0)
    print(f"device: {jax.devices()[0].device_kind}")
    print(f"output rows per gather: {N_OUT}  row bytes: {ROW}  "
          f"output MB: {N_OUT * ROW / 1e6:.0f}")

    for cap in (65_536, 262_144, 1_048_576):
        idx = rng.integers(0, cap, (K, N_OUT)).astype(np.int32)
        idx_sorted = np.sort(idx, axis=1)

        frames8 = jnp.zeros((cap, ROW), jnp.uint8)
        t8 = probe_xla(frames8, jnp.asarray(idx))
        t8s = probe_xla(frames8, jnp.asarray(idx_sorted))
        del frames8

        frames32 = jnp.zeros((cap, ROW // 4), jnp.int32)
        t32 = probe_xla(frames32, jnp.asarray(idx))
        t32s = probe_xla(frames32, jnp.asarray(idx_sorted))
        del frames32

        bw = N_OUT * ROW / 1e9
        print(f"cap {cap:>9}: uint8 {t8*1e3:7.2f} ms ({bw/t8:6.1f} GB/s) | "
              f"uint8-sorted {t8s*1e3:7.2f} | "
              f"int32 {t32*1e3:7.2f} ({bw/t32:6.1f} GB/s) | "
              f"int32-sorted {t32s*1e3:7.2f}")


if __name__ == "__main__":
    main()
