"""Small shared pieces of the harness: printing, the compile clock, the
device fence, percentiles, data-file loading."""

from __future__ import annotations

import json
import math
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def emit(**kv) -> None:
    """One JSON object per line, before the contract's last line."""
    print(json.dumps(kv, default=float), flush=True)


def load_json(*parts: str) -> dict:
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark: data file missing: {path}")
    with open(path) as fh:
        return json.load(fh)


class CompileClock:
    """Compile seconds (trace + lowering + backend compile) and
    persistent-cache hits/misses from JAX's monitoring events (copy of
    ``chip_smoke.CompileClock``). ``backend_compiles`` counts programs, so
    a compilation inside the measured window is seen."""

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.backend_compiles = 0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event in self.COMPILE_EVENTS:
            self.compile_s += secs
            if event == self.COMPILE_EVENTS[2]:
                self.backend_compiles += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def fence() -> float:
    """Wait for every device array this process holds, twice (an ingest
    thread may donate one between the listing and the wait: arrays that
    report deleted are skipped). Needs no handle into the program. Returns
    the host clock after the device has drained."""
    import jax

    for _ in range(2):
        for a in jax.live_arrays():
            try:
                if not a.is_deleted():
                    a.block_until_ready()
            except RuntimeError:        # donated between the two lines
                continue
    return time.perf_counter()


def percentile(values, q: float) -> float:
    """Nearest-rank percentile of a non-empty sequence."""
    xs = sorted(values)
    return xs[min(len(xs) - 1, max(0, math.ceil(q * len(xs)) - 1))]


def gaps_ms(starts) -> list[float]:
    return [1e3 * (b - a) for a, b in zip(starts, starts[1:])]


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak
