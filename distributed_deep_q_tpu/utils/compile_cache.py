"""Where the persistent XLA compile cache lives — decided from outside.

One rule for every entry point that holds the chip (``main.main``,
``chip_smoke.py``), applied before first backend use:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code, so the environment is never overridden and no second
  directory appears.
- otherwise: ``<checkout>/.jax_cache`` (git-ignored). A FIXED path —
  the directory is part of the cache key, so a temp name, a pid or a
  timestamp would never hit.

Whichever it is, a program's metadata is part of its cache key
(``jax_compilation_cache_include_metadata_in_key``): the ``ddq.*`` scope
names live in that metadata and ``profiling.scope_table`` reads them back
out of the executable, so an entry another commit wrote for the same
operations under OTHER names must not be handed to this one (JAX's default
would: PERF.md §6, PR 36). The price: a commit that moves a traced line
compiles that program cold once — as the programs that hold a Mosaic
kernel already did, whose serialized body carries its locations.

Child processes: the in-code setting does not travel (spawned actors and
multi-process workers start from a fresh import and never call this), so
only an exported ``JAX_COMPILATION_CACHE_DIR`` reaches them.

``CompileClock`` is the one clock for what compiling costs: seconds of
tracing + lowering + backend compile (or cache load), the number of
programs, and persistent-cache hits and misses, from JAX's own monitoring
events. ``chip_smoke.py`` splits its phases with it, and both train loops
put ``process_clock().row()`` in their log rows, where a count that
still grows in steady state is a recompile.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> str:
    """Apply the rule above; returns the directory in use."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileClock:
    """Compile seconds, compiled programs and persistent-cache hits and
    misses since construction. JAX keeps its listeners for the life of
    the process, so a loop that may run many times in one process shares
    ``process_clock()`` instead of making its own."""

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                      "/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.programs = 0       # backend compiles and cache loads
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event in self.COMPILE_EVENTS:
            self.compile_s += secs
            if event == self.COMPILE_EVENTS[2]:
                self.programs += 1

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def row(self) -> dict[str, float]:
        """The two cumulative keys a train loop's log row carries."""
        return {"compile/count": self.programs,
                "compile/seconds": round(self.compile_s, 3)}


_process_clock: CompileClock | None = None


def process_clock() -> CompileClock:
    """The process's shared clock, started on first call."""
    global _process_clock
    if _process_clock is None:
        _process_clock = CompileClock()
    return _process_clock
