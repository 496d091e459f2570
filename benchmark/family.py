"""What belongs to one model family is found by a name in its configuration
file, and the rules every family is held to live here, where a family
cannot leave them out.

A configuration names (all under ``benchmark/``, dotted names allowed):

- ``"check"``: the module that builds the checked object and compares it
  with the reference. Its interface is two functions:
  ``build_checked(conf, cfg, seed, rows, episode, beta_steps=None,
  mark=...) -> (solver, replay, stream, mirror, rec)`` for the drivers
  (``rec["driven_steps"]`` says how many grad steps it drove) and
  ``compare(conf, seed, mirror, rec, *, quant=None) -> {"numbers": {name:
  value}, "steps": ..., "print": {...}}``. It supplies numbers; it does
  not decide ``correct``. Two optional names: ``FOLLOWED_CHUNKS``, how
  many chunks the comparison follows, which ``control.py --follow-chunks``
  sets before it builds; ``toy(conf, traffic)``, the family's toy sizes as
  a ``conf_patch``, which ``rehearse.py`` walks its cells with on the CPU.
- ``"reference"``: the plain reference, ``reference/<name>.py``; its
  ``EXACT_LIMITS`` are the exact comparisons' limits.
- ``"limits"``: every other limit, read on the chip for this configuration.
- ``"counts"``: ``{"module": <module>, "print": {<printed key>: <function>}}``
  — where the functions that count a kernel's operations and bytes from
  ``hparams`` live (a ``roofline`` metric's ``count`` is resolved there),
  and which of them ``run.py`` prints before the last line.

``verdict`` is what ``run.py`` and ``control.py`` call: the family's
numbers, held to the limits by the one rule (``judge``).
"""

from __future__ import annotations

import importlib
import math
import time

from benchmark.common import emit


def _module(conf: dict, key: str, name):
    if not isinstance(name, str) or not name:
        raise SystemExit(f"configuration {conf['name']} names no {key} "
                         "module (benchmark/README.md, Adding things)")
    return importlib.import_module(f"benchmark.{name}")


def load_check(conf: dict):
    return _module(conf, "check", conf.get("check"))


def load_reference(conf: dict):
    name = conf.get("reference")
    return _module(conf, "reference", name and f"reference.{name}")


def load_counts(conf: dict):
    return _module(conf, "counts", conf.get("counts", {}).get("module"))


def printed_counts(conf: dict) -> dict:
    """The counts a configuration asks to see beside the program's own
    census: ``{printed key: function(hparams)}`` of its counts module."""
    wanted = conf.get("counts", {}).get("print", {})
    if not wanted:
        return {}
    counts = load_counts(conf)
    return {key: getattr(counts, fn)(conf["hparams"])
            for key, fn in wanted.items()}


def judge(conf: dict, nums: dict) -> tuple[bool, dict]:
    """The rule every family is held to. Exact limits are the reference's
    (``EXACT_LIMITS``); every other limit is the configuration's own, read
    on the chip — a number without a limit has not been read, and ends the
    run. A number that is not finite, or over its limit, is not correct.
    Returns ``(correct, {name: [value, limit]})``."""
    limits = {**conf.get("limits", {}),
              **load_reference(conf).EXACT_LIMITS}
    if not nums:
        raise SystemExit(f"configuration {conf['name']}: its comparison "
                         "compared nothing")
    missing = sorted(set(nums) - set(limits))
    if missing:
        raise SystemExit(f"configuration {conf['name']} states no limit for "
                         f"{missing}: read them with benchmark/control.py")
    numbers = {k: [v, limits[k]] for k, v in nums.items()}
    correct = all(math.isfinite(v) and v <= lim
                  for v, lim in numbers.values())
    return bool(correct), numbers


def verdict(conf: dict, seed: int, mirror, rec, *, quant=None,
            label: str = "check") -> dict:
    """Let the family's comparison follow the recorded steps, hold its
    numbers to their limits, and print every number beside its limit.
    Returns ``{"correct": bool, "numbers": {name: [value, limit]},
    "seconds": s, "steps": the family's per-step readings}``. With
    ``quant`` the CONTROL stands where the program stood."""
    t0 = time.perf_counter()
    got = load_check(conf).compare(conf, seed, mirror, rec, quant=quant)
    correct, numbers = judge(conf, got["numbers"])
    secs = time.perf_counter() - t0
    emit(**{label: numbers}, correct=correct,
         reference_seconds=round(secs, 3), quant=quant,
         **got.get("print", {}))
    return dict(correct=correct, numbers=numbers, seconds=secs,
                steps=got.get("steps"))
