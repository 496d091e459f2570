#!/usr/bin/env python
"""Diff two bench result files (one JSON line each) and flag regressions.

Usage::

    python scripts/bench_diff.py old.json new.json
    python scripts/bench_diff.py --tolerance 0.05 old.json new.json

Compares every numeric metric present in both files. A metric has
REGRESSED when it moves in its bad direction (throughput down, latency /
op-count up) by more than its tolerance — the larger recorded ``spread``
of the two runs when one exists (benches record run-to-run relative
spread next to gated metrics), else ``--tolerance`` (default 2%).

Exit status is 1 iff a metric regressed; stdlib only, no repo imports, so
it runs anywhere the jsons land.
"""

from __future__ import annotations

import argparse
import json
import sys

# metric -> its recorded run-to-run spread key, where the bench doesn't
# follow the "<prefix>_steps_per_s" / "<prefix>_spread" convention
SPREAD_KEY = {
    "value": "flagship_spread",
    "idle_uniform_steps_per_s": "idle_spread",
    "pallas_off_steps_per_s": "idle_spread",
    "flagship_under_ingest_steps_per_s": "under_ingest_spread",
    # linearity ratios divide two curve points, so their run-to-run
    # spread is the (first-order) SUM of the points' spreads — the bench
    # records that sum next to each ratio
    "multihost_linearity_2x": "multihost_linearity_2x_spread",
    "multihost_linearity_4x": "multihost_linearity_4x_spread",
    # health-plane overhead rows (ISSUE 13) share one measured spread
    "health_sample_us": "health_spread",
    "health_verdict_us": "health_spread",
    "health_disabled_us": "health_spread",
    "mfu_live": "flagship_spread",
    # learn_metrics on-vs-off overhead (ISSUE 16): the pct divides two
    # timed points, so its noise is the sum of their spreads — recorded
    # as learn_spread (learn_off/on_steps_per_s follow the automatic
    # "<prefix>_spread" convention and need no entry here)
    "learn_overhead_pct": "learn_spread",
    # elasticity rows (ISSUE 17) share one measured handoff spread; the
    # remap fractions are ring properties (deterministic given the host
    # set) but ride the same key so a ring change gates like noise would
    "handoff_export_ms": "elasticity_spread",
    "handoff_import_ms": "elasticity_spread",
    "remap_fraction_grow": "elasticity_spread",
    "remap_fraction_shrink": "elasticity_spread",
    # multi-tenant serving rows (ISSUE 20) share one measured spread;
    # shadow_overhead_pct divides two timed latencies, so its noise is
    # the sum of their spreads — folded into the same recorded key
    "tenant_swap_us": "tenant_spread",
    "shadow_overhead_pct": "tenant_spread",
    "executor_apply_us": "tenant_spread",
}

# substrings marking metrics where UP is the bad direction
# (_rpcs: cross_host_replay_rpcs is a badness LEDGER — any cross-host
# replay traffic is a sharding violation, so up must gate, and the
# common old=0 case makes any appearance an infinite regression)
_LOWER_BETTER = ("_ms", "_fusions", "_convs", "_copies", "fusions",
                 "spread", "_rpcs", "_us", "overhead_pct",
                 # remap fraction: more of the fleet reconnecting per
                 # membership change is strictly worse (reconnect storm)
                 "remap_fraction")
# keys that are configuration echoes / identities, not metrics
# (max_in_flight_rows is the writers' backpressure watermark — a state
# echo of the pacing loop, not a quality axis with a bad direction;
# inference_curve's SLO/batch knobs are config echoes, sheds a state
# echo, and local_actions_per_s the comparison-host baseline the
# speedup already folds in — gating it would gate host CPU noise;
# multihost_curve's n_hosts is the point's identity and dispatch_k its
# calibration echo)
_SKIP = ("_chain_k", "_vs_", "vs_baseline", "ring_capacity",
         "flagship_batch", "concurrent_writers", "peak_flops", "n", "rc",
         "flops_per_step", "max_in_flight_rows", "inference_slo_ms",
         "inference_max_batch", "inference_cutoff_us", "sheds",
         "local_actions_per_s", "n_hosts", "dispatch_k", "n_envs",
         # elasticity bench identities: rows carried per handoff and the
         # acting fleet the remap fractions are computed over
         "handoff_rows", "fleet_size",
         # config echo: the live-vs-offline MFU agreement bound bench.py
         # asserts; the gated quality axes are mfu / mfu_live themselves
         "mfu_live_tolerance")


def _parsed(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    return doc.get("parsed", doc) if isinstance(doc, dict) else {}


def _lower_is_better(key: str) -> bool:
    return any(tag in key for tag in _LOWER_BETTER)


def _skipped(key: str) -> bool:
    return key in _SKIP or any(tag in key for tag in _SKIP if tag != "n")


def _spread_for(key: str, a: dict, b: dict) -> float | None:
    sk = SPREAD_KEY.get(key)
    if sk is None and key.endswith("_steps_per_s"):
        sk = key[: -len("_steps_per_s")] + "_spread"
    if sk is None:
        return None
    vals = [d[sk] for d in (a, b) if isinstance(d.get(sk), (int, float))]
    return max(vals) if vals else None


def _flatten(d: dict, prefix: str = "") -> dict:
    """Nested curve rows (``ingest_curve``, ``inference_curve``) become
    dotted keys; each nested dict's own ``spread`` rides along under its
    dotted name and becomes the tolerance for its siblings."""
    out = {}
    for k, v in d.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, f"{key}."))
        elif isinstance(v, (int, float)) and not isinstance(v, bool):
            out[key] = float(v)
    return out


def diff(a: dict, b: dict, tolerance: float):
    """-> (rows, failed). Each row: (key, old, new, rel_delta, tol,
    status) with status in {ok, improved, regressed}."""
    fa, fb = _flatten(a), _flatten(b)
    rows, failed = [], False
    for key in sorted(fa.keys() & fb.keys()):
        if _skipped(key) or key.endswith(".spread"):
            continue
        old, new = fa[key], fb[key]
        if key.endswith("spread"):
            continue
        tol = _spread_for(key, a, b)
        if tol is None:
            # nested curves record spread alongside the metric
            tol = fa.get(key.rsplit(".", 1)[0] + ".spread")
        if tol is None:
            tol = tolerance
        delta = (new - old) / abs(old) if old else (0.0 if new == old
                                                    else float("inf"))
        bad = -delta if _lower_is_better(key) else delta
        if bad < -tol:
            status, failed = "regressed", True
        elif bad > tol:
            status = "improved"
        else:
            status = "ok"
        rows.append((key, old, new, delta, tol, status))
    return rows, failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", help="baseline bench json")
    ap.add_argument("new", help="candidate bench json")
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="relative tolerance for metrics with no "
                         "recorded spread (default 0.02)")
    ap.add_argument("--all", action="store_true",
                    help="print every compared metric, not just moves")
    args = ap.parse_args(argv)

    rows, failed = diff(_parsed(args.old), _parsed(args.new),
                        args.tolerance)
    if not rows:
        print("no shared numeric metrics to compare")
        return 2

    width = max(len(r[0]) for r in rows)
    marks = {"regressed": "!!", "improved": "++", "ok": "  "}
    shown = 0
    for key, old, new, delta, tol, status in rows:
        if status == "ok" and not args.all:
            continue
        shown += 1
        print(f"{marks[status]} {key:<{width}}  {old:>12.4g} -> "
              f"{new:>12.4g}  {delta:+8.2%} (tol {tol:.2%}) "
              f"{status}")
    if shown == 0:
        print(f"all {len(rows)} shared metrics within tolerance")
    print(f"\n{len(rows)} metrics compared; "
          f"{sum(r[5] == 'regressed' for r in rows)} regressed, "
          f"{sum(r[5] == 'improved' for r in rows)} improved")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
