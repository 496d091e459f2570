#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip (actor children are CPU). Everything that
belongs to one configuration, one traffic mix or one per-layer metric is a
file found by the name in ``BENCHMARK.json``, and what belongs to one model
family (its comparison, its reference, its counts) by a name in the
configuration (``family.py``; see ``benchmark/README.md``). Every line but
the last is one JSON object of things worth reading; the last line is the
contract's object, and the numbers compared are standard error's last
lines. No TPU, fewer chips than the cell
asks for, a compilation inside the window, a missing data file or a trace
pattern that matches nothing: exit code != 0 and no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.common import (  # noqa: E402
    CompileClock, HERE, emit, gaps_ms, load_json, percentile)

MIN_GAPS = 200          # chunk_gap_p95_ms needs ten samples past the tail


def load_cell(name: str) -> tuple[dict, dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: unknown workload {name!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    with open(os.path.join(ROOT, files[cell["config"]])) as fh:
        conf = json.load(fh)
    traffic = load_json("traffic", f"{cell['traffic']}.json")
    return bench, cell, dict(conf=conf, traffic=traffic)


def metrics_for(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def end_to_end(result: dict, wanted: set, strict: bool = True) -> dict:
    """The end-to-end values a cell's result carries (over all the work and
    all the time of the window)."""
    window = result["t_close"] - result["t_open"]
    gaps = gaps_ms(result["chunk_starts"])
    emit(chunk_gaps=len(gaps), window_s=window,
         chunk_gap_median_ms=percentile(gaps, 0.5) if gaps else None)
    out = {"grad_steps_per_s": result["steps"] / window,
           "setup_s": result["setup_s"]}
    if "chunk_gap_p95_ms" in wanted:
        if strict and len(gaps) < MIN_GAPS:
            raise SystemExit(f"only {len(gaps)} chunk gaps in the window; "
                             f"the 95th percentile needs {MIN_GAPS}")
        out["chunk_gap_p95_ms"] = percentile(gaps, 0.95)
    return out


def per_layer(bench: dict, cell: str, ctx, strict: bool = True) -> dict:
    out = {}
    for m in metrics_for(bench, "per_layer", cell):
        spec = load_json("layer_metrics", f"{m['name']}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec["args"])
        if value is None and not strict:
            continue
        if value is None:
            raise SystemExit(f"per-layer metric {m['name']} found nothing "
                             f"to read in cell {cell}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(args, *, backend: str = "tpu", conf_patch=None) -> dict:
    """Everything after the look for a chip: the driver, the metrics, the
    verdict. Returns the contract's object (``test_control.py`` drives this
    with the timed path broken underneath)."""
    from benchmark import family, trace_reduce

    bench, cell, files = load_cell(args.workload)
    conf, traffic = files["conf"], files["traffic"]
    if conf_patch:
        conf_patch(conf, traffic)
    out_dir = os.path.join(HERE, "out", args.workload,
                           f"seed{args.seed}_trace{args.trace}")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)
    clock = CompileClock()
    ctx = types.SimpleNamespace(
        conf=conf, traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), backend=backend, clock=clock,
        t_start=T_START, out_dir=out_dir)
    emit(workload=args.workload, seed=args.seed, seconds=args.seconds,
         trace=args.trace, config=conf["name"], reduced=conf["reduced"],
         reduced_why=conf["reduced_why"], assumed=conf["assumed"],
         traffic=traffic)

    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
    result = driver.run(ctx)
    emit(setup_s=result["setup_s"], compile_s_total=clock.compile_s,
         cache_hits=clock.hits,
         cache_misses=clock.misses,
         compiles_in_window=result["compiles_in_window"],
         memory_peak_bytes=result["memory_peak_bytes"],
         program_flops_per_step=result["program_flops_per_step"])
    if result["compiles_in_window"]:
        raise SystemExit(f"{result['compiles_in_window']} compilation(s) "
                         "inside the measured window")

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    peaks_all = load_json("peaks.json")
    emit(**family.printed_counts(conf))
    line: dict = {}
    if args.trace:
        if dev.device_kind not in peaks_all and backend == "tpu":
            raise SystemExit(f"no peaks for device kind {dev.device_kind!r}")
        trace = None
        if backend == "tpu":
            trace = trace_reduce.load(
                trace_reduce.find_xplane(result["trace_dir"]))
        rctx = types.SimpleNamespace(
            trace=trace, result=result, conf=conf, hp=conf["hparams"],
            peaks=peaks_all.get(dev.device_kind, {}))
        try:
            metrics = per_layer(bench, args.workload, rctx,
                                backend == "tpu")
        except trace_reduce.NothingMatched as e:
            raise SystemExit(f"trace: {e}")
        if trace is not None:
            device.update(trace_reduce.busy(trace))
            line["breakdown"] = {
                "device_ops": trace_reduce.top_ops(trace),
                "idle_gaps": trace_reduce.idle_gaps(trace)}
    else:
        listed = metrics_for(bench, "end_to_end", args.workload)
        values = end_to_end(result, {m["name"] for m in listed},
                            backend == "tpu")
        metrics = {}
        for m in listed:
            if m["name"] not in values:
                if backend != "tpu":
                    continue
                raise SystemExit(f"cell {args.workload} does not produce "
                                 f"{m['name']}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}

    # the reference follows the program's first steps only now: outside
    # set-up, outside the window, after the ring was freed
    v = family.verdict(conf, args.seed, result["mirror"], result["rec"])
    failed = int(result["failed"])
    return {"correct": bool(v["correct"] and failed == 0),
            "attempted": int(result["attempted"]), "failed": failed,
            "metrics": metrics, "device": device, **line,
            "compared": v["numbers"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "distributed_deep_q_tpu")):
        print("benchmark: the program (distributed_deep_q_tpu/) is not in "
              "this directory — nothing to measure", file=sys.stderr)
        return 1
    _, cell, _ = load_cell(args.workload)

    from benchmark import program

    cache_dir = program.place_compile_cache()   # before first backend use
    import jax

    # small programs too: set-up must find everything in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"benchmark: JAX found platform {devs[0].platform!r}, not a "
              "TPU — nothing was run", file=sys.stderr)
        return 1
    if len(devs) < cell["chips"]:
        print(f"benchmark: cell needs {cell['chips']} chip(s), JAX reports "
              f"{len(devs)}", file=sys.stderr)
        return 1
    emit(jax=jax.__version__, device_kind=devs[0].device_kind,
         devices=len(devs), compile_cache_dir=cache_dir,
         cache_from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")))
    line = run_cell(args)
    sys.stdout.flush()
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
