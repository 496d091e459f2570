"""The scope-table reader under tier-1: the cases of
``benchmark/test_scope_reader.py`` (the three ways ``scope_table_time``
finds nothing, the partition of a program's device time by innermost
scope), imported here so the driver's run guards them — as
``test_benchmark_seam.py`` does for ``benchmark/test_seam.py`` — plus one
case that holds every new metric's data file to what the reader takes."""

from __future__ import annotations

import inspect
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.test_scope_reader import *  # noqa: E402,F401,F403
from benchmark.test_scope_reader import ctx  # noqa: E402,F401  (fixture)


def _scope_metrics():
    from benchmark.common import load_json
    from benchmark.test_seam import bench_json

    out = []
    for m in bench_json()["per_layer"]:
        spec = load_json("layer_metrics", f"{m['name']}.json")
        if spec["reader"] == "scope_table_time":
            out.append((m, spec))
    return out


@pytest.mark.parametrize("name", [m["name"] for m, _ in _scope_metrics()])
def test_scope_metric_files_match_the_reader_and_the_program(name):
    """Every scope a metric names is one the program records (a
    ``jax.named_scope`` literal in its source), its arguments are the
    reader's, and it lists the cells it is read in."""
    from benchmark.readers import scope_table_time

    m, spec = next(x for x in _scope_metrics() if x[0]["name"] == name)
    params = inspect.signature(scope_table_time.read).parameters
    assert set(spec["args"]) == set(params) - {"ctx"}
    assert m["workloads"] and m["source"] == "device_trace"
    src = ""
    for rel in ("parallel/learner.py", "replay/device_per.py",
                "models/qnet.py"):
        with open(os.path.join(ROOT, "distributed_deep_q_tpu", rel)) as fh:
            src += fh.read()
    for scope in spec["args"]["scopes"]:
        assert f'named_scope("{scope}")' in src, scope
