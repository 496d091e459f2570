"""``readers/scope_table_time.py`` on a small synthetic trace and the table
``profiling.TraceWindow`` would have written beside it: the three ways to
find nothing, and the partition of a program's device time by innermost
scope. Run by hand:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_scope_reader.py -q
"""

from __future__ import annotations

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.readers import scope_table_time as stt  # noqa: E402

US = 1e3    # ns
TRAIN = "^jit_(tree|plane)_train_fn"

# two executions of a train program of chain 2, 100 us each, and one of
# the sample program; the while is a container, ``%copy.9`` has no scope
OPS = [("%while.3 = (s32[], f32[8]) while(%tuple.1)", 0, 100),
       ("%fusion.1 = f32[8] fusion(%p)", 0, 10),
       ("%convolution.7 = f32[8] convolution(%fusion.1)", 10, 30),
       ("%copy.4 = f32[8] copy(%convolution.7)", 40, 5),
       ("%and_convert_fusion.12 = (f32[8], f32[8]) fusion(%copy.4)", 45, 20),
       ("%copy.9 = s32[] copy(%constant.1)", 65, 5),
       ("%fusion.2 = f32[8] fusion(%p)", 70, 30)]
TABLE = {
    "jit_tree_train_fn": {
        "scopes": {"fusion.1": ["ddq.train", "ddq.unpack"],
                   "convolution.7": ["ddq.train", "ddq.conv_in"],
                   "copy.4": ["ddq.train", "ddq.conv_in"],
                   "and_convert_fusion.12": ["ddq.train", "ddq.loss"],
                   "fusion.2": ["ddq.train"],
                   "fusion.77": ["ddq.train", "ddq.optimizer"]},
        "mixed": {"and_convert_fusion.12": ["ddq.loss", "ddq.optimizer"]},
        "inherited": {"copy.4": "convolution.7"}},
    "jit_sample_fn": {
        "scopes": {"fusion.55": ["ddq.sample", "ddq.draw"]},
        "mixed": {}, "inherited": {}}}


def trace():
    mods, ops = [], []
    for k in range(2):
        t0 = k * 500 * US
        mods.append((f"jit_tree_train_fn({k})", t0, 100 * US))
        ops += [(n, t0 + s * US, d * US) for n, s, d in OPS]
    mods.append(("jit_sample_fn(7)", 300 * US, 50 * US))
    ops.append(("%fusion.55 = f32[8] fusion(%p)", 300 * US, 40 * US))
    # an operation of the same name outside any execution of the program
    ops.append(("%fusion.1 = f32[8] fusion(%p)", 900 * US, 10 * US))
    return {"/device:TPU:0": {"XLA Modules": mods, "XLA Ops": ops}}


@pytest.fixture
def ctx(tmp_path):
    with open(tmp_path / stt.SCOPES_FILE, "w") as fh:
        json.dump({"programs": TABLE, "unavailable": {},
                   "scope_table_s": 0.01}, fh)
    return types.SimpleNamespace(trace=trace(), hp={"fused_chain": 2},
                                 result={"trace_dir": str(tmp_path)})


def read(ctx, scopes, program=TRAIN, per="fused_chain"):
    return stt.read(ctx, program=program, scopes=scopes, per_execution=per)


@pytest.mark.parametrize("scopes,want_us", [
    (["ddq.unpack"], 10), (["ddq.conv_in"], 35), (["ddq.loss"], 20),
    (["ddq.train"], 30), (["ddq.unpack", "ddq.loss"], 30),
], ids=lambda v: "+".join(v) if isinstance(v, list) else "")
def test_scope_time_per_step(ctx, scopes, want_us):
    # per execution over the chain of 2, in ms
    assert read(ctx, scopes) == pytest.approx(want_us / 2 / 1e3)


def test_sample_program_is_read_per_execution(ctx):
    assert read(ctx, ["ddq.draw"], "^jit_sample_fn", 1) == \
        pytest.approx(0.040)


def test_the_innermost_scopes_partition_the_program(ctx, capsys):
    scopes = {st[-1] for st in TABLE["jit_tree_train_fn"]["scopes"].values()}
    named = sum(read(ctx, [s]) or 0.0 for s in sorted(scopes))
    printed = [json.loads(line) for line in
               capsys.readouterr().out.splitlines()]
    part = next(p["scope_partition_ms_per_execution"] for p in printed
                if "scope_partition_ms_per_execution" in p)
    assert list(part) == ["jit_tree_train_fn"]      # printed once
    part = part["jit_tree_train_fn"]
    assert part["executions"] == 2
    assert part["total"] == pytest.approx(0.100)    # the container is out
    assert part["by_scope"][stt.UNSCOPED] == pytest.approx(0.005)
    assert part["mixed"] == pytest.approx(0.020)
    assert part["inherited"] == pytest.approx(0.005)
    # per step x chain + what no scope owns = the program's operations
    assert 2 * named + part["by_scope"][stt.UNSCOPED] == \
        pytest.approx(part["total"])


def test_no_trace_is_none(ctx):
    ctx.trace = None
    assert read(ctx, ["ddq.unpack"]) is None


def test_no_file_reads_zero_after_one_line(ctx, capsys):
    os.remove(os.path.join(ctx.result["trace_dir"], stt.SCOPES_FILE))
    assert read(ctx, ["ddq.unpack"]) == 0.0
    assert read(ctx, ["ddq.conv_in"]) == 0.0
    said = [line for line in capsys.readouterr().out.splitlines()
            if "program_scopes" in line]
    assert len(said) == 1 and "none written" in said[0]


@pytest.mark.parametrize("program,scopes", [
    (TRAIN, ["ddq.plane_pack"]),        # the program has no such scope
    (TRAIN, ["ddq.optimizer"]),         # the scope names no event
    ("^jit_token_train_fn", ["ddq.optimizer"]),     # no such program
], ids=["scope_missing", "no_event", "program_missing"])
def test_a_lost_scope_is_none(ctx, program, scopes):
    assert read(ctx, scopes, program) is None
