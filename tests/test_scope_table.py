"""The program's scope table (ISSUE 36): ``profiling.scope_table`` turns a
compiled module's text into ``{instruction: [ddq.* scopes]}``, the two
fused CNN programs carry the scopes PERF.md §3 lists, and a ``TraceWindow``
leaves ``ddq_scopes.json`` beside the trace it captured — found again from
the executables that ran, with nothing compiled and nothing on a step's
path while no window captures."""

import json
import os
import re
import sys

import numpy as np
import pytest

from distributed_deep_q_tpu import profiling, tracing

pytestmark = [pytest.mark.tracing]

CHAIN = 2

# -- (a) the table function on hand-written module text ---------------------
# the chip compiler's spelling: scheduled module, fused computations first,
# tuple types with ``/*index=5*/`` comments, metadata after the operands
MODULE = '''HloModule jit_tree_train_fn, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0:T(128)} parameter(0)
  %mul.1 = f32[8]{0:T(128)} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(tree_train_fn)/ddq.train/while/body/ddq.unpack/mul" stack_frame_id=3}
  ROOT %bitcast.9 = f32[8]{0:T(128)} bitcast(%mul.1)
}

%fused_computation.2 (param_0.2: f32[8], param_1.2: f32[8]) -> (f32[8], f32[8]) {
  %param_0.2 = f32[8]{0:T(128)} parameter(0)
  %param_1.2 = f32[8]{0:T(128)} parameter(1)
  %add.2 = f32[8]{0:T(128)} add(%param_0.2, %param_1.2), metadata={op_name="jit(tree_train_fn)/ddq.train/while/body/ddq.loss/add" stack_frame_id=4}
  %sub.2 = f32[8]{0:T(128)} subtract(%add.2, %param_1.2), metadata={op_name="jit(tree_train_fn)/ddq.train/while/body/ddq.optimizer/ddq.optimizer/sub" stack_frame_id=5}
  ROOT %tuple.2 = (f32[8]{0:T(128)}, f32[8]{0:T(128)}) tuple(%add.2, %sub.2)
}

%region_0.5 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.5 = f32[] add(%a, %b)
}

%body.3 (arg: (s32[], f32[8], /*index=2*/f32[8])) -> (s32[], f32[8], /*index=2*/f32[8]) {
  %arg = (s32[]{:T(128)}, f32[8]{0:T(128)}, /*index=2*/f32[8]{0:T(128)}) parameter(0)
  %get-tuple-element.1 = f32[8]{0:T(128)} get-tuple-element(%arg), index=1
  %slice-start.1 = ((f32[8]{0:T(128)}), f32[8]{0:T(128)S(1)}, s32[]{:S(2)}) slice-start(%get-tuple-element.1), slice={[0:8]}
  %slice-done.1 = f32[8]{0:T(128)S(1)} slice-done(%slice-start.1)
  %fusion.1 = f32[8]{0:T(128)} fusion(%slice-done.1), kind=kLoop, calls=%fused_computation.1
  %convolution.7 = f32[8]{0:T(128)} convolution(%fusion.1, %fusion.1), window={size=1}, dim_labels=b0f_0io->b0f, metadata={op_name="jit(tree_train_fn)/ddq.train/while/body/transpose(jvp(ddq.conv_in))/conv1/conv_general_dilated" stack_frame_id=6}
  %copy.4 = f32[8]{0:T(128)} copy(%convolution.7), backend_config={"flag_configs":[]}
  %reduce-window.1 = f32[8]{0:T(128)} reduce-window(%copy.4, %constant.1), window={size=8 pad=7_0}, to_apply=%region_0.5, metadata={op_name="reduce_window_sum" stack_frame_id=8}
  %and_convert_fusion.12 = (f32[8]{0:T(128)}, /*index=1*/f32[8]{0:T(128)}) fusion(%copy.4, %reduce-window.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(tree_train_fn)/ddq.train/while/body/ddq.loss/add" stack_frame_id=4}
  %token_train_fn.3 = f32[8]{0:T(128)} custom-call(%copy.4), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "TUzvUgFNTElS
  %0 = not an instruction of this module
"}},
    metadata={op_name="jit(tree_train_fn)/ddq.train/while/body/ddq.experts/pallas_call" source_file="ops.py" source_line=12}
  %reshape.26 = f32[8]{0:T(128)} reshape(%token_train_fn.3), metadata={op_name="jit(tree_train_fn)/reshape" stack_frame_id=1}
  %sample_fn.1 = f32[8]{0:T(128)} custom-call(%reshape.26), custom_call_target="tpu_custom_call", metadata={op_name="jit(tree_train_fn)/pallas_call" stack_frame_id=2}
  %constant.1 = f32[] constant(0), metadata={op_name="jit(tree_train_fn)/ddq.train/const"}
  ROOT %tuple.3 = (s32[]{:T(128)}, f32[8]{0:T(128)}, /*index=2*/f32[8]{0:T(128)}) tuple(%get-tuple-element.1, %copy.4, %sample_fn.1)
}

ENTRY %main.9 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0:T(128)} parameter(0), metadata={op_name="state.params"}
  %while.3 = (s32[]{:T(128)}, f32[8]{0:T(128)}, /*index=2*/f32[8]{0:T(128)}) while(%tuple.1), condition=%cond.2, body=%body.3, metadata={op_name="jit(tree_train_fn)/ddq.train/while" stack_frame_id=9}
  %call.4 = f32[8]{0:T(128)} call(%p), to_apply=%region_0.5, metadata={op_name="jit(tree_train_fn)/ddq.train/call"}
  ROOT %get-tuple-element.9 = f32[8]{0:T(128)} get-tuple-element(%while.3), index=1, metadata={op_name="jit(tree_train_fn)/ddq.train/while" stack_frame_id=9}
}
'''


@pytest.fixture(scope="module")
def hand():
    return profiling.scope_table(MODULE)


@pytest.mark.parametrize("instr,want", [
    # nested scopes, outermost first; a name nested in itself given once
    ("sub.2", ["ddq.train", "ddq.optimizer"]),
    # transform wrappers keep the name inside them
    ("convolution.7", ["ddq.train", "ddq.conv_in"]),
    # a fusion without metadata whose ROOT is unscoped: its fused
    # instructions' scope
    ("fusion.1", ["ddq.train", "ddq.unpack"]),
    # a multi-output fusion behind a tuple type with an index comment
    ("and_convert_fusion.12", ["ddq.train", "ddq.loss"]),
    # a Mosaic call whose metadata spans lines
    ("token_train_fn.3", ["ddq.train", "ddq.experts"]),
    # compiler-made: the first scoped operand's (a relayout copy, and an
    # expander's pass that kept only its own op_name) ...
    ("copy.4", ["ddq.train", "ddq.conv_in"]),
    ("reduce-window.1", ["ddq.train", "ddq.conv_in"]),
    # ... else the first scoped user's, through a chain
    ("slice-done.1", ["ddq.train", "ddq.unpack"]),
    ("slice-start.1", ["ddq.train", "ddq.unpack"]),
    ("get-tuple-element.9", ["ddq.train"]),
], ids=lambda v: v if isinstance(v, str) else "")
def test_table_gives_an_instruction_its_scope_stack(hand, instr, want):
    assert hand["scopes"][instr] == want


@pytest.mark.parametrize("instr", [
    "while.3", "call.4",                # containers: their bodies' time
    "p", "param_0.1", "constant.1",     # no device event of their own
    "reshape.26", "sample_fn.1",        # traced by the program, unscoped
    "0",                                # a line inside a kernel's payload
])
def test_table_leaves_out(hand, instr):
    assert instr not in hand["scopes"]
    assert instr not in hand["inherited"]


def test_table_marks_mixed_fusions_and_inherited_scopes(hand):
    assert hand["mixed"] == {
        "and_convert_fusion.12": ["ddq.loss", "ddq.optimizer"]}
    assert hand["inherited"]["copy.4"] == "convolution.7"
    assert hand["inherited"]["reduce-window.1"] == "copy.4"
    assert hand["inherited"]["slice-done.1"] == "fusion.1"
    assert "fusion.1" not in hand["inherited"]      # it owns what it fused
    assert profiling.hlo_module_name(MODULE) == "jit_tree_train_fn"


# -- (b) the tiny fused pair, both bodies ----------------------------------
SAMPLE_SCOPES = {"ddq.sample_prep", "ddq.meta_pack", "ddq.draw"}
TRAIN_SCOPES = {"ddq.unpack", "ddq.conv_in", "ddq.conv_mid", "ddq.fc",
                "ddq.loss", "ddq.optimizer", "ddq.priority_writeback"}
PLANE_SCOPES = {"ddq.plane_pack", "ddq.plane_unpack", "ddq.grad_plane"}


@pytest.fixture(scope="module", params=["plane", "tree"])
def pair(request, toy_fused_pair):
    """The toy fused pair, run for two chunks; ``stack_forwards=off`` is
    what gives the tree body at a toy batch."""
    from distributed_deep_q_tpu.solver import FusedStepStream

    def patch(cfg):
        if request.param == "tree":
            cfg.train.stack_forwards = "off"

    solver, dev = toy_fused_pair(CHAIN, patch)
    stream = FusedStepStream(solver, dev, CHAIN)
    for _ in range(2 * CHAIN):
        stream.next(10 ** 6)
    return request.param, solver, dev, stream


class _Compiles:
    """Counts the programs JAX hands to the backend (or looks up in the
    persistent cache): what the benchmark's drivers refuse inside their
    window."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@pytest.fixture(scope="module")
def compiles():
    return _Compiles()


def test_fused_pair_carries_every_scope_in_the_right_program(pair, compiles):
    body, solver, dev, _ = pair
    n0 = compiles.n
    exes = solver.fused_executables(dev, CHAIN)
    assert compiles.n == n0             # the executables that RAN
    texts = {k: exe.as_text() for k, exe in exes.items()}
    assert profiling.hlo_module_name(texts["sample"]) == "jit_sample_fn"
    assert profiling.hlo_module_name(texts["train"]) == \
        f"jit_{body}_train_fn"
    sample = profiling.scope_table(texts["sample"])
    train = profiling.scope_table(texts["train"])

    def innermost(t):
        return {st[-1] for st in t["scopes"].values()}

    assert innermost(sample) == SAMPLE_SCOPES
    assert all(st[0] == "ddq.sample" for st in sample["scopes"].values())
    want = TRAIN_SCOPES | {"ddq.train"} | (
        PLANE_SCOPES if body == "plane" else set())
    assert innermost(train) == want
    assert all(st[0] == "ddq.train" for st in train["scopes"].values())
    for t in (sample, train):
        assert set(t["mixed"]) <= set(t["scopes"])
        assert set(t["inherited"]) <= set(t["scopes"])


def test_window_gather_stays_unscoped(pair):
    """XLA names a Mosaic custom-call after the innermost scope around it
    and ``gather_windows_roofline`` finds this one as ``%sample_fn.N``:
    nothing the program traced outside ``ddq.sample`` carries a scope —
    here the loops the interpreted kernel lowers to, on the chip its
    custom-call (``test_chip_compile.py``)."""
    _, solver, dev, _ = pair
    text = solver.fused_executables(dev, CHAIN)["sample"].as_text()
    table = profiling.scope_table(text)["scopes"]
    # (a fusion answers for what it fused: one that took a scoped
    # operation in is that operation's)
    outside = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+) = \S+ (?!fusion)[^\n]*"
        r"op_name=\"jit\(sample_fn\)/(?!ddq\.)[^\"]*while", text, re.M)]
    assert len(outside) > 10
    assert not [i for i in outside if i in table]


def _inner_instructions(text: str) -> set[str]:
    """Instructions of the computations a fusion ``calls`` or a reduce /
    scatter / sort applies per element: never device events of their
    own."""
    applied = set(re.findall(r"(?:to_apply|calls)=%([\w.\-]+)", text))
    out: set[str] = set()
    for block in re.split(r"\n\n", text):
        head = profiling._HLO_HEADER_RE.search(block)
        if head and head.group(1).lstrip("%") in applied:
            out.update(m.group(2)
                       for m in profiling._HLO_INSTR_RE.finditer(block))
    return out


def test_every_train_instruction_has_one_innermost_scope(pair):
    """The scopes of the train program partition it: every instruction
    that can be a device event of its own is in the table (under
    ``ddq.train`` where under nothing narrower), once."""
    _, solver, dev, _ = pair
    text = solver.fused_executables(dev, CHAIN)["train"].as_text()
    table = profiling.scope_table(text)["scopes"]
    skip = _inner_instructions(text)
    missing = [m.group(2) for m in profiling._HLO_INSTR_RE.finditer(text)
               if m.group(3) not in profiling._HLO_NO_EVENT
               and m.group(2) not in table and m.group(2) not in skip]
    assert missing == []
    assert all(st and len(st) == len(set(st)) for st in table.values())


# -- (c) the TraceWindow leaves the file -----------------------------------
def test_trace_window_leaves_the_scope_tables(pair, compiles, tmp_path):
    body, solver, dev, stream = pair
    logdir = str(tmp_path / "t")
    n0 = compiles.n
    trace = profiling.TraceWindow(logdir, start_step=0, num_steps=CHAIN)
    for step in range(CHAIN + 1):
        trace.on_step(step)
        if step < CHAIN:
            stream.next(10 ** 6)
    assert trace._done and not tracing.PROFILING
    assert compiles.n == n0     # a driver's window would refuse a compile
    with open(os.path.join(logdir, profiling.SCOPES_FILE)) as fh:
        wrote = json.load(fh)
    assert wrote["unavailable"] == {}
    assert wrote["scope_table_s"] > 0
    programs = wrote["programs"]
    assert {"jit_sample_fn", f"jit_{body}_train_fn", "jit_write"} <= \
        set(programs)
    assert "ddq.conv_in" in {
        st[-1] for st in programs[f"jit_{body}_train_fn"]["scopes"].values()}
    assert {st[0] for st in programs["jit_write"]["scopes"].values()} == \
        {"ddq.write"}


def test_a_source_that_fails_is_named_with_the_reason(tmp_path):
    class Owner:
        def programs(self):
            raise RuntimeError("no ring yet")

    owner = Owner()
    profiling.register_programs(owner, Owner.programs)
    path = profiling.write_scope_tables(str(tmp_path))
    with open(path) as fh:
        wrote = json.load(fh)
    reason = wrote["unavailable"][Owner.programs.__qualname__]
    assert reason == "RuntimeError: no ring yet"
    del owner


def test_an_empty_logdir_leaves_nothing_and_imports_nothing(tmp_path,
                                                            monkeypatch):
    monkeypatch.chdir(tmp_path)
    before = set(sys.modules)
    trace = profiling.TraceWindow("", start_step=0, num_steps=1)
    for step in range(3):
        trace.on_step(step)
    trace.close()
    assert set(sys.modules) == before
    assert os.listdir(tmp_path) == []
    assert not trace._active and not trace._done


def test_no_window_no_span(pair):
    """Registering the programs put nothing on a step's path."""
    _, _, _, stream = pair
    assert not tracing.ENABLED and not tracing.PROFILING
    assert tracing.span("learner_chunk") is tracing._NULL
    stream.next(10 ** 6)
    assert tracing.span("sample") is tracing._NULL
    assert tracing.drain() == []


def test_fused_train_flops_reads_the_executable_that_ran(pair, compiles):
    """``compile_fused_train`` (the flops and op censuses' artifact) finds
    the loop's own executable: no second compile of the train program."""
    _, solver, dev, _ = pair
    n0 = compiles.n
    assert profiling.fused_train_flops(solver, dev, CHAIN) > 0
    assert compiles.n == n0
    assert np.isfinite(profiling.fused_train_flops(solver, dev, CHAIN))
