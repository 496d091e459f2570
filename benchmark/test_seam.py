"""The seam a second model family comes in through, on the CPU in seconds
(no 1M ring). Run by hand, as the other tests here:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_seam.py -q

(a) both accepted configurations resolve to the frame-ring comparison and
    counts, and print the counts the parent's harness printed;
(b) a throwaway family laid down as NEW files only — a configuration whose
    ``hparams`` hold no ``frame_shape`` / ``stack`` / ``n_step``, a
    three-leaf weight tree, its traffic, driver, reference, comparison,
    counts and one roofline metric over its own count — runs through
    ``run.run_cell(..., backend="cpu")`` to the contract's object in a
    copy of ``benchmark/`` + ``BENCHMARK.json`` in which no file that was
    there has changed (``BENCHMARK.json`` gains entries only);
(c) the same family with one limit missing ends the run, and with a
    number that is not finite is not correct: the shared rule
    (``family.judge``), which no comparison module can leave out;
(d) ``metrics_for`` asks none of the frame-ring family's four device
    metrics of a cell outside their lists, and every name in every
    ``workloads`` list of ``BENCHMARK.json`` is a cell;
(e) one per-layer metric a mechanism, not one a family (PR 47): every cell
    reads what it read at PR 46 (``fixtures/per_layer_readings_at_pr46
    .json``), under one name a reading; no two entries read the same thing
    unless the later one is a family's copy waiting for its fold.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counts, family, run  # noqa: E402
from benchmark.common import load_json  # noqa: E402

CELLS = ("ddqn_per_b512.learner_only", "ddqn_per_b512.fleet4",
         "dqn_b32.learner_only")
FRAME_RING_ONLY = ("sample_ms_per_chunk", "train_ms_per_step", "train_mfu",
                   "gather_windows_roofline")
TOY_CELL = "toyq.toy_learner"


def bench_json(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


# --- (a) the accepted configurations ---------------------------------------

@pytest.mark.parametrize("cell,flops,gather_bytes", [
    # what the parent's run.py printed for these configurations (shapes
    # alone: counts.py on the configuration's hparams)
    ("ddqn_per_b512.learner_only", 44491079680.0, 234881024.0),
    ("ddqn_per_b512.fleet4", 44491079680.0, 234881024.0),
    ("dqn_b32.learner_only", 2182610944.0, 10485760.0),
])
def test_accepted_configurations_resolve_to_the_frame_ring_family(
        cell, flops, gather_bytes):
    from benchmark import check
    from benchmark.reference import dqn

    conf = run.load_cell(cell)[2]["conf"]
    assert family.load_check(conf) is check
    assert family.load_counts(conf) is counts
    assert family.load_reference(conf) is dqn
    assert family.printed_counts(conf) == {
        "analytic_flops_per_step": flops,
        "gather_bytes_per_chunk": gather_bytes}
    assert flops == counts.train_flops_per_step(conf["hparams"])


def test_a_configuration_that_names_no_comparison_is_refused():
    with pytest.raises(SystemExit, match="names no check module"):
        family.load_check({"name": "x"})
    with pytest.raises(SystemExit, match="names no counts module"):
        family.load_counts({"name": "x", "counts": {}})
    assert family.printed_counts({"name": "x", "hparams": {}}) == {}


# --- (d) BENCHMARK.json's lists ---------------------------------------------

def test_every_listed_workload_is_a_cell():
    bench = bench_json()
    cells = {w["name"] for w in bench["workloads"]}
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            assert set(m.get("workloads", ())) <= cells, m["name"]
            assert m.get("workloads", True), f"{m['name']}: empty list"


@pytest.mark.parametrize("cell,count", zip(CELLS, (21, 29, 22)))
def test_the_accepted_cells_keep_their_per_layer_metrics(cell, count):
    names = [m["name"] for m in run.metrics_for(bench_json(), "per_layer",
                                                cell)]
    assert len(names) == count
    assert set(FRAME_RING_ONLY) <= set(names)


# --- (e) one metric a mechanism ----------------------------------------------

TOKEN_CELLS = ("smallthinker_21b_tokenq_ep8.seq_learner_only",
               "lfm2_24b_tokenq_ep8.seq_learner_only",
               "keye_vl2_30b_tokenq_ep16.seq_learner_only",
               "moonlight_16b_tokenq_ep8.seq_learner_only",
               "laguna_xs2_tokenq_ep16.seq_learner_only",
               "sdar_30b_tokenq_ep16.seq_learner_only")
ALL_CELLS = (*CELLS, *TOKEN_CELLS)
# what was accepted for the three CNN cells at PR 35 comes first in their
# lists (PR 36 appended 11 / 11 / 12)
ACCEPTED_AT_PR35 = dict(zip(CELLS, (10, 18, 10)))
# per cent of the token-slots the experts held take under even routing
EXPECTED_HELD_SHARE = dict(zip(TOKEN_CELLS,
                               (12.5, 12.5, 6.25, 12.5, 6.25, 6.25)))


def metric_spec(name: str) -> dict:
    return load_json("layer_metrics", f"{name}.json")


def cell_names(cell: str) -> list[str]:
    return [m["name"] for m in run.metrics_for(bench_json(), "per_layer",
                                               cell)]


@pytest.mark.parametrize("cell,count", zip(
    ALL_CELLS, (21, 29, 22, 16, 17, 20, 19, 22, 21)))
def test_every_cell_keeps_its_count_with_what_was_accepted_first(cell, count):
    names = cell_names(cell)
    assert len(names) == count and len(set(names)) == count
    if cell in CELLS:
        assert set(FRAME_RING_ONLY) <= set(names[:ACCEPTED_AT_PR35[cell]])
        return
    # the readings every token cell shares (the first token cell's list)
    # stand before what only some families have
    first = set(cell_names(TOKEN_CELLS[0]))
    shared = [n in first for n in names]
    assert shared == sorted(shared, reverse=True), names
    # the whole step's share of the peak, and a roofline a kernel
    assert "tokenq_train_mfu" in names and "expert_ffn_roofline" in names


@pytest.mark.parametrize("cell", ALL_CELLS)
def test_a_cell_reads_what_it_read_at_pr46(cell):
    """(reader, arguments) of every metric the cell lists, ``scale.expected``
    resolved through the cell's own counts module to its number: the
    fixture was made from PR 46's ``BENCHMARK.json`` and metric files."""
    want = load_json("fixtures", "per_layer_readings_at_pr46.json")[cell]
    conf = run.load_cell(cell)[2]["conf"]
    got = []
    for name in cell_names(cell):
        spec = metric_spec(name)
        scale = spec["args"].get("scale", {})
        if isinstance(scale.get("expected"), str):
            scale["expected"] = getattr(
                family.load_counts(conf), scale["expected"])(conf["hparams"])
        got.append([spec["reader"], spec["args"]])

    def canon(rows):
        return sorted(json.dumps(r, sort_keys=True) for r in rows)
    assert canon(got) == canon(want)


@pytest.mark.parametrize("cell", TOKEN_CELLS)
def test_the_expected_held_share_comes_from_the_configuration(cell):
    conf = run.load_cell(cell)[2]["conf"]
    hp = conf["hparams"]
    held = hp.get("experts_held", hp.get("moe_experts_held"))
    width = hp.get("router_experts", hp.get("moe_router_experts"))
    share = family.load_counts(conf).expected_slots_held_share(hp)
    assert share == 100.0 * held / width == EXPECTED_HELD_SHARE[cell]


@pytest.mark.parametrize("cell", [TOKEN_CELLS[1], TOKEN_CELLS[4]],
                         ids=["expected_12.5", "expected_6.25"])
def test_the_roofline_scales_by_the_name_as_it_did_by_the_number(cell):
    """``hlo_scope_roofline`` with ``scale.expected`` as the NAME of the
    family's function reads what it read with PR 46's number in the
    file, on a small synthetic trace: one execution of the train program
    (chain from the configuration), one grouped matmul of 2 ms under
    ``ddq.experts``, log rows inside and outside the traced steps."""
    from benchmark.readers import hlo_scope_roofline

    conf = run.load_cell(cell)[2]["conf"]
    hp = conf["hparams"]
    gmm = "%gmm.1 = bf16[8,8] custom-call(%p), custom_call_target=\"tpu\""
    ctx = types.SimpleNamespace(
        conf=conf, hp=hp, peaks=load_json("peaks.json")["TPU v5 lite"],
        trace={"/device:TPU:0": {
            "XLA Modules": [("jit_token_train_fn(1)", 0, 5_000_000)],
            "XLA Ops": [(gmm, 1_000_000, 2_000_000)]}},
        result={"hlo_scopes": {"jit_token_train_fn": {"gmm.1":
                                                      "ddq.experts"}},
                "traced_steps": (4, 8),
                "rows": [{"step": 4, "moe_slots_held_share": 50.0},
                         {"step": 8, "moe_slots_held_share": 5.0}]})
    args = metric_spec("expert_ffn_roofline")["args"]
    assert args["scale"]["expected"] == "expected_slots_held_share"
    by_name = hlo_scope_roofline.read(ctx, **args)
    number = EXPECTED_HELD_SHARE[cell]
    by_number = hlo_scope_roofline.read(
        ctx, **{**args, "scale": {**args["scale"], "expected": number}})
    work = family.load_counts(conf).expert_ffn_flops(hp) * 5.0 / number
    assert by_name == by_number == pytest.approx(
        100.0 * (work / 197e12) / (2e-3 / hp["fused_chain"]), rel=1e-12)


def test_no_two_per_layer_entries_read_the_same_thing(capsys):
    """Equal (reader, arguments) is allowed only to a family's copy that
    waits for its fold: a ``model_config`` PR may only add, so it names
    its copy ``<family>_<accepted name>`` and lists its own cells. The
    count is printed, never refused: filling the room is what it is for."""
    per_layer = bench_json()["per_layer"]
    cells = {w["name"] for w in bench_json()["workloads"]}
    seen: dict[str, dict] = {}
    waiting, refused = [], []
    for m in per_layer:
        spec = metric_spec(m["name"])
        key = json.dumps([spec["reader"], spec["args"]], sort_keys=True)
        first = seen.setdefault(key, m)
        if first is m:
            continue
        disjoint = not (set(first.get("workloads", cells))
                        & set(m.get("workloads", cells)))
        fam = m["name"].removesuffix("_" + first["name"])
        if fam not in ("", m["name"]) and disjoint:
            waiting.append((m["name"], first["name"]))
        else:
            refused.append((m["name"], first["name"]))
    with capsys.disabled():
        print(f"\nper_layer: {len(per_layer)} of 128 entries; "
              f"{len(waiting)} copies a fold would free: {waiting}")
    assert not refused, (
        f"{refused}: append the cell to the earlier entry's workloads (a "
        "benchmark PR), or name the copy <family>_<accepted name> with "
        "cells of its own (benchmark/README.md, Adding a model family)")


def test_every_metric_file_is_listed_and_every_entry_has_its_file():
    files = {f[:-5] for f in os.listdir(
        os.path.join(ROOT, "benchmark", "layer_metrics"))
        if f.endswith(".json")}
    assert files == {m["name"] for m in bench_json()["per_layer"]}


def test_a_cell_of_another_family_is_asked_none_of_the_frame_ring_metrics():
    bench = bench_json()
    asked = {m["name"] for m in run.metrics_for(bench, "per_layer", TOY_CELL)}
    assert not asked & set(FRAME_RING_ONLY)
    assert asked == {"compile_s", "host_loop_ms_per_step",
                     "device_idle_share"}
    assert [m["name"] for m in run.metrics_for(bench, "end_to_end", TOY_CELL)
            ] == ["grad_steps_per_s", "setup_s"]


# --- (c) the shared rule, directly ------------------------------------------

CONF = {"name": "c", "reference": "dqn", "limits": {"gap": 0.5,
                                                    "illegal_draws": 7}}


def test_judge_holds_numbers_to_their_limits():
    ok, numbers = family.judge(CONF, {"gap": 0.25, "illegal_draws": 0})
    # an exact limit is the reference's: a configuration cannot loosen it
    assert ok and numbers == {"gap": [0.25, 0.5], "illegal_draws": [0, 0]}
    assert not family.judge(CONF, {"gap": 0.75})[0]
    assert not family.judge(CONF, {"gap": 0.25, "illegal_draws": 1})[0]


@pytest.mark.parametrize("bad", [float("nan"), float("inf"), -float("inf")])
def test_judge_takes_no_number_that_is_not_finite(bad):
    assert not family.judge(CONF, {"gap": bad})[0]


def test_judge_ends_the_run_on_a_number_without_a_limit_or_no_number():
    with pytest.raises(SystemExit, match=r"no limit for \['unread'\]"):
        family.judge(CONF, {"gap": 0.1, "unread": 0.0})
    with pytest.raises(SystemExit, match="compared nothing"):
        family.judge(CONF, {})


# --- (b) a throwaway family, as new files only ------------------------------

FAMILY_FILES = {
    "benchmark/configs/toyq.json": json.dumps({
        "name": "toyq", "source": "a test's throwaway family",
        "reference": "toyq", "check": "families.toyq.check",
        "counts": {"module": "families.toyq.counts",
                   "print": {"toy_flops_per_step": "step_flops"}},
        "reduced": [], "reduced_why": {}, "assumed": {},
        "hparams": {"obs_dim": 16, "num_actions": 4, "batch_size": 8,
                    "fused_chain": 4, "lr": 0.01, "gamma": 0.5,
                    "rows": 256},
        "limits": {"loss_max_rel": 1e-4, "delta_norm_worst_leaf": 1e-4}}),
    "benchmark/traffic/toy_learner.json": json.dumps({
        "driver": "toy_learner", "row_every": 8,
        "loop": "closed: a toy learner on a toy ring"}),
    "benchmark/layer_metrics/toy_step_roofline.json": json.dumps({
        "reader": "roofline", "args": {
            "line": "XLA Modules", "pattern": "^jit_sample_fn",
            "count": "step_flops", "peak": "bf16_flops", "bound": "flops",
            "work_per_execution": "fused_chain"}}),
    "benchmark/families/__init__.py": "",
    "benchmark/families/toyq/__init__.py": "",
    "benchmark/families/toyq/counts.py": '''
def step_flops(hp):
    """Forward on s and s', backward on s: multiply-adds count 2."""
    return 2.0 * 4 * hp["batch_size"] * hp["obs_dim"] * hp["num_actions"]
''',
    "benchmark/reference/toyq.py": '''
"""Plain numpy reference of the toy family: a linear Q-net with a learned
output scale, TD(0) with a squared loss, plain SGD. Three leaves."""
import numpy as np

EXACT_LIMITS = {"row_mismatch": 0}


def rows(seed, hp):
    rng = np.random.default_rng([seed, 7])
    n, d = hp["rows"], hp["obs_dim"]
    return dict(obs=rng.standard_normal((n, d)).astype(np.float32),
                nxt=rng.standard_normal((n, d)).astype(np.float32),
                action=rng.integers(0, hp["num_actions"], n),
                reward=rng.standard_normal(n).astype(np.float32))


def init_weights(seed, hp):
    rng = np.random.default_rng([seed, 8])
    return dict(w=(0.1 * rng.standard_normal(
        (hp["obs_dim"], hp["num_actions"]))).astype(np.float32),
        b=np.zeros(hp["num_actions"], np.float32), scale=np.float32(1.0))


def draws(seed, hp, steps):
    rng = np.random.default_rng([seed, 9])
    return rng.integers(0, hp["rows"], (steps, hp["batch_size"]))


def step(theta, batch, hp):
    obs, nxt, a, r = batch
    q = theta["scale"] * (obs @ theta["w"] + theta["b"])
    target = r + hp["gamma"] * (
        theta["scale"] * (nxt @ theta["w"] + theta["b"])).max(1)
    qa = q[np.arange(len(a)), a]
    td = qa - target
    loss = float(np.mean(td ** 2))
    g = np.zeros_like(q)
    g[np.arange(len(a)), a] = 2.0 * td / len(a)
    lin = obs @ theta["w"] + theta["b"]
    grads = dict(w=theta["scale"] * obs.T @ g, b=theta["scale"] * g.sum(0),
                 scale=np.float32((g * lin).sum()))
    return {k: (theta[k] - hp["lr"] * grads[k]).astype(np.float32)
            for k in theta}, loss
''',
    "benchmark/families/toyq/check.py": '''
"""The toy family's comparison: the two functions of ``family.py``."""
import numpy as np

from benchmark.family import load_reference

FOLLOWED = 3


class Stream:
    """The timed path: one jitted TD step over rows drawn from the ring."""

    def __init__(self, hp, theta, ring, draws):
        import jax
        import jax.numpy as jnp

        def td_step(theta, obs, nxt, a, r):
            def loss_fn(th):
                q = th["scale"] * (obs @ th["w"] + th["b"])
                target = jax.lax.stop_gradient(r + hp["gamma"] * (
                    th["scale"] * (nxt @ th["w"] + th["b"])).max(1))
                qa = jnp.take_along_axis(q, a[:, None], 1)[:, 0]
                return jnp.mean((qa - target) ** 2)
            loss, g = jax.value_and_grad(loss_fn)(theta)
            return jax.tree.map(lambda p, d: p - hp["lr"] * d, theta,
                                g), loss

        self.step_fn = jax.jit(td_step)
        self.theta = jax.tree.map(jnp.asarray, theta)
        self.ring, self.draws, self.k = ring, draws, 0

    def next(self):
        idx = self.draws[self.k % len(self.draws)]
        self.k += 1
        r = self.ring
        self.theta, loss = self.step_fn(self.theta, r["obs"][idx],
                                        r["nxt"][idx], r["action"][idx],
                                        r["reward"][idx])
        return {"loss": loss, "idx": idx}


def build_checked(conf, cfg, seed, rows, episode, beta_steps=None,
                  mark=lambda name: None):
    hp = conf["hparams"]
    ref = load_reference(conf)
    ring = ref.rows(seed, hp)
    theta0 = ref.init_weights(seed, hp)
    stream = Stream(hp, theta0, ring, ref.draws(seed, hp, 64))
    fed, losses = [], []
    for _ in range(FOLLOWED):
        m = stream.next()
        fed.append(np.asarray(m["idx"]))
        losses.append(float(m["loss"]))
    rec = dict(driven_steps=FOLLOWED, fed=fed, losses=losses,
               theta={k: np.asarray(v) for k, v in stream.theta.items()})
    return None, ring, stream, ring, rec


def compare(conf, seed, mirror, rec, *, quant=None):
    hp = conf["hparams"]
    ref = load_reference(conf)
    theta0 = ref.init_weights(seed, hp)
    theta, gold = theta0, []
    want = ref.draws(seed, hp, 64)[:FOLLOWED]
    for idx in want:
        theta, loss = ref.step(theta, (mirror["obs"][idx], mirror["nxt"][idx],
                                       mirror["action"][idx],
                                       mirror["reward"][idx]), hp)
        gold.append(loss)
    nums = {"row_mismatch": int(sum((a != b).sum()
                                    for a, b in zip(rec["fed"], want)))}
    g, a = np.asarray(gold), np.asarray(rec["losses"])
    nums["loss_max_rel"] = float(np.max(np.abs(a - g) / np.abs(g)))

    def change(th):
        return {k: np.linalg.norm(th[k] - theta0[k]) for k in theta0}
    dp, dr = change(rec["theta"]), change(theta)
    med = float(np.median(list(dr.values())))
    nums["delta_norm_worst_leaf"] = float(max(
        abs(dp[k] - dr[k]) / max(dr[k], med) for k in dr))
    return dict(numbers=nums, steps={"loss": [rec["losses"], gold]},
                print={"followed_steps": FOLLOWED})
''',
    "benchmark/drivers/toy_learner.py": '''
"""Traffic kind of the toy family: its own adapter and its own loop."""
import time

from benchmark import family
from benchmark.common import fence, memory_peak_bytes


def run(ctx):
    conf, traffic = ctx.conf, ctx.traffic
    chain = conf["hparams"]["fused_chain"]
    _, ring, stream, mirror, rec = family.load_check(conf).build_checked(
        conf, None, ctx.seed, None, None)
    compiles0 = ctx.clock.backend_compiles
    t_open = fence()
    compile_s_at_open = ctx.clock.compile_s
    starts, rows, losses, steps = [], [], [], 0
    t_row = t_open
    while True:
        t = time.perf_counter()
        if steps % chain == 0:
            if t >= t_open + ctx.seconds:
                break
            starts.append(t)
        losses.append(stream.next()["loss"])
        steps += 1
        if steps % traffic["row_every"] == 0:
            now = time.perf_counter()
            rows.append({"step": steps, "t": now - t_open,
                         "time_step_ms": 1e3 * (now - t_row)
                         / traffic["row_every"]})
            t_row = now
    t_close = fence()
    bad = sum(1 for x in losses if not float(x) == float(x))
    return dict(
        t_open=t_open, t_close=t_close, setup_s=t_open - ctx.t_start,
        steps=steps, chunk_starts=starts, attempted=len(starts), failed=bad,
        rows=rows, memory_peak_bytes=memory_peak_bytes(),
        compiles_in_window=ctx.clock.backend_compiles - compiles0,
        compile_s_at_open=compile_s_at_open, trace_dir=None, mirror=mirror,
        rec=rec, program_flops_per_step=None)
''',
}

BENCH_ENTRIES = {
    "configs": {"name": "toyq", "source": "a test's throwaway family",
                "file": "benchmark/configs/toyq.json", "reduced": [],
                "why": "linear Q-net, three leaves, no frames"},
    "workloads": {"name": TOY_CELL, "config": "toyq",
                  "traffic": "toy_learner", "chips": 1,
                  "why": "a family that shares no file with the frame ring"},
    "per_layer": {"name": "toy_step_roofline", "unit": "%",
                  "better": "higher", "source": "device_trace",
                  "layer": "model", "moves": "grad_steps_per_s",
                  "workloads": [TOY_CELL]},
}

SCRIPT = r'''
import argparse, json, sys, types
import jax
jax.config.update("jax_platforms", "cpu")
from benchmark import run, trace_reduce
from benchmark.common import load_json
from benchmark.readers import roofline

mode = sys.argv[1]


def patch(conf, traffic):
    if mode == "missing_limit":
        del conf["limits"]["delta_norm_worst_leaf"]
    if mode == "not_finite":
        conf["hparams"]["lr"] = 1e38


if mode == "roofline":
    conf = run.load_cell("toyq.toy_learner")[2]["conf"]
    spec = load_json("layer_metrics", "toy_step_roofline.json")
    ctx = types.SimpleNamespace(
        trace=trace_reduce.load(sys.argv[2]), conf=conf, hp=conf["hparams"],
        peaks=load_json("peaks.json")["TPU v5 lite"])
    print("RESULT " + json.dumps(roofline.read(ctx, **spec["args"])))
else:
    ns = argparse.Namespace(workload="toyq.toy_learner", seed=2 ** 31 + 5,
                            seconds=0.3, trace=int(sys.argv[2]))
    print("RESULT " + json.dumps(run.run_cell(ns, backend="cpu",
                                              conf_patch=patch)))
'''


def tree_hashes(root: str) -> dict[str, str]:
    out = {}
    for base, dirs, files in os.walk(os.path.join(root, "benchmark")):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
        for f in files:
            if f.endswith(".pyc"):
                continue
            path = os.path.join(base, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """``benchmark/`` + ``BENCHMARK.json`` alone in a directory (no program
    beside them), with the throwaway family laid down as new files and
    ``BENCHMARK.json`` given its three entries."""
    root = str(tmp_path_factory.mktemp("seam"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "out"))
    before = tree_hashes(root)
    bench = bench_json()
    for path, text in FAMILY_FILES.items():
        full = os.path.join(root, path)
        assert not os.path.exists(full), f"{path} is not a new file"
        os.makedirs(os.path.dirname(full), exist_ok=True)
        with open(full, "w") as fh:
            fh.write(text)
    for group, entry in BENCH_ENTRIES.items():
        bench[group].append(entry)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)
    return types.SimpleNamespace(root=root, before=before)


def drive(copy, *argv, expect_rc=0):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", SCRIPT, *argv], cwd=copy.root,
                       env=env, capture_output=True, text=True, timeout=300)
    assert (p.returncode == 0) == (expect_rc == 0), p.stderr[-3000:]
    result = [ln for ln in p.stdout.splitlines() if ln.startswith("RESULT ")]
    return (json.loads(result[-1][7:]) if result else None), p


@pytest.mark.parametrize("trace", [0, 1])
def test_a_new_family_runs_as_new_files_only(copy, trace):
    line, p = drive(copy, "ok", str(trace))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"] and list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["compared"]) == {"row_mismatch", "loss_max_rel",
                                     "delta_norm_worst_leaf"}
    assert line["compared"]["row_mismatch"] == [0, 0]
    want = ({"compile_s", "host_loop_ms_per_step"} if trace
            else {"grad_steps_per_s", "setup_s"})
    assert set(line["metrics"]) == want
    # the configuration's own count was printed, the frame ring's were not
    assert '"toy_flops_per_step": 4096.0' in p.stdout
    assert "analytic_flops_per_step" not in p.stdout
    # no file that was there has changed; BENCHMARK.json gained entries only
    after = tree_hashes(copy.root)
    assert {k: after[k] for k in copy.before} == copy.before
    assert set(after) - set(copy.before) == set(FAMILY_FILES)
    old, new = bench_json(), bench_json(copy.root)
    for key, value in old.items():
        assert new[key] == value or new[key][:len(value)] == value


def test_a_roofline_metric_reads_the_new_familys_own_count(copy):
    fixture = os.path.join(ROOT, "benchmark", "fixtures",
                           "ddqn_per_b512_learner_only_3chunks.xplane.pb")
    value, _ = drive(copy, "roofline", fixture)
    # 3 executions of 4 steps of 4 096 FLOPs in 0.01104955 s of device time
    assert value == pytest.approx(
        100.0 * (4096.0 * 3 * 4 / 197e12) / 0.01104955, rel=1e-6)


def test_a_new_family_with_a_limit_missing_is_refused(copy):
    line, p = drive(copy, "missing_limit", "0", expect_rc=1)
    assert line is None
    assert "states no limit for ['delta_norm_worst_leaf']" in p.stderr


def test_a_new_family_with_a_number_not_finite_is_not_correct(copy):
    line, _ = drive(copy, "not_finite", "0")
    assert line["correct"] is False
    assert not all(v == v and abs(v) != float("inf")
                   for v, _ in line["compared"].values())
