"""The comparison that decides ``correct`` has to be able to say no.

Run by hand on the CPU (toy size; ``benchmark/rehearse.py``'s):

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/test_control.py -q

1. The control — the reference in float8_e4m3 standing where the program
   stood — comes out not correct on three seeds, while the program comes
   out correct on the same seeds (chip readings at the cells' own sizes
   are in PERF.md §2; ``benchmark/control.py`` makes them).
2. The harness, past its look for a chip, driven with the timed path broken
   underneath — a train call that hands back the state it was given —
   reports ``correct: false``.
"""

from __future__ import annotations

import argparse
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SEEDS = (11, 2 ** 31 + 12, 13)
CELLS = ("ddqn_per_b512.learner_only", "dqn_b32.learner_only")


@pytest.fixture(scope="module", autouse=True)
def cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    from benchmark import control, rehearse

    rs = control.readings(cell, SEEDS, backend="cpu",
                          conf_patch=rehearse.toy)
    summary = control.summarize(rs)
    assert summary["sound_all_correct"], summary
    assert summary["control_all_not_correct"], summary


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    import jax
    import jax.numpy as jnp

    from benchmark import rehearse, run
    from distributed_deep_q_tpu.solver import Solver

    real = Solver.train_steps_device_per

    def broken(self, replay, chain=None):
        kept = jax.tree.map(jnp.copy, self.state)
        out = real(self, replay, chain)
        self.state = kept           # the optimizer step is thrown away
        return out

    monkeypatch.setattr(Solver, "train_steps_device_per", broken)
    ns = argparse.Namespace(workload=CELLS[0], seed=SEEDS[0], seconds=1.0,
                            trace=0)
    line = run.run_cell(ns, backend="cpu", conf_patch=rehearse.toy)
    assert line["correct"] is False, line


def test_the_unbroken_path_is_correct():
    from benchmark import rehearse, run

    ns = argparse.Namespace(workload=CELLS[0], seed=SEEDS[1], seconds=1.0,
                            trace=0)
    line = run.run_cell(ns, backend="cpu", conf_patch=rehearse.toy)
    assert line["correct"] is True, line
