"""The ``tokenq`` family's adapter to ``distributed_deep_q_tpu``: its
solver (``SequenceSolver``) and its ring (``DeviceTokenReplay``), and the
HLO scope table of its train program. Its Config is a preset of the
program, so ``benchmark/program.make_cfg`` builds it (and on a program
without the family fails at once: ``KeyError`` of the unknown preset). The
yardstick (reference, counts, readers) never imports this."""

from __future__ import annotations

import re


def make_solver(cfg):
    from distributed_deep_q_tpu.parallel.sequence_learner import (
        SequenceSolver)

    return SequenceSolver(cfg)


def make_replay(cfg, solver, beta_steps=None):
    """The token ring as ``train.train_tokenq`` builds it."""
    from distributed_deep_q_tpu.train import make_token_replay

    ring = make_token_replay(cfg, solver.mesh)
    if beta_steps is not None:
        ring.beta_steps = int(beta_steps)
    return ring


def leaf_names(solver) -> list[str]:
    """Leaf names in the order of the step's ``grad_leaf_norm``."""
    from distributed_deep_q_tpu.models import tokenq

    return list(tokenq.named_leaves(solver.state.params))


SCOPE = re.compile(r"ddq\.[a-z_]+")
INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = ", re.M)
OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> dict[str, str]:
    """``{HLO instruction name: innermost ddq.* scope}`` from a compiled
    module's text: XLA keeps the name stack (``jax.named_scope``) of the
    op an instruction came from in its ``op_name``, and the device trace
    names every event by its instruction. An instruction's text runs to
    the next one's (a Mosaic call's metadata spans lines)."""
    out = {}
    starts = list(INSTR.finditer(hlo_text))
    for m, nxt in zip(starts, starts[1:] + [None]):
        body = hlo_text[m.end():nxt.start() if nxt else len(hlo_text)]
        names = [s for op in OP_NAME.findall(body) for s in SCOPE.findall(op)]
        if names:
            out[m.group(1)] = names[-1]
    return out


def train_program_scopes(solver, replay, chain: int) -> dict[str, str]:
    """The scope table of the fused token TRAIN program as compiled for
    this ring (found again in the compile cache: nothing runs)."""
    import numpy as np

    from distributed_deep_q_tpu.solver import sample_key_schedule

    learner = solver.learner
    sample, train = learner.token_fused_programs(
        replay, solver.config.replay.batch_size, chain)
    keys = sample_key_schedule(0, 0, replay.num_shards, chain)
    batch, idx = sample(keys, replay.ring, replay.dmeta["prio"],
                        replay.device_inputs(),
                        np.full(chain, 0.5, np.float32))
    text = train.lower(solver.state, batch, idx, replay.dmeta["prio"],
                       replay.dmaxp).compile().as_text()
    return hlo_scopes(text)
