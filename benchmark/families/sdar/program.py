"""The ``sdar`` family's adapter to ``distributed_deep_q_tpu``: it runs on
the ``tokenq`` family's solver (``SequenceSolver``), ring
(``DeviceTokenReplay``) and HLO scope table, so it is that family's
adapter by import, plus what the program makes of a recorded feed
(``packed_feed``: the packed token ids, the decision rows and the span
returns, by the program's own functions) and what shapes alone fix of
the block mask (``mask_shares``). Its Config is a preset of the
program (``benchmark/program.make_cfg``; on a program without the preset
that fails at once: ``KeyError``). The yardstick never imports this."""

from __future__ import annotations

import numpy as np

from benchmark.families.tokenq.program import (  # noqa: F401
    hlo_scopes, leaf_names, make_replay, make_solver, train_program_scopes)


def packed_feed(cfg, feed: dict) -> dict:
    """What the train program derives from one chunk's recorded feed
    (``tokens`` [chain, b, T+1], ``reveal`` [chain, b, G], ``reward`` /
    ``discount`` / ``mask`` [chain, b, T]), by the functions it runs:
    ``packed`` [chain, b, N] token ids, ``dec_rows`` [chain, b, G],
    ``ret`` / ``gamma`` / ``valid`` [chain, b, G - 1]."""
    import jax
    import jax.numpy as jnp

    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.ops.losses import span_returns

    tq = cfg.net.tokenq
    t = cfg.replay.sequence_length

    @jax.jit
    def one(tokens, reveal, reward, discount, mask):
        rows, step = tokenq.bd_decision_rows(reveal, t, tq.block_length)
        ret, gamma, valid = span_returns(
            reward, discount, mask, step[:, :-1],
            step[:, 1:] - step[:, :-1], 2 * tq.block_length - 1)
        return {"packed": tokenq.bd_pack(tokens, reveal, cfg.net),
                "dec_rows": rows, "ret": ret, "gamma": gamma,
                "valid": valid}

    steps = [one(*(jnp.asarray(feed[k][s]) for k in (
        "tokens", "reveal", "reward", "discount", "mask")))
        for s in range(len(feed["tokens"]))]
    return {k: np.stack([np.asarray(s[k]) for s in steps])
            for k in steps[0]}


def mask_shares(solver) -> dict[str, float]:
    """What shapes alone fix of the block mask, percent: the pairs the four
    rules allow over the packed rows' square, and the blocks the forward
    kernel RUNS over all its blocks (the kernel's own block table — the
    one the solver's train program was built from, so nothing is built
    again)."""
    from distributed_deep_q_tpu.ops import attention
    from distributed_deep_q_tpu.parallel.mesh import pallas_interpret

    cfg = solver.config
    tq, t = cfg.net.tokenq, cfg.replay.sequence_length
    n = len(attention.bd_rows(t, tq.block_length)[0])
    return {
        "bd_pairs_allowed_share": 100.0 * float(
            attention.bd_allowed_per_row(t, tq.block_length).sum()) / n ** 2,
        "bd_blocks_run_share": 100.0 * attention.bd_blocks_run_share(
            n, t, tq.block_length,
            tq.num_attention_heads // tq.num_key_value_heads,
            block=tq.attn_block, compute_block=tq.attn_compute_block,
            fused_bwd=tq.attn_fused_bwd,
            interpret=pallas_interpret(solver.mesh))}
