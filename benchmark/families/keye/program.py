"""The ``keye`` family's adapter to ``distributed_deep_q_tpu``: it runs on
the ``tokenq`` family's solver (``SequenceSolver``), ring
(``DeviceTokenReplay``) and HLO scope table, so it is that family's
adapter by import; ``program_selection`` asks the program's own backbone
which (query, key) pairs it keeps (after the window). Its Config is a
preset of the program (``benchmark/program.make_cfg``; on a program
without the preset that fails at once: ``KeyError``). The yardstick never
imports this."""

from benchmark.families.tokenq.program import (  # noqa: F401
    hlo_scopes, leaf_names, make_replay, make_solver, train_program_scopes)


def program_selection(solver, named_weights: dict, tokens):
    """What asks the program which pairs its sparse layers keep on
    ``tokens`` [B, T+1] at ``named_weights`` (leaf name -> array): a
    function of no arguments → ``[layers, B, T+1, (T+1 + 7) // 8]`` uint8
    on the host, the keys of a query packed by ``numpy.packbits`` — from
    ``models/tokenq.backbone``'s own selection bits, the function the
    train step traces, run ON ITS OWN: a side program, not the timed one.
    It holds nothing of the solver but its Config and the names of its
    leaves, so the comparison calls it after the window has closed and
    the ring has gone: its compile is no part of ``setup_s``, its memory
    none of ``memory_peak_bytes``."""
    import jax
    import numpy as np

    from distributed_deep_q_tpu.models import tokenq
    from distributed_deep_q_tpu.ops.sparse_attention import unpack_selection
    from distributed_deep_q_tpu.parallel.mesh import pallas_interpret

    net = solver.config.net
    interpret = pallas_interpret(solver.mesh)
    names = jax.tree.map(lambda leaf: 0, solver.state.params)
    tokens = np.asarray(tokens)

    def run():
        params = tokenq.from_named(names, named_weights)
        bits = jax.jit(lambda params, tokens: tokenq.backbone(
            params, tokens, net, interpret, index_loss=False)[1]["dsa_bits"])
        t1 = tokens.shape[1]
        block = net.tokenq.indexer_q_chunk
        return np.stack([np.stack([
            np.packbits(unpack_selection(seq, block)[:t1, :t1], axis=-1)
            for seq in layer]) for layer in np.asarray(bits(params, tokens))])

    return run
