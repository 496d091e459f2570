"""The ``nemotron`` family's adapter to ``distributed_deep_q_tpu``: it runs
on the ``tokenq`` family's solver (``SequenceSolver``), ring
(``DeviceTokenReplay``) and HLO scope table, so it is that family's
adapter by import. Its Config is a preset of the program
(``benchmark/program.make_cfg``; on a program without the preset that
fails at once: ``KeyError``). The yardstick never imports this."""

from benchmark.families.tokenq.program import (  # noqa: F401
    hlo_scopes, leaf_names, make_replay, make_solver, train_program_scopes)
