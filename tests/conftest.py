"""Test harness configuration.

Forces JAX onto the CPU platform with 8 virtual devices so the full
multi-device learner path (shard_map + psum over a `dp` mesh) is exercised
without TPU hardware — the TPU-native analogue of the reference's
`--backend`-switch "dummy backend" testing pattern (SURVEY.md §4 [M]).

Both options are set here, before any backend is initialized (conftest
runs before test modules import jax users), so the suite is on the CPU
whatever platform the environment names.
"""

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
