"""Batched inference policy — the device-resident forward behind the
``InferenceServer`` (rpc/inference_server.py).

The Podracer/Sebulba split (arXiv:2104.06272) centralizes the actor
forward on the accelerator: actors ship observations, the learner-side
policy answers with actions. This module is that forward. It is the SAME
jitted Flax apply ``QNet`` runs on the actor CPU — one program, one
parameter tree — which is what makes remote and local inference bitwise
comparable: given identical θ and observations, the Q-value rows agree,
and argmax (computed host-side with ``np.argmax`` on both paths, same
tie-breaking) agrees too. The train step's stacked-forward machinery
(``stacked_q_apply``) vmaps this very apply over a θ/θ⁻ weight axis;
inference needs only the single-net slice of it.

**Bucketed compilation.** XLA compiles one program per input shape. A
microbatching server sees every batch size from 1 to ``max_batch``; left
alone that is ``max_batch`` compiled programs and an unbounded compile
tail. Instead every batch pads (zero rows, sliced off after the forward)
to the smallest of a few fixed ``buckets`` — at most ``len(buckets)``
XLA programs ever, the set actually compiled is exposed as a gauge
(``compiled_buckets``). Oversized batches fold into chunks of the
largest bucket, so the bound holds for any input.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from distributed_deep_q_tpu.config import NetConfig
from distributed_deep_q_tpu.models.qnet import build_qnet, init_params

__all__ = ["BatchedPolicy"]


class BatchedPolicy:
    """Bucket-padded batched Q-forward with the ``QNet`` weight surface.

    Construction compiles nothing; each bucket compiles on first use and
    is counted. ``set_weights`` takes the same flat numpy leaf list the
    RPC plane ships (``QNet.get_weights`` order), so the learner feeds it
    directly from ``solver.get_weights()``.
    """

    def __init__(self, cfg: NetConfig, seed: int = 0, obs_dim: int = 4,
                 buckets: tuple = (8, 32, 128, 256)):
        import jax

        if cfg.kind == "r2d2":
            raise ValueError(
                "BatchedPolicy serves feed-forward torsos; recurrent "
                "actors carry per-episode LSTM state that cannot be "
                "microbatched across actors — keep r2d2 on local inference")
        if not buckets or any(int(b) <= 0 for b in buckets):
            raise ValueError(f"inference buckets must be positive: {buckets}")
        self.cfg = cfg
        self.buckets = tuple(sorted(int(b) for b in set(buckets)))
        self.module = build_qnet(cfg)
        self.params = init_params(self.module, cfg, seed, obs_dim)
        self._treedef = jax.tree_util.tree_structure(self.params)
        # the exact apply QNet jits on the actor side — same program
        # family, so remote vs local Q rows match bitwise on one platform
        self._fwd = jax.jit(jax.named_scope("ddq.infer")(
            lambda p, o: self.module.apply({"params": p}, o)))
        self._compiled: set[int] = set()
        self.forwards = 0
        self.rows = 0

    # -- bucket math --------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest bucket holding ``n`` rows (largest bucket if none do —
        the caller then loops in largest-bucket chunks)."""
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def compiled_buckets(self) -> list[int]:
        """Bucket sizes that have actually compiled — what the
        ``inference/compiled_buckets`` gauge and the tests of the
        ≤ len(buckets) XLA-program bound read."""
        return sorted(self._compiled)

    # -- forward ------------------------------------------------------------

    def forward(self, obs: np.ndarray,
                params: Any = None) -> tuple[np.ndarray, np.ndarray]:
        """Actions + Q-values for a stacked observation batch.

        Returns ``(actions int64 [n], q float32 [n, A])``. Rows are
        independent; padding rows are zeros and sliced off before the
        argmax, so they never influence a real row.

        ``params`` overrides the installed tree for this forward only —
        the multi-tenant inference plane serves several θ generations
        through the SAME jitted program (θ is a traced argument, so
        every tenant shares the compiled-bucket census).
        """
        n = obs.shape[0]
        cap = self.buckets[-1]
        if n > cap:
            parts = [self.forward(obs[i:i + cap], params=params)
                     for i in range(0, n, cap)]
            return (np.concatenate([p[0] for p in parts]),
                    np.concatenate([p[1] for p in parts]))
        bucket = self.bucket_for(n)
        if n < bucket:
            pad = np.zeros((bucket - n,) + obs.shape[1:], obs.dtype)
            obs = np.concatenate([obs, pad])
        self._compiled.add(bucket)
        self.forwards += 1
        self.rows += n
        tree = self.params if params is None else params
        q = np.asarray(self._fwd(tree, obs))[:n]
        # host-side argmax, same call as QNet.argmax_action — identical
        # tie-breaking keeps the remote/local action streams bitwise equal
        return np.argmax(q, axis=-1), q

    # -- weight IO (numpy; the RPC serialization surface) -------------------

    def get_weights(self) -> list[np.ndarray]:
        import jax

        return [np.asarray(x)
                for x in jax.tree_util.tree_leaves(self.params)]

    def set_weights(self, flat: list[Any]) -> None:
        self.params = self.unflatten(flat)

    def unflatten(self, flat: list[Any]) -> Any:
        """Rebuild a parameter tree from the flat RPC leaf list WITHOUT
        installing it — tenant θ generations live outside ``params`` so
        installing one tenant never disturbs another's forward."""
        import jax

        return jax.tree_util.tree_unflatten(self._treedef, list(flat))
