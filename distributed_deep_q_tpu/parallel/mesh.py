"""Device-mesh construction — the rebuilt ``--backend`` switch (SURVEY §7.1).

The reference selects its compute backend with a ``--backend`` flag on the
``Solver`` [M]. Here a backend is (platform, device mesh): ``tpu`` uses the
accelerator platform JAX initialized; ``cpu`` forces the host platform with
N virtual devices (``jax_num_cpu_devices``) — the dummy/test backend that
lets the full multi-device psum learner run anywhere (SURVEY §4).

Mesh axes: ``dp`` (data parallel — batch sharded, grads psum'ed over ICI)
and ``model`` (tensor-parallel hook; size 1 for every reference config —
SURVEY §2.2 records TP/PP as deliberately out of scope).
"""

from __future__ import annotations

import os
import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_deep_q_tpu.config import MeshConfig

AXIS_DP = "dp"
AXIS_MODEL = "model"

# -- declarative partition rules (ISSUE 10; SNIPPETS.md [2][3] idiom) ------
#
# regex → PartitionSpec, matched with ``re.search`` against the
# '/'-joined path of every leaf in a pytree. First match wins; scalars
# short-circuit to replicated; the final catch-all means resolution
# never fails. Today every config runs ``model=1`` so all of these
# BEHAVE replicated — the rules are the declarative seam that lets a
# torso grow past replicated without touching the learner: widen the
# net, raise ``mesh.model``, and the same table shards it.
#
# Matching the leaf PATH (not just the leaf name) means the rules
# resolve identically for ``params/Conv_0/kernel`` and its optimizer
# mirrors ``opt_state/.../mu/Conv_0/kernel`` — moments inherit their
# parameter's spec for free.
DEFAULT_PARTITION_RULES: tuple[tuple[str, P], ...] = (
    # torso conv kernels [H, W, Cin, Cout]: shard output features
    (r"torso/conv\d+/kernel$", P(None, None, None, AXIS_MODEL)),
    # torso dense kernels [in, out]: shard output features
    (r"torso/fc\d+/kernel$", P(None, AXIS_MODEL)),
    # per-output-feature vectors ride with their kernel's output shard
    (r"torso/(conv|fc)\d+/bias$", P(AXIS_MODEL)),
    # heads (q/value/advantage — num_actions wide, tiny), the LSTM, and
    # every scalar stay replicated
    (r".*", P()),
)


def _path_name(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def match_partition_rules(rules, tree):
    """Resolve a pytree of ``PartitionSpec``s from ``(regex, spec)`` rules.

    Scalar leaves are always replicated (a spec can't partition rank 0);
    everything else takes the first rule whose regex ``re.search``-matches
    its '/'-joined tree path. Raises on an unmatched leaf — add a
    catch-all ``(".*", P())`` tail if silence is wanted (the default
    table has one).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs = []
    for path, leaf in flat:
        name = _path_name(path)
        if np.ndim(leaf) == 0:
            specs.append(P())
            continue
        for pat, spec in rules:
            if re.search(pat, name):
                specs.append(spec)
                break
        else:
            raise ValueError(f"no partition rule matches {name!r}")
    return jax.tree_util.tree_unflatten(treedef, specs)


def tree_shardings(mesh: Mesh, tree, rules=None):
    """Pytree of ``NamedSharding``s for ``tree`` under the rule table —
    the placement argument for ``put_replicated`` / ``device_put`` when
    the model axis is real (>1). Specs that name an axis of size 1
    still produce valid shardings (they behave replicated)."""
    specs = match_partition_rules(rules or DEFAULT_PARTITION_RULES, tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def set_cpu_device_count(n: int, *, exact: bool = False) -> None:
    """Pre-backend-init: request ``n`` virtual CPU devices.

    By default never *lowers* an earlier request — a small mesh built
    first must not cap later larger ones. ``exact=True`` overrides that
    (multihost sizes each process's local slice exactly, even when the
    parent's environment asked for more).
    """
    if not exact:
        n = max(jax.config.jax_num_cpu_devices, n)
    jax.config.update("jax_num_cpu_devices", n)


def _cpu_devices(n: int) -> list[jax.Device]:
    """Force-create n virtual CPU devices (works pre- or post-backend-init).

    ``n`` counts GLOBAL devices. In multi-controller mode (multihost
    learner, SURVEY §5.8) the per-process device count was already fixed by
    ``initialize_multihost`` — raising it here would inflate the global
    device count — so the override only runs when no distributed client is
    connected.
    """
    if not jax.distributed.is_initialized():
        try:
            # pre-init: ``--backend cpu`` wins over whatever platform the
            # environment names (JAX_PLATFORMS, or an attached chip JAX
            # would pick by default)
            jax.config.update("jax_platforms", "cpu")
            set_cpu_device_count(n)
        except RuntimeError:
            # a backend is already up in this process: the device count
            # is fixed, and the check below says whether it suffices
            pass
    devs = jax.devices("cpu")
    if len(devs) < n:
        raise RuntimeError(
            f"backend=cpu wants {n} virtual devices but only {len(devs)} exist; "
            "set mesh.num_fake_devices before any JAX backend initialization")
    return devs[:n]


def mesh_devices(cfg: MeshConfig) -> list[jax.Device]:
    if cfg.backend == "cpu":
        n = cfg.num_fake_devices if cfg.dp == 0 else cfg.dp * max(cfg.model, 1)
        return _cpu_devices(n)
    if cfg.backend != "tpu":
        raise ValueError(f"unknown backend {cfg.backend!r} (want tpu|cpu)")
    devs = jax.devices()
    found = sorted({d.platform for d in devs})
    if found != ["tpu"]:
        # no silent fallback: a run asked for the chip must not train on
        # whatever platform JAX happened to find
        raise RuntimeError(
            f"backend=tpu but JAX found platform {'/'.join(found)!r} "
            f"({len(devs)} device(s), JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '')!r}); pass --backend cpu "
            "to run on the host")
    return devs


def pallas_interpret(mesh: Mesh) -> bool:
    """THE compile-or-interpret rule for every Pallas kernel in the
    package (``gather_windows``, ``scatter_rows``, ``fused_dqn_loss``):
    Mosaic compiles for a mesh of TPU devices; anywhere else (the CPU
    test mesh) the kernels run in interpret mode. Decided from the mesh's
    own devices — never from ``jax.default_backend()``, which names the
    process default rather than where this program will run."""
    return mesh.devices.flat[0].platform != "tpu"


def make_mesh(cfg: MeshConfig) -> Mesh:
    devs = mesh_devices(cfg)
    model = max(cfg.model, 1)
    dp = cfg.dp if cfg.dp > 0 else len(devs) // model
    devs = devs[: dp * model]
    arr = np.asarray(devs).reshape(dp, model)
    mesh = Mesh(arr, (AXIS_DP, AXIS_MODEL))
    if cfg.backend == "tpu" and pallas_interpret(mesh):
        raise RuntimeError(
            "backend=tpu must compile its Pallas kernels, but the mesh's "
            f"devices are {mesh.devices.flat[0].platform!r}")
    return mesh
