"""The refusals that keep a CPU run from passing as a chip run (ISSUE 21):
no silent device fallback, a strict peak table on the chip path, the
compile cache placed from outside, the native core built from its source."""

import hashlib
import os
import types

import jax
import pytest

from distributed_deep_q_tpu import native
from distributed_deep_q_tpu.config import MeshConfig
from distributed_deep_q_tpu.parallel.mesh import make_mesh, pallas_interpret
from distributed_deep_q_tpu.profiling import peak_flops_for
from distributed_deep_q_tpu.utils import compile_cache


def test_backend_tpu_refuses_a_cpu_only_process():
    with pytest.raises(RuntimeError, match=r"backend=tpu.*'cpu'"):
        make_mesh(MeshConfig(backend="tpu"))


def test_cpu_mesh_interprets_pallas_kernels():
    mesh = make_mesh(MeshConfig(backend="cpu", num_fake_devices=2))
    assert pallas_interpret(mesh) is True


METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    keyed = getattr(jax.config, METADATA_IN_KEY)
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    jax.config.update(METADATA_IN_KEY, keyed)


@pytest.mark.parametrize("env", [True, False], ids=["env_dir", "checkout"])
def test_cache_helper_keys_programs_by_their_scope_names(
        monkeypatch, tmp_path, restore_cache_dir, env):
    """The ``ddq.*`` names are read back out of executables
    (``profiling.scope_table``): an entry another commit wrote for the same
    operations under other names must miss (ISSUE 36), wherever the cache
    lives."""
    if env:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    jax.config.update(METADATA_IN_KEY, False)
    compile_cache.place_compile_cache()
    assert getattr(jax.config, METADATA_IN_KEY) is True


def test_cache_helper_leaves_config_alone_when_env_names_a_dir(
        monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.listdir(tmp_path)     # JAX owns it; nothing made here


def test_cache_helper_picks_checkout_jax_cache_without_env(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(checkout, ".jax_cache")
    assert compile_cache.place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


@pytest.mark.parametrize("kind,backend,want", [
    ("TPU v5 lite", "tpu", 197e12),
    ("TPU v5 lite", "cpu", 197e12),
    ("cpu", "cpu", None),               # no published peak: MFU absent
], ids=["v5e-on-tpu", "v5e-on-cpu", "cpu-on-cpu"])
def test_peak_flops_table(kind, backend, want):
    dev = types.SimpleNamespace(device_kind=kind)
    assert peak_flops_for(dev, backend=backend) == want


@pytest.mark.parametrize("kind", ["TPU v99", "cpu", ""])
def test_peak_flops_unknown_kind_is_an_error_on_backend_tpu(kind):
    dev = types.SimpleNamespace(device_kind=kind)
    with pytest.raises(ValueError, match="no published peak"):
        peak_flops_for(dev, backend="tpu")


def test_native_artifact_is_keyed_by_its_source_hash():
    with open(native._SRC, "rb") as fh:
        digest = hashlib.sha256(fh.read()).hexdigest()[:16]
    assert os.path.basename(native._artifact()) == \
        f"_replay_core.{digest}.so"
    assert native.backend() in ("native", "numpy")
    if native.backend() == "native":
        assert os.path.exists(native._artifact())


def test_chip_smoke_fails_without_a_tpu_and_prints_no_result():
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for argv in ([], ["--chips", "4"]):
        run = subprocess.run(
            [sys.executable, os.path.join(root, "chip_smoke.py"), *argv],
            env=env, cwd=root, capture_output=True, text=True, timeout=120)
        assert run.returncode != 0
        assert run.stdout == ""         # no result line, no "ok"
        assert "not a TPU" in run.stderr

# ---------------------------------------------------------------------------
# actors keep to their share of the host's cores (PR 30)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("actor_id, num_actors, cores, want", [
    (0, 4, range(13), {0, 1, 2}),          # the benchmark's host: 3 each,
    (3, 4, range(13), {9, 10, 11}),        # one core left over
    (1, 2, {2, 3, 5, 7}, {5, 7}),          # a mask the process was given
    (5, 16, range(4), {1}),                # more actors than cores: wrap
    (0, 1, range(8), set(range(8))),       # alone: everything
])
def test_actor_cores_split_the_host_evenly(actor_id, num_actors, cores,
                                           want):
    from distributed_deep_q_tpu.actors.supervisor import actor_cores
    assert actor_cores(actor_id, num_actors, cores) == want


@pytest.mark.skipif(not hasattr(os, "sched_setaffinity"),
                    reason="no CPU affinity on this platform")
def test_actor_process_keeps_to_its_core_share():
    """In a process of its own (the mask is inherited by every thread
    started after it, and must not leak into this test worker)."""
    import multiprocessing

    from distributed_deep_q_tpu.actors import supervisor
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    p = ctx.Process(target=_report_core_share, args=(q, 1, 2))
    p.start()
    before, after = q.get(timeout=60)
    p.join(timeout=30)
    assert not p.is_alive()
    assert set(after) == supervisor.actor_cores(1, 2, before)
    assert len(after) == max(1, len(before) // 2)


def _report_core_share(q, actor_id, num_actors):
    from distributed_deep_q_tpu.actors import supervisor
    before = sorted(os.sched_getaffinity(0))
    supervisor._keep_to_core_share(actor_id, num_actors)
    q.put((before, sorted(os.sched_getaffinity(0))))
