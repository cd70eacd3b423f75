"""What the chip bring-up added: the compile-cache rule, the peaks table, and
the entry point that must refuse to run without the chip."""

import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(argv, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "DS_TPU_ACCELERATOR")}
    full.update(env)
    return subprocess.run([sys.executable, *argv], capture_output=True,
                          text=True, timeout=120, cwd=REPO, env=full)


@pytest.fixture()
def cache_config():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])


def test_compile_cache_env_set_is_left_alone(monkeypatch, cache_config):
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert place_compile_cache() == "/somewhere/else"
    # nothing set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_unset_is_checkout_jax_cache(monkeypatch, cache_config):
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert place_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want  # for workers
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert place_compile_cache() == want  # fixed: same path every time


def test_peaks_table_known_and_unknown_kind():
    from deepspeed_tpu.accelerator.peaks import device_peaks

    v5e = device_peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bytes_per_s == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        device_peaks("cpu")
    with pytest.raises(ValueError, match="TPU v9"):
        device_peaks("TPU v9")


def test_chip_smoke_refuses_without_a_chip():
    p = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert p.returncode != 0
    assert "refusing to run" in p.stderr and "JAX_PLATFORMS" in p.stderr
    assert p.stdout.strip() == ""  # no result line
    p = _run(["chip_smoke.py"], DS_TPU_ACCELERATOR="cpu")
    assert p.returncode != 0 and "DS_TPU_ACCELERATOR" in p.stderr


def test_importing_the_package_initializes_no_backend():
    """A launcher parent imports deepspeed_tpu and then starts the worker that
    needs the chip: the import must not take it. With a platform that does not
    exist, any backend initialization would raise."""
    p = _run(["-c", "import deepspeed_tpu; print('imported')"],
             JAX_PLATFORMS="no_such_platform")
    assert p.returncode == 0 and "imported" in p.stdout, p.stderr[-500:]


def test_maybe_shard_noops_only_where_the_constraint_is_meaningless(devices):
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.models.api import maybe_shard
    from deepspeed_tpu.runtime.topology import mesh_context

    x = jnp.ones((8, 8))
    assert maybe_shard(x, P("dp")) is x  # no mesh bound
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("dp", "tp"))
    with mesh_context(mesh):
        y = jax.jit(lambda a: maybe_shard(a * 2, P("dp", "tp")))(x)
        z = jax.jit(lambda a: maybe_shard(a * 2, P("sp")))(x)  # absent axis
    assert tuple(y.sharding.spec) == ("dp", "tp")
    assert z.sharding.is_fully_replicated
