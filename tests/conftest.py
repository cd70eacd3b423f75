"""Test harness: simulated 8-device CPU mesh.

The reference's trick (SURVEY.md §4) is ``DistributedTest`` spawning N real processes
over NCCL on one box. The TPU-native equivalent is *simpler*: JAX can present N
virtual CPU devices in a single process (``xla_force_host_platform_device_count``),
so every sharding/collective path compiles and runs exactly as it would on an N-chip
mesh — no process spawning, no fake backends. These env vars MUST be set before jax
is imported anywhere in the test process.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DS_TPU_ACCELERATOR"] = "cpu"
# AOT-report tests load libtpu for compile-only topology work, in-process AND
# in CLI subprocesses — skip libtpu's single-process lockfile
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "true")

import jax  # noqa: E402  (after the environment above: nothing imports it earlier)
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 simulated devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="module", autouse=True)
def _a_files_programs_go_with_the_file():
    """A worker's process bears only so many retained executables: with one
    more family file, whichever worker took it aborted inside XLA's CPU
    compile of a trivial program in a LATER file (``ROADMAP.md`` D10; PR 56
    and PR 58 both met it). So what a file compiled goes when its tests are
    done; the next file compiles what it needs, as it would in a worker of
    its own."""
    yield
    jax.clear_caches()


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


def random_batch(rng, batch_size: int, seq_len: int, vocab: int = 256, gas: int = 1):
    shape = (batch_size, seq_len) if gas == 1 else (gas, batch_size, seq_len)
    return {"input_ids": rng.integers(0, vocab, size=shape, dtype=np.int32)}


def pytest_generate_tests(metafunc):
    """The cases of a test of a ``ServedFamilyContract`` class
    (``served_contract.py``) are the keys of the class's own tables: ``path``
    runs over ``PATHS``, ``fault`` over ``FAULTS``, and so on (the class's
    ``TABLES``), each case's id its key."""
    for arg, table in getattr(metafunc.cls, "TABLES", {}).items():
        if arg in metafunc.fixturenames:
            metafunc.parametrize(arg, sorted(getattr(metafunc.cls, table)))
