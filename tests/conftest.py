"""Test harness: fake multi-device CPU backend.

SURVEY.md §5: ``--xla_force_host_platform_device_count=8`` gives 8 virtual
CPU devices — real Mesh, real shard_map, real collective semantics, no
cluster. This must be in XLA_FLAGS before jax initializes its backends, hence
the env mutation at module import time (conftest imports before any test).

The suite is CPU-only whatever the machine holds: the platform is pinned to
``cpu`` below, before any backend initializes, so running the tests on a host
with a chip neither takes the chip nor depends on it.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# initialize() turns the persistent compile cache on for every entry point;
# the suite must stay hermetic — no test, and no child process a test
# starts (workers, the tools' --smoke twins), may read a program another run
# compiled or write one into the checkout. Through the environment, so
# children inherit it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402  (after XLA_FLAGS)
import pytest  # noqa: E402

# Tests are CPU-only (fake multi-device mesh): pin the platform *before* any
# backend initialization.
jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected >=8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _default_to_cpu():
    """Run every test on CPU so results are fast and deterministic even on a
    box whose default backend is a TPU."""
    cpu0 = jax.devices("cpu")[0]
    with jax.default_device(cpu0):
        yield


@pytest.fixture()
def mesh8(cpu_devices):
    """A dp=8 mesh over the fake CPU devices (all other axes size 1)."""
    from orion_tpu.config import ParallelConfig
    from orion_tpu.runtime import build_mesh

    return build_mesh(ParallelConfig(dp=8), devices=cpu_devices[:8])


def make_mesh(cpu_devices, **axes):
    """Helper: build a mesh with the given axis sizes over fake CPU devices."""
    from orion_tpu.config import ParallelConfig
    from orion_tpu.runtime import build_mesh

    cfg = ParallelConfig(**axes)
    return build_mesh(cfg, devices=cpu_devices[: cfg.num_devices])


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (see ROADMAP.md); heavy cases "
        "and files that exceed the 870s CPU budget run in the full tier",
    )
