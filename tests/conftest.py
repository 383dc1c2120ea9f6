"""Test configuration: force an 8-device virtual CPU mesh.

Mirrors the reference's in-one-JVM multi-node testing strategy
(test/framework/.../InternalTestCluster.java): instead of real TPU chips,
tests run on the CPU backend with 8 virtual devices so mesh/sharding code
paths execute deterministically (SURVEY.md §4.6.3).

Must run before any jax import — pytest imports conftest first.
"""

import os

# tests run on the CPU backend with 8 virtual devices, whatever the
# environment asks for
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (multi-process)")


@pytest.fixture()
def tmp_data_dir(tmp_path):
    return str(tmp_path / "data")
