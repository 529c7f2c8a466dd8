"""Test bootstrap: force JAX onto a virtual 8-device CPU mesh BEFORE jax import.

This is the CI tier from SURVEY.md §4: real compile/execute semantics with no
TPU hardware (the reference's miniredis-style fake-backend idiom), and 8
virtual devices so multi-chip sharding paths are exercised for real.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def mock_container():
    from gofr_tpu import new_mock_container

    return new_mock_container()


@pytest.fixture()
def free_port():
    from gofr_tpu.testutil import get_free_port

    return get_free_port()
