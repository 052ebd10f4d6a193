"""Test harness: force an 8-device virtual CPU platform so sharding tests run
without TPU hardware (SURVEY.md §4 pattern (4): in-process multi-host tests
replacing the reference's localhost pservers in test_CompareSparse.cpp)."""

import os
import sys

# Tests always run on the virtual 8-device CPU mesh: JAX_PLATFORMS=cpu, set
# before jax is imported, is all it takes.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

import numpy as np
import pytest


@pytest.fixture
def rng():
    import jax
    return jax.random.PRNGKey(0)


@pytest.fixture
def np_rng():
    return np.random.RandomState(0)


def free_port():
    """An OS-assigned free TCP port for multi-process rendezvous tests."""
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port
