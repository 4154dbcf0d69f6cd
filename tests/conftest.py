"""
Test harness configuration.

The unit tier runs on the JAX CPU backend with 8 virtual host devices —
the analogue of the reference's pytest-spark local-mode JVM: the same
sharding, replication and gather code paths execute without TPU
hardware. The device count is an XLA flag, so it must be set before
anything imports jax; the platform is pinned in-process as well, so the
suite is a CPU run even when ``JAX_PLATFORMS`` was left unset.
"""

import os

# device-count matrix knob (build_tools/ runs the suite at 4 and 8 —
# the analogue of the reference's spark 2.4 / 3.0 version matrix)
N_VIRTUAL_DEVICES = int(os.environ.get("SKDIST_TEST_DEVICES", "8"))

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + f" --xla_force_host_platform_device_count={N_VIRTUAL_DEVICES}"
)

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache, placed by the program's own rule
# (JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache):
# forest/linear kernel compiles dominate suite wall time, and the cache
# survives across pytest runs and is shared by the xdist workers —
# safe, because entries key on program + flags.
from skdist_tpu.parallel import compile_cache

compile_cache.enable_disk_cache()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def eight_devices():
    devices = jax.devices()
    assert len(devices) == N_VIRTUAL_DEVICES
    return devices


@pytest.fixture(scope="session")
def tpu_backend():
    """A TPUBackend over the virtual CPU device mesh."""
    from skdist_tpu.parallel import TPUBackend

    return TPUBackend()


@pytest.fixture
def clf_data():
    """Tiny deterministic classification problem (mirrors the synthetic
    arrays used throughout the reference tests, e.g. test_search.py:38-45)."""
    rng = np.random.RandomState(0)
    X = np.vstack([
        rng.normal(loc=c, scale=0.5, size=(60, 8)) for c in (-2.0, 0.0, 2.0)
    ]).astype(np.float32)
    y = np.repeat([0, 1, 2], 60)
    perm = rng.permutation(len(y))
    return X[perm], y[perm]


@pytest.fixture
def binary_data():
    rng = np.random.RandomState(1)
    X = np.vstack([
        rng.normal(loc=c, scale=0.7, size=(80, 6)) for c in (-1.0, 1.0)
    ]).astype(np.float32)
    y = np.repeat([0, 1], 80)
    perm = rng.permutation(len(y))
    return X[perm], y[perm]


@pytest.fixture
def reg_data():
    rng = np.random.RandomState(2)
    X = rng.normal(size=(200, 10)).astype(np.float32)
    w = rng.normal(size=10)
    y = (X @ w + 0.1 * rng.normal(size=200)).astype(np.float32)
    return X, y
