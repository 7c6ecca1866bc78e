"""Test config: force an 8-device virtual CPU platform so multi-chip sharding
paths are exercised without TPU hardware (the analogue of the reference's
fake in-process device lists in op-handle tests,
``details/broadcast_op_handle_test.cc``).
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# Lock-order deadlock detection: PYTEST_CURRENT_TEST is absent during
# collection/import, so pin the checker on explicitly for the whole run.
from paddle_tpu.core import locks as _locks  # noqa: E402

_locks.set_enabled(True)


@pytest.fixture
def rng():
    return np.random.RandomState(1234)
