"""Test env: force an 8-device virtual CPU mesh before JAX initializes.

Multi-device sharding/collective behavior is tested without TPU hardware via
``--xla_force_host_platform_device_count`` (the capability the reference lacks
— its only multi-node test rig was a pseudo-distributed Hadoop install).
"""

import os

# Force, not setdefault: tests run on the virtual 8-device CPU mesh only,
# whatever the ambient environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np
import pytest


@pytest.fixture()
def rng():
    # function-scoped so each test draws a deterministic stream regardless of
    # which other tests run or in what order
    return np.random.default_rng(0)
