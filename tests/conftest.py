"""Test harness config: force an 8-device virtual CPU platform.

Tier-1 runs where there is no chip: all sharding/mesh tests run against
XLA's host-platform device emulation, which exercises the same GSPMD
partitioning and collective lowering paths. What only a chip can show —
Mosaic layout/VMEM failures, real collectives, device memory — is
chip_smoke.py's job (run through the chip tool), not this suite's.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: spawns real service subprocesses")


@pytest.fixture
def rng():
    return np.random.default_rng(12345)
