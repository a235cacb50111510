"""Test harness configuration.

The reference simulates multi-node as multi-process single-host with a real
NCCL/GLOO backend (tests/unit/common.py:105 DistributedExec). The TPU-native
equivalent: a *virtual 8-device CPU mesh* via
``--xla_force_host_platform_device_count`` so every collective XLA emits is
real (ring algorithms on host), just not timed. The provisioning recipe is
shared with the driver gate (``__graft_entry__._provision``) so the test mesh
and the gate mesh can't diverge.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _provision  # noqa: E402

_provision(8)

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_topology():
    """Each test builds its own mesh topology."""
    yield
    from deepspeed_tpu.utils import groups
    groups.reset()
