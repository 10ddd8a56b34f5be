"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip TPU hardware is unavailable in CI, so all sharding/collective
paths are exercised on a virtual 8-device CPU topology, mirroring how the
reference tests multi-node behavior without a cluster (reference:
rust/scheduler/src/lib.rs:444-491 tests gRPC services via direct calls).
"""

import os

# Force CPU, whatever the ambient environment says: tests want the fast
# virtual 8-device CPU topology (set before jax is first imported).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs[:8]
