"""Test harness configuration.

Forces JAX onto the host CPU with a virtual 8-device platform so multi-chip
sharding (Mesh/pjit/shard_map) is exercised without TPU hardware. Must run
before jax is imported anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

assert jax.device_count() == 8, (
    f"test harness expected 8 virtual CPU devices, got {jax.devices()}"
)
