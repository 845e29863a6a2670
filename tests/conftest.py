"""Test config: 8 virtual CPU devices, mirroring the reference's
multi-node-on-one-machine strategy (SURVEY.md §4).

The tests run on the CPU: JAX reads JAX_PLATFORMS when it is first
imported, so it is set here, before any test module imports jax.
"""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
