"""Test harness: run everything on CPU with 8 fake XLA devices.

This is the TPU-native answer to "multi-node without a cluster" (SURVEY.md §4):
``--xla_force_host_platform_device_count=8`` gives every test a real 8-device
mesh to shard over, so DP/FSDP/TP/SP sharding is exercised without hardware.

``JAX_PLATFORMS=cpu`` is forced here, before jax is imported, and children
the tests spawn inherit it: no test ever reaches for an accelerator.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")
