"""The benchmark's own tests: ``python -m pytest benchmark/tests -q`` from the
repo's root, on the CPU. They touch no chip and report no device metric."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(HERE), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
