"""Test configuration: put JAX on a virtual 8-device CPU platform so
multi-chip sharding paths run without TPU hardware.  The chip path is
`chip_smoke.py`, run through the chip tool; tests/test_chip_compile.py
compiles for a described chip without one."""

import os
import sys

# JAX reads both at import; every test process and every child it starts
# stays on the CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
