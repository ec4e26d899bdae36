"""``python -m pytest benchmarks/tests -q`` from the root of the checkout, on
the CPU.  Not part of tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# four virtual devices, for the tests of a four-chip layout in this process
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_"
                               "force_host_platform_device_count=4").strip()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
