"""The benchmark's tests run on the CPU, with four virtual devices for the
cell whose merge crosses chips, and import the harness from ``bench/``.

    python -m pytest bench/tests
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS",
                                                                ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")
_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROOT = os.path.dirname(_BENCH)
for p in (os.path.join(_ROOT, "src"), _BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
