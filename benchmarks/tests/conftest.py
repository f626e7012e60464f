"""The benchmark's own tests run on the CPU with four virtual devices:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider

Nothing here needs the chip, describes a topology or loads libtpu.
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
