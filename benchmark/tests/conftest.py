"""The benchmark's own tests run on the CPU with four virtual devices.

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider -p no:xdist

They are no part of tier-1 (``pytest tests/``)."""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
