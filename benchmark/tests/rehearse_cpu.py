"""CPU rehearsal: the benchmark's own runner code, end to end, over tiny
cells of its own (benchmark/tests/rehearsal, never named in
BENCHMARK.json), on ``JAX_PLATFORMS=cpu`` with four virtual devices.

    python3 benchmark/tests/rehearse_cpu.py --workload debug-serve.debug_chat \\
        --seed 1 --seconds 3 --trace 0

It exists to find wrong paths before chip time. Its result line says
``"platform": "cpu"``; a number it prints is never a device metric.
"""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))

from benchmark import run  # noqa: E402

if __name__ == "__main__":
    run.main(allow_cpu=True, manifest_path=HERE / "rehearsal" / "manifest.json")
