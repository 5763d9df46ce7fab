"""The benchmark's self-tests run on the CPU, at small sizes, without a
chip and without the persistent compile cache; the CPU shows four
devices so that the four-chip cell's sharded path runs too."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))
