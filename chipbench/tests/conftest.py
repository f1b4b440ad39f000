"""Tests of the benchmark's own yardstick; run by hand with ``JAX_PLATFORMS=cpu pytest chipbench/tests``."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
