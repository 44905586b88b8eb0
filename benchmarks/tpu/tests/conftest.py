"""The benchmark's own tests: on the CPU, at tiny sizes, never timed.

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tpu/tests
"""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))
