"""The benchmark's own tests run on the CPU, from the repository root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
