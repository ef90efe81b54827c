"""Tests of the on-chip benchmark's harness, run on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/chip/tests -q

They import the harness modules and the program from this checkout."""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]
