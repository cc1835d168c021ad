"""Tests of the chip benchmark's harness, run on the CPU.

They import the harness's modules from the benchmark's directory and the
simulator from ``src``; no persistent compilation cache is written.
"""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parents[1] / "src"))
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
