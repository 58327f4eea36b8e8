"""The benchmark's own tests run by path: ``python -m pytest bench/tests``.
They import the harness from ``bench/`` and the program from ``src/``, and
keep the CPU's compiled programs out of the checkout's compile cache,
which the benchmark's runs on the chip use."""
import sys
from pathlib import Path

import jax

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
jax.config.update("jax_enable_compilation_cache", False)
