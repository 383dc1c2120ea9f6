"""The benchmark's own tests: the CPU backend, interpret-mode kernels,
the device check patched here (the harness itself has no CPU option)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["ES_TPU_PALLAS"] = "interpret"
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
