"""Tier-1 guards the benchmark's contract (asked since ISSUE 27).

``benchmark/tests/test_benchmark_contract.py`` (no JAX, under a second)
holds the contract between the harness and what is added to it as
files. It is loaded here by file path, under a module name of its own
(neither directory is a package, and two modules of one basename clash),
and its tests and its ``passages`` fixture are re-exported: no copy, and
nothing under ``benchmark/`` knows of this file.
"""

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
# as benchmark/tests/conftest.py does: the harness's packages
# (``harness``, ``references``, ``generators``) are top-level there
sys.path[:0] = [BENCH, ROOT]

_spec = importlib.util.spec_from_file_location(
    "benchmark_contract_cases",
    os.path.join(BENCH, "tests", "test_benchmark_contract.py"))
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: obj for name, obj in vars(_cases).items()
                  if name.startswith("test_") or name == "passages"})
