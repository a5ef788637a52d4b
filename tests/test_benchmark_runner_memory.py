"""The cases of benchmarks/tests/test_runner_memory.py, counted in tier-1.

The reader of the three runner-memory metrics
(`benchmarks/layer_metrics/runner_memory.py`) on two ledgers recorded on the
v5e, on the ledger of a program from before the stamp, on an executable
without an analysis, without the ledger, without allocator statistics, and on
a runner compiled after set-up.  The cases live with the benchmark and are
loaded from there, by path, so that both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_runner_memory.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_runner_memory", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
