"""The cases of benchmarks/tests/test_setup_phases.py, counted in tier-1.

The shared reader of the seven set-up metrics
(`benchmarks/layer_metrics/setup_phase.py`) on two ledgers recorded on the
v5e, a ledger without `bytes_in_use`, a program without the ledger, and
phases opened after set-up.  The cases live with the benchmark and are
loaded from there, by path, so that both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_setup_phases.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_setup_phases", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
