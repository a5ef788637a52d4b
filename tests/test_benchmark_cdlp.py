"""The cases of benchmarks/tests/test_cdlp.py, counted in tier-1.

The plain CDLP reference against two oracles built otherwise (a forced tie,
a doubled edge, a self-loop), and the CDLP metrics' readers on a trace
recorded on the v5e.  The cases live with the benchmark and are loaded from
there, by path, so that both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_cdlp.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_cdlp", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

graph = _cases.graph  # the cases' fixture
globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
