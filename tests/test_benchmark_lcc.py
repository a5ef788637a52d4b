"""The cases of benchmarks/tests/test_lcc.py, counted in tier-1.

The LCC metrics' readers on a trace recorded on the v5e, `lcc_list_bytes`
against a hand count, and the rehearsal of the cell `g500-lcc.lcc`.  The
cases live with the benchmark and are loaded from there, by path, so that
both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_lcc.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_lcc", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
