"""The cases of benchmarks/tests/test_scopes.py, counted in tier-1.

`benchmarks/reduce_scopes.py` joins the device operations of a profiler
trace to the library's `grape.*` named scopes and lays the mirrored `obs`
spans against the device's idle time.  The chip's trace differs from the
CPU's (`tf_op` in the event metadata), so the join is pinned on traces
recorded on the v5e; the cases live with the benchmark and are loaded
from there, by path, so that both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_scopes.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_scopes", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
