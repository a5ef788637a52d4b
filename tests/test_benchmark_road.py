"""The cases of benchmarks/tests/test_road.py, counted in tier-1.

The road-like surrogate's generator against its configuration, the readers
of the round record's metrics on a stub and on the trace recorded on the
v5e, and the cell `road-like.bfs-key1` rehearsed.  The cases live with the
benchmark and are loaded from there, by path, so that both suites run the
same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_road.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_road", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
