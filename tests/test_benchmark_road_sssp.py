"""The cases of benchmarks/tests/test_road_sssp.py, counted in tier-1.

The configuration `road-like-sssp` against its generator and SciPy's
Dijkstra, the readers of the metrics the cell `road-like-sssp.sssp-key1`
brings on a stub and on nothing to read, and the cell rehearsed.  The cases
live with the benchmark and are loaded from there, by path, so that both
suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_road_sssp.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_road_sssp", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
