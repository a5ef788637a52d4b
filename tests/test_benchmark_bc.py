"""The cases of benchmarks/tests/test_bc.py, counted in tier-1.

The configuration `g500-bc` against its generator and the plain reference,
the readers of the metrics the cell `g500-bc.bc-key1` brings on a stub and on
nothing to read, the comparison on an answer with zeros, and the cell
rehearsed.  The cases live with the benchmark and are loaded from there, by
path, so that both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_bc.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_bc", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
