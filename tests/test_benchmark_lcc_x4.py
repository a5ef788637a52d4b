"""The cases of benchmarks/tests/test_lcc_x4.py, counted in tier-1.

The ring reader (`benchmarks/layer_metrics/lcc_ring.py`) and the LCC
readers on a trace recorded on the four-chip v5e, the ring's bytes against
a hand count, and the rehearsal of the cell `g500-lcc-x4.lcc` on four
virtual CPU devices.  The cases live with the benchmark and are loaded from
there, by path, so that both suites run the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_lcc_x4.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_lcc_x4", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
