"""The cases of benchmarks/tests/test_cdlp_datagen.py, counted in tier-1.

The Datagen-like surrogate's generator against its configuration and against
`scripts/gen_datagen_like.py`, the reader of the packed arm's metrics on a
stub, and the cell `datagen-like.cdlp-10r` rehearsed.  The cases live with
the benchmark and are loaded from there, by path, so that both suites run
the same code.
"""

import importlib.util
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "tests", "test_cdlp_datagen.py")
_spec = importlib.util.spec_from_file_location("benchmarks_test_cdlp_datagen", _PATH)
_cases = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_cases)

globals().update({name: case for name, case in vars(_cases).items()
                  if name.startswith("test_")})
