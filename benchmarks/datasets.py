"""A configuration's dataset: graph files, plain references and search keys,
made once per checkout from the configuration's own seed and kept under
`benchmarks/cache/`.

A Graphalytics dataset is a fixed file, and Graph500 draws its 64 search
keys once per graph: so the graph and the pool of keys belong to the
configuration (`generator_seed`), and a run's `--seed` decides which keys
its callers ask and in which order (`drivers/`).  Nothing here imports the
library under test or JAX.
"""

from __future__ import annotations

import functools
import hashlib
import importlib
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_ROOT = os.path.join(HERE, "cache")


class Dataset:
    """Files and lazily made plain answers of one generated graph."""

    def __init__(self, config: dict, scale: int, log=print):
        self.gen = gen = config["generator"]
        self.generator = importlib.import_module(f"benchmarks.graphs.{gen['name']}")
        self.scale = scale
        self.seed = int(gen["generator_seed"])
        self.n = 1 << scale
        # keyed by all the files depend on (the whole generator block and
        # the scale), so configurations that load the same graph (one
        # chip, four chips) share them and a changed parameter never
        # finds another's files
        block = hashlib.sha1(json.dumps(gen, sort_keys=True).encode()).hexdigest()[:10]
        self.dir = os.path.join(CACHE_ROOT, f"{gen['name']}-s{scale}-{block}")
        self.efile = os.path.join(self.dir, "graph.e")
        self.vfile = os.path.join(self.dir, "graph.v")
        self.fragment_prefix = os.path.join(self.dir, "fragments")
        self.log = log
        self.hits, self.misses = [], []  # what came from the cache, what was made
        self._answers = {}

    # ---- files ----

    def ensure_files(self) -> dict:
        """The edge and vertex files at their fixed paths; made if absent."""
        info_path = os.path.join(self.dir, "graph.json")
        if all(os.path.exists(p) for p in (self.efile, self.vfile, info_path)):
            self.hits.append("graph files")
            with open(info_path) as f:
                return json.load(f)
        self.misses.append("graph files")
        os.makedirs(self.dir, exist_ok=True)
        info = self.generator.write_files(self.gen, self.scale, self.efile, self.vfile)
        _write_json(info_path, info)
        return info

    # ---- the graph in memory, for the references only ----

    @functools.cached_property
    def edges(self):
        self.log(f"drawing the edge list in memory (scale {self.scale})")
        return self.generator.edges(self.gen, self.scale)

    @functools.cached_property
    def _matrices(self):
        from benchmarks.graphs.csr import symmetric_csr

        self.log("building the references' SciPy matrices")
        return symmetric_csr(self.n, *self.edges)

    @property
    def minw(self):
        """Lightest parallel weight per distinct (row, col)."""
        return self._matrices[0]

    @property
    def mult(self):
        """Multiplicity per distinct (row, col)."""
        return self._matrices[1]

    # ---- plain answers ----

    def reference(self, app: str, params: dict) -> np.ndarray:
        """The plain answer of `app` under `params`, from `.npy` if there."""
        tag = "-".join(f"{k}{params[k]}" for k in sorted(params)) or "all"
        name = f"{app}-{tag}"
        if name in self._answers:
            return self._answers[name]
        path = os.path.join(self.dir, "references", name + ".npy")
        if os.path.exists(path):
            self.hits.append(f"reference {name}")
            want = np.load(path)
        else:
            self.misses.append(f"reference {name}")
            mod = importlib.import_module(f"benchmarks.references.{app}")
            want = mod.reference(self, params)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            np.save(path + ".tmp.npy", want)
            os.replace(path + ".tmp.npy", path)
        self._answers[name] = want
        return want

    # ---- search keys ----

    def key_pool(self, size: int) -> list:
        """Graph500's search keys for this graph: `size` distinct vertices
        with at least one edge, drawn uniformly from the configuration's
        seed."""
        path = os.path.join(self.dir, f"keys-{size}.json")
        if os.path.exists(path):
            self.hits.append(f"search keys {size}")
            with open(path) as f:
                return json.load(f)["keys"]
        self.misses.append(f"search keys {size}")
        from benchmarks.graphs.csr import degrees

        src, dst, _ = self.edges
        has_edge = np.flatnonzero(degrees(self.n, src, dst) > 0)
        keys = np.random.default_rng(self.seed).choice(
            has_edge, size=min(size, len(has_edge)), replace=False).tolist()
        _write_json(path, {"rule": "uniform over vertices with an edge", "keys": keys})
        return keys


def _write_json(path: str, obj) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)
