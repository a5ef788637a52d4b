"""`run.py` end to end off the chip, and `BENCHMARK.json` against its contract.

    python -m pytest benchmarks/tests

Run by hand; not part of tier-1.  Every rehearsal is a child process at the
configuration's `rehearse_scale` (10); the four-chip cell gets four virtual
CPU devices.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cell(cell, *extra, cwd=ROOT, chips=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    if chips > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={chips}"
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "3", "--seconds", "1", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


def cell_metrics(cell, kind):
    e2e = [m for m in BENCH["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in BENCH["per_layer"]
            if ("workloads" not in m or cell in m["workloads"]) and m["moves"] in moved]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_a_well_formed_last_line(cell, trace):
    chips = next(w["chips"] for w in BENCH["workloads"] if w["name"] == cell)
    out = run_cell(cell, "--trace", str(trace), "--rehearse", chips=chips)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    # a rehearsal names no device
    assert last["device"]["platform"] == "rehearsal" and last["device"]["kind"] is None
    assert last["rehearsal"] is True
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in cell_metrics(cell, kind)}
    assert last["metrics"], "no metric reported"
    for name, m in last["metrics"].items():
        assert m["unit"] == allowed[name]
        assert isinstance(m["value"], float)
    if trace:
        assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
        for key in ("device_ops", "idle_gaps"):
            assert len(last["breakdown"][key]) <= 10
        assert last["metrics"]["compiles_in_window"]["value"] == 0
    else:
        assert "setup_s" in last["metrics"] and len(last["metrics"]) >= 2
    earlier = "\n".join(lines[:-1])
    for needle in ("platform", "device_kind", "jaxlib", "reduced:", "assumed:",
                   "samples", "LoadGraph:", "dataset cache", "executable cache"):
        assert needle in earlier, needle


def test_without_rehearse_a_cpu_run_is_refused():
    out = run_cell(CELLS[0], "--trace", "0")
    assert out.returncode != 0
    assert out.stdout == ""
    assert "not 'tpu'" in out.stderr


def test_too_few_devices_is_refused():
    cell = next(w["name"] for w in BENCH["workloads"] if w["chips"] == 4)
    out = run_cell(cell, "--trace", "0", "--rehearse", chips=1)
    assert out.returncode != 0 and out.stdout == ""


def test_the_benchmark_alone_in_a_directory_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("cache", "__pycache__"))
    out = run_cell(CELLS[0], "--trace", "0", "--rehearse", cwd=str(tmp_path))
    assert out.returncode != 0
    assert out.stdout == ""


# ---- BENCHMARK.json against the contract ----


def test_benchmark_json_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in CELLS
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_cells_and_configurations():
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(CELLS) // 2)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and w["config"] in configs
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        assert len(cell_metrics(w["name"], "end_to_end")) >= 2
        assert cell_metrics(w["name"], "per_layer")
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/") and len(c["source"]) <= 200
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert c["name"] in {w["config"] for w in BENCH["workloads"]}
        assert body["chips"] == max(w["chips"] for w in BENCH["workloads"]
                                    if w["config"] == c["name"])
    assert len({c["source"] for c in configs.values()}) == len(configs)
    assert len({c["file"] for c in configs.values()}) == len(configs)


def test_every_per_layer_metric_has_its_file_and_agrees():
    for m in BENCH["per_layer"]:
        spec = json.load(open(os.path.join(
            ROOT, "benchmarks", "layer_metrics", m["name"] + ".json")))
        for key in ("name", "layer", "unit", "better", "source", "moves"):
            assert spec[key] == m[key], (m["name"], key)
        assert ("reader" in spec) != ("reading" in spec)
        if "reader" in spec:
            assert os.path.exists(os.path.join(
                ROOT, "benchmarks", "layer_metrics", spec["reader"] + ".py"))


def test_file_names_under_paths_use_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    listed = subprocess.run(["git", "ls-files", "--cached", "--others",
                             "--exclude-standard", "benchmarks"],
                            cwd=ROOT, capture_output=True, text=True).stdout.split()
    assert listed
    for f in listed:
        assert ok.match(f), f
