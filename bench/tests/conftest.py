"""Fixtures for the benchmark's own tests: a tiny benchmark tree, built
in a temporary directory from the real configuration and traffic files
with their scale cut down, so a run fits the CPU."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the scale of a tiny lane: 1 simulated second, 64 pipeline slots
TINY = {"duration": 1.0, "max_pipelines": 64, "max_containers": 16}


def write_tiny_tree(root: pathlib.Path) -> pathlib.Path:
    """A copy of BENCHMARK.json and its files under ``root``, with every
    configuration cut to :data:`TINY` and every mix to a few lanes."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench = root / spec["paths"][0]
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    for c in spec["configs"]:
        f = root / c["file"]
        cfg = json.loads(f.read_text())
        cfg["params"].update(TINY)
        f.write_text(json.dumps(cfg))
    for f in (bench / "traffic").glob("*.json"):
        t = json.loads(f.read_text())
        if "lanes_per_call" in t:
            t["lanes_per_call"] = 4
        # a pass over the pool is two calls
        t["pool_lanes"] = 2 * t.get("lanes_per_call", 1)
        t["check_lanes_min"] = 1
        f.write_text(json.dumps(t))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return write_tiny_tree(tmp_path_factory.mktemp("tiny_bench"))
