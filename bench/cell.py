"""Find everything one benchmark cell needs, by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix; each lives in a file of
its own (``configs/<config>.json``, ``traffic/<traffic>.json``). The mix
names the kind of call it sends, a module of its own
(``kinds/<kind>.py``); the configuration names the plain reference of
its semantics (``references/<reference>.py``); each per-layer metric
has a reader of its own (``layers/<metric>.py``). Adding a cell,
configuration, mix, kind of call, reference or metric is adding files
and entries: nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict       # the configuration file, as written
    traffic: dict      # the traffic mix file, as written
    end_to_end: list   # metric entries of BENCHMARK.json this cell reports
    per_layer: list
    readers: dict      # per-layer metric name -> read(ctx) function
    kind: object       # the traffic mix's kind module
    reference: pathlib.Path  # the configuration's reference module

    @property
    def params(self) -> dict:
        """The ``SimParams`` fields the configuration runs with."""
        return self.config["params"]

    def make_traffic(self, params, seed: int):
        """The generator of this cell's calls for a run with ``seed``."""
        return self.kind.Traffic(params, self.traffic, seed)


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(path: pathlib.Path, prefix: str):
    """The module in file ``path``, loaded once per path."""
    name = f"bench_{prefix}_{path.stem}_{abs(hash(str(path))):x}"
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise FileNotFoundError(f"no {prefix} file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: pathlib.Path):
    """The ``read(ctx)`` function of one per-layer metric's reader file."""
    return load_module(path, "layer").read


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """Resolve cell ``name`` of ``root/BENCHMARK.json`` to its files."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    bench = root / spec["paths"][0]
    traffic = json.loads(
        (bench / "traffic" / f"{w['traffic']}.json").read_text()
    )
    per_layer = [m for m in spec["per_layer"] if _reports(m, name)]
    readers = {
        m["name"]: load_reader(bench / "layers" / f"{m['name']}.py")
        for m in per_layer
    }
    return Cell(
        kind=load_module(bench / "kinds" / f"{traffic['kind']}.py", "kind"),
        reference=bench / "references" / f"{config['reference']}.py",
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _reports(m, name)],
        per_layer=per_layer,
        readers=readers,
    )
