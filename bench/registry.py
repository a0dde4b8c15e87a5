"""Find a cell's configuration, traffic mix, system and metric readers by name.

Everything that belongs to one configuration, one traffic mix, one kind
of traffic or one per-layer metric lives in a file of its own under the
checkout's ``bench/``, found from the names in ``BENCHMARK.json``:

* configuration ``<c>``: the file ``BENCHMARK.json`` names for it (a JSON
  object of sizes whose ``"system"`` key says which driver serves it and
  whose ``"reference"`` key names its plain reference beside it);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, parameters for the
  kind of traffic its ``"kind"`` key names;
* traffic kind ``<k>``: ``bench/traffic/kinds/<k>.py``, the generator of
  that kind's request streams and its request call (see
  :mod:`bench.workload`);
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, a module with a
  ``read(ctx) -> float | None`` function;
* system ``<s>``: ``bench/systems/<s>.py`` (set-up, window and check for
  one kind of deployment).

So a later change adds a configuration, a mix, a kind or a metric by
adding a file and an entry, and edits nothing that exists.

With ``rehearse`` (the tests' CPU runs) each configuration's and mix's
``"rehearse"`` object is merged over it, nested objects key by key.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List

__all__ = ["Cell", "Registry", "merged"]


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: Path


def merged(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on it; nested objects merge key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _load_module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def _module_name(prefix: str, name: str) -> str:
    return prefix + name.replace(".", "_").replace("-", "_")


class Registry:
    """``BENCHMARK.json`` under ``root`` and the files it names."""

    def __init__(self, root: Path, rehearse: bool = False):
        self.root = Path(root)
        self.bench = self.root / "bench"
        self.rehearse = rehearse
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, path: Path) -> dict:
        data = json.loads(path.read_text())
        return merged(data, data.get("rehearse", {})) if self.rehearse else data

    def _config_file(self, config_name: str) -> Path:
        conf = {c["name"]: c for c in self.spec["configs"]}[config_name]
        return self.root / conf["file"]

    def cell(self, name: str) -> Cell:
        cells = {w["name"]: w for w in self.spec["workloads"]}
        if name not in cells:
            raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
        w = cells[name]
        config = self._json(self._config_file(w["config"]))
        traffic = self._json(self.bench / "traffic" / f"{w['traffic']}.json")
        e2e = [m for m in self.spec["end_to_end"]
               if name in m.get("workloads", [name])]
        reported = {m["name"] for m in e2e}
        per_layer = [
            m for m in self.spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)
        ]
        return Cell(
            name=name, chips=int(w["chips"]), config_name=w["config"],
            config=config, traffic_name=w["traffic"], traffic=traffic,
            end_to_end=e2e, per_layer=per_layer, root=self.root,
        )

    def system(self, cell: Cell) -> ModuleType:
        name = cell.config["system"]
        return _load_module(self.bench / "systems" / f"{name}.py",
                            _module_name("bench_system_", name))

    def kind(self, cell: Cell) -> ModuleType:
        name = cell.traffic["kind"]
        return _load_module(self.bench / "traffic" / "kinds" / f"{name}.py",
                            _module_name("bench_kind_", name))

    def reader(self, metric: str) -> ModuleType:
        return _load_module(self.bench / "metrics" / f"{metric}.py",
                            _module_name("bench_metric_", metric))

    def reference(self, cell: Cell) -> ModuleType:
        """The configuration's plain reference, the file beside its sizes."""
        path = self._config_file(cell.config_name).parent / cell.config["reference"]
        return _load_module(path, _module_name("bench_reference_", cell.config_name))
