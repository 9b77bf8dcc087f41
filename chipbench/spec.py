"""Find cells, configurations, traffic mixes and metric readers by name.

Nothing here knows a particular cell: a configuration is
``configs/<config>.json`` with its plain reference ``configs/<config>.py``
beside it, a traffic mix is ``mixes/<traffic>.json``, and a per-layer metric
is read by ``metrics/<metric>.py``. Paths are resolved against the root that
holds ``BENCHMARK.json``.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List

BENCH_DIR = "chipbench"


class SpecError(RuntimeError):
    """BENCHMARK.json or a file it names is missing or inconsistent."""


def load_benchmark(root: Path) -> Dict[str, Any]:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json in {root}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str) -> ModuleType:
    """Import a file by path (its name may hold '-' and '.')."""
    if not path.is_file():
        raise SpecError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_dyn_" + "".join(c if c.isalnum() else "_" for c in name),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with everything it names, loaded."""

    def __init__(self, root: Path, bench: Dict[str, Any], name: str):
        self.root = Path(root)
        self.bench = bench
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError(f"unknown workload {name!r}; known: "
                            f"{sorted(cells)}")
        self.workload = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        cname = self.workload["config"]
        if cname not in configs:
            raise SpecError(f"workload {name} names unknown config {cname}")
        self.config_entry = configs[cname]
        cfile = self.root / self.config_entry["file"]
        if not cfile.is_file():
            raise SpecError(f"missing config file {cfile}")
        self.config = json.loads(cfile.read_text())
        self.reference = load_module(cfile.with_suffix(".py"), cname)
        mfile = self.root / BENCH_DIR / "mixes" / (
            self.workload["traffic"] + ".json")
        if not mfile.is_file():
            raise SpecError(f"missing traffic mix {mfile}")
        self.mix = json.loads(mfile.read_text())
        self.chips = int(self.workload["chips"])

    def _applies(self, metric: Dict[str, Any], e2e_names: List[str]) -> bool:
        if "workloads" in metric:
            return self.name in metric["workloads"]
        if "moves" in metric:               # per-layer: where its arrow goes
            return metric["moves"] in e2e_names
        return True

    @property
    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"]
                if self._applies(m, [])]

    @property
    def per_layer(self) -> List[Dict[str, Any]]:
        names = [m["name"] for m in self.end_to_end]
        return [m for m in self.bench["per_layer"]
                if self._applies(m, names)]

    def reader(self, metric_name: str) -> ModuleType:
        return load_module(
            self.root / BENCH_DIR / "metrics" / (metric_name + ".py"),
            metric_name)
