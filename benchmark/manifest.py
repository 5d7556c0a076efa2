"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

The harness holds no cell, configuration, traffic or metric name in code.  A
cell is one entry of ``workloads``; its configuration is the ``configs`` entry
of that name (``file`` says where), its traffic is ``traffic/<name>.json``,
and each per-layer metric that lists the cell (or lists none) is
``layer_metrics/<name>.json``.  Files are looked for under every directory of
``paths`` beside the manifest first and beside this module second, so a later
PR — or a test in a temporary directory — adds a cell by adding files and one
entry, and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_MANIFEST = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")


class ManifestError(Exception):
    """The manifest or one of the files it names is missing or wrong."""


def _load_json(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


class Manifest:
    def __init__(self, path: str = DEFAULT_MANIFEST):
        self.path = os.path.abspath(path)
        self.root = os.path.dirname(self.path)
        if not os.path.isfile(self.path):
            raise ManifestError(f"no manifest at {self.path}")
        self.data = _load_json(self.path)
        #: directories searched for data files and code, in order
        self.dirs: List[str] = []
        for p in self.data.get("paths", []):
            d = os.path.join(self.root, p)
            if os.path.isdir(d) and d not in self.dirs:
                self.dirs.append(d)
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    # ------------------------------------------------------------ look-ups
    def find(self, sub: str, name: str) -> Optional[str]:
        for d in self.dirs:
            path = os.path.join(d, sub, name)
            if os.path.isfile(path):
                return path
        return None

    def need(self, sub: str, name: str) -> str:
        path = self.find(sub, name)
        if path is None:
            raise ManifestError(
                f"{sub}/{name} not found under any of {self.dirs}")
        return path

    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in {self.path}; it has "
            f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                for base in (self.root, os.path.dirname(HERE)):
                    path = os.path.join(base, c["file"])
                    if os.path.isfile(path):
                        cfg = _load_json(path)
                        cfg["_file"] = path
                        return cfg
                raise ManifestError(f"config file {c['file']} not found")
        raise ManifestError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _load_json(self.need("traffic", name + ".json"))

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.data["end_to_end"] if _lists(m, cell)]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.data["per_layer"] if _lists(m, cell)]

    def layer_metric(self, name: str) -> Dict[str, Any]:
        return _load_json(self.need("layer_metrics", name + ".json"))

    def module(self, sub: str, name: str):
        """Import ``<sub>/<name>.py`` (a generator or a reader) by path."""
        path = self.need(sub, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{sub}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


def _lists(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]
