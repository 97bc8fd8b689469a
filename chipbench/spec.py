"""``BENCHMARK.json`` and the files it names: the harness is driven by data.

A cell is one entry of ``workloads``. Everything that belongs to one
configuration, one traffic mix or one metric sits in a file of its own,
found by name under the directories ``paths`` lists (searched in order,
the harness's own directory last), so a later PR adds files and entries
and edits nothing:

- ``configs/<config>.json`` -- the sizes as run (the entry's ``file``);
- ``traffic/<traffic>.json`` -- every parameter of the mix, and which
  driver runs it (``"driver": "serve"`` or ``"train"``);
- ``metrics/<metric>.json`` -- ``{"reader": <name>, "args": {...}}``;
- ``readers/<reader>.py`` -- ``read(evidence, **args)`` -> number or None
  (``evidence``: ``chipbench/evidence.py``);
- ``families/<family>.py``, ``reference/<family>.py``,
  ``drivers/<driver>.py`` -- code, loaded by file path.
"""
import importlib.util
import json
import os

__all__ = ["Spec", "HERE"]

HERE = os.path.dirname(os.path.abspath(__file__))
DEFAULT_BENCHMARK = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")


class Spec:
    def __init__(self, benchmark_json: str = None):
        self.path = os.path.abspath(benchmark_json or DEFAULT_BENCHMARK)
        self.root = os.path.dirname(self.path)
        with open(self.path) as fh:
            self.data = json.load(fh)
        self.search = [os.path.join(self.root, p)
                       for p in self.data["paths"]]
        if HERE not in [os.path.abspath(p) for p in self.search]:
            self.search.append(HERE)
        self._modules = {}

    # ------------------------------------------------------------ lookup
    def find(self, kind: str, filename: str) -> str:
        for base in self.search:
            path = os.path.join(base, kind, filename)
            if os.path.isfile(path):
                return path
        raise FileNotFoundError(
            f"no {kind}/{filename} under {self.search}")

    def load_json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name + ".json")) as fh:
            return json.load(fh)

    def load_module(self, kind: str, name: str):
        """``<kind>/<name>.py`` as a module, loaded by path so that a
        directory added beside the harness needs no package edit."""
        key = (kind, name)
        if key not in self._modules:
            path = self.find(kind, name + ".py")
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{kind}_{name}", path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[key] = module
        return self._modules[key]

    # ------------------------------------------------------------- cells
    def cell(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in {self.path}; known: "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as fh:
                    return json.load(fh)
        raise KeyError(f"no config {name!r} in {self.path}")

    def traffic(self, name: str) -> dict:
        return self.load_json("traffic", name)

    def metrics(self, group: str, cell_name: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.data[group]
                if "workloads" not in m or cell_name in m["workloads"]]

    def read_metrics(self, group: str, cell_name: str, evidence) -> dict:
        """Run each metric's reader over the run's evidence. A reader
        that finds nothing to read returns None and the metric is left
        out of the line."""
        out = {}
        for entry in self.metrics(group, cell_name):
            definition = self.load_json("metrics", entry["name"])
            reader = self.load_module("readers", definition["reader"])
            value = reader.read(evidence, **definition.get("args", {}))
            if value is not None:
                out[entry["name"]] = {"value": float(value),
                                      "unit": entry["unit"]}
        return out
