"""Finds everything a cell is made of by the names in BENCHMARK.json.

A cell (one entry of ``workloads``) names a configuration and a traffic mix.
Each is a data file; drivers, references and per-layer readers are Python
files found by the name a data file gives. Nothing here knows a cell, a
configuration or a metric by name.

Search order for every file: the spec root (``--spec-root``, used by the tests
to bring their own tiny cells) and then the benchmark's own directory.
"""
import importlib.util
import json
import os

CELLS_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(os.path.dirname(CELLS_DIR))


class SpecError(Exception):
    pass


def _load_json(path):
    with open(path) as f:
        return json.load(f)


class Spec:
    """BENCHMARK.json plus the directories its names are looked up in."""

    def __init__(self, spec_root=None):
        self.root = os.path.abspath(spec_root) if spec_root else REPO_ROOT
        path = os.path.join(self.root, "BENCHMARK.json")
        if not os.path.exists(path):
            raise SpecError("no BENCHMARK.json in %s" % self.root)
        self.benchmark = _load_json(path)
        own = os.path.join(self.root, self.benchmark["paths"][0])
        self.dirs = [own] if own == CELLS_DIR else [own, CELLS_DIR]

    def find(self, kind, name, ext):
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.exists(path):
                return path
        raise SpecError("no %s/%s%s under %s" % (kind, name, ext, self.dirs))

    def cell(self, name):
        for w in self.benchmark["workloads"]:
            if w["name"] == name:
                return w
        raise SpecError("BENCHMARK.json has no workload %r" % name)

    def config(self, name):
        for c in self.benchmark["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise SpecError("BENCHMARK.json has no config %r" % name)

    def traffic(self, name):
        return _load_json(self.find("traffic", name, ".json"))

    def metrics_for(self, cell_name, group):
        """The metrics of ``group`` (end_to_end | per_layer) that list this
        cell, or list no cells at all."""
        out = []
        for m in self.benchmark[group]:
            cells = m.get("workloads")
            if cells is None or cell_name in cells:
                out.append(m)
        return out

    def layer_metric(self, name):
        """``a.b`` reads ``a.b.json`` where there is one, else ``a.json``: a
        quantity split by the end-to-end metric it moves shares one file."""
        try:
            return _load_json(self.find("layer_metrics", name, ".json"))
        except SpecError:
            if "." not in name:
                raise
            return self.layer_metric(name.rsplit(".", 1)[0])

    def module(self, kind, name):
        path = self.find(kind, name, ".py")
        modname = "cells_%s_%s" % (kind, name)
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
