"""Cell lookup: everything a cell names is a file found by that name.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration's ``file`` is its JSON of sizes; ``<path>/traffic/<mix>.json``
is the mix; ``<path>/entries/<entry>.py`` drives the entry point the
configuration names; ``<path>/metrics/<metric>.py`` reads one per-layer
metric; ``<path>/reference/<config>.py`` is the plain reference. Nothing
here lists names: a later PR adds a file and an entry, and edits none.
"""
import importlib.util
import json
import os


class CellError(Exception):
    """A cell, configuration, mix or metric that cannot be resolved."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name=None):
    """Import one file by path (file names may hold ``.`` and ``-``)."""
    if not os.path.isfile(path):
        raise CellError("no such file: %s" % path)
    name = name or "bench_" + os.path.basename(path)[:-3].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(root, paths, *rel):
    """First ``<root>/<path>/<rel...>`` that exists over the benchmark's
    ``paths``; raises naming every place looked in."""
    tried = []
    for p in paths:
        cand = os.path.join(root, p, *rel)
        if os.path.exists(cand):
            return cand
        tried.append(cand)
    raise CellError("not found: %s" % ", ".join(tried))


class Cell(object):
    """One resolved entry of ``workloads`` with its files loaded."""

    def __init__(self, root, workload):
        self.root = root
        bench_file = os.path.join(root, "BENCHMARK.json")
        if not os.path.isfile(bench_file):
            raise CellError("no BENCHMARK.json under %s" % root)
        self.bench = load_json(bench_file)
        self.paths = list(self.bench["paths"])
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise CellError("unknown workload %r (have %s)"
                            % (workload, ", ".join(sorted(cells))))
        self.spec = cells[workload]
        self.name = workload
        self.chips = int(self.spec["chips"])
        cfgs = {c["name"]: c for c in self.bench["configs"]}
        if self.spec["config"] not in cfgs:
            raise CellError("cell %s names unknown config %r"
                            % (workload, self.spec["config"]))
        self.config_name = self.spec["config"]
        self.config = load_json(os.path.join(root,
                                             cfgs[self.config_name]["file"]))
        self.traffic_name = self.spec["traffic"]
        self.traffic = load_json(find(root, self.paths, "traffic",
                                      self.traffic_name + ".json"))

    def _reports(self, metric):
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self):
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    @property
    def per_layer(self):
        """Per-layer metrics due in this cell: those that list it, and
        those with no list whose ``moves`` this cell reports."""
        e2e = {m["name"] for m in self.end_to_end}
        return [m for m in self.bench["per_layer"]
                if self._reports(m) and m["moves"] in e2e]

    def module(self, kind, name):
        return load_module(find(self.root, self.paths, kind, name + ".py"))

    def builder(self):
        """The configuration's builder: ``file`` relative to the root."""
        return load_module(os.path.join(self.root, self.config["builder"]))

    def reference(self):
        return self.module("reference", self.config_name)

    def entry(self):
        return self.module("entries", self.config["entry"])

    def limits(self):
        """The cell's limits on the numbers compared: the configuration's
        ``limits`` for this mix, else its ``default``."""
        lim = self.config.get("limits", {})
        return dict(lim.get(self.traffic_name, lim.get("default", {})))
