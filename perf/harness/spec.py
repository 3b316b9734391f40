"""Everything a cell is, read from data: ``BENCHMARK.json`` names the cell's
configuration file, its traffic file (``perf/traffic/<traffic>.json``) and
its metrics (``perf/metrics/<name>.json``).  A later PR adds entries and
files; nothing here is edited for a new cell."""
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _load(path):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, name, root=ROOT):
        self.root = root
        self.bench = _load(os.path.join(root, "BENCHMARK.json"))
        rows = [w for w in self.bench["workloads"] if w["name"] == name]
        if not rows:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json "
                f"({[w['name'] for w in self.bench['workloads']]})")
        self.workload = rows[0]
        self.name = name
        self.chips = int(self.workload["chips"])
        cfg_row = [c for c in self.bench["configs"]
                   if c["name"] == self.workload["config"]][0]
        self.config = _load(os.path.join(root, cfg_row["file"]))
        self.bench_dir = os.path.join(root, self.bench["paths"][0])
        self.traffic = _load(os.path.join(
            self.bench_dir, "traffic", self.workload["traffic"] + ".json"))

    def _reported_here(self, metric):
        cells = metric.get("workloads")
        return cells is None or self.name in cells

    def end_to_end(self):
        return [m for m in self.bench["end_to_end"]
                if self._reported_here(m)]

    def per_layer(self):
        return [m for m in self.bench["per_layer"] if self._reported_here(m)]

    def metric_file(self, name):
        """The metric's own file: its reader and the arithmetic's
        parameters (``perf/metrics/<name>.json``)."""
        return _load(os.path.join(self.bench_dir, "metrics", name + ".json"))
