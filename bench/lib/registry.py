"""Find everything by name: cells in ``BENCHMARK.json``, configurations
in ``configs/<name>.json``, traffic mixes in ``traffic/<name>.json``,
the call each mix drives in ``calls/<kind>.py`` and per-layer metric
readers in ``metrics/<name>.py``. Adding any of these is adding files
(and ``BENCHMARK.json`` entries); nothing here changes."""
from __future__ import annotations

import importlib.util
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)


class Registry:
    """The benchmark as ``BENCHMARK.json`` describes it, rooted at a
    checkout (``repo``) whose ``bench/`` holds the data files."""

    def __init__(self, repo: str = REPO, bench: str = BENCH):
        self.repo, self.bench = repo, bench
        with open(os.path.join(repo, "BENCHMARK.json")) as fh:
            self.spec = json.load(fh)

    def _one(self, key: str, name: str) -> dict:
        hits = [e for e in self.spec[key] if e["name"] == name]
        if len(hits) != 1:
            raise KeyError(f"BENCHMARK.json has no {key} entry {name!r}")
        return hits[0]

    def workload(self, name: str) -> dict:
        return self._one("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._one("configs", name)
        with open(os.path.join(self.repo, entry["file"])) as fh:
            return json.load(fh)

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.bench, "traffic", f"{name}.json"))

    def call(self, kind: str):
        return _load_module(os.path.join(self.bench, "calls", f"{kind}.py"),
                            f"bench_call_{kind}")

    def metric_reader(self, name: str):
        return _load_module(os.path.join(self.bench, "metrics", f"{name}.py"),
                            f"bench_metric_{name}")

    def metrics(self, section: str, workload: str) -> list:
        """The ``end_to_end`` or ``per_layer`` entries a cell reports:
        those that list it, and those that list no cells."""
        return [m for m in self.spec[section]
                if workload in m.get("workloads", [workload])]


def _load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _load_module(path: str, modname: str):
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
