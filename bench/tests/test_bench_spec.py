"""BENCHMARK.json and every data file of the benchmark parse, keep the
contract's shapes and characters, and name files that exist."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench.lib import registry  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP = {"command", "paths", "run_seconds", "configs", "workloads",
       "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        text = fh.read()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level(spec):
    assert set(spec) == TOP
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    cmd = spec["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert os.path.exists(os.path.join(ROOT, cmd[1]))
    assert any(cmd[1].startswith(p + "/") for p in spec["paths"])
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51


def test_names_unique_and_well_formed(spec):
    for key, keys in (("configs", CONFIG_KEYS), ("workloads", CELL_KEYS)):
        names = [e["name"] for e in spec[key]]
        assert len(set(names)) == len(names)
        for e in spec[key]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and _line(e["why"])
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")


def test_configs(spec):
    used = {c["config"] for c in spec["workloads"]}
    assert 1 <= len(spec["configs"]) <= 24
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c in spec["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert _line(c["source"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as fh:
            body = json.load(fh)
        assert body["name"] == c["name"]
        assert set(c["reduced"]) == set(body["reduced"])
        assert body["control"] in body["guarantees"]


def test_cells(spec):
    reg = registry.Registry(ROOT)
    cells = spec["workloads"]
    assert 1 <= len(cells) <= 24
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 2)
    for c in cells:
        assert c["chips"] in (1, 4) and NAME.match(c["traffic"])
        traffic = reg.traffic(c["traffic"])
        assert hasattr(reg.call(traffic["call"]), "Driver")
        e2e = [m["name"] for m in reg.metrics("end_to_end", c["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert reg.metrics("per_layer", c["name"])


def test_metrics(spec):
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    cells = {c["name"] for c in spec["workloads"]}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == LAYER_KEYS
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(registry.Registry(ROOT).metric_reader(m["name"]).read)
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_bench_file_names():
    for dirpath, dirnames, files in os.walk(os.path.join(ROOT, "bench")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert PATH.match(rel), rel


@pytest.mark.parametrize("kind", ["configs", "traffic"])
def test_data_files_parse(kind):
    d = os.path.join(ROOT, "bench", kind)
    names = sorted(os.listdir(d))
    assert names
    for n in names:
        assert n.endswith(".json")
        with open(os.path.join(d, n)) as fh:
            json.load(fh)


def test_added_files_are_found(tmp_path):
    """A configuration, a traffic mix, a call, a cell and a per-layer
    metric are added by adding files and BENCHMARK.json entries only."""
    import shutil
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "calls", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", d), bench / d)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    cfg = json.loads((bench / "configs" / "trcd-polybench.json").read_text())
    cfg["name"] = "trcd-other"
    (bench / "configs" / "trcd-other.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-mix.json").write_text(json.dumps(
        {"call": "tiny_call", "max_accesses": 100}))
    (bench / "calls" / "tiny_call.py").write_text(
        "SPAN = 'tiny'\nclass Driver:\n    pass\n")
    (bench / "metrics" / "tiny_metric.py").write_text(
        "def read(ctx):\n    return ctx.get('requests')\n")
    spec["configs"].append({"name": "trcd-other", "source": "x",
                            "file": "bench/configs/trcd-other.json",
                            "reduced": [], "why": "throwaway"})
    spec["workloads"].append({"name": "tiny.cell", "config": "trcd-other",
                              "traffic": "tiny-mix", "chips": 1,
                              "why": "throwaway"})
    spec["per_layer"].append({"name": "tiny_metric", "unit": "count",
                              "better": "lower", "source": "host_clock",
                              "layer": "tiny", "moves": "emu_req_per_s",
                              "workloads": ["tiny.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    reg = registry.Registry(str(tmp_path), str(bench))
    cell = reg.workload("tiny.cell")
    assert reg.config(cell["config"])["name"] == "trcd-other"
    assert reg.call(reg.traffic(cell["traffic"])["call"]).SPAN == "tiny"
    names = [m["name"] for m in reg.metrics("per_layer", "tiny.cell")]
    assert "tiny_metric" in names and "device_idle_share" in names
    assert "tiny_metric" not in [m["name"] for m in
                                 reg.metrics("per_layer", "trcd.sweep")]
    assert reg.metric_reader("tiny_metric").read({"requests": 7}) == 7
