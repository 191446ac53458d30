"""The harness on the CPU at a tiny size: it refuses to measure without
a TPU; a sound run reads `correct`; the control (the reference with one
guarantee dropped) reads not correct; and a run with the timed path
broken underneath reads not correct, once for each fault a cell can
have. The tiny cells are files in a temporary checkout, added beside
copies of the real ones. Each tiny cell is set up (and compiled) once
for the module; every case then drives its window and its check."""
import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench import control, run  # noqa: E402
from bench.lib import registry  # noqa: E402

SEED = 2 ** 31 + 11
TINY = {
    "tiny.sweep": {"call": "campaign", "max_accesses": 300, "variants": 4,
                   "kernels": [0]},
    "tiny.stream": {"call": "stream", "max_accesses": 300, "chunk": 64,
                    "kernels": [0, 20]},
}
# the faults each cell can have; "exchange" stands for the 4-chip cell,
# whose batch the tiny sweep's 4 variants split in four like its shards
FAULTS = {"tiny.sweep": ("half", "exchange", "altered", "unchanged"),
          "tiny.stream": ("half", "altered", "unchanged")}
PINNED = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_PROCESS_BOUNDS",
          "TPU_PROCESS_BOUNDS", "TPU_LOG_DIR")


@pytest.fixture(autouse=True)
def _env(monkeypatch):
    """The harness pins one chip through the environment; keep that
    out of the rest of the test process."""
    for k in PINNED:
        monkeypatch.setenv(k, os.environ.get(k, "unset-by-test"))


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("checkout")
    bench = tmp / "bench"
    for d in ("configs", "traffic", "calls", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", d), bench / d)
    # a device with most rows weak, so that a few hundred requests are
    # sure to activate weak rows and the control has something to break
    trcd = json.loads((bench / "configs" / "trcd-polybench.json").read_text())
    trcd["name"] = "tiny-trcd"
    trcd["device_model"]["weak_target"] = 0.9
    (bench / "configs" / "tiny-trcd.json").write_text(json.dumps(trcd))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["configs"].append({"name": "tiny-trcd", "source": "x", "why": "x",
                            "file": "bench/configs/tiny-trcd.json",
                            "reduced": []})
    for name, traffic in TINY.items():
        (bench / "traffic" / f"{name}.json").write_text(json.dumps(traffic))
        spec["workloads"].append({"name": name, "config": "tiny-trcd",
                                  "traffic": name, "chips": 1, "why": "tiny"})
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return registry.Registry(str(tmp), str(bench))


@pytest.fixture(scope="module")
def opened(reg):
    """Every tiny cell set up once, warm-up included."""
    with pytest.MonkeyPatch.context() as mp:
        for k in PINNED:
            mp.setenv(k, os.environ.get(k, "unset-by-test"))
        yield {c: run.open_cell(reg, c, SEED, require_tpu=False, cache=False)
               for c in TINY}


@pytest.fixture
def reuse(opened, monkeypatch):
    """``run.open_cell`` hands back the module's set-up cell."""
    monkeypatch.setattr(run, "open_cell",
                        lambda reg, workload, *a, **k: opened[workload])


def _run(reg, cell, capsys, before_window=None):
    rc = run.main(["--workload", cell, "--seed", str(SEED),
                   "--seconds", "0.05", "--trace", "0"], reg=reg,
                  require_tpu=False, cache=False, before_window=before_window)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def test_refuses_without_a_tpu(capsys):
    rc = run.main(["--workload", "trcd.sweep", "--seed", "1", "--seconds", "1"],
                  cache=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(reg, reuse, cell, capsys):
    res = _run(reg, cell, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["compared_points"]["value"] >= 1
    assert set(res["metrics"]) == {"emu_req_per_s", "point_latency_p95_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_is_not_correct(reg, reuse, cell):
    r = control.readings(reg, cell, SEED, 0.05, require_tpu=False,
                         cache=False)
    assert r["mismatched_points"] == 0
    assert r["control_mismatched_points"] > 0


def _batch(args):
    import jax
    return next(a for a in jax.tree_util.tree_leaves(args[:2])
                if getattr(a, "ndim", 0) >= 1).shape[0]


def _fault(kind):
    """A replacement for every executable call that post-processes its
    outputs as the fault."""
    import jax
    from repro.core import emulator
    orig = emulator._CachedRunner.__call__

    def call(self, *args):
        out = orig(self, *args)
        bb = _batch(args)

        def rows(leaf, keep):
            if getattr(leaf, "ndim", 0) < 1 or leaf.shape[0] != bb:
                return leaf
            idx = np.arange(bb) % keep
            return leaf[idx]

        if kind == "half":      # the second half of the batch is left out
            return jax.tree_util.tree_map(lambda x: rows(x, bb - bb // 2), out)
        if kind == "exchange":  # the other chips' shards never arrive
            return jax.tree_util.tree_map(lambda x: rows(x, max(bb // 4, 1)), out)
        if kind == "altered":   # an answer altered where it is produced
            if isinstance(out, dict):
                return dict(out, t_resp=out["t_resp"].at[0, 0].add(1))
            ss, (k, ti, tr, ptr) = out
            return ss, (k, ti, tr.at[0, args[1].shape[1] - 1].add(1), ptr)
        # "unchanged": every step returns the state it was given
        if isinstance(out, dict):
            zero = {f: v * 0 for f, v in out.items()}
            return dict(zero, t_resp=zero["t_resp"] + emulator.BIG)
        ss = args[0]
        return ss, (ss.kind, ss.emu.t_issue, ss.emu.t_resp, ss.emu.ptr)
    return call


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(FAULTS)
                                        for f in FAULTS[c]])
def test_broken_path_is_not_correct(reg, reuse, cell, fault, capsys,
                                    monkeypatch):
    """Set-up runs sound; the window's calls run broken."""
    from repro.core import emulator
    res = _run(reg, cell, capsys, before_window=lambda: monkeypatch.setattr(
        emulator._CachedRunner, "__call__", _fault(fault)))
    assert res["correct"] is False


def test_one_chip_cells_see_one_chip(monkeypatch):
    """A one-chip cell pins one chip of the host before JAX starts; a
    four-chip cell leaves the host's chips as they are."""
    for k in PINNED:
        monkeypatch.delenv(k, raising=False)
    run.pin_devices(4)
    assert "TPU_VISIBLE_CHIPS" not in os.environ
    run.pin_devices(1)
    assert os.environ["TPU_VISIBLE_CHIPS"] == "0"
    assert os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
