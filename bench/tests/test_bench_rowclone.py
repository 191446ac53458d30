"""The RowClone cell (configuration ``rowclone-sec7``) on the CPU at a
tiny size: the configuration states the program's systems; the plain
reference lays out the program's traces and emulates every point of
``RowClone.campaign`` bit for bit, on both systems and all four request
kinds; its control differs where RowClone ops run; and the harness
finds the cell, its call and its metrics by name, reads `correct` on a
sound run and not correct under the control or a broken timed path,
and reports the new per-layer metrics in a traced run, equal to their
formulas over the program's span log."""
import contextlib
import io
import itertools
import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench import control, run  # noqa: E402
from bench.lib import program_spans, reference_rowclone as ref, registry, sut  # noqa: E402
from bench.tests.test_bench_harness import PINNED, _env, _fault  # noqa: E402,F401

SEED = 2 ** 31 + 13
CELL = "tiny.rowclone"
TINY = {"call": "rowclone", "sizes": [8192, 16384],
        "workloads": ["copy", "init"], "settings": ["noflush", "clflush"],
        "systems": ["easydram_ts", "pidram_nots"],
        "alloc_base_rows": {"copy": [0, 16384], "init": [0, 262144]}}
NEW_METRICS = ("rc_request_share", "trace_build_ms_per_call")

with open(os.path.join(ROOT, "bench", "configs", "rowclone-sec7.json")) as _fh:
    CFG = json.load(_fh)


@pytest.fixture(scope="module")
def reg(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("checkout")
    bench = tmp / "bench"
    for d in ("configs", "traffic", "calls", "metrics"):
        shutil.copytree(os.path.join(ROOT, "bench", d), bench / d)
    (bench / "traffic" / f"{CELL}.json").write_text(json.dumps(TINY))
    spec = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    spec["workloads"].append({"name": CELL, "config": "rowclone-sec7",
                              "traffic": CELL, "chips": 1, "why": "tiny"})
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            m["workloads"].append(CELL)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return registry.Registry(str(tmp), str(bench))


@pytest.fixture(scope="module")
def opened(reg):
    """The tiny cell set up once, warm-up included."""
    with pytest.MonkeyPatch.context() as mp:
        for k in PINNED:
            mp.setenv(k, os.environ.get(k, "unset-by-test"))
        yield run.open_cell(reg, CELL, SEED, require_tpu=False, cache=False)


@pytest.fixture
def reuse(opened, monkeypatch):
    monkeypatch.setattr(run, "open_cell", lambda reg, workload, *a, **k: opened)


@pytest.fixture(scope="module")
def points(opened):
    driver = opened[1]
    return driver.call(driver.inputs(0))


def _run(reg, capsys, trace=0, before_window=None):
    rc = run.main(["--workload", CELL, "--seed", str(SEED), "--seconds",
                   "0.05", "--trace", str(trace)], reg=reg, require_tpu=False,
                  cache=False, before_window=before_window)
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1]), out.err


# ------------------------------------------------------ the configuration

@pytest.mark.parametrize("name,preset", [("easydram_ts", "JETSON_NANO"),
                                         ("pidram_nots", "PIDRAM_LIKE")])
def test_systems_are_the_programs_presets(name, preset):
    from repro.core import timescale
    entry = CFG["systems"][name]
    assert entry["preset"] == preset
    want = getattr(timescale, preset)
    for k, v in entry["system"].items():
        assert getattr(want, k) == v, k
    sysc = sut.system(dict(CFG, system=entry["system"]))
    assert sysc == want
    with open(os.path.join(ROOT, "bench", "configs", "trcd-polybench.json")) as fh:
        trcd = json.load(fh)
    for k in ("timing", "geometry"):
        assert CFG[k] == trcd[k]
    assert {k: CFG["device_model"][k] for k in trcd["device_model"]} == \
        trcd["device_model"]


def test_harness_finds_the_cell_by_name():
    reg = registry.Registry(ROOT)
    cell = reg.workload("rowclone.sweep")
    assert cell["chips"] == 1
    assert reg.config(cell["config"])["name"] == "rowclone-sec7"
    traffic = reg.traffic(cell["traffic"])
    assert reg.call(traffic["call"]).SPAN == "campaign.run"
    names = [m["name"] for m in reg.metrics("per_layer", "rowclone.sweep")]
    assert set(NEW_METRICS) <= set(names)
    for other in ("trcd.sweep", "trcd.stream", "trcd.sweep-x4"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in reg.metrics("per_layer", other)}


# ------------------------------------------------------ the reference

@pytest.mark.parametrize("fail_rate", [0.02, 0.99])
@pytest.mark.parametrize("workload,arm,setting", list(itertools.product(
    ("copy", "init"), ("cpu", "rowclone"), ("noflush", "clflush"))))
def test_reference_lays_out_the_programs_traces(workload, arm, setting,
                                                fail_rate):
    """At the configuration's clone failure rate, and at one high
    enough that rows fall back to the CPU."""
    from repro.core import traces
    from repro.core.dram import Geometry
    from repro.core.profiling import DeviceModel
    cfg = dict(CFG, device_model=dict(CFG["device_model"],
                                      clone_fail_rate=fail_rate))
    dm = cfg["device_model"]
    device = DeviceModel(Geometry(**cfg["geometry"]), seed=dm["seed"],
                         weak_target=dm["weak_target"],
                         clone_fail_rate=fail_rate)
    gen = {"copy": traces.copy_workload, "init": traces.init_workload}
    rng = np.random.default_rng(fail_rate > 0.5)
    fallbacks = 0
    for nb, cld in ((16384, 4), (65536, 20)):
        base = int(rng.integers(*TINY["alloc_base_rows"][workload]))
        tr, meta = gen[workload](nb, device.geo, arm, device, setting,
                                 alloc_base_row=base, cpu_line_delta=cld)
        mine = ref.build(workload, arm, setting, nb, cld, base, cfg,
                         ref.Device(cfg))
        for f in ("kind", "bank", "row", "delta", "dep"):
            assert np.array_equal(mine[f], getattr(tr, f)), f
        fallbacks += meta["fallback_rows"]
    if arm == "rowclone":
        assert (fallbacks > 0) == (fail_rate > 0.5)


@pytest.mark.parametrize("system", TINY["systems"])
def test_every_point_equals_the_reference(opened, points, system):
    driver = opened[1]
    mine = [p for p in points if p.key[3] == system]
    assert len(mine) == 16
    kinds = set()
    for p in mine:
        want = driver.reference(p)
        assert sut.differs(p.result, want) == [], p.key
        assert p.result["served"] == p.n_real
        _, workload, setting, _, arm = p.key
        kinds |= set(ref.build(workload, arm, setting, p.ref_args["n_bytes"],
                               4, p.ref_args["base"], CFG,
                               ref.Device(CFG))["kind"].tolist())
    assert kinds == {ref.READ, ref.WRITE, ref.RC_COPY, ref.RC_INIT}


@pytest.mark.parametrize("system", TINY["systems"])
def test_control_differs_where_rowclone_runs(opened, points, system):
    driver = opened[1]
    for p in points:
        if p.key[3] != system:
            continue
        broken = driver.reference(p, "rc_full_sequence")
        assert bool(sut.differs(p.result, broken)) == (p.key[4] == "rowclone")


def test_nots_reference_charges_the_software_controller():
    """A software controller slower than PiDRAM's (the Jetson preset's
    400 + 120 cycles a decision at 100 MHz, seen by the 50 MHz core)
    in mode nots: 260 cycles a decision, and the requests that arrive
    meanwhile are visible to it."""
    cfg = dict(CFG, systems={"slow": dict(CFG["systems"]["easydram_ts"],
                                          mode="nots")})
    sysr = ref.System(cfg, "slow")
    assert (sysr.mc_lat, sysr.mc_issue, sysr.slack) == (0, 260, 260)
    from repro.core import emulator
    from repro.core.emulator import Trace
    tr = ref.build("copy", "cpu", "noflush", 8192, 4, 77, cfg, ref.Device(cfg))
    sysc = sut.system(dict(cfg, system=cfg["systems"]["slow"]["system"]))
    got = sut.keep(emulator.run(Trace.of(**tr), sysc, "nots"), len(tr["kind"]))
    assert sut.differs(got, ref.emulate(tr, sysr)) == []


# ------------------------------------------------------ the harness

def test_sound_run_is_correct(reg, reuse, capsys):
    res, _ = _run(reg, capsys)
    assert res["correct"] is True and res["failed"] == 0
    assert res["checks"]["compared_points"]["value"] == 32
    assert set(res["metrics"]) == {"emu_req_per_s", "point_latency_p95_s",
                                   "setup_s"}


def test_control_is_not_correct(reg, reuse):
    r = control.readings(reg, CELL, SEED, 0.05, require_tpu=False,
                         cache=False)
    assert r["control"] == "rc_full_sequence"
    assert r["mismatched_points"] == 0
    assert r["control_mismatched_points"] == 16   # the rowclone arms


@pytest.mark.parametrize("fault", ["half", "altered", "unchanged"])
def test_broken_path_is_not_correct(reg, reuse, fault, capsys, monkeypatch):
    from repro.core import emulator
    res, _ = _run(reg, capsys, before_window=lambda: monkeypatch.setattr(
        emulator._CachedRunner, "__call__", _fault(fault)))
    assert res["correct"] is False


@pytest.fixture(scope="module")
def traced(reg, opened):
    """One ``--trace 1`` run: its result line, calls and span records."""
    from repro.core import spans
    with pytest.MonkeyPatch.context() as mp:
        for k in PINNED:
            mp.setenv(k, os.environ.get(k, "unset-by-test"))
        mp.setenv("LIBTPU_INIT_ARGS", os.environ.get("LIBTPU_INIT_ARGS", ""))
        mp.setattr(run, "open_cell", lambda reg, workload, *a, **k: opened)
        spans.clear()
        so, se = io.StringIO(), io.StringIO()
        with contextlib.redirect_stdout(so), contextlib.redirect_stderr(se):
            rc = run.main(["--workload", CELL, "--seed", str(SEED),
                           "--seconds", "0.05", "--trace", "1"], reg=reg,
                          require_tpu=False, cache=False)
        assert rc == 0
        out = {"result": json.loads(so.getvalue().splitlines()[-1]),
               "calls": int(re.search(r"calls=(\d+) ", se.getvalue()).group(1)),
               "records": spans.records()}
        spans.clear()
    return out


def test_traced_run_reports_the_new_metrics(traced, opened):
    """Each call's dispatches count the writes and RowClone ops of the
    call's traces; the metrics are their formulas over the span log."""
    t, driver = traced, opened[1]
    m = t["result"]["metrics"]
    assert set(NEW_METRICS) <= set(m)
    recs = t["records"]
    disp = [r.counts for r in recs if r.name == "emu.dispatch"]
    builds = [r for r in recs if r.name == "tech.traces"]
    assert len(disp) == 10 * t["calls"] and len(builds) == t["calls"]
    c = driver.study.campaign(*driver.grid, driver.systems,
                              alloc_base_rows=driver.inputs(0))
    kinds = np.concatenate([p.trace.kind for p in c.points])
    per_call = {"requests": len(kinds),
                "write_requests": int(np.count_nonzero(kinds == ref.WRITE)),
                "rc_requests": int(np.count_nonzero(
                    np.isin(kinds, (ref.RC_COPY, ref.RC_INIT))))}
    for k, v in per_call.items():
        assert sum(d[k] for d in disp) == v * t["calls"], k
    assert m["rc_request_share"]["value"] == pytest.approx(
        100 * per_call["rc_requests"] / per_call["requests"])
    assert all(b.counts == {"points": 32, "fallback_rows": 0} for b in builds)
    assert m["trace_build_ms_per_call"]["value"] == pytest.approx(
        sum(b.end_ns - b.start_ns for b in builds) / 1e6 / t["calls"])
    assert m["trace_build_ms_per_call"]["unit"] == "ms/call"


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_without_the_programs_record(reg, traced, name,
                                                          monkeypatch):
    """A program without the count or the span (a parent checkout),
    or without a span log at all, reads nothing, and nothing raises."""
    reader = reg.metric_reader(name)
    older = [r._replace(counts={k: v for k, v in r.counts.items()
                                if k != "rc_requests"})
             for r in traced["records"] if r.name != "tech.traces"]
    monkeypatch.setattr(program_spans, "records", lambda: older)
    assert reader.read({}) is None
    monkeypatch.setattr(program_spans, "records", lambda: None)
    assert reader.read({}) is None
