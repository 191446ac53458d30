"""The metrics read from the program's own spans, on the CPU at a tiny
size: a ``--trace 1`` run of each tiny cell of the harness tests
reports them, equal to their formulas over the span log, whose counts
are the cell's slot budgets and padded batches; and each reader reports
nothing where the log cannot be trusted or the program has no spans."""
import contextlib
import io
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench import run  # noqa: E402
# the harness tests' tiny cells and their fixtures, set up once here too
from bench.tests.test_bench_harness import (  # noqa: E402,F401
    PINNED, SEED, TINY, _env, opened, reg)

SPAN_METRICS = ("scan_slots_per_kreq", "lane_fill", "device_us_per_scan_slot",
                "host_ms_per_dispatch")
WAITING = ("emu.call", "emu.wait", "emu.prefetch_wait")


def _host_events(path):
    """Names of the program's and the harness's events on the host
    plane of a profile."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [e.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("emu.") or e.name == "traffic"
            or e.name in ("campaign.run", "run_stream_many")]


@pytest.fixture(scope="module")
def traced(reg, opened):
    """One ``--trace 1`` run of each tiny cell: its result line, its
    stderr, the program's span records and the host-plane events of
    its profile."""
    from bench.lib import xplane
    from repro.core import spans
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        for k in PINNED:
            mp.setenv(k, os.environ.get(k, "unset-by-test"))
        mp.setenv("LIBTPU_INIT_ARGS", os.environ.get("LIBTPU_INIT_ARGS", ""))
        mp.setattr(run, "open_cell",
                   lambda reg, workload, *a, **k: opened[workload])
        host = []
        extract = xplane.extract

        def keep_host(path, names):
            host[:] = _host_events(path)
            return extract(path, names)

        mp.setattr(xplane, "extract", keep_host)
        for cell in TINY:
            spans.clear()
            so, se = io.StringIO(), io.StringIO()
            with contextlib.redirect_stdout(so), \
                    contextlib.redirect_stderr(se):
                rc = run.main(["--workload", cell, "--seed", str(SEED),
                               "--seconds", "0.05", "--trace", "1"], reg=reg,
                              require_tpu=False, cache=False)
            assert rc == 0
            line = re.search(r"calls=(\d+) .* requests=(\d+) ", se.getvalue())
            out[cell] = {"result": json.loads(so.getvalue().splitlines()[-1]),
                         "calls": int(line.group(1)),
                         "requests": int(line.group(2)),
                         "records": spans.records(), "host": list(host)}
        spans.clear()
    return out


def _dispatches(t):
    return [r.counts for r in t["records"] if r.name == "emu.dispatch"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_traced_run_reads_the_program_spans(traced, cell):
    t = traced[cell]
    m = {k: v["value"] for k, v in t["result"]["metrics"].items()}
    assert t["result"]["correct"] is True
    # no TPU plane on the CPU, so no device time to divide
    assert set(SPAN_METRICS) - set(m) == {"device_us_per_scan_slot"}
    disp = _dispatches(t)
    assert sum(c["requests"] for c in disp) == t["requests"]
    assert sum(r.counts["requests"] for r in t["records"]
               if r.name == "emu.call") == t["requests"]
    slots = sum(c["slots"] for c in disp)
    lane_slots = sum(c["slots"] * c["lanes"] for c in disp)
    assert m["scan_slots_per_kreq"] == pytest.approx(
        1000 * slots / t["requests"])
    assert m["lane_fill"] == pytest.approx(
        100 * 2 * t["requests"] / lane_slots)
    host = sum(r.end_ns - r.start_ns for r in t["records"]
               if r.name not in WAITING)
    assert m["host_ms_per_dispatch"] == pytest.approx(host / 1e6 / len(disp))


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_spans_one_call_id_per_call(traced, cell):
    """Every call of the window is one call id, with one ``emu.call``
    at its top, children inside their parents, spans on more than one
    thread (the executor's workers, or the prefetch thread), and no
    host-time span inside another (host_ms_per_dispatch sums them)."""
    t = traced[cell]
    calls = {}
    for r in t["records"]:
        calls.setdefault(r.call, []).append(r)
    assert len(calls) == t["calls"]
    for rs in calls.values():
        assert [r.name for r in rs if r.parent is None] == ["emu.call"]
        by_id = {r.id: r for r in rs}
        for r in rs:
            if r.parent is not None:
                p = by_id[r.parent]
                assert p.start_ns <= r.start_ns <= r.end_ns <= p.end_ns
                assert p.name in WAITING
        assert len({r.thread for r in rs}) >= 2


@pytest.mark.parametrize("cell", sorted(TINY))
def test_dispatch_counts_are_the_budgets(traced, opened, cell):
    """Each call's dispatches run the program's slot budgets at its
    padded batches, and count the requests each newly emulates."""
    from repro.core import emulator
    t, driver = traced[cell], opened[cell][1]
    lens = [len(x["kind"]) for _, x in driver.inputs(0)]
    got = sorted(tuple(c[k] for k in ("slots", "lanes", "requests",
                                      "shards"))
                 for c in _dispatches(t))
    if cell == "tiny.sweep":
        groups = {}
        for n in lens:
            groups.setdefault(emulator._bucket(n), []).append(n)
        want = [(emulator.slot_budget(b, max(ns)),
                 emulator._batch_bucket(len(ns)), sum(ns), 1)
                for b, ns in groups.items()] * 2   # base and reduced arm
    else:
        chunk = TINY[cell]["chunk"]
        want = [(emulator.stream_slot_budget(chunk, driver.sys),
                 emulator._batch_bucket(len(lens)),
                 sum(min(max(n - i * chunk, 0), chunk) for n in lens), 1)
                for i in range(-(-max(lens) // chunk))]
    assert got == sorted(want * t["calls"])


@pytest.mark.parametrize("cell", sorted(TINY))
def test_program_spans_on_the_host_plane(traced, cell):
    """Each span the program recorded is one host-plane event of its
    name; no program span takes a harness span's name."""
    t = traced[cell]
    names = sorted(r.name for r in t["records"])
    assert all(n.startswith("emu.") for n in names)
    assert sorted(n for n in t["host"] if n.startswith("emu.")) == names
    assert "traffic" in t["host"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_device_time_per_slot(traced, reg, cell, monkeypatch):
    """With device time to divide: busy time per slot a device ran, and
    on one chip times slots per kilo-request it is the device time per
    request."""
    from repro.core import spans
    t = traced[cell]
    monkeypatch.setattr(spans, "records", lambda: t["records"])
    ctx = {"trace": {"busy_ns": {"TPU:0": 3.0e6}, "idle_share": 0.5},
           "requests": t["requests"], "compiles": 0}
    per_slot = reg.metric_reader("device_us_per_scan_slot").read(ctx)
    assert per_slot == pytest.approx(3.0e3 / sum(
        c["slots"] * c["shards"] for c in _dispatches(t)))
    per_req = reg.metric_reader("scan_device_us_per_req").read(ctx)
    kreq = reg.metric_reader("scan_slots_per_kreq").read(ctx)
    assert per_slot * kreq / 1000 == pytest.approx(per_req)


@pytest.mark.parametrize("name", SPAN_METRICS)
@pytest.mark.parametrize("state", ["empty", "missing", "dropped",
                                   "counted_twice"])
def test_span_readers_report_nothing_without_spans(traced, reg, name, state,
                                                   monkeypatch):
    """An empty log, a program with no span module (the benchmark run
    against an older checkout), a log that dropped spans at its cap, or
    one whose dispatches count more requests than its calls returned (a
    retried task's dispatch recorded twice) reads as no value, not an
    error or a number from part of the window."""
    import repro.core
    from repro.core import spans
    spans.clear()
    recs = traced["tiny.sweep"]["records"]
    if state == "missing":
        monkeypatch.delattr(repro.core, "spans")
        monkeypatch.setitem(sys.modules, "repro.core.spans", None)
    elif state == "dropped":
        monkeypatch.setattr(spans, "records", lambda: recs)
        monkeypatch.setattr(spans, "dropped", lambda: 1)
    elif state == "counted_twice":
        again = next(r for r in recs if r.name == "emu.dispatch")
        monkeypatch.setattr(spans, "records", lambda: recs + [again])
    ctx = {"trace": {"busy_ns": {"TPU:0": 1.0e9}, "idle_share": 0.1},
           "requests": 10, "compiles": 0}
    assert reg.metric_reader(name).read(ctx) is None
