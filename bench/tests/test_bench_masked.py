"""``masked_request_share`` and the ``masked_requests`` count it reads,
on the CPU at a tiny size: a ``--trace 1`` run of the tiny sweep, whose
base and reduced arms share one dispatch per bucket, reads 50; the tiny
stream, which masks no lane, reads 0; and the reader reports nothing
where the log cannot be trusted or the program counts no masked lanes."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

# the tiny cells, their fixtures and their traced runs, set up once here too
from bench.tests.test_bench_harness import (  # noqa: E402,F401
    PINNED, SEED, TINY, _env, opened, reg)
from bench.tests.test_bench_spans import _dispatches, traced  # noqa: E402,F401

NAME = "masked_request_share"


@pytest.mark.parametrize("cell,share", [("tiny.stream", 0.0),
                                        ("tiny.sweep", 50.0)])
def test_traced_run_reads_the_masked_share(traced, cell, share):
    t = traced[cell]
    m = t["result"]["metrics"]
    assert t["result"]["correct"] is True
    assert m[NAME] == {"value": pytest.approx(share), "unit": "%"}
    disp = _dispatches(t)
    assert m[NAME]["value"] == pytest.approx(
        100 * sum(c["masked_requests"] for c in disp) / t["requests"])


@pytest.mark.parametrize("cell", sorted(TINY))
def test_dispatches_run_both_arms_at_once(traced, opened, cell):
    """The tiny sweep's base and reduced arms of a bucket are one
    dispatch, twice the lanes, the base lanes' requests masked; the
    stream's windows are as they were, nothing masked."""
    from repro.core import emulator
    t, driver = traced[cell], opened[cell][1]
    lens = [len(x["kind"]) for _, x in driver.inputs(0)]
    got = sorted(tuple(c[k] for k in ("slots", "lanes", "requests",
                                      "masked_requests", "shards"))
                 for c in _dispatches(t))
    if cell == "tiny.sweep":
        groups = {}
        for n in lens:
            groups.setdefault(emulator._bucket(n), []).append(n)
        want = [(emulator.slot_budget(b, max(ns)),
                 emulator._batch_bucket(2 * len(ns)), 2 * sum(ns), sum(ns), 1)
                for b, ns in groups.items()]
    else:
        chunk = TINY[cell]["chunk"]
        want = [(emulator.stream_slot_budget(chunk, driver.sys),
                 emulator._batch_bucket(len(lens)),
                 sum(min(max(n - i * chunk, 0), chunk) for n in lens), 0, 1)
                for i in range(-(-max(lens) // chunk))]
    assert got == sorted(want * t["calls"])


@pytest.mark.parametrize("state", ["empty", "missing", "dropped",
                                   "counted_twice", "uncounted"])
def test_masked_share_reports_nothing_without_spans(traced, reg, state,
                                                   monkeypatch):
    """An empty log, a program with no span module, a log that dropped
    spans, one whose dispatches count more requests than its calls
    returned, or a program whose dispatches do not count masked lanes
    (the benchmark run against an older checkout) reads as no value."""
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
    elif state == "uncounted":
        older = [r._replace(counts={
            k: v for k, v in r.counts.items() if k != "masked_requests"})
            for r in recs]
        monkeypatch.setattr(spans, "records", lambda: older)
    ctx = {"trace": {"busy_ns": {"TPU:0": 1.0e9}, "idle_share": 0.1},
           "requests": 10, "compiles": 0}
    assert reg.metric_reader(NAME).read(ctx) is None
