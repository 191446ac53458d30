"""The reduction from a profiler trace to busy time, idle share, top
operations and labelled idle gaps, on small traces whose answers are
worked out by hand."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench.lib import trace_reduce as tr  # noqa: E402

DEVICES = {"TPU:0": [(100, 300, "a"), (250, 400, "b"), (600, 700, "a")],
           "TPU:1": [(150, 200, "c"), (900, 1200, "c")]}
SPANS = [("traffic", 50, 100), ("campaign.run", 100, 800),
         ("traffic", 800, 850), ("campaign.run", 850, 1100)]


def test_union_and_gaps():
    assert tr.union(DEVICES["TPU:0"], 0, 1000) == [[100, 400], [600, 700]]
    assert tr.union(DEVICES["TPU:1"], 0, 1000) == [[150, 200], [900, 1000]]
    assert tr.gaps(DEVICES["TPU:0"], 50, 1100) == [(50, 100), (400, 600),
                                                  (700, 1100)]
    assert tr.busy_ns([], 0, 10) == 0.0


def test_reduce_by_hand():
    r = tr.reduce(DEVICES, SPANS)
    assert r["window_ns"] == 1050
    assert r["busy_ns"] == {"TPU:0": 400.0, "TPU:1": 250.0}
    assert r["busy_mean_ns"] == 325.0
    assert r["idle_share"] == pytest.approx(1 - 650 / 2100)
    assert r["device_ops"] == [("a", 300.0), ("c", 250.0), ("b", 150.0)]
    assert r["idle_gaps"] == [("campaign.run", 700.0), ("campaign.run", 400.0),
                              ("campaign.run", 200.0), ("campaign.run", 100.0),
                              ("traffic", 50.0)]


def test_no_device_reads_nothing():
    r = tr.reduce({}, SPANS)
    assert r["idle_share"] is None and r["busy_mean_ns"] == 0.0


def test_recorded_trace():
    """A slice of a TPU v5e trace of one emulator call: the device's
    XLA programs and the harness's span around the call."""
    with open(os.path.join(ROOT, "bench", "tests", "data",
                           "trace_small.json")) as fh:
        rec = json.load(fh)
    devices = {d: [tuple(e) for e in ev] for d, ev in rec["devices"].items()}
    spans = [tuple(s) for s in rec["spans"]]
    r = tr.reduce(devices, spans)
    want = rec["expected"]
    assert r["window_ns"] == want["window_ns"]
    assert r["busy_ns"] == want["busy_ns"]
    assert r["idle_share"] == pytest.approx(want["idle_share"])
    assert [list(x) for x in r["device_ops"]] == want["device_ops"]
    assert [list(x) for x in r["idle_gaps"]] == want["idle_gaps"]
