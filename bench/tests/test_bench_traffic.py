"""The benchmark's copy of the PolyBench traffic gives the program's
traces, matches the digests stored at the commit that defined the
benchmark, and its per-call variants keep the shape of the work."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench.lib import polybench as pb  # noqa: E402

GEO = {"n_banks": 16, "n_rows": 32768, "row_bytes": 8192}
with open(os.path.join(ROOT, "bench", "tests", "data",
                       "polybench_digests.json")) as _fh:
    DIGESTS = json.load(_fh)


@pytest.mark.parametrize("max_accesses", [6000, 60000])
def test_suite_matches_stored_digests(max_accesses, tmp_path):
    want = DIGESTS[str(max_accesses)]
    for cache in (str(tmp_path), str(tmp_path)):   # generate, then read back
        got = pb.suite(max_accesses, GEO, cache)
        assert [pb.KERNELS[i][0] for i in range(len(got))] == list(want)
        for (name, *_), tr in zip(pb.KERNELS, got):
            assert len(tr["kind"]) == want[name]["n"]
            assert pb.digest(tr) == want[name]["sha256"], name


def test_copy_equals_program_generator():
    from repro.core import traces
    from repro.core.dram import Geometry
    geo = Geometry()
    for i in range(len(pb.KERNELS)):
        tr, _ = traces.polybench_trace(traces.POLYBENCH[i], geo,
                                       max_accesses=1000, seed=i)
        mine = pb.kernel_trace(i, 1000, GEO)
        for f in pb.FIELDS:
            assert np.array_equal(getattr(tr, f), mine[f]), (i, f)


def test_llc_filter_writes_back_dirty_lines():
    from repro.core.cachesim import filter_stream
    rng = np.random.RandomState(3)
    addrs = rng.randint(0, 1 << 22, 20000) * 8
    writes = rng.rand(20000) < 0.4
    a, w, _ = filter_stream(addrs, writes)
    b, v = pb.llc_filter(addrs, writes)
    assert w.any() and np.array_equal(a, b) and np.array_equal(w, v)


def test_variant_keeps_the_work_and_changes_the_answers():
    tr = pb.suite(6000, GEO)[0]
    a = pb.variant(tr, 2 ** 31 + 17, 3, 1, 16, 32768)
    b = pb.variant(tr, 2 ** 31 + 17, 3, 1, 16, 32768)
    c = pb.variant(tr, 2 ** 31 + 17, 4, 1, 16, 32768)
    for f in pb.FIELDS:
        assert np.array_equal(a[f], b[f])
    assert not np.array_equal(a["bank"], c["bank"])
    for f in ("kind", "dep"):
        assert np.array_equal(a[f], tr[f])
    assert np.array_equal(a["delta"][1:], tr["delta"][1:])
    assert 0 <= a["delta"][0] - tr["delta"][0] < 1 << 14
    same = lambda x: (x["bank"][:, None] == x["bank"][None, :500]) & \
        (x["row"][:, None] == x["row"][None, :500])  # noqa: E731
    assert np.array_equal(same(a), same(tr))
    assert a["row"].min() >= 0 and a["row"].max() < 32768
