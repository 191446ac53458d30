"""The reference's own rebuilds of what the configuration states (the
weak-row map, the Bloom filter) equal the program's, so the comparison
that decides `correct` starts from the same deployment; and its control
drops the one guarantee it names."""
import itertools
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [p for p in (ROOT, os.path.join(ROOT, "src")) if p not in sys.path]

from bench.lib import reference as ref, sut  # noqa: E402

with open(os.path.join(ROOT, "bench", "configs", "trcd-polybench.json")) as _fh:
    CFG = json.load(_fh)


def test_weak_rows_and_bloom_match_the_program():
    from repro.core.bloom import BloomFilter
    from repro.core.dram import Geometry
    from repro.core.profiling import DeviceModel
    dm = DeviceModel(Geometry(**CFG["geometry"]),
                     seed=CFG["device_model"]["seed"],
                     weak_target=CFG["device_model"]["weak_target"])
    weak = ref.weak_rows(CFG)
    assert np.array_equal(np.sort(weak), np.sort(dm.weak_rows()))
    m, k = CFG["bloom"]["m_bits"], CFG["bloom"]["k"]
    words = BloomFilter.build(dm.weak_rows(), m_bits=m, k=k).bits
    bits = np.unpackbits(words.view(np.uint8), bitorder="little")
    mine = ref.Bloom(weak[:5000], m, k)
    want = BloomFilter.build(weak[:5000], m_bits=m, k=k)
    probe = np.arange(0, 16 * 32768, 97)
    assert [int(x) in mine for x in probe] == want.contains(probe).tolist()
    assert bits.sum() == sum(ref.Bloom(weak, m, k).bits)


def test_control_drops_only_the_weak_row_guarantee():
    """With the weak-row guarantee dropped, a trace that activates weak
    rows reads otherwise; one that activates none reads the same."""
    sysr = ref.System(CFG)
    weak = ref.weak_rows(CFG)
    bloom = ref.Bloom(weak, CFG["bloom"]["m_bits"], CFG["bloom"]["k"])
    n_rows = CFG["geometry"]["n_rows"]
    strong = list(itertools.islice(
        (g for g in range(0, 16 * n_rows, 7) if g not in bloom), 64))
    for rows, differs in ((weak[:64], True), (strong, False)):
        rows = np.asarray(rows)
        tr = {"kind": np.zeros(len(rows), np.int32),
              "bank": (rows // n_rows).astype(np.int32),
              "row": (rows % n_rows).astype(np.int32),
              "delta": np.full(len(rows), 40, np.int32),
              "dep": np.zeros(len(rows), np.int32)}
        sound = ref.emulate(tr, sysr, bloom)
        broken = ref.emulate(tr, sysr, bloom,
                             guarantee_broken="weak_rows_nominal")
        assert bool(sut.differs(sound, broken)) is differs
