"""RowClone + tRCD-reduction technique behaviour (Secs. 7-8)."""
import itertools

import numpy as np
import pytest

from repro.core import spans, traces
from repro.core.dram import Geometry, NOP, RC_COPY, RC_INIT, WRITE
from repro.core.profiling import DeviceModel
from repro.core.techniques import Platform, RowClone, TRCDReduction
from repro.core.timescale import JETSON_NANO, PIDRAM_LIKE

# the two systems of the Sec. 7 study: EasyDRAM time-scaled, PiDRAM-like
SYSTEMS = {"ts": Platform(JETSON_NANO, "ts", 4),
           "nots": Platform(PIDRAM_LIKE, "nots", 20)}
# (cpu, rowclone) exec_cycles of evaluate_batch([8 KiB, 16 KiB, 8 KiB])
# for each (mode, cpu_line_delta, workload, setting), as the per-setting
# campaigns computed them before evaluate_batch went through
# RowClone.campaign; the third size repeats the first
SEED_CYCLES = {
    ("ts", None, "copy", "noflush"): ((5986, 148), (12413, 285)),
    ("ts", None, "copy", "clflush"): ((12467, 6131), (24718, 12693)),
    ("ts", None, "init", "noflush"): ((5977, 148), (12382, 285)),
    ("ts", None, "init", "clflush"): ((6020, 230), (12425, 392)),
    ("ts", 4, "copy", "noflush"): ((5982, 148), (12409, 285)),
    ("ts", 4, "copy", "clflush"): ((12467, 6131), (24718, 12693)),
    ("ts", 4, "init", "noflush"): ((5977, 148), (12382, 285)),
    ("ts", 4, "init", "clflush"): ((6020, 230), (12425, 392)),
    ("nots", 20, "copy", "noflush"): ((5120, 15), (10240, 27)),
    ("nots", 20, "copy", "clflush"): ((5376, 272), (10769, 557)),
    ("nots", 20, "init", "noflush"): ((2560, 15), (5120, 27)),
    ("nots", 20, "init", "clflush"): ((2561, 17), (5123, 31)),
}


@pytest.fixture(scope="module")
def device():
    return DeviceModel(Geometry())


class TestDeviceModel:
    def test_weak_fraction_matches_paper(self, device):
        # Fig. 12: 84.5% strong / 15.5% weak
        assert abs(device.weak_fraction() - 0.155) < 0.01

    def test_trcd_all_below_nominal(self, device):
        assert device.min_trcd_ns.max() < 13.5  # all cells beat the datasheet

    def test_weak_rows_spatially_clustered(self, device):
        """Autocorrelation of weakness along rows >> iid baseline."""
        w = device.weak[0].astype(float)
        ac = np.corrcoef(w[:-1], w[1:])[0, 1]
        assert ac > 0.2

    def test_clonable_requires_same_subarray(self, device):
        assert not device.clonable(0, 10, 600)   # crosses subarray boundary
        assert not device.clonable(0, 10, 10)    # src == dst

    def test_clonable_deterministic(self, device):
        for args in ((0, 10, 11), (3, 100, 101), (7, 513, 514)):
            assert device.clonable(*args) == device.clonable(*args)


class TestRowClone:
    def test_allocator_satisfies_constraints(self, device):
        geo = Geometry()
        tr, meta = traces.copy_workload(1 << 20, geo, "rowclone", device)
        assert meta["fallback_rows"] <= meta["rows"] * 0.05
        assert (np.isin(tr.kind, (RC_COPY,)).sum()
                == meta["rows"] - meta["fallback_rows"])

    def test_speedup_over_cpu(self, device):
        rc = RowClone(JETSON_NANO, device)
        out = rc.evaluate(1 << 20, "copy", "noflush", "ts")
        assert out["rowclone"].speedup_vs_cpu > 2.0

    def test_clflush_reduces_benefit(self, device):
        rc = RowClone(JETSON_NANO, device)
        nf = rc.evaluate(1 << 18, "copy", "noflush", "ts")["rowclone"].speedup_vs_cpu
        cf = rc.evaluate(1 << 18, "copy", "clflush", "ts")["rowclone"].speedup_vs_cpu
        assert cf < nf

    def test_nots_inflates_speedup(self, device):
        """The paper's headline: platforms without time scaling report
        inflated RowClone benefits."""
        ts = RowClone(JETSON_NANO, device).evaluate(
            1 << 20, "copy", "noflush", "ts")["rowclone"].speedup_vs_cpu
        nots = RowClone(PIDRAM_LIKE, device).evaluate(
            1 << 20, "copy", "noflush", "nots")["rowclone"].speedup_vs_cpu
        assert nots > 1.5 * ts


    @pytest.mark.parametrize("key", list(SEED_CYCLES), ids=str)
    def test_evaluate_batch_unchanged(self, device, key):
        mode, cld, workload, setting = key
        rc = RowClone(PIDRAM_LIKE if mode == "nots" else JETSON_NANO, device)
        out = rc.evaluate_batch([8192, 16384, 8192], workload, setting, mode,
                                cpu_line_delta=cld)
        want = SEED_CYCLES[key]
        assert [(d["cpu"].exec_cycles, d["rowclone"].exec_cycles)
                for d in out] == [*want, want[0]]
        for nb, d in zip((8192, 16384, 8192), out):
            for arm in ("cpu", "rowclone"):
                r = d[arm]
                assert (r.mode, r.setting, r.n_bytes, r.fallback_rows) == \
                    (arm, setting, nb, 0)
            assert d["rowclone"].speedup_vs_cpu == \
                d["cpu"].exec_cycles / d["rowclone"].exec_cycles

    def test_campaign_is_the_whole_grid(self, device):
        """One Campaign holds every (size, workload, setting, system,
        arm) point, in that order, each with its system's config, mode
        and copy-loop cost, over the caller's allocations."""
        alloc = {"copy": 1001, "init": 50003}
        c = RowClone(JETSON_NANO, device).campaign(
            [8192, 16384], systems=SYSTEMS, alloc_base_rows=alloc)
        grid = list(itertools.product(range(2), ("copy", "init"),
                                      ("noflush", "clflush"), SYSTEMS,
                                      ("cpu", "rowclone")))
        assert [tuple(p.meta[k] for k in ("j", "workload", "setting",
                                           "system", "arm"))
                for p in c.points] == grid
        gen = {"copy": traces.copy_workload, "init": traces.init_workload}
        for p in c.points:
            m, pf = p.meta, SYSTEMS[p.meta["system"]]
            assert (p.sys, p.mode, m["n_bytes"]) == \
                (pf.sys, pf.mode, (8192, 16384)[m["j"]])
            tr, meta = gen[m["workload"]](
                m["n_bytes"], device.geo, m["arm"], device, m["setting"],
                alloc_base_row=alloc[m["workload"]],
                cpu_line_delta=pf.cpu_line_delta)
            assert m["fallback_rows"] == meta["fallback_rows"]
            for f in ("kind", "bank", "row", "delta", "dep"):
                assert np.array_equal(getattr(p.trace, f), getattr(tr, f))

    def test_campaign_runs_both_systems(self, device):
        """The grid's records: every point served, time scaling hiding
        the slow platform, and the platform without it inflating the
        copy speedup (the paper's Sec. 7 finding)."""
        recs = RowClone(JETSON_NANO, device).campaign(
            [8192], systems=SYSTEMS).run()
        assert all(r["served"] == r["n_requests"] for r in recs)
        res = RowClone.results(recs)
        assert len(res) == 8
        speed = {k: d["rowclone"].speedup_vs_cpu for k, d in res.items()}
        assert all(v > 1 for v in speed.values())
        assert speed[(0, "copy", "noflush", "nots")] > \
            1.5 * speed[(0, "copy", "noflush", "ts")]

    @pytest.mark.parametrize("fail_rate", [0.02, 0.9])
    def test_trace_span_counts_points_and_fallbacks(self, monkeypatch,
                                                    fail_rate):
        monkeypatch.setattr(spans, "profiler_active", lambda: True)
        spans.clear()
        dev = DeviceModel(Geometry(), clone_fail_rate=fail_rate)
        c = RowClone(JETSON_NANO, dev).campaign([16384], systems=SYSTEMS)
        (rec,) = spans.records()
        spans.clear()
        fallbacks = sum(p.meta["fallback_rows"] for p in c.points)
        assert rec.name == "tech.traces" and rec.parent is None
        assert rec.counts == {"points": 16, "fallback_rows": fallbacks}
        assert (fallbacks > 0) == (fail_rate > 0.5)

    def test_campaign_refuses_what_it_cannot_lay_out(self, device):
        import dataclasses
        rc = RowClone(JETSON_NANO, device)
        with pytest.raises(KeyError, match="move"):
            rc.campaign([8192], workloads=("move",))
        other = dataclasses.replace(
            JETSON_NANO, geometry=Geometry(n_banks=8))
        with pytest.raises(ValueError, match="geometry"):
            rc.campaign([8192], systems={"x": Platform(other)})


@pytest.mark.parametrize("path", ["batched", "stream"])
def test_dispatch_kind_counts_equal_the_traces(monkeypatch, device, path):
    """Every dispatch counts the WRITE and the RowClone requests among
    those it newly emulates: a batched group its traces', a stream
    window its fresh blocks'. Planned only: nothing compiles."""
    from repro.core import emulator
    monkeypatch.setattr(emulator._CachedRunner, "prime", lambda self: self)
    c = RowClone(JETSON_NANO, device).campaign([8192, 16384],
                                               systems={"ts": SYSTEMS["ts"]})
    trs = [p.trace for p in c.points]

    def counts(kinds):
        kinds = np.asarray(kinds)
        return (int(np.count_nonzero(kinds != NOP)),
                int(np.count_nonzero(kinds == WRITE)),
                int(np.count_nonzero(np.isin(kinds, (RC_COPY, RC_INIT)))))

    names = ("requests", "write_requests", "rc_requests")
    if path == "batched":
        tasks = emulator.prepare_tasks(trs, JETSON_NANO, "ts", None,
                                       [None] * len(trs))
        groups = {}
        for tr in trs:
            groups.setdefault(emulator._bucket(tr.n), []).append(tr.kind)
        got = sorted(tuple(t.counts[k] for k in names) for t in tasks)
        assert got == sorted(counts(np.concatenate(ks))
                             for ks in groups.values())
    else:
        (st,) = emulator.prepare_stream_tasks(trs, JETSON_NANO, "ts", None,
                                              [None] * len(trs), chunk=64)
        _, ctx = st.pack()
        wins = [tuple(w[k] for k in names) for _, w in st.windows(ctx)]
        assert len(wins) > 1
        assert tuple(map(sum, zip(*wins))) == \
            counts(np.concatenate([tr.kind for tr in trs]))


class TestTRCD:
    def test_bloom_safety(self, device):
        t = TRCDReduction(JETSON_NANO, device)
        t.characterize()
        s = t.safety_check()
        assert s["false_negatives"] == 0          # never unsafe
        assert s["false_positive_rate"] < 0.05    # rarely pessimistic

    def test_end_to_end_speedup(self, device):
        t = TRCDReduction(JETSON_NANO, device)
        tr, _ = traces.polybench_trace(traces.POLYBENCH[3], Geometry(),
                                       max_accesses=8000)
        r = t.evaluate_trace(tr)
        assert 1.0 <= r["speedup"] < 1.25  # single-digit % (paper avg 2.75%)
