"""Batched campaigns (`run_many` / `Campaign`) vs the single-trace path:
bit-exactness, Sec. 6 invariants under batching, compile-cache behavior."""
import numpy as np
import pytest

from repro.core import emulator
from repro.core.bloom import BloomFilter
from repro.core.campaign import Campaign, plan_groups
from repro.core.emulator import Trace, run, run_many, run_ref_many
from repro.core.timescale import JETSON_NANO


def mixed_traces(n_traces=4, base=70, seed=3):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n_traces):
        n = base + 17 * i  # varied lengths, one 128 bucket
        out.append(Trace.of(kind=rng.randint(0, 2, n),
                            bank=rng.randint(0, 16, n),
                            row=rng.randint(0, 4096, n),
                            delta=rng.randint(1, 8, n),
                            dep=rng.randint(0, 2, n)))
    return out


def small_bloom(seed=0, m_bits=1 << 14, k=3):
    rng = np.random.RandomState(seed)
    bf = BloomFilter.build(rng.randint(0, 1 << 19, 200).astype(np.uint32),
                           m_bits=m_bits, k=k)
    return (bf.bits, bf.k, bf.m_bits)


class TestRunManyExactness:
    def test_matches_per_trace_run(self):
        trs = mixed_traces()
        batch = run_many(trs, JETSON_NANO, "ts")
        for tr, b in zip(trs, batch):
            s = run(tr, JETSON_NANO, "ts")
            assert int(b["exec_cycles"]) == int(s["exec_cycles"])
            assert int(b["row_hits"]) == int(s["row_hits"])
            np.testing.assert_array_equal(b["t_resp"], s["t_resp"])
            np.testing.assert_array_equal(b["t_issue"], s["t_issue"])
            assert b["avg_load_latency_cycles"] == s["avg_load_latency_cycles"]

    def test_matches_with_shared_bloom(self):
        trs = mixed_traces(3)
        bloom = small_bloom()
        batch = run_many(trs, JETSON_NANO, "ts", blooms=bloom)
        for tr, b in zip(trs, batch):
            s = run(tr, JETSON_NANO, "ts", bloom=bloom)
            assert int(b["exec_cycles"]) == int(s["exec_cycles"])
            np.testing.assert_array_equal(b["t_resp"], s["t_resp"])

    def test_stacked_blooms_match_shared(self):
        """Per-trace filter stacking: identical filters per trace must
        reproduce the shared-broadcast result bit-for-bit."""
        trs = mixed_traces(3)
        bloom = small_bloom()
        shared = run_many(trs, JETSON_NANO, "ts", blooms=bloom)
        stacked = run_many(trs, JETSON_NANO, "ts", blooms=[bloom] * len(trs))
        for a, b in zip(shared, stacked):
            assert int(a["exec_cycles"]) == int(b["exec_cycles"])
            np.testing.assert_array_equal(a["t_resp"], b["t_resp"])

    def test_results_in_input_order(self):
        trs = mixed_traces(4)
        batch = run_many(trs, JETSON_NANO, "ts")
        singles = [run(tr, JETSON_NANO, "ts") for tr in trs]
        assert [int(b["exec_cycles"]) for b in batch] \
            == [int(s["exec_cycles"]) for s in singles]


class TestBatchedInvariants:
    def test_ts_equals_reference_inside_one_batch(self):
        """Sec. 6: the time-scaled result must coincide with the RTL
        reference — including when both arms run inside one batched
        campaign across ts/nots/reference and bloom arms."""
        trs = mixed_traces(2, base=80, seed=5)
        bloom = small_bloom(1)
        c = Campaign()
        for i, tr in enumerate(trs):
            for mode in ("ts", "reference", "nots"):
                c.add(tr, JETSON_NANO, mode=mode, i=i, arm="plain")
            for mode in ("ts", "reference"):
                c.add(tr, JETSON_NANO, mode=mode, bloom=bloom, i=i, arm="bloom")
        recs = c.run()
        by = {(r["i"], r["arm"], r["mode"]): int(r["exec_cycles"])
              for r in recs}
        for i in range(len(trs)):
            assert by[(i, "plain", "ts")] == by[(i, "plain", "reference")]
            assert by[(i, "bloom", "ts")] == by[(i, "bloom", "reference")]
            # nots leaks FPGA-platform slowness -> must differ from ts
            assert by[(i, "plain", "nots")] != by[(i, "plain", "ts")]

    def test_per_trace_modes_in_run_many(self):
        trs = mixed_traces(2)
        out = run_many(trs + trs, JETSON_NANO,
                       mode=["ts", "ts", "reference", "reference"])
        assert int(out[0]["exec_cycles"]) == int(out[2]["exec_cycles"])
        assert int(out[1]["exec_cycles"]) == int(out[3]["exec_cycles"])
        assert out[2]["mode"] == "reference"


class TestCompileCache:
    def test_second_same_shaped_batch_hits_cache(self):
        trs = mixed_traces(4, seed=11)
        run_many(trs, JETSON_NANO, "ts")  # populate
        before = emulator.cache_stats()
        # same shapes, different contents -> must NOT recompile
        trs2 = mixed_traces(4, seed=12)
        run_many(trs2, JETSON_NANO, "ts")
        after = emulator.cache_stats()
        assert after["misses"] == before["misses"]
        assert after["hits"] == before["hits"] + 1

    def test_batch_axis_padding_shares_executable(self):
        """3 traces pad the batch axis to 4: same executable as a
        4-trace batch of the same bucket."""
        run_many(mixed_traces(4, seed=13), JETSON_NANO, "ts")
        before = emulator.cache_stats()
        out = run_many(mixed_traces(3, seed=14), JETSON_NANO, "ts")
        after = emulator.cache_stats()
        assert len(out) == 3
        assert after["misses"] == before["misses"]

    def test_campaign_group_count(self):
        trs = mixed_traces(3)
        bloom = small_bloom()
        c = Campaign()
        for tr in trs:
            c.add(tr, JETSON_NANO, mode="ts")
            c.add(tr, JETSON_NANO, mode="ts", bloom=bloom)
            c.add(tr, JETSON_NANO, mode="nots")
        # one group per (bucket, sys, mode): the unfiltered ts points ride
        # the filtered ones' dispatch with their filter mask off
        assert c.n_groups() == 2

    def test_mixed_ts_reference_share_group(self):
        """'reference' compiles to the 'ts' program, so mixing the two
        in one campaign is a single compile group — and each record
        still reports its own mode."""
        tr = mixed_traces(1)[0]
        c = (Campaign().add(tr, JETSON_NANO, mode="ts")
                       .add(tr, JETSON_NANO, mode="reference"))
        assert c.n_groups() == 1
        r = c.run()
        assert int(r[0]["exec_cycles"]) == int(r[1]["exec_cycles"])
        assert r[0]["mode"] == "ts" and r[1]["mode"] == "reference"


class TestSlotBudget:
    """Exact per-group scan budgets + the lowered bucket floor: the
    engine must spend slots proportional to real work, and stay
    bit-identical to the uniform-budget reference engine."""

    def test_bucket_floor_lowered(self):
        assert emulator._bucket(1) == 32
        assert emulator._bucket(8) == 32
        assert emulator._bucket(32) == 32
        assert emulator._bucket(33) == 64
        assert emulator._bucket(300) == 512  # unchanged above the floor

    def test_budget_formula(self):
        # full bucket of real requests degenerates to the uniform budget
        assert emulator.slot_budget(512, 512) == 2 * 512 + 4
        # an 8-request trace no longer burns 2*256+4 = 516 slots
        assert emulator.slot_budget(emulator._bucket(8), 8) <= 40
        # monotone in n_real and capped by the degenerate budget
        buds = [emulator.slot_budget(256, r) for r in range(0, 257, 8)]
        assert buds == sorted(buds)
        assert buds[-1] == 2 * 256 + 4

    def test_small_trace_matches_reference(self):
        rng = np.random.RandomState(2)
        tr = Trace.of(kind=np.zeros(8), bank=rng.randint(0, 16, 8),
                      row=rng.randint(0, 4096, 8), delta=np.full(8, 3),
                      dep=np.ones(8))
        a = run(tr, JETSON_NANO, "ts")
        b = emulator.run_ref(tr, JETSON_NANO, "ts")
        assert int(a["exec_cycles"]) == int(b["exec_cycles"])
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], b["t_issue"])

    @pytest.mark.parametrize("n", [31, 32, 33, 64, 65])
    def test_bucket_boundaries_match_reference(self, n):
        rng = np.random.RandomState(n)
        tr = Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, 16, n),
                      row=rng.randint(0, 4096, n),
                      delta=rng.randint(1, 8, n), dep=rng.randint(0, 2, n))
        a = run(tr, JETSON_NANO, "ts")
        b = emulator.run_ref(tr, JETSON_NANO, "ts")
        for k in ("exec_cycles", "row_hits", "served", "dram_ticks",
                  "smc_fpga_cycles"):
            assert int(a[k]) == int(b[k]), k
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], b["t_issue"])

    def test_mid_trace_nops_match_reference(self):
        """NOP runs inside the trace (not just padding) stress the
        frontier's NOP resolution and the budget's sufficiency
        accounting. Re-baselined in PR 4 to the corrected idle-hop
        behavior: the idle hop is skipped while the hardware queue is
        empty (both engines changed together), so a NOP run that drains
        the queue no longer saturates mc_release to BIG-1 — every real
        request now completes with a sane response tag, and the two
        engines must still agree bit-for-bit."""
        rng = np.random.RandomState(7)
        n = 60
        kind = rng.randint(0, 2, n)
        kind[10:18] = 4   # 8 consecutive NOPs
        kind[30:33] = 4
        tr = Trace.of(kind=kind, bank=rng.randint(0, 16, n),
                      row=rng.randint(0, 4096, n),
                      delta=rng.randint(0, 6, n), dep=rng.randint(0, 2, n))
        a = run(tr, JETSON_NANO, "ts")
        b = emulator.run_ref(tr, JETSON_NANO, "ts")
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], b["t_issue"])
        assert int(a["served"]) == int(b["served"])
        # corrected behavior: no response poisoning, everything serves
        real = kind != 4
        assert int(a["served"]) == int(real.sum())
        assert (np.asarray(a["t_resp"])[:n][real] < int(emulator.BIG)).all()

    @pytest.mark.parametrize("mode,window,sched", [
        ("ts", 1, "frfcfs"), ("nots", 4, "frfcfs"),
        ("reference", 2, "fcfs"), ("ts", 4, "fcfs")])
    def test_modes_and_configs_match_reference(self, mode, window, sched):
        """Deterministic slice of the hypothesis property (which is
        skipped when hypothesis is absent): mode x window x scheduler
        bit-identity between the budgeted fast core and the reference."""
        import dataclasses
        rng = np.random.RandomState(5)
        n = 45
        tr = Trace.of(kind=rng.randint(0, 5, n), bank=rng.randint(0, 16, n),
                      row=rng.randint(0, 4096, n),
                      delta=rng.randint(0, 24, n), dep=rng.randint(0, 3, n))
        sysc = dataclasses.replace(JETSON_NANO, window=window,
                                   scheduler=sched)
        a = run(tr, sysc, mode)
        b = emulator.run_ref(tr, sysc, mode)
        for k in ("exec_cycles", "row_hits", "served", "dram_ticks",
                  "smc_fpga_cycles"):
            assert int(a[k]) == int(b[k]), k
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], b["t_issue"])

    def test_bloom_arm_matches_reference(self):
        rng = np.random.RandomState(9)
        n = 64
        bloom = small_bloom(4)
        tr = Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, 16, n),
                      row=rng.randint(0, 4096, n), delta=rng.randint(1, 8, n),
                      dep=rng.randint(0, 2, n))
        a = run(tr, JETSON_NANO, "ts", bloom=bloom)
        b = emulator.run_ref(tr, JETSON_NANO, "ts", bloom=bloom)
        assert int(a["exec_cycles"]) == int(b["exec_cycles"])
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])

    def test_budget_in_compile_key_stays_consistent(self):
        """Identical trace shapes must keep hitting one executable; the
        budget quantization must not fork cache entries for same-shape
        reruns of the same point."""
        rng = np.random.RandomState(21)
        tr = Trace.of(kind=np.zeros(40), bank=rng.randint(0, 16, 40),
                      row=rng.randint(0, 4096, 40), delta=np.full(40, 2))
        run(tr, JETSON_NANO, "ts")
        before = emulator.cache_stats()
        run(tr, JETSON_NANO, "ts")
        run_many([tr], JETSON_NANO, "ts")
        after = emulator.cache_stats()
        assert after["misses"] == before["misses"]
        assert after["hits"] == before["hits"] + 2

    def test_group_budget_covers_shorter_members(self):
        """A batch group's budget comes from its largest member; the
        shorter members (more padding NOPs than the budget's pad term
        assumes real) must still complete and match their solo runs."""
        short = Trace.of(kind=np.zeros(33), bank=np.arange(33) % 16,
                         row=np.arange(33), delta=np.full(33, 2))
        long = Trace.of(kind=np.zeros(64), bank=np.arange(64) % 16,
                        row=np.arange(64) % 4096, delta=np.full(64, 2))
        assert emulator._bucket(short.n) == emulator._bucket(long.n)
        batch = run_many([short, long], JETSON_NANO, "ts")
        for tr, b in zip((short, long), batch):
            s = run(tr, JETSON_NANO, "ts")
            assert int(b["exec_cycles"]) == int(s["exec_cycles"])
            assert int(b["served"]) == tr.n


class TestApiEdges:
    def test_extend_rejects_short_metas(self):
        c = Campaign()
        # ValueError, not AssertionError: the guard survives python -O
        # and reports both lengths
        with pytest.raises(ValueError, match="metas \\(1\\).*traces \\(3\\)"):
            c.extend(mixed_traces(3), JETSON_NANO, metas=[{"a": 1}])
        assert len(c) == 0  # nothing silently added

    def test_meta_cannot_shadow_result_fields(self):
        c = Campaign()
        c.add(mixed_traces(1)[0], JETSON_NANO, exec_cycles=0)
        # ValueError, not AssertionError: the guard survives python -O
        with pytest.raises(ValueError, match="shadow"):
            c.run()

    def test_list_typed_shared_bloom_broadcasts(self):
        """Shared-vs-per-trace bloom dispatch is by content, not
        container type: a list-typed (words, k, m) still broadcasts."""
        trs = mixed_traces(2)
        bloom = small_bloom()
        a = run_many(trs, JETSON_NANO, "ts", blooms=bloom)
        b = run_many(trs, JETSON_NANO, "ts", blooms=list(bloom))
        for x, y in zip(a, b):
            assert int(x["exec_cycles"]) == int(y["exec_cycles"])
        s = run(trs[0], JETSON_NANO, "ts", bloom=list(bloom))
        assert int(s["exec_cycles"]) == int(a[0]["exec_cycles"])

    def test_campaign_list_typed_bloom(self):
        tr = mixed_traces(1)[0]
        bloom = small_bloom()
        c = (Campaign().add(tr, JETSON_NANO, bloom=bloom)
                       .add(tr, JETSON_NANO, bloom=list(bloom)))
        assert c.n_groups() == 1  # same filter shape -> one group
        r = c.run()
        assert int(r[0]["exec_cycles"]) == int(r[1]["exec_cycles"])

    def test_tuple_of_per_trace_blooms_stacks(self):
        trs = mixed_traces(3)
        blooms = tuple(small_bloom(seed) for seed in range(3))
        stacked = run_many(trs, JETSON_NANO, "ts", blooms=blooms)
        for tr, bf, r in zip(trs, blooms, stacked):
            single = run(tr, JETSON_NANO, "ts", bloom=bf)
            assert int(single["exec_cycles"]) == int(r["exec_cycles"])


FIELDS = ("exec_cycles", "row_hits", "served", "dram_ticks",
          "smc_fpga_cycles", "t_resp", "t_issue")


def assert_same(a, b):
    for f in FIELDS:
        np.testing.assert_array_equal(np.asarray(a[f]), np.asarray(b[f]), f)


class TestFilterMask:
    """Points that differ only in whether the weak-row filter applies
    share one group and one dispatch; the unfiltered ones ride it with
    their lane's filter mask off, bit-identical to running apart."""

    @staticmethod
    def arms(trs, mode, filters):
        bloom = small_bloom()
        c = Campaign()
        for i, tr in enumerate(trs):
            # "stacked": a distinct filter of one shape per point
            b = bloom if filters == "shared" else small_bloom(seed=i)
            c.add(tr, JETSON_NANO, mode=mode, i=i, arm="base")
            c.add(tr, JETSON_NANO, mode=mode, bloom=b, i=i, arm="reduced")
        return c

    @pytest.mark.parametrize("filters", ["shared", "stacked"])
    @pytest.mark.parametrize("mode", ["ts", "nots"])
    def test_mixed_arms_match_separate_groups_and_reference(self, mode,
                                                           filters):
        trs = mixed_traces(3)
        c = self.arms(trs, mode, filters)
        assert c.n_groups() == 1
        got = c.run()
        blooms = [p.bloom for p in c.points[1::2]]
        base, reduced = run_many(trs, JETSON_NANO, mode), \
            run_many(trs, JETSON_NANO, mode, blooms=blooms)
        ref_base, ref_reduced = run_ref_many(trs, JETSON_NANO, mode), \
            run_ref_many(trs, JETSON_NANO, mode, blooms=blooms)
        for i in range(len(trs)):
            assert_same(got[2 * i], base[i])
            assert_same(got[2 * i], ref_base[i])
            assert_same(got[2 * i + 1], reduced[i])
            assert_same(got[2 * i + 1], ref_reduced[i])
        # in ts the filter changes the answers, so a mask that leaked
        # either way would show (in nots the slow SMC hides DRAM timing
        # and the arms coincide)
        assert (mode == "nots") != any(
            not np.array_equal(got[2 * i]["t_resp"], got[2 * i + 1]["t_resp"])
            for i in range(len(trs)))

    @pytest.mark.parametrize("on", [(1, 0, 1), (0, 1, 0)])
    def test_run_many_takes_unfiltered_entries(self, on):
        trs = mixed_traces(3)
        bloom = small_bloom()
        blooms = [bloom if o else None for o in on]
        words, mask = emulator._normalize_blooms(blooms, 3)
        assert words == tuple(bloom) and mask == [bool(o) for o in on]
        for tr, bl, r in zip(trs, blooms,
                             run_many(trs, JETSON_NANO, "ts", blooms=blooms)):
            assert_same(r, run(tr, JETSON_NANO, "ts", bloom=bl))
        assert emulator._normalize_blooms([None, None], 2) == (None, None)

    def test_two_filter_shapes_keep_the_unfiltered_group(self):
        trs = mixed_traces(2)
        blooms = (None, small_bloom(), small_bloom(m_bits=1 << 15))
        c = Campaign()
        for tr in trs:
            for b in blooms:
                c.add(tr, JETSON_NANO, bloom=b)
        groups = plan_groups(c.points)
        assert sorted(groups.values()) == [[0, 3], [1, 4], [2, 5]]
        assert emulator.group_key(trs[0].n, JETSON_NANO, "ts", None) \
            in groups
        for p, r in zip(c.points, c.run()):
            assert_same(r, run(p.trace, JETSON_NANO, "ts", bloom=p.bloom))

    def test_unfiltered_only_campaign_compiles_as_before(self):
        """No filtered point: the group compiles the no-filter program
        under the key it always had (no words, no mask operand), one
        miss for one group."""
        import dataclasses
        sysc = dataclasses.replace(JETSON_NANO, window=3)  # a fresh key
        trs = mixed_traces(3)
        c = Campaign().extend(trs, sysc)
        assert list(plan_groups(c.points)) == [
            emulator.group_key(trs[0].n, sysc, "ts", None)]
        before = emulator.cache_stats()["misses"]
        c.run()
        assert emulator.cache_stats()["misses"] - before == 1
        slots = emulator.slot_budget(128, max(t.n_real for t in trs))
        key = emulator.compile_key(128, 3, sysc, "ts", None, slots)
        runner = emulator._COMPILE_CACHE[
            ("fast", emulator._shard_count(4), key)]
        assert len(runner.avals) == 5   # the five trace arrays alone

    def test_streams_do_not_mix_filter_arms(self):
        trs = mixed_traces(2)
        with pytest.raises(ValueError, match="all carry a filter"):
            emulator.run_stream_many(trs, JETSON_NANO, "ts",
                                     blooms=[small_bloom(), None])
