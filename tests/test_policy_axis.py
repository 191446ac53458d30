"""ISSUE 10 pins: the runtime policy operand and the vmapped policy
axis must be bit-identical to the staged-constant VM — across engines
(fast/ref), modes (ts/nots), faults on/off, streaming windows, and
mixed table-length buckets — while compiling once per BUCKET, never per
program. Deterministic versions of the hypothesis property in
tests/test_property.py (hypothesis is optional in this container)."""
import dataclasses

import numpy as np
import pytest

from repro.core import emulator, smcprog
from repro.core.campaign import Campaign, Point
from repro.core.emulator import Trace, run, run_many, run_policies
from repro.core.faults import FaultModel
from repro.core.policysearch import (crossover, mutate, random_program,
                                     search)
from repro.core.timescale import JETSON_NANO

ALL_FIELDS = ("exec_cycles", "row_hits", "served", "dram_ticks",
              "smc_fpga_cycles")


def mk_trace(seed=0, n=60):
    rng = np.random.RandomState(seed)
    return Trace.of(kind=rng.randint(0, 5, n), bank=rng.randint(0, 16, n),
                    row=rng.randint(0, 4096, n), delta=rng.randint(0, 24, n),
                    dep=rng.randint(0, 3, n))


def program_pool(seed=11, n_random=4):
    rng = np.random.RandomState(seed)
    progs = list(smcprog.builtin_programs().values())
    progs += [random_program(rng, name=f"r{i}") for i in range(n_random)]
    return progs


def assert_same(a, b, n, label=""):
    for k in ALL_FIELDS:
        assert int(a[k]) == int(b[k]), (label, k)
    np.testing.assert_array_equal(a["t_resp"][:n], b["t_resp"][:n])
    np.testing.assert_array_equal(a["t_issue"][:n], b["t_issue"][:n])
    assert a["avg_load_latency_cycles"] == b["avg_load_latency_cycles"], label


class TestRuntimeOperandBitIdentity:
    @pytest.mark.parametrize("mode", ["ts", "nots"])
    def test_axis_matches_staged(self, mode):
        """One dispatch over the policy axis == per-program staged
        constants, every output field."""
        tr = mk_trace(0)
        progs = program_pool()
        axis = run_policies(tr, JETSON_NANO, progs, mode=mode,
                            derive_cost=False, serial=True)
        for p, r in zip(progs, axis):
            staged = run(tr, dataclasses.replace(JETSON_NANO, policy=p),
                         mode)
            assert_same(staged, r, tr.n, p.name)

    def test_derive_cost_matches_with_policy(self):
        """derive_cost=True charges each program its length-derived SMC
        cost — the with_policy semantics — visibly in nots mode."""
        tr = mk_trace(1)
        progs = program_pool(n_random=2)
        axis = run_policies(tr, JETSON_NANO, progs, mode="nots",
                            derive_cost=True, serial=True)
        for p, r in zip(progs, axis):
            staged = run(tr, JETSON_NANO.with_policy(p), "nots")
            assert_same(staged, r, tr.n, p.name)

    def test_ref_engine_matches_fast(self):
        """The kept pre-optimization engine mirrors the table VM."""
        tr = mk_trace(2)
        progs = program_pool(n_random=2)
        costs = [p.smc_cycles() for p in progs]
        fast = run_many([tr] * len(progs), JETSON_NANO, "ts",
                        policies=progs, policy_costs=costs, serial=True)
        ref = emulator.run_ref_many([tr] * len(progs), JETSON_NANO, "ts",
                                    policies=progs, policy_costs=costs,
                                    serial=True)
        for p, f, r in zip(progs, fast, ref):
            assert_same(f, r, tr.n, p.name)

    def test_streaming_matches_single_shot(self):
        """The chunked-window driver carries the policy operand through
        every window; chunk boundaries change nothing. Stream results
        are exact-length; single-shot are bucket-padded."""
        tr = mk_trace(3, n=150)
        progs = program_pool(n_random=2)
        costs = [p.smc_cycles() for p in progs]
        single = run_many([tr] * len(progs), JETSON_NANO, "ts",
                          policies=progs, policy_costs=costs, serial=True)
        stream = emulator.run_stream_many(
            [tr] * len(progs), JETSON_NANO, "ts", chunk=64,
            policies=progs, policy_costs=costs, serial=True)
        for p, a, s in zip(progs, single, stream):
            for k in ALL_FIELDS:
                assert int(a[k]) == int(s[k]), (p.name, k)
            np.testing.assert_array_equal(a["t_resp"][:tr.n], s["t_resp"])
            np.testing.assert_array_equal(a["t_issue"][:tr.n], s["t_issue"])

    def test_faults_and_mitigation_on_the_axis(self):
        """Mitigation programs (PARA/TRR) ride the axis under the fault
        model: BER/flips/mitigations match the staged path exactly."""
        fm = FaultModel(seed=3, hammer_threshold=64, hammer_flip_fp=30000,
                        weak_fp=200)
        sysf = JETSON_NANO.with_faults(fm)
        tr = mk_trace(4, n=100)
        progs = list(smcprog.mitigation_programs().values())
        axis = run_policies(tr, sysf, progs, mode="ts",
                            derive_cost=False, serial=True)
        for p, r in zip(progs, axis):
            staged = run(tr, dataclasses.replace(sysf, policy=p), "ts")
            assert_same(staged, r, tr.n, p.name)
            for k in ("flips", "mitigations", "weak_hits"):
                if k in staged:
                    assert int(staged[k]) == int(r[k]), (p.name, k)


class TestCompileScaling:
    def test_one_compile_per_bucket(self):
        """The axis contract: compiles count table-length BUCKETS, not
        programs. 8 bucket-8 programs + 1 bucket-32 program == exactly
        2 executables."""
        tr = mk_trace(5, n=40)
        progs = program_pool(n_random=2)          # all bucket 8
        b = smcprog.PolicyBuilder()
        v = b.score_age()
        for _ in range(10):                       # 21 ops -> bucket 32
            v = b.add(v, b.const(1))
        progs.append(b.build(score=v, name="long21"))
        assert {smcprog.table_bucket(p.n_ops) for p in progs} == {8, 32}
        emulator.cache_clear()
        run_policies(tr, JETSON_NANO, progs, mode="ts", serial=True)
        assert emulator.cache_stats()["misses"] == 2

    def test_repeat_sweep_compiles_nothing(self):
        tr = mk_trace(6, n=40)
        rng = np.random.RandomState(0)
        progs = [random_program(rng, name=f"p{i}") for i in range(12)]
        run_policies(tr, JETSON_NANO, progs, mode="ts", serial=True)
        before = emulator.cache_stats()["misses"]
        rng2 = np.random.RandomState(99)          # different CONTENT
        progs2 = [random_program(rng2, name=f"q{i}") for i in range(12)]
        run_policies(tr, JETSON_NANO, progs2, mode="ts", serial=True)
        assert emulator.cache_stats()["misses"] == before


class TestCampaignPolicyAxis:
    def test_axis_default_one_group_matches_legacy(self):
        tr = mk_trace(7)
        progs = program_pool(n_random=2)
        c = Campaign()
        c.add_policy_grid(tr, JETSON_NANO, progs)
        assert c.n_groups() == 1                  # one bucket, one group
        axis = c.run(serial=True)
        c2 = Campaign()
        c2.add_policy_grid(tr, JETSON_NANO, progs, policy_axis=False)
        assert c2.n_groups() == len(progs)
        legacy = c2.run(serial=True)
        for a, b in zip(axis, legacy):
            assert a["policy"] == b["policy"]
            assert_same(a, b, tr.n, a["policy"])

    def test_filter_arm_rides_the_policy_axis(self):
        """A policy grid run with and without the weak-row filter is one
        group and one dispatch; every row is bit-identical to its staged
        program run with or without the filter."""
        from repro.core.bloom import BloomFilter
        tr = mk_trace(9)
        progs = program_pool(n_random=2)
        rng = np.random.RandomState(0)
        bf = BloomFilter.build(rng.randint(0, 1 << 19, 150).astype(np.uint32),
                               m_bits=1 << 14, k=3)
        c = Campaign()
        for b in (None, (bf.bits, bf.k, bf.m_bits)):
            c.points += [Point(tr, JETSON_NANO, "ts", b, {"policy": p.name},
                               policy=p,
                               policy_cost=JETSON_NANO.smc_cycles_per_decision)
                         for p in progs]
        assert c.n_groups() == 1
        for pt, r in zip(c.points, c.run(serial=True)):
            staged = run(tr, dataclasses.replace(JETSON_NANO, policy=pt.policy),
                         "ts", bloom=pt.bloom)
            assert_same(staged, r, tr.n, pt.meta["policy"])

    def test_mixed_buckets_raise_naming_program(self):
        b = smcprog.PolicyBuilder()
        v = b.score_age()
        for _ in range(5):
            v = b.add(v, b.mul(v, v))             # 11 ops -> bucket 16
        big = b.build(score=v, name="wide-prog")
        with pytest.raises(ValueError, match="wide-prog"):
            Campaign().add_policy_grid(
                mk_trace(8, n=16), JETSON_NANO,
                [smcprog.frfcfs_program(), big])

    def test_checkpoint_digest_separates_policies(self):
        """Two points differing only in their runtime policy must get
        different content digests (checkpoint addresses)."""
        tr = mk_trace(9, n=16)
        a = Point(tr, JETSON_NANO, "ts", None, {},
                  policy=smcprog.frfcfs_program(), policy_cost=400)
        b = Point(tr, JETSON_NANO, "ts", None, {},
                  policy=smcprog.fcfs_program(), policy_cost=400)
        plain = Point(tr, JETSON_NANO, "ts", None, {})
        assert len({a.content_digest(), b.content_digest(),
                    plain.content_digest()}) == 3

    def test_service_policy_axis_stats(self):
        from repro.service.server import SweepServer
        tr = mk_trace(10, n=24)
        progs = program_pool(n_random=0)
        with SweepServer(max_batch=64, coalesce_window_s=0.02) as srv:
            cl = srv.register("c1")
            pts = [Point(tr, JETSON_NANO, "ts", None, {"policy": p.name},
                         policy=p, policy_cost=p.smc_cycles())
                   for p in progs]
            futs = srv.submit_points(cl, pts)
            recs = [f.result(300) for f in futs]
            st = srv.stats()
        assert st["policies_per_dispatch"] == float(len(progs))
        assert st["dispatches"]["policy_points"] == len(progs)
        assert sum(g["policies"] for g in st["groups"].values()) \
            == len(progs)
        legacy = run_policies(tr, JETSON_NANO, progs, mode="ts",
                              serial=True)
        for r, l in zip(recs, legacy):
            np.testing.assert_array_equal(r["t_resp"], l["t_resp"])


class TestPackingAndVM:
    def test_pack_program_layout(self):
        p = smcprog.frfcfs_program()
        t = smcprog.pack_program(p)
        assert t.shape == (9, 4) and t.dtype == np.int32
        assert tuple(t[0]) == (p.n_ops, p.score_reg, p.boost_reg,
                               p.mitigate_reg)
        assert (t[1 + p.n_ops:] == 0).all()       # OP_CONST 0 padding

    def test_pack_too_small_bucket_names_program(self):
        rng = np.random.RandomState(0)
        p = dataclasses.replace(random_program(rng, max_ops=8),
                                name="fat")
        with pytest.raises(ValueError, match="fat"):
            smcprog.pack_program(p, bucket=1)

    def test_table_bucket_floor_and_growth(self):
        assert smcprog.table_bucket(1) == 8
        assert smcprog.table_bucket(8) == 8
        assert smcprog.table_bucket(9) == 16
        assert smcprog.table_bucket(17) == 32
        with pytest.raises(ValueError):
            smcprog.table_bucket(0)

    def test_validate_errors_carry_row_and_opname(self):
        bad = smcprog.PolicyProgram(
            ((smcprog.OP_AGE, 0, 0, 0), (smcprog.OP_ADD, 0, 1, 0)),
            score_reg=1)
        with pytest.raises(ValueError, match=r"row 1 \(op_add\)"):
            bad.validate()


class TestPolicySearch:
    def test_generators_always_valid(self):
        rng = np.random.RandomState(0)
        for _ in range(200):
            p = random_program(rng)
            m = mutate(p, rng)
            c = crossover(p, random_program(rng), rng)
            for q in (p, m, c):
                q.validate()
                assert q.n_ops <= 8

    def test_search_is_deterministic_and_never_below_baseline(self):
        tr = mk_trace(12, n=48)
        a = search(tr, JETSON_NANO, generations=2, population=6,
                   seed=5, serial=True)
        b = search(tr, JETSON_NANO, generations=2, population=6,
                   seed=5, serial=True)
        assert a.best.digest == b.best.digest
        assert a.best_fitness == b.best_fitness
        # baseline is in the seed population: the result can only tie
        # or beat it
        assert a.best_fitness <= a.baseline_fitness
        assert a.n_dispatches <= 2
