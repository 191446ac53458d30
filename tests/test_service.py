"""Sweep service (ISSUE 9): multi-client bit-identity, cross-client
coalescing, weighted fairness, typed backpressure, socket transport,
drain/abort shutdown with resumable checkpoints, and the two
concurrency fixes that ride along (consistent ``cache_stats``
snapshots, executor atexit poisoning)."""
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import emulator, executor
from repro.core.bloom import BloomFilter
from repro.core.campaign import Campaign, Point, plan_groups
from repro.core.emulator import Trace
from repro.core.faults import FaultModel
from repro.core.smcprog import frfcfs_program
from repro.core.timescale import JETSON_NANO
from repro.service import (QueueFullError, ServerClosedError, SweepClient,
                           SweepServer, load_pending)

SYS_FAULTS = JETSON_NANO.with_faults(
    FaultModel(seed=3, hammer_threshold=8, hammer_flip_fp=30000,
               weak_fp=16000, retention_ticks=30, victim_slots=16))
SYS_POLICY = JETSON_NANO.with_policy(frfcfs_program())


def mk_traces(n_traces, base=56, step=9, seed=11):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n_traces):
        n = base + step * i
        out.append(Trace.of(kind=rng.randint(0, 2, n),
                            bank=rng.randint(0, 16, n),
                            row=rng.randint(0, 4096, n),
                            delta=rng.randint(1, 8, n),
                            dep=rng.randint(0, 2, n)))
    return out


def small_bloom(seed=0):
    rng = np.random.RandomState(seed)
    bf = BloomFilter.build(rng.randint(0, 1 << 19, 150).astype(np.uint32),
                           m_bits=1 << 14, k=3)
    return (bf.bits, bf.k, bf.m_bits)


def mixed_points(n_base=5, seed=11):
    """A grid mixing modes, fault/policy systems, and a bloom arm —
    every group-key dimension the coalescer must keep separate."""
    trs = mk_traces(n_base, seed=seed)
    bloom = small_bloom()
    pts = []
    for i, tr in enumerate(trs):
        pts.append(Point(tr, JETSON_NANO, "ts", None, {"idx": len(pts)}))
        pts.append(Point(tr, JETSON_NANO, "nots", None, {"idx": len(pts)}))
        if i % 2 == 0:
            pts.append(Point(tr, SYS_FAULTS, "ts", None, {"idx": len(pts)}))
            pts.append(Point(tr, JETSON_NANO, "ts", bloom,
                             {"idx": len(pts)}))
        else:
            pts.append(Point(tr, SYS_POLICY, "ts", None, {"idx": len(pts)}))
    return pts


def serial_reference(pts):
    c = Campaign()
    for p in pts:
        c.add(p.trace, p.sys, mode=p.mode, bloom=p.bloom, **p.meta)
    return c.run(serial=True)


def assert_same_record(a, b):
    assert int(a["exec_cycles"]) == int(b["exec_cycles"])
    np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
    np.testing.assert_array_equal(a["t_issue"], b["t_issue"])


class TestBitIdentity:
    def test_three_clients_mixed_grid_matches_serial_campaign(self):
        """K concurrent clients submitting an interleaved mixed grid
        (ts/nots x plain/fault/policy/bloom) get records bit-identical
        to one serial Campaign over the same points."""
        pts = mixed_points()
        ref = serial_reference(pts)
        got = {}
        errs = []
        with SweepServer(coalesce_window_s=0.05) as srv:
            def client(k):
                try:
                    cli = SweepClient(server=srv, name=f"c{k}")
                    cli.submit_points([p for j, p in enumerate(pts)
                                       if j % 3 == k])
                    for r in cli.collect():
                        got[r["idx"]] = r
                except BaseException as e:   # pragma: no cover
                    errs.append(e)
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(3)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(180)
            st = srv.stats()
        assert not errs, errs
        assert len(got) == len(ref)
        for i, r in enumerate(ref):
            assert_same_record(got[i], r)
        assert st["dispatches"]["points"] == len(pts)
        assert st["rejected"] == 0

    def test_filter_arms_share_dispatches_like_campaign(self):
        """Base and filtered points from two clients coalesce by the
        campaign's rule: one dispatch per bucket, each record
        bit-identical to a serial Campaign over the same points."""
        trs = mk_traces(3)   # buckets 64, 128, 128
        bloom = small_bloom()
        pts = [Point(tr, JETSON_NANO, "ts", b, {"idx": 2 * i + (b is not None)})
               for i, tr in enumerate(trs) for b in (None, bloom)]
        ref = serial_reference(pts)
        with SweepServer(coalesce_window_s=0.25) as srv:
            clis = [SweepClient(server=srv, name=f"c{k}") for k in range(2)]
            for k, cli in enumerate(clis):
                cli.submit_points(pts[k::2])
            got = {r["idx"]: r for cli in clis for r in cli.collect()}
            st = srv.stats()
        assert st["dispatches"]["count"] == len(plan_groups(pts)) == 2
        for i, r in enumerate(ref):
            assert_same_record(got[i], r)

    def test_coalesces_across_clients(self):
        """Same-group points from different clients share dispatches:
        the mean distinct-clients-per-dispatch exceeds 1."""
        tr = mk_traces(1, base=64)[0]
        with SweepServer(coalesce_window_s=0.25) as srv:
            clis = [SweepClient(server=srv, name=f"c{k}") for k in range(3)]
            for k, cli in enumerate(clis):
                cli.submit_points([Point(tr, JETSON_NANO, "ts", None,
                                         {"k": k, "j": j})
                                   for j in range(4)])
            recs = [cli.collect() for cli in clis]
            st = srv.stats()
        assert st["dispatches"]["count"] == 1
        assert st["coalesce_ratio"] == 3.0
        assert st["points_per_dispatch"] == 12.0
        base = recs[0][0]
        for rs in recs:
            assert len(rs) == 4
            for r in rs:
                assert_same_record(r, base)

    def test_collect_preserves_submission_order(self):
        pts = mixed_points(3, seed=4)
        ref = serial_reference(pts)
        with SweepServer(coalesce_window_s=0.02) as srv:
            cli = SweepClient(server=srv, name="solo")
            cli.submit_points(pts)
            out = cli.collect()
        assert [r["idx"] for r in out] == [r["idx"] for r in ref]
        for a, b in zip(out, ref):
            assert_same_record(a, b)


class TestBackpressure:
    def test_per_client_bound_is_typed_and_atomic(self):
        trs = mk_traces(4, base=48, step=0)
        with SweepServer(max_pending=2, coalesce_window_s=30.0,
                         max_batch=512) as srv:
            cli = SweepClient(server=srv, name="hog")
            with pytest.raises(QueueFullError) as ei:
                cli.submit_points([Point(t, JETSON_NANO, "ts") for t in trs])
            assert ei.value.scope == "per-client"
            assert ei.value.bound == 2 and ei.value.requested == 4
            # all-or-nothing: nothing from the rejected batch is queued
            assert srv.stats()["clients"]["hog"]["queue_depth"] == 0
            assert srv.stats()["clients"]["hog"]["rejected"] == 4
            cli.submit_points([Point(t, JETSON_NANO, "ts")
                               for t in trs[:2]])  # now fits
            srv.close(drain=True)
            assert len(cli.collect()) == 2

    def test_global_bound_names_the_global_scope(self):
        trs = mk_traces(3, base=48, step=0)
        with SweepServer(max_pending=8, max_queue=2, max_batch=512,
                         coalesce_window_s=30.0) as srv:
            a = SweepClient(server=srv, name="a")
            b = SweepClient(server=srv, name="b")
            a.submit_points([Point(trs[0], JETSON_NANO, "ts"),
                             Point(trs[1], JETSON_NANO, "ts")])
            with pytest.raises(QueueFullError) as ei:
                b.submit(trs[2], JETSON_NANO)
            assert ei.value.scope == "global"
            srv.close(drain=True)
            assert len(a.collect()) == 2

    def test_closed_server_raises_typed(self):
        tr = mk_traces(1)[0]
        srv = SweepServer()
        cli = SweepClient(server=srv, name="late")
        srv.close()
        with pytest.raises(ServerClosedError):
            cli.submit(tr, JETSON_NANO)
        with pytest.raises(ServerClosedError):
            SweepClient(server=srv, name="later")

    def test_stream_points_rejected_typed(self):
        with SweepServer() as srv:
            cli = SweepClient(server=srv, name="s")
            with pytest.raises(ValueError, match="stream"):
                cli.submit_points([Point(mk_traces(1)[0], JETSON_NANO,
                                         "ts", stream=True)])


class TestFairness:
    def test_stride_order_gives_weighted_share(self):
        """With A at weight 1 and B at weight 2 queued together, the
        dispatcher's stride drain interleaves them 1:2 — B holds two of
        every three leading slots (first six: A,B,B,A,B,B)."""
        tr = mk_traces(1, base=64)[0]
        srv = SweepServer(coalesce_window_s=30.0, max_batch=512)
        try:
            a = SweepClient(server=srv, name="a", weight=1.0)
            b = SweepClient(server=srv, name="b", weight=2.0)
            # the server condition uses an RLock: holding it here keeps
            # the dispatcher from draining until BOTH batches are queued
            with srv._cond:
                a.submit_points([Point(tr, JETSON_NANO, "ts", None,
                                       {"c": "a", "j": j})
                                 for j in range(4)])
                b.submit_points([Point(tr, JETSON_NANO, "ts", None,
                                       {"c": "b", "j": j})
                                 for j in range(4)])
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with srv._cond:
                    jobs = [j for bk in srv._buckets.values()
                            for j in bk.jobs]
                if len(jobs) == 8:
                    break
                time.sleep(0.01)
            order = [j.client for j in jobs]
            assert order[:6] == ["a", "b", "b", "a", "b", "b"], order
            srv.close(drain=True)
            assert len(a.collect()) == 4 and len(b.collect()) == 4
        finally:
            srv.close(drain=False)


class TestSocket:
    def test_roundtrip_stats_and_typed_errors(self):
        pts = mixed_points(3, seed=9)
        ref = serial_reference(pts)
        with SweepServer(coalesce_window_s=0.02, max_pending=64) as srv:
            host, port = srv.listen()
            with SweepClient(address=(host, port), name="far") as cli:
                assert cli.name == "far"
                cli.submit_points(pts)
                out = cli.collect()
                for a, b in zip(out, ref):
                    assert_same_record(a, b)
                st = cli.stats()
                assert st["clients"]["far"]["completed"] == len(pts)
            # typed backpressure crosses the wire with fields intact
            with SweepServer(max_pending=1, coalesce_window_s=30.0) as tiny:
                h2, p2 = tiny.listen()
                with SweepClient(address=(h2, p2), name="far2") as cli2:
                    with pytest.raises(QueueFullError) as ei:
                        cli2.submit_points(
                            [Point(pts[0].trace, JETSON_NANO, "ts"),
                             Point(pts[1].trace, JETSON_NANO, "ts")])
                    assert ei.value.scope == "per-client"
                    assert ei.value.bound == 1


class TestCheckpoint:
    def test_drain_close_leaves_loadable_group_checkpoints(self, tmp_path):
        d = str(tmp_path)
        pts = mixed_points(3, seed=6)
        with SweepServer(checkpoint=d, coalesce_window_s=0.02) as srv:
            cli = SweepClient(server=srv, name="a")
            cli.submit_points(pts)
            first = cli.collect()
        assert any(f.startswith("group-") for f in os.listdir(d))
        # a fresh server serves the identical grid from disk: zero
        # executor dispatches, bit-identical records
        with SweepServer(checkpoint=d, coalesce_window_s=0.02) as srv:
            cli = SweepClient(server=srv, name="b")
            cli.submit_points(pts)
            again = cli.collect()
            st = srv.stats()
        assert st["dispatches"]["loaded_from_checkpoint"] \
            == st["dispatches"]["count"] > 0
        for a, b in zip(first, again):
            assert_same_record(a, b)

    def test_abort_close_pends_unfinished_and_resumes(self, tmp_path):
        """close(drain=False) fails queued points with a typed error
        naming the manifest dir; Campaign.run(checkpoint=dir) then
        finishes the sweep bit-identically, loading finished groups."""
        d = str(tmp_path)
        pts = mixed_points(4, seed=8)
        half, rest = pts[: len(pts) // 2], pts[len(pts) // 2:]
        with SweepServer(checkpoint=d, coalesce_window_s=0.02) as srv:
            cli = SweepClient(server=srv, name="a")
            cli.submit_points(half)
            cli.collect()
        srv = SweepServer(checkpoint=d, coalesce_window_s=30.0,
                          max_batch=512)
        cli = SweepClient(server=srv, name="a")
        cli.submit_points(rest)
        srv.close(drain=False)
        with pytest.raises(ServerClosedError) as ei:
            cli.collect()
        assert ei.value.checkpoint == d
        pend = load_pending(d)
        assert [p.meta["idx"] for p in pend] == [p.meta["idx"] for p in rest]
        c = Campaign()
        for p in half + pend:
            c.add(p.trace, p.sys, mode=p.mode, bloom=p.bloom, **p.meta)
        resumed = c.run(checkpoint=d)
        ref = serial_reference(pts)
        assert len(resumed) == len(ref)
        for a, b in zip(resumed, ref):
            assert_same_record(a, b)


class TestShutdownSafety:
    def test_interpreter_exit_without_close_does_not_hang(self):
        """A client process that never closes its server — including
        one with queued-but-undispatched points — must exit cleanly:
        the service atexit hook closes live servers before the executor
        pool poisons itself."""
        code = """
import numpy as np
from repro.core.emulator import Trace
from repro.core.timescale import JETSON_NANO
from repro.service import SweepServer, SweepClient
rng = np.random.RandomState(0)
def mk():
    return Trace.of(kind=rng.randint(0, 2, 48), bank=rng.randint(0, 16, 48),
                    row=rng.randint(0, 4096, 48), delta=rng.randint(1, 8, 48),
                    dep=rng.randint(0, 2, 48))
srv = SweepServer(coalesce_window_s=0.01)
cli = SweepClient(server=srv, name="x")
cli.submit(mk(), JETSON_NANO)
assert cli.collect()[0]["exec_cycles"] > 0
# second server: points queued behind a huge window, NEVER collected,
# NEVER closed -- exit must still be clean
srv2 = SweepServer(coalesce_window_s=3600.0)
cli2 = SweepClient(server=srv2, name="y")
cli2.submit(mk(), JETSON_NANO)
print("EXITING")
"""
        env = dict(os.environ, PYTHONPATH="src")
        proc = subprocess.run([sys.executable, "-c", code], cwd="/root/repo",
                              env=env, capture_output=True, text=True,
                              timeout=420)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "EXITING" in proc.stdout

    def test_executor_shutdown_poisons_then_set_workers_rearms(self):
        class Probe:
            retryable = False

            def __init__(self):
                self.ran = threading.Event()

            def run(self):
                self.ran.set()

        prev = executor.workers()
        try:
            executor.shutdown()
            assert executor.is_shutdown()
            with pytest.raises(RuntimeError, match="shut down"):
                executor.submit_task(Probe())
            executor.set_workers(prev)
            assert not executor.is_shutdown()
            p = Probe()
            assert executor.submit_task(p).result(30) is None  # no failure
            assert p.ran.is_set()
        finally:
            executor.set_workers(prev)


def test_cache_stats_consistent_under_threads():
    """Satellite 1: `cache_stats()` snapshots must be internally
    consistent (lookups == hits + misses, size <= capacity,
    size == misses - evictions between clears) even while worker
    threads drive lookups through the executable LRU."""
    trs = mk_traces(2, base=40, step=24, seed=2)
    stop = threading.Event()
    errs = []

    def reader():
        while not stop.is_set():
            s = emulator.cache_stats()
            try:
                assert s["lookups"] == s["hits"] + s["misses"]
                assert s["size"] <= s["capacity"]
                assert s["size"] == s["misses"] - s["evictions"]
            except AssertionError as e:   # pragma: no cover
                errs.append(e)
                stop.set()
                return

    def worker(tr):
        for _ in range(30):
            if stop.is_set():
                return
            emulator.run(tr, JETSON_NANO, "ts")

    threads = [threading.Thread(target=reader) for _ in range(2)] + \
        [threading.Thread(target=worker, args=(trs[i % 2],))
         for i in range(3)]
    for t in threads[2:]:
        t.start()
    for t in threads[:2]:
        t.start()
    for t in threads[2:]:
        t.join(300)
    stop.set()
    for t in threads[:2]:
        t.join(30)
    assert not errs, errs[0]
