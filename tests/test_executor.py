"""The overlapped campaign executor (PR 5): bit-identity of overlapped /
sharded execution vs the serial PR 4 group loop, add-order preservation,
the LRU bound on the in-memory executable cache, the persistent on-disk
compile cache across processes, and the ValueError API guards. PR 8
adds the fault-tolerance layer: per-task failure isolation with
aggregate errors, bounded retry + dispatch timeouts, the stream-prefetch
shutdown contract, and campaign checkpoint/resume (including a
kill-mid-campaign subprocess resume)."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import emulator, executor, smcprog
from repro.core.bloom import BloomFilter
from repro.core.campaign import Campaign
from repro.core.emulator import Trace, run_many
from repro.core.timescale import JETSON_NANO


def mk_trace(rng, n):
    return Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, 16, n),
                    row=rng.randint(0, 4096, n), delta=rng.randint(1, 8, n),
                    dep=rng.randint(0, 2, n))


def small_bloom(seed=0):
    rng = np.random.RandomState(seed)
    bf = BloomFilter.build(rng.randint(0, 1 << 19, 150).astype(np.uint32),
                           m_bits=1 << 14, k=3)
    return (bf.bits, bf.k, bf.m_bits)


def mixed_grid_campaign(seed=3):
    """A heterogeneous grid spanning modes x policies x bloom arms x two
    length buckets — the shape the overlapped executor must keep
    bit-identical to the serial loop."""
    rng = np.random.RandomState(seed)
    trs = [mk_trace(rng, n) for n in (40, 44, 90, 95)]
    bloom = small_bloom(seed)
    prog = smcprog.frfcfs_program()
    c = Campaign()
    for i, tr in enumerate(trs):
        for mode in ("ts", "nots"):
            c.add(tr, JETSON_NANO, mode=mode, i=i, arm="plain")
        c.add(tr, JETSON_NANO, mode="ts", bloom=bloom, i=i, arm="bloom")
        c.add_policy_grid(tr, JETSON_NANO, [prog], mode="ts",
                          derive_cost=False, i=i, arm="policy")
    return c


class TestOverlapBitIdentity:
    def test_campaign_overlapped_matches_serial(self):
        c = mixed_grid_campaign()
        assert c.n_groups() >= 6  # genuinely heterogeneous
        a = c.run(serial=True)
        b = c.run()
        assert len(a) == len(b) == len(c)
        for x, y in zip(a, b):
            assert int(x["exec_cycles"]) == int(y["exec_cycles"])
            assert int(x["row_hits"]) == int(y["row_hits"])
            np.testing.assert_array_equal(x["t_resp"], y["t_resp"])
            np.testing.assert_array_equal(x["t_issue"], y["t_issue"])
            assert x["mode"] == y["mode"]

    def test_run_many_overlapped_matches_serial(self):
        rng = np.random.RandomState(11)
        trs = [mk_trace(rng, n) for n in (35, 70, 140, 40, 80)]
        modes = ["ts", "nots", "ts", "reference", "ts"]
        a = run_many(trs, JETSON_NANO, modes, serial=True)
        b = run_many(trs, JETSON_NANO, modes)
        for x, y in zip(a, b):
            assert int(x["exec_cycles"]) == int(y["exec_cycles"])
            np.testing.assert_array_equal(x["t_resp"], y["t_resp"])

    def test_add_order_preserved(self):
        """Records come back in add order even though groups execute
        concurrently and finish in arbitrary order."""
        c = mixed_grid_campaign(seed=9)
        for j, p in enumerate(c.points):
            p.meta["seq"] = j
        recs = c.run()
        assert [r["seq"] for r in recs] == list(range(len(c)))
        # and per-point identity against the single-trace path
        k = len(c) // 2
        p = c.points[k]
        solo = emulator.run(p.trace, p.sys, p.mode, bloom=p.bloom)
        assert int(solo["exec_cycles"]) == int(recs[k]["exec_cycles"])

    def test_executor_propagates_worker_errors(self):
        def boom():
            raise RuntimeError("pack failed")
        tasks = [executor.GroupTask(fn=lambda: None, pack=boom,
                                    finalize=lambda o, c: None)
                 for _ in range(3)]
        with pytest.raises(RuntimeError, match="pack failed"):
            executor.execute(tasks, serial=False)

    def test_set_workers_validates_and_restores(self):
        old = executor.set_workers(1)
        try:
            # workers=1 forces the serial fallback; results unchanged
            rng = np.random.RandomState(2)
            trs = [mk_trace(rng, 40), mk_trace(rng, 90)]
            out = run_many(trs, JETSON_NANO, ["ts", "nots"])
            assert all(r is not None for r in out)
            with pytest.raises(ValueError, match="worker count"):
                executor.set_workers(0)
        finally:
            executor.set_workers(old)


class FakeTask:
    """Executor-contract probe: controllable failures, no XLA compiles."""
    retryable = True

    def __init__(self, label, fails=0, sleep=0.0):
        self.label, self.cost = label, 1
        self.fails, self.sleep, self.runs = fails, sleep, 0

    def run(self):
        self.runs += 1
        time.sleep(self.sleep)
        if self.runs <= self.fails:
            raise RuntimeError(f"boom {self.label} run{self.runs}")


class TestFailureIsolation:
    def test_all_failures_aggregated_with_every_label(self):
        """One bad task must not hide another: the aggregate error names
        every failed label and carries per-task records."""
        with pytest.raises(executor.ExecutionError) as ei:
            executor.execute([FakeTask("a", fails=9), FakeTask("ok"),
                              FakeTask("b", fails=9)], serial=True)
        assert "2 task(s) failed" in str(ei.value)
        assert "a" in str(ei.value) and "b" in str(ei.value)
        assert {f.label for f in ei.value.failures} == {"a", "b"}
        assert all(isinstance(f.error, RuntimeError)
                   for f in ei.value.failures)

    def test_siblings_complete_despite_failure(self):
        ok, bad = FakeTask("ok"), FakeTask("bad", fails=9)
        fails = executor.execute([bad, ok], serial=True,
                                 raise_on_error=False)
        assert ok.runs == 1
        assert [f.label for f in fails] == ["bad"]

    def test_retry_with_backoff_recovers_transient_failure(self):
        flaky = FakeTask("flaky", fails=2)
        out = executor.execute([flaky], serial=True, retries=3,
                               backoff=0.001)
        assert out == [] and flaky.runs == 3
        # exhausted retries still fail, reporting the attempt count
        dead = FakeTask("dead", fails=99)
        fails = executor.execute([dead], serial=True, retries=2,
                                 backoff=0.001, raise_on_error=False)
        assert fails[0].attempts == 3 and dead.runs == 3

    def test_non_retryable_tasks_never_retry(self):
        t = FakeTask("stream-ish", fails=1)
        t.retryable = False
        fails = executor.execute([t], serial=True, retries=5,
                                 backoff=0.001, raise_on_error=False)
        assert t.runs == 1 and fails[0].attempts == 1

    def test_dispatch_timeout_abandons_stuck_task(self):
        """Needs >= 2 workers: with one, the sibling queues behind the
        abandoned thread (timeouts only bound DISPATCHED work)."""
        slow, quick = FakeTask("slow", sleep=1.5), FakeTask("quick")
        old = executor.set_workers(max(2, executor.workers()))
        try:
            t0 = time.monotonic()
            fails = executor.execute([slow, quick], serial=False,
                                     timeout=0.3, raise_on_error=False)
            dt = time.monotonic() - t0
        finally:
            executor.set_workers(old)  # joins the abandoned sleeper
        assert dt < 1.0  # returned without waiting the sleep out
        assert [f.label for f in fails] == ["slow"]
        assert isinstance(fails[0].error, TimeoutError)
        assert quick.runs == 1


class TestStreamPrefetchShutdown:
    """The prefetch thread must stop deterministically on ANY exit from
    StreamTask.run() — normal completion, a window raising in fn, or the
    feeder itself failing — never leak waiting on a full queue."""

    @staticmethod
    def _prefetch_threads():
        return [t for t in threading.enumerate()
                if t.name.startswith("repro-stream-prefetch")]

    @staticmethod
    def _task(n_windows=64, fn=None):
        def windows(ctx):
            for i in range(n_windows):
                yield (np.full(4, i),), 0
        return executor.StreamTask(
            fn=fn or (lambda state, a: (state + 1, (a,))),
            pack=lambda: (0, None), windows=windows,
            consume=lambda out, ctx: None,
            finalize=lambda state, ctx: None, label="probe")

    def _assert_no_leak(self):
        deadline = time.monotonic() + 5.0
        while self._prefetch_threads() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert self._prefetch_threads() == []

    def test_normal_completion_leaves_no_thread(self):
        self._task().run()
        self._assert_no_leak()

    def test_consumer_error_stops_feeder_promptly(self):
        """fn raising on an early window: the feeder is still trying to
        queue dozens more. Shutdown must drain it out of q.put() fast."""
        def fn(state, a):
            if state == 2:
                raise RuntimeError("window exploded")
            return state + 1, (a,)

        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="window exploded"):
            self._task(n_windows=500, fn=fn).run()
        assert time.monotonic() - t0 < 5.0
        self._assert_no_leak()

    def test_feeder_error_surfaces_on_consumer(self):
        def windows(ctx):
            yield (np.zeros(1),), 0
            raise ValueError("generator died")

        t = self._task()
        t.windows = windows
        with pytest.raises(ValueError, match="generator died"):
            t.run()
        self._assert_no_leak()


def _identical_records(a, b):
    assert set(a) == set(b)
    for k in a:
        x, y = a[k], b[k]
        if isinstance(x, np.ndarray) or isinstance(y, np.ndarray):
            np.testing.assert_array_equal(x, y)
        else:
            assert x == y, k


class TestCampaignFaultTolerance:
    def _campaign(self):
        rng = np.random.RandomState(23)
        tr1, tr2 = mk_trace(rng, 44), mk_trace(rng, 46)
        c = Campaign()
        c.add(tr1, JETSON_NANO, workload="a")
        c.add(tr2, JETSON_NANO, workload="b")       # same group as a
        c.add(tr1, JETSON_NANO, mode="nots", workload="a-nots")
        return c

    def test_checkpoint_resume_recomputes_nothing(self, tmp_path):
        ck = str(tmp_path / "ckpt")
        c = self._campaign()
        r1 = c.run(checkpoint=ck)
        assert c.last_run["loaded"] == 0 and c.last_run["computed"] == 2
        assert len(os.listdir(ck)) == 2
        c2 = self._campaign()
        r2 = c2.run(checkpoint=ck)
        assert c2.last_run["loaded"] == 2 and c2.last_run["computed"] == 0
        for a, b in zip(r1, r2):
            _identical_records(a, b)
        # and checkpointing itself never changes results
        r3 = self._campaign().run()
        for a, b in zip(r1, r3):
            _identical_records(a, b)

    def test_checkpoint_is_content_addressed(self, tmp_path):
        """A different trace in the group must MISS the old file."""
        ck = str(tmp_path / "ckpt")
        c = self._campaign()
        c.run(checkpoint=ck)
        c2 = self._campaign()
        c2.points[0].trace = mk_trace(np.random.RandomState(99), 44)
        c2.run(checkpoint=ck)
        assert c2.last_run["loaded"] == 1       # only the untouched group
        assert c2.last_run["computed"] == 1

    def test_quarantine_completes_other_groups(self, monkeypatch):
        c = self._campaign()
        baseline = self._campaign().run()
        orig = emulator.prepare_tasks

        def poisoned(trs, sysc, modes, blooms, outs):
            tasks = orig(trs, sysc, modes, blooms, outs)
            if modes[0] == "nots":
                for t in tasks:
                    def die():
                        raise RuntimeError("pack died")
                    t.pack = die
            return tasks

        monkeypatch.setattr(emulator, "prepare_tasks", poisoned)
        recs = c.run(on_error="quarantine")
        assert c.last_run["failed"] == 1 and c.last_run["computed"] == 1
        errs = [r for r in recs if "error" in r]
        assert len(errs) == 1 and errs[0]["workload"] == "a-nots"
        assert errs[0]["error_type"] == "RuntimeError"
        assert "pack died" in errs[0]["error"]
        good = [r for r in recs if "error" not in r]
        for a, b in zip([r for r in baseline
                         if r["workload"] != "a-nots"], good):
            _identical_records(a, b)
        # default on_error='raise' still raises the aggregate
        with pytest.raises(executor.ExecutionError, match="pack died"):
            self._campaign().run()

    def test_run_validates_on_error(self):
        with pytest.raises(ValueError, match="on_error"):
            Campaign().run(on_error="ignore")

    def test_killed_campaign_resumes_bit_identically(self, tmp_path):
        """The end-to-end resume contract: a process killed mid-campaign
        (first group checkpointed, second never ran) restarts, recomputes
        ZERO finished groups and produces the full result set, matching
        this process bit-for-bit."""
        child = tmp_path / "child.py"
        ck = tmp_path / "ckpt"
        cache = tmp_path / "xla_cache"
        child.write_text(
            "import json, os, sys\n"
            "from repro.utils.jax_compat import "
            "enable_persistent_compile_cache\n"
            "enable_persistent_compile_cache(sys.argv[1])\n"
            "import numpy as np\n"
            "from repro.core import emulator\n"
            "from repro.core.campaign import Campaign\n"
            "from repro.core.emulator import Trace\n"
            "from repro.core.timescale import JETSON_NANO\n"
            "rng = np.random.RandomState(29)\n"
            "def mk(n):\n"
            "    return Trace.of(kind=rng.randint(0, 2, n),\n"
            "                    bank=rng.randint(0, 16, n),\n"
            "                    row=rng.randint(0, 4096, n),\n"
            "                    delta=rng.randint(1, 8, n),\n"
            "                    dep=rng.randint(0, 2, n))\n"
            "c = Campaign()\n"
            "c.add(mk(40), JETSON_NANO, workload='w0')\n"
            "c.add(mk(40), JETSON_NANO, mode='nots', workload='w1')\n"
            "if os.environ.get('DIE_MID_CAMPAIGN'):\n"
            "    orig = emulator.prepare_tasks\n"
            "    def sabotage(trs, sysc, modes, blooms, outs):\n"
            "        ts = orig(trs, sysc, modes, blooms, outs)\n"
            "        if modes[0] == 'nots':\n"
            "            for t in ts:\n"
            "                t.pack = lambda: os._exit(9)\n"
            "        return ts\n"
            "    emulator.prepare_tasks = sabotage\n"
            "recs = c.run(serial=True, checkpoint=sys.argv[2])\n"
            "print(json.dumps({\n"
            "  'loaded': c.last_run['loaded'],\n"
            "  'computed': c.last_run['computed'],\n"
            "  'exec': [int(r['exec_cycles']) for r in recs],\n"
            "  'resp': [int(np.asarray(r['t_resp']).astype(np.int64).sum())\n"
            "           for r in recs]}))\n")
        src = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src"))
        env = dict(os.environ)
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

        env_kill = dict(env, DIE_MID_CAMPAIGN="1")
        p1 = subprocess.run(
            [sys.executable, str(child), str(cache), str(ck)], env=env_kill,
            capture_output=True, text=True, timeout=420)
        assert p1.returncode == 9, (p1.returncode, p1.stderr[-2000:])
        files = os.listdir(ck)
        assert len(files) == 1      # group w0 persisted before the kill

        p2 = subprocess.run(
            [sys.executable, str(child), str(cache), str(ck)], env=env,
            capture_output=True, text=True, timeout=420)
        assert p2.returncode == 0, p2.stderr[-2000:]
        out = json.loads(p2.stdout.strip().splitlines()[-1])
        assert out["loaded"] == 1 and out["computed"] == 1
        assert len(os.listdir(ck)) == 2

        # bit-identity against this process, fresh compute, no checkpoint
        rng = np.random.RandomState(29)
        c = Campaign()
        c.add(mk_trace(rng, 40), JETSON_NANO, workload="w0")
        c.add(mk_trace(rng, 40), JETSON_NANO, mode="nots", workload="w1")
        here = c.run(serial=True)
        assert out["exec"] == [int(r["exec_cycles"]) for r in here]
        assert out["resp"] == [
            int(np.asarray(r["t_resp"]).astype(np.int64).sum())
            for r in here]


class TestSharding:
    def test_forced_single_device_shard_map_bit_identical(self):
        """The shard_map code path itself (1-device mesh) must be
        bit-identical to the plain vmap path — the single-device half
        of the sharding contract."""
        rng = np.random.RandomState(5)
        trs = [mk_trace(rng, 40) for _ in range(4)]
        bloom = small_bloom(5)
        old = emulator.set_sharding("force")
        try:
            a = run_many(trs, JETSON_NANO, "ts")
            ab = run_many(trs, JETSON_NANO, "ts", blooms=bloom)
        finally:
            emulator.set_sharding(old)
        b = run_many(trs, JETSON_NANO, "ts")
        bb = run_many(trs, JETSON_NANO, "ts", blooms=bloom)
        for x, y in zip(a + ab, b + bb):
            assert int(x["exec_cycles"]) == int(y["exec_cycles"])
            np.testing.assert_array_equal(x["t_resp"], y["t_resp"])

    def test_set_sharding_validates(self):
        with pytest.raises(ValueError, match="sharding mode"):
            emulator.set_sharding("sometimes")

    def test_shard_count_divisibility(self):
        """Sharding only engages when the padded batch divides across a
        power-of-two device count; 'off' always disables."""
        old = emulator.set_sharding("off")
        try:
            assert emulator._shard_count(8) == 0
        finally:
            emulator.set_sharding(old)

    def test_multi_device_sharded_and_persistent_cache(self, tmp_path):
        """Two forced host devices in a subprocess: the shard_map'd
        batch axis must reproduce this (single-device, unsharded)
        process bit-for-bit, and a second process over the same
        persistent cache dir must skip the XLA compiles (hits > 0)."""
        child = tmp_path / "child.py"
        cache = tmp_path / "xla_cache"
        child.write_text(
            "import json, os, sys\n"
            "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')\n"
            "    + ' --xla_force_host_platform_device_count=2')\n"
            "import numpy as np\n"
            "from repro.utils.jax_compat import (\n"
            "    enable_persistent_compile_cache, persistent_cache_stats)\n"
            "enable_persistent_compile_cache(sys.argv[1])\n"
            "import jax\n"
            "from repro.core import emulator\n"
            "from repro.core.emulator import Trace, run_many\n"
            "from repro.core.timescale import JETSON_NANO\n"
            "assert jax.local_device_count() == 2\n"
            "assert emulator._shard_count(4) == 2  # sharding engages\n"
            "rng = np.random.RandomState(17)\n"
            "def mk(n):\n"
            "    return Trace.of(kind=rng.randint(0, 2, n),\n"
            "                    bank=rng.randint(0, 16, n),\n"
            "                    row=rng.randint(0, 4096, n),\n"
            "                    delta=rng.randint(1, 8, n),\n"
            "                    dep=rng.randint(0, 2, n))\n"
            "trs = [mk(40), mk(42), mk(44), mk(46), mk(90), mk(95)]\n"
            "out = run_many(trs, JETSON_NANO,\n"
            "               ['ts'] * 4 + ['nots', 'nots'])\n"
            "print(json.dumps({\n"
            "  'exec': [int(r['exec_cycles']) for r in out],\n"
            "  'resp': [int(np.asarray(r['t_resp']).astype(np.int64).sum())\n"
            "           for r in out],\n"
            "  'pcache': persistent_cache_stats()}))\n")
        src = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src"))
        env = dict(os.environ)
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        outs = []
        for _ in range(2):
            p = subprocess.run(
                [sys.executable, str(child), str(cache)], env=env,
                capture_output=True, text=True, timeout=420)
            assert p.returncode == 0, p.stderr[-2000:]
            outs.append(json.loads(p.stdout.strip().splitlines()[-1]))
        first, second = outs
        # same sweep in this (single-device) process, no sharding
        rng = np.random.RandomState(17)
        trs = [mk_trace(rng, n) for n in (40, 42, 44, 46, 90, 95)]
        here = run_many(trs, JETSON_NANO, ["ts"] * 4 + ["nots", "nots"])
        assert first["exec"] == second["exec"] \
            == [int(r["exec_cycles"]) for r in here]
        assert first["resp"] == second["resp"] \
            == [int(np.asarray(r["t_resp"]).astype(np.int64).sum())
                for r in here]
        # cold process: everything misses; warm process: disk hits
        assert first["pcache"]["misses"] > 0
        assert second["pcache"]["hits"] > 0
        assert second["pcache"]["misses"] == 0


class TestCachePlacement:
    def test_env_dir_is_the_only_cache_dir(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set, the persistent cache goes
        there: enable_persistent_compile_cache sets no other directory,
        and a run's executables land in it."""
        env_dir = tmp_path / "env_cache"
        code = (
            "import os\n"
            "import numpy as np\n"
            "from repro.utils import jax_compat\n"
            "d = jax_compat.enable_persistent_compile_cache()\n"
            "import jax\n"
            "assert d == os.environ['JAX_COMPILATION_CACHE_DIR'], d\n"
            "assert jax.config.jax_compilation_cache_dir == d\n"
            "from repro.core.emulator import Trace, run\n"
            "from repro.core.timescale import JETSON_NANO\n"
            "run(Trace.of(np.zeros(8), np.arange(8), np.zeros(8),\n"
            "             np.ones(8)), JETSON_NANO)\n"
            "assert jax_compat.persistent_cache_stats()['dir'] == d\n")
        src = os.path.abspath(
            os.path.join(os.path.dirname(__file__), "..", "src"))
        env = dict(os.environ, PYTHONPATH=src,
                   JAX_COMPILATION_CACHE_DIR=str(env_dir))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           cwd=str(tmp_path), capture_output=True,
                           text=True, timeout=300)
        assert p.returncode == 0, p.stderr[-2000:]
        assert os.listdir(env_dir)
        assert not (tmp_path / "artifacts").exists()

    def test_default_dir_is_the_checkout_not_the_cwd(self, tmp_path,
                                                     monkeypatch):
        from repro.utils import jax_compat
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        seen = []
        for sub in ("a", "b"):
            (tmp_path / sub).mkdir()
            monkeypatch.chdir(tmp_path / sub)
            seen.append(jax_compat.default_cache_dir())
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert seen[0] == seen[1] == os.path.join(root, "artifacts",
                                                  "xla_cache")


class TestCacheLRU:
    def test_lru_bounds_hundred_group_sweep(self):
        """A 100-group sweep must not retain 100 executables: the LRU
        cap bounds the cache and counts evictions; cache_clear resets
        every counter, including the new ones."""
        emulator.cache_clear()
        old = emulator.set_cache_capacity(8)
        try:
            base = emulator.compile_key(32, 1, JETSON_NANO, "ts", None, 40)
            for i in range(100):  # 100 distinct compile keys
                key = (32, 40 + 2 * i) + base[2:]
                emulator._batched_fn(key)
            st = emulator.cache_stats()
            assert st["size"] <= 8
            assert st["misses"] == 100
            assert st["evictions"] == 92
            # most-recent key is retained...
            emulator._batched_fn((32, 40 + 2 * 99) + base[2:])
            assert emulator.cache_stats()["hits"] == 1
            # ...the oldest was evicted
            emulator._batched_fn((32, 40) + base[2:])
            assert emulator.cache_stats()["misses"] == 101
            emulator.cache_clear()
            st = emulator.cache_stats()
            assert (st["hits"], st["misses"], st["evictions"], st["size"]) \
                == (0, 0, 0, 0)
        finally:
            emulator.set_cache_capacity(old)
            emulator.cache_clear()

    def test_lru_end_to_end_eviction_and_recompile(self):
        """Through the real run path: with capacity 2, a third distinct
        group evicts the first, and revisiting it recompiles (a miss,
        not a stale hit) with results unchanged."""
        rng = np.random.RandomState(31)
        t32, t64, t128 = (mk_trace(rng, n) for n in (20, 40, 80))
        emulator.cache_clear()
        old = emulator.set_cache_capacity(2)
        try:
            first = int(emulator.run(t32, JETSON_NANO, "ts")["exec_cycles"])
            emulator.run(t64, JETSON_NANO, "ts")
            emulator.run(t128, JETSON_NANO, "ts")
            st = emulator.cache_stats()
            assert st["size"] == 2 and st["evictions"] == 1
            again = emulator.run(t32, JETSON_NANO, "ts")
            st2 = emulator.cache_stats()
            assert st2["misses"] == st["misses"] + 1  # genuinely recompiled
            assert int(again["exec_cycles"]) == first
        finally:
            emulator.set_cache_capacity(old)
            emulator.cache_clear()

    def test_capacity_validation_and_shrink(self):
        with pytest.raises(ValueError, match="capacity"):
            emulator.set_cache_capacity(0)
        old = emulator.set_cache_capacity(4)
        emulator.set_cache_capacity(old)
        assert emulator.cache_stats()["capacity"] == old


class TestValueErrorGuards:
    """The mode guards must be real exceptions (asserts vanish under
    ``python -O``) and carry the offending value."""

    def test_campaign_add_bad_mode(self):
        with pytest.raises(ValueError, match="'warp'"):
            Campaign().add(mk_trace(np.random.RandomState(0), 8),
                           JETSON_NANO, mode="warp")

    def test_add_policy_grid_bad_mode(self):
        with pytest.raises(ValueError, match="'fast'"):
            Campaign().add_policy_grid(
                mk_trace(np.random.RandomState(0), 8), JETSON_NANO,
                [smcprog.frfcfs_program()], mode="fast")

    def test_run_many_bad_mode(self):
        tr = mk_trace(np.random.RandomState(0), 8)
        with pytest.raises(ValueError, match="'emu'"):
            run_many([tr], JETSON_NANO, "emu")
        with pytest.raises(ValueError, match="match len"):
            run_many([tr, tr], JETSON_NANO, ["ts"])

    def test_run_bad_mode(self):
        with pytest.raises(ValueError, match="'x'"):
            emulator.run(mk_trace(np.random.RandomState(0), 8),
                         JETSON_NANO, "x")
