"""Paper-figure benchmarks (Table 1 / Figs. 8, 10-14 + Sec. 6 validation).

Each function returns a list of (name, value, derived) rows; ``run.py``
prints them as CSV. Modeled-CPU calibration: the TS configuration models
the Jetson Nano's A57 (3-wide OoO, 64B NEON copies -> few cycles/line);
the No-TS configuration models PiDRAM's 50 MHz single-issue rv64
(word-granular copy loop -> ~20 cycles/line). Same program, different
modeled CPUs — exactly the modeling gap the paper quantifies.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro.core import emulator, traces
from repro.core.cachesim import LLC
from repro.core.campaign import Campaign
from repro.core.dram import Geometry
from repro.core.emulator import Trace, run, run_many
from repro.core.profiling import DeviceModel
from repro.core.techniques import RowClone, TRCDReduction
from repro.core.timescale import JETSON_NANO, PIDRAM_LIKE, SystemConfig

GEO = Geometry()
TS_LINE_DELTA = 4     # A57-class copy loop, cycles per 64B line
NOTS_LINE_DELTA = 20  # 50 MHz in-order rv64 copy loop

_DEVICE = None


def device():
    global _DEVICE
    if _DEVICE is None:
        _DEVICE = DeviceModel(GEO)
    return _DEVICE


# ---------------- Sec. 6: time-scaling validation ----------------

def bench_timescale_validation():
    """Sec. 6 validation, batched: every (kernel x {ts, reference}) arm
    runs in one Campaign (ts and reference share one executable), and
    the FPGA-clock invariance sweep is a second Campaign over the three
    SMC-speed SystemConfigs."""
    rows = []
    c = Campaign()
    for i, kern in enumerate(traces.POLYBENCH[:10]):
        tr, _ = traces.polybench_trace(kern, GEO, max_accesses=4000, seed=i)
        if tr is None:
            continue
        for mode in ("ts", "reference"):
            c.add(tr, JETSON_NANO, mode=mode, kern=kern.name)
    arms = {(r["kern"], r["mode"]): int(r["exec_cycles"]) for r in c.run()}
    kerns = sorted({k for k, _ in arms})
    errs = [abs(arms[(k, "ts")] - arms[(k, "reference")])
            / arms[(k, "reference")] for k in kerns]
    rows.append(("timescale_validation_avg_err", float(np.mean(errs)),
                 "paper<0.001"))
    rows.append(("timescale_validation_max_err", float(np.max(errs)),
                 "paper<0.01"))
    # invariance to FPGA-side clocks (the content of the claim)
    tr, _ = traces.polybench_trace(traces.POLYBENCH[0], GEO, 3000)
    inv = Campaign()
    for s in (50, 400, 5000):
        inv.add(tr, dataclasses.replace(JETSON_NANO,
                                        smc_cycles_per_decision=s),
                mode="ts", smc=s)
    execs = {int(r["exec_cycles"]) for r in inv.run()}
    rows.append(("timescale_fpga_invariance_spread", float(len(execs) - 1),
                 "0=exact"))
    return rows


# ---------------- Fig. 8: latency profile ----------------

def bench_latency_profile():
    """Average cycles/load vs working-set size; L1 modeled inside deltas,
    L2 = the LLC model, then DRAM. All (size x mode) points execute as
    one batched Campaign (one compile per system config)."""
    rows = []
    c = Campaign()
    cached = []
    for kb in (64, 256, 1024, 4096):
        out = traces.pointer_chase(kb * 1024, GEO, n_loads=3000)
        if out is None:
            cached.append(kb)
            continue
        tr, n_total, n_miss = out
        for mode, sysc in (("ts", JETSON_NANO), ("nots", PIDRAM_LIKE)):
            c.add(tr, sysc, mode=mode, kb=kb, n_total=n_total, n_miss=n_miss)
    recs = {(r["mode"], r["kb"]): r for r in c.run()}
    for kb in (64, 256, 1024, 4096):
        for mode in ("ts", "nots"):
            if kb in cached:
                rows.append((f"latency_{mode}_{kb}KiB_cyc_per_load", 2.0,
                             "cached"))
                continue
            r = recs[(mode, kb)]
            # cycles/load over ALL loads: hits cost ~2 cycles
            n_total, n_miss = r["n_total"], r["n_miss"]
            cyc = (2.0 * (n_total - n_miss)
                   + float(r["avg_load_latency_cycles"]) * n_miss) / n_total
            rows.append((f"latency_{mode}_{kb}KiB_cyc_per_load",
                         round(cyc, 2), f"miss_frac={n_miss/n_total:.2f}"))
    return rows


# ---------------- Figs. 10/11: RowClone ----------------

def bench_rowclone(setting="noflush"):
    rows = []
    rc_ts = RowClone(JETSON_NANO, device())
    rc_nots = RowClone(PIDRAM_LIKE, device())
    # clflush traces carry the per-line flush stream too; cap their size so
    # the section stays minutes, not tens of minutes, on one core
    sizes = (65536, 1 << 20, 4 << 20) if setting == "noflush"         else (65536, 512 << 10, 1 << 20)
    for wl in ("copy", "init"):
        # one batched campaign per (workload, system): the whole size
        # sweep shares a compile-key group instead of a jit per point
        a_all = rc_ts.evaluate_batch(sizes, wl, setting, "ts",
                                     cpu_line_delta=TS_LINE_DELTA)
        b_all = rc_nots.evaluate_batch(sizes, wl, setting, "nots",
                                       cpu_line_delta=NOTS_LINE_DELTA)
        sp_ts, sp_nots = [], []
        for nb, a, b in zip(sizes, a_all, b_all):
            sp_ts.append(a["rowclone"].speedup_vs_cpu)
            sp_nots.append(b["rowclone"].speedup_vs_cpu)
            rows.append((f"rowclone_{wl}_{setting}_{nb}B_ts",
                         round(sp_ts[-1], 2), "speedup_x"))
            rows.append((f"rowclone_{wl}_{setting}_{nb}B_nots",
                         round(sp_nots[-1], 2), "speedup_x"))
        rows.append((f"rowclone_{wl}_{setting}_avg_ts",
                     round(float(np.mean(sp_ts)), 2),
                     "paper_ts=15.0x_copy/1.8x_init"))
        rows.append((f"rowclone_{wl}_{setting}_avg_nots",
                     round(float(np.mean(sp_nots)), 2),
                     "paper_nots=306.7x_copy/36.7x_init"))
        rows.append((f"rowclone_{wl}_{setting}_inflation",
                     round(float(np.mean(sp_nots) / np.mean(sp_ts)), 2),
                     "paper~20x"))
    return rows


# ---------------- Figs. 12/13: tRCD reduction ----------------

def bench_trcd_profile():
    d = device()
    hm = d.trcd_heatmap(banks=2, rows=4096)
    return [
        ("trcd_strong_fraction", round(1 - d.weak_fraction(), 4), "paper=0.845"),
        ("trcd_min_ns", round(float(hm.min()), 2), "all<13.5"),
        ("trcd_max_ns", round(float(hm.max()), 2), "all<13.5"),
        ("trcd_row_autocorr", round(float(np.corrcoef(
            d.weak[0][:-1], d.weak[0][1:])[0, 1]), 3), "clustered>0.2"),
    ]


def bench_trcd_endtoend(n_kernels=None):
    d = device()
    t = TRCDReduction(JETSON_NANO, d)
    t.characterize()
    safety = t.safety_check()
    rows = [("trcd_bloom_false_neg", safety["false_negatives"], "must=0"),
            ("trcd_bloom_fpr", round(safety["false_positive_rate"], 4), "<0.05")]
    kerns = traces.POLYBENCH[:n_kernels] if n_kernels else traces.POLYBENCH
    names, trs = [], []
    for i, kern in enumerate(kerns):
        tr, n_acc = traces.polybench_trace(kern, GEO, max_accesses=6000, seed=i)
        if tr is None:
            continue
        names.append(kern.name)
        trs.append(tr)
    # whole suite, base + reduced arms, in one batched campaign
    speedups = []
    for name, r in zip(names, t.evaluate_traces(trs)):
        speedups.append(r["speedup"])
        rows.append((f"trcd_speedup_{name}", round(r["speedup"], 4), "x"))
    rows.append(("trcd_speedup_avg", round(float(np.mean(speedups)), 4),
                 "paper=1.0275"))
    rows.append(("trcd_speedup_max", round(float(np.max(speedups)), 4),
                 "paper=1.0976"))
    return rows


# ---------------- Fig. 14: simulation speed ----------------

def _timed_median(fn, reps=5):
    """Median warm wall-clock of fn() over reps (first call not timed)."""
    fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2], out


def bench_sim_speed(steady_n=4000, steady_batch=8):
    rows = []
    names, trs = [], []
    for i, kern in enumerate(traces.POLYBENCH[:6]):
        tr, _ = traces.polybench_trace(kern, GEO, max_accesses=4000, seed=i)
        if tr is None:
            continue
        names.append(kern.name)
        trs.append(tr)
    # per-kernel emulation speed (warm cache, single dispatch each)
    speeds = []
    run_many(trs, JETSON_NANO, "ts")  # warm the batched jit cache
    for name, tr in zip(names, trs):
        run(tr, JETSON_NANO, "ts")  # warm the batch-of-one shape
        t0 = time.perf_counter()
        r = run(tr, JETSON_NANO, "ts")
        dt = time.perf_counter() - t0
        mhz = float(r["exec_cycles"]) / dt / 1e6
        speeds.append(mhz)
        rows.append((f"sim_speed_{name}_MHz", round(mhz, 2),
                     "emulated_cycles_per_host_sec"))
    rows.append(("sim_speed_avg_MHz", round(float(np.mean(speeds)), 2),
                 "paper~10MHz_on_FPGA"))
    # batched campaign speed: all kernels in one vmapped dispatch
    t0 = time.perf_counter()
    rs = run_many(trs, JETSON_NANO, "ts")
    dt = time.perf_counter() - t0
    total = float(sum(int(r["exec_cycles"]) for r in rs))
    rows.append(("sim_speed_batched_MHz", round(total / dt / 1e6, 2),
                 f"{len(trs)}_kernels_one_dispatch"))

    # steady-state engine A/B at N=steady_n: the O(Q)-per-slot core vs the
    # kept pre-optimization reference core (emulator.run_ref_many), same
    # batch, both warm — compile amortization plays no part here. The
    # paper's headline axis (Fig. 14) is evaluation throughput, so run.py
    # fails the run when this ratio is missing or below its 2x gate.
    rng = np.random.RandomState(11)
    steady = []
    for _ in range(steady_batch):
        steady.append(Trace.of(kind=rng.randint(0, 2, steady_n),
                               bank=rng.randint(0, 16, steady_n),
                               row=rng.randint(0, 4096, steady_n),
                               delta=rng.randint(1, 8, steady_n),
                               dep=rng.randint(0, 2, steady_n)))
    t_fast, out_fast = _timed_median(
        lambda: run_many(steady, JETSON_NANO, "ts"))
    t_ref, out_ref = _timed_median(
        lambda: emulator.run_ref_many(steady, JETSON_NANO, "ts"))
    fast_cycles = [int(r["exec_cycles"]) for r in out_fast]
    assert fast_cycles == [int(r["exec_cycles"]) for r in out_ref], \
        "optimized core diverged from the reference core"
    total = float(sum(fast_cycles))
    speedup = t_ref / max(t_fast, 1e-9)
    rows.append(("sim_speed_steady_MHz", round(total / t_fast / 1e6, 2),
                 f"{steady_batch}x{steady_n}_reqs_warm"))
    rows.append(("sim_speed_steady_ref_MHz", round(total / t_ref / 1e6, 2),
                 "pre_optimization_core"))
    # gate enforcement (>=2x) lives in benchmarks/run.py (STEADY_GATE),
    # which fails the run when this row is missing or below gate — an
    # exception here would discard the measurements needed to diagnose
    # the regression
    rows.append(("sim_speed_steady_speedup_x", round(speedup, 2),
                 "accept>=2x"))
    return rows


# ---------------- streaming driver: constant-memory unbounded traces ----------------

def bench_streaming(total_requests=1_000_000, n_streams=8, chunk=16384,
                    steady_n=4000, steady_batch=8):
    """The PR 7 streaming-driver benchmark, three claims per run.

    (1) Bit-identity sanity: a streamed trace equals the single-shot
    engine exactly (the full contract lives in tests/test_streaming.py
    and the hypothesis property; this is the smoke-level pin).

    (2) Constant-memory scale: ``total_requests`` requests — far beyond
    any padded single-shot bucket — flow through
    ``emulator.run_stream_many`` as ``n_streams`` synthetic streams
    (same request distribution as the sim_speed steady workload),
    generated window-by-window so the full trace never exists on host
    or device. Gated by ``run.py``: exactly ONE streaming compile key
    (``streaming_compile_keys``; a length-dependent key would recompile
    per bucket and its padded scan would not fit memory at this size),
    peak RSS under the recorded budget (``streaming_rss_mb``), and
    per-chunk throughput within 10% of the 8x{steady_n} single-shot
    steady state (``streaming_tput_ratio`` >= 0.9 — the freeze-gated
    window scan does the same O(Q)+O(1) slot work, the halo re-scan and
    host-side chunking are amortized by the chunk size, and the
    executor's prefetch thread hides window assembly under the scan).

    (3) The per-request cost decomposition behind (2): requests/sec for
    the stream vs the single-shot steady dispatch, plus wall and window
    counts so regressions localize.

    Both arms are timed end-to-end INCLUDING workload synthesis from
    the same ``traces.synthetic_stream`` generator — the single-shot
    arm rebuilds its 8x{steady_n} traces inside the timed region — so
    the ratio isolates the driver (windowed scan + halo + freeze +
    chunk assembly vs one padded dispatch) rather than charging
    generation of 1M requests to one arm only."""
    import resource

    rows = []
    # (1) smoke bit-identity, sized to straddle several chunk boundaries
    rng = np.random.RandomState(31)
    n = 2000
    tr = Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, 16, n),
                  row=rng.randint(0, 4096, n), delta=rng.randint(1, 8, n),
                  dep=rng.randint(0, 2, n))
    a = run(tr, JETSON_NANO, "ts")
    s = emulator.run_stream(tr, JETSON_NANO, "ts", chunk=512)
    assert int(a["exec_cycles"]) == int(s["exec_cycles"]), \
        "streamed result diverged from single-shot"
    np.testing.assert_array_equal(a["t_resp"][:n], s["t_resp"])
    np.testing.assert_array_equal(a["t_issue"][:n], s["t_issue"])
    rows.append(("streaming_bit_identity", 1, "stream==single_shot"))

    # (2) single-shot steady-state baseline: same distribution AND same
    # generator as the streamed arm (bench_sim_speed's gate workload),
    # traces rebuilt inside the timed region. Both arms are measured
    # with the paired/interleaved GC-parked protocol (_paired_ratio) —
    # machine drift hits both arms of a pair equally, which matters
    # because the streamed arm is ~30x longer per measurement.
    SINGLE_REPS = 4  # batch the short arm per timed region: one 8x4000
    # dispatch is ~30ms, too short to time against a ~1s stream without
    # scheduler-quantum jitter dominating the per-pair ratio

    def single_shot():
        for r in range(SINGLE_REPS):
            trs = [next(iter(traces.synthetic_stream(
                steady_n, window=steady_n, seed=500 + r * 100 + i)))
                for i in range(steady_batch)]
            run_many(trs, JETSON_NANO, "ts")

    per = total_requests // n_streams
    last: dict = {}

    def stream():
        last["res"] = emulator.run_stream_many(
            [lambda i=i: traces.synthetic_stream(per, window=chunk, seed=i)
             for i in range(n_streams)],
            JETSON_NANO, "ts", chunk=chunk, collect="aggregate")

    # compile-cache misses across the warm-up AND every timed repeat
    # must total exactly one streaming compile: the key depends on
    # (chunk, batch, sys, mode), never on how many requests flow
    # through. The single-shot arm's own batched executable is warmed
    # BEFORE the counting window so the delta isolates streaming keys.
    single_shot()
    st0 = emulator.cache_stats()
    pair_r, t_single, wall = _paired_ratio(single_shot, stream, pairs=7)
    st1 = emulator.cache_stats()
    served = sum(int(r["served"]) for r in last["res"])
    assert served == total_requests, \
        f"stream served {served} of {total_requests}"
    single_n = SINGLE_REPS * steady_batch * steady_n
    single_rps = single_n / t_single
    stream_rps = total_requests / wall
    # per-pair median of (stream rps / single-shot rps): t_single/t_stream
    # scaled by the request-count ratio of the two arms
    ratio = pair_r * total_requests / single_n
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    keys = st1["misses"] - st0["misses"]
    windows = -(-per // chunk)  # final window drains the tail in place
    rows += [
        ("streaming_total_requests", total_requests,
         f"{n_streams}_streams_x_{per}"),
        ("streaming_wall_s", round(wall, 3), f"{windows}_windows_per_stream"),
        ("streaming_rps", round(stream_rps, 1), f"chunk={chunk}"),
        ("streaming_single_shot_rps", round(single_rps, 1),
         f"{steady_batch}x{steady_n}_steady"),
        # gate enforcement (>=0.9x, ==1 key, RSS budget) lives in run.py
        ("streaming_tput_ratio", round(ratio, 3),
         "accept>=0.9_paired_median"),
        ("streaming_compile_keys", keys, "accept==1_length_independent"),
        ("streaming_rss_mb", round(rss_mb, 1), "accept<=budget"),
    ]
    return rows


# ---------------- campaign subsystem: batched-vs-looped sweep ----------------

def bench_campaign_speed(n_traces=16, n_requests=180):
    """Compile-amortization benchmark for the run_many/Campaign path.

    A (n_traces x {ts, nots}) sweep is executed from a cold compile
    cache two ways: looped single-point ``run`` calls where every point
    pays a fresh jit compile (what the pre-campaign paper sweeps paid —
    their points differ in bucket / SystemConfig / mode / bloom, so the
    old per-point jit rarely hit cache; simulated by clearing the cache
    around each point) vs one batched Campaign that compiles at most
    once per (bucket, slot-budget, mode, bloom-shape) group.
    Steady-state (warm cache) wall-clocks are reported too: with the
    O(Q)-per-slot core the vmapped batch amortizes per-slot dispatch
    overhead across the batch axis, so batched execution now beats
    warm looping as well (campaign_warm_speedup_x; the enforced >=2x
    engine gate at N=4000 lives in sim_speed). Acceptance: cold
    speedup >= 3x."""
    rng = np.random.RandomState(7)
    trs = []
    for i in range(n_traces):
        n = n_requests + rng.randint(0, 64)  # varied length, one bucket
        trs.append(Trace.of(kind=np.zeros(n), bank=rng.randint(0, 16, n),
                            row=rng.randint(0, 4096, n),
                            delta=np.full(n, 3), dep=np.ones(n)))
    grid = [(tr, m) for m in ("ts", "nots") for tr in trs]
    c = Campaign()
    for tr, m in grid:
        c.add(tr, JETSON_NANO, mode=m)

    t0 = time.perf_counter()
    looped = []
    for tr, m in grid:
        emulator.cache_clear()  # every heterogeneous point recompiled
        looped.append(int(run(tr, JETSON_NANO, m)["exec_cycles"]))
    t_loop_cold = time.perf_counter() - t0
    for tr, m in grid:  # untimed pass: genuinely warm the jit cache
        run(tr, JETSON_NANO, m)
    t0 = time.perf_counter()
    looped_warm = [int(run(tr, JETSON_NANO, m)["exec_cycles"])
                   for tr, m in grid]
    t_loop_warm = time.perf_counter() - t0

    emulator.cache_clear()
    t0 = time.perf_counter()
    recs = c.run()
    t_batch_cold = time.perf_counter() - t0
    stats = emulator.cache_stats()
    t0 = time.perf_counter()
    c.run()
    t_batch_warm = time.perf_counter() - t0

    batched = [int(r["exec_cycles"]) for r in recs]
    assert batched == looped == looped_warm, \
        "batched campaign diverged from looped runs"
    expected_groups = len({(emulator._bucket(tr.n), m) for tr, m in grid})
    assert stats["misses"] == expected_groups, \
        f"compiled {stats['misses']} times for {expected_groups} groups"
    speedup = t_loop_cold / max(t_batch_cold, 1e-9)
    warm_speedup = t_loop_warm / max(t_batch_warm, 1e-9)
    if len(grid) >= 32:  # full-size run: amortization must dominate
        assert speedup >= 3.0, \
            f"cold campaign speedup {speedup:.2f}x below the 3x gate"
    return [
        ("campaign_looped_cold_s", round(t_loop_cold, 2),
         f"{len(grid)}_points_fresh_compile_each"),
        ("campaign_batched_cold_s", round(t_batch_cold, 2),
         f"compiles={stats['misses']}"),
        ("campaign_speedup_x", round(speedup, 2), "accept>=3x"),
        ("campaign_looped_warm_s", round(t_loop_warm, 2), "jit_cache_hot"),
        ("campaign_batched_warm_s", round(t_batch_warm, 2), "jit_cache_hot"),
        ("campaign_warm_speedup_x", round(warm_speedup, 2),
         "steady_state_batched_vs_looped"),
        ("campaign_compile_groups", stats["misses"],
         "one_per_bucket_mode_bloom"),
    ]


# ---------------- executor subsystem: overlapped groups ----------------

def _paired_ratio(f_base, f_new, pairs=7):
    """Noise-robust warm A/B: alternate base/new measurements (slow
    machine drift hits both arms of a pair equally) with the cyclic GC
    parked during each timed region (a gen-2 collection pauses every
    thread, which halves the overlapped executor's parallelism in
    whichever arm it lands on — the standard ``timeit`` hygiene).
    Returns (median per-pair ratio, median base s, median new s)."""
    import gc

    def timed(f):
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            f()
            return time.perf_counter() - t0
        finally:
            gc.enable()

    f_base()
    f_new()
    tb, tn = [], []
    for _ in range(pairs):
        tb.append(timed(f_base))
        tn.append(timed(f_new))
    ratios = sorted(b / max(n, 1e-9) for b, n in zip(tb, tn))
    return (ratios[len(ratios) // 2],
            sorted(tb)[len(tb) // 2], sorted(tn)[len(tn) // 2])


def bench_executor_speed(n_per=8, n_requests=3000):
    """The PR 5 campaign-executor benchmark.

    Overlapped dispatch: a heterogeneous grid (>= 12 compile-key
    groups: three length buckets/budgets x {ts, nots} x {hard-coded
    scheduler, policy-VM program}) executed warm via ``Campaign.run()``
    (groups overlap across the executor's worker pool in LPT order;
    host packing of group k+1 proceeds while group k runs inside XLA,
    independent groups run concurrently across cores) vs
    ``run(serial=True)`` (the PR 4 in-order group loop). Bit-identity
    is asserted first; the paired-ratio wall-clock speedup is gated
    >= 1.5x by ``run.py`` (``executor_speed_overlap_speedup_x``)
    whenever >1 hardware thread is available. (Cross-process reuse of
    the persistent compile cache is pinned on the CPU by
    ``tests/test_executor.py``; a bench process never spawns a second
    process that needs the device its parent holds.)
    """
    from repro.core import smcprog

    rng = np.random.RandomState(41)

    def mk(n):
        return Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, 16, n),
                        row=rng.randint(0, 4096, n),
                        delta=rng.randint(1, 8, n), dep=rng.randint(0, 2, n))

    sys_prog = dataclasses.replace(JETSON_NANO,
                                   policy=smcprog.frfcfs_program())
    lengths = (n_requests // 2, n_requests, 2 * n_requests)  # 3 buckets
    c = Campaign()
    g = 0
    for length in lengths:
        for sysc in (JETSON_NANO, sys_prog):
            for mode in ("ts", "nots"):
                for j in range(n_per):
                    c.add(mk(length + rng.randint(0, 16)), sysc, mode=mode,
                          g=g, j=j)
                g += 1
    assert c.n_groups() >= 12, f"grid collapsed to {c.n_groups()} groups"

    serial = c.run(serial=True)   # warms every executable for both paths
    overlap = c.run()
    for a, b in zip(serial, overlap):
        assert int(a["exec_cycles"]) == int(b["exec_cycles"]), \
            "overlapped executor diverged from the serial group loop"
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
    speedup, t_serial, t_overlap = _paired_ratio(
        lambda: c.run(serial=True), lambda: c.run())
    return [
        ("executor_speed_groups", c.n_groups(), f"{len(c)}_points"),
        ("executor_speed_serial_warm_s", round(t_serial, 3),
         "pr4_in_order_group_loop"),
        ("executor_speed_overlap_warm_s", round(t_overlap, 3),
         "overlapped_executor"),
        # gate enforcement (>=1.5x, multicore hosts) lives in run.py
        ("executor_speed_overlap_speedup_x", round(speedup, 2),
         "accept>=1.5x_paired_median"),
    ]


# ---------------- policy subsystem: software-defined scheduler sweep ----------------

def bench_policy_sweep(n_traces=8, n_requests=1200):
    """The MC-policy VM benchmark, two claims per run.

    (1) Interpreter overhead: the built-in FR-FCFS *program* (policy VM
    inside the scan) vs the hard-coded ``sys.scheduler`` branch, same
    traces, both warm — the VM stages to near-identical XLA, so the
    steady-state ratio must stay <= 1.3x (``run.py`` fails the run on
    the ``policy_sweep_interp_overhead_x`` row, same mechanism as the
    sim_speed gate). Correctness is asserted bit-exactly first.

    (2) Policy grid through Campaign: every built-in program over a
    bursty multi-bank workload in ONE Campaign — one compiled
    executable and one batched dispatch per program group (asserted on
    the compile-cache counters), with ts-mode results invariant to each
    program's length-derived SMC cost."""
    rng = np.random.RandomState(23)
    trs = []
    for _ in range(n_traces):
        # bursty arrivals keep several requests visible per decision,
        # so scheduling policy actually has choices to make
        delta = np.where(np.arange(n_requests) % 8 == 0, 400, 0)
        row = np.where(rng.rand(n_requests) < 0.6, 7,
                       rng.randint(0, 4096, n_requests))
        trs.append(Trace.of(kind=rng.randint(0, 2, n_requests),
                            bank=rng.randint(0, 4, n_requests),
                            row=row, delta=delta))
    from repro.core import smcprog
    sys_hard = dataclasses.replace(JETSON_NANO, window=8)
    sys_prog = dataclasses.replace(sys_hard,
                                   policy=smcprog.frfcfs_program())

    out_hard = run_many(trs, sys_hard, "ts")  # warm both executables
    out_prog = run_many(trs, sys_prog, "ts")
    for a, b in zip(out_hard, out_prog):
        assert int(a["exec_cycles"]) == int(b["exec_cycles"]), \
            "policy VM frfcfs diverged from the hard-coded scheduler"
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
    t_hard, _ = _timed_median(lambda: run_many(trs, sys_hard, "ts"))
    t_prog, _ = _timed_median(lambda: run_many(trs, sys_prog, "ts"))
    overhead = t_prog / max(t_hard, 1e-9)

    rows = [
        ("policy_sweep_hardcoded_s", round(t_hard, 3),
         f"{n_traces}x{n_requests}_reqs_warm"),
        ("policy_sweep_vm_frfcfs_s", round(t_prog, 3), "policy_vm_scan"),
        # gate enforcement (<=1.3x) lives in benchmarks/run.py
        ("policy_sweep_interp_overhead_x", round(overhead, 3),
         "accept<=1.3x"),
    ]

    # (2) the policy grid: all built-ins, one batched dispatch per group
    emulator.cache_clear()
    programs = list(smcprog.builtin_programs().values())
    c = Campaign()
    for i, tr in enumerate(trs[:2]):
        # policy_axis=False on purpose: this section pins the STAGED
        # per-program path (the PR-4 contract the policy_axis section
        # measures its speedup against)
        c.add_policy_grid(tr, sys_hard, programs, mode="ts", i=i,
                          policy_axis=False)
    recs = c.run()
    stats = emulator.cache_stats()
    assert c.n_groups() == len(programs), \
        f"{c.n_groups()} groups for {len(programs)} programs"
    assert stats["misses"] == len(programs), \
        f"compiled {stats['misses']} times for {len(programs)} program groups"
    by = {(r["i"], r["policy"]): r for r in recs}
    base = {i: int(by[(i, "frfcfs")]["exec_cycles"]) for i in range(2)}
    for p in programs:
        execs = [int(by[(i, p.name)]["exec_cycles"]) for i in range(2)]
        rel = float(np.mean([base[i] / max(e, 1)
                             for i, e in enumerate(execs)]))
        rows.append((f"policy_sweep_{p.name}_vs_frfcfs", round(rel, 4),
                     f"smc_cycles={p.smc_cycles()}"))
    rows.append(("policy_sweep_grid_compiles", stats["misses"],
                 f"one_per_program_group_of_{len(programs)}"))
    return rows


def bench_policy_axis(n_requests=1200, n_policies=256, n_baseline=6):
    """ISSUE 10: the runtime policy operand + vmapped policy axis.

    (1) Compile scaling: a ``n_policies``-candidate sweep (two table-
    length buckets by construction) must compile exactly once per
    BUCKET, not once per program (``policy_axis_compiles`` ==
    ``policy_axis_buckets``, gated in run.py).

    (2) Throughput: the batched axis at ``n_policies`` candidates must
    beat the PR-4 staged per-program loop >= 5x per policy
    (``policy_axis_speedup_x``). The staged arm recompiles per program
    (content rides its compile key), so it is measured cold on
    ``n_baseline`` programs and extrapolated linearly — charitable to
    the baseline, since its per-policy cost only grows with the sweep.

    (3) Bit-identity: axis results must equal the staged runs exactly
    (``policy_axis_bitident``)."""
    from repro.core import smcprog
    from repro.core.policysearch import random_program

    rng = np.random.RandomState(29)
    delta = np.where(np.arange(n_requests) % 8 == 0, 400, 0)
    row = np.where(rng.rand(n_requests) < 0.6, 7,
                   rng.randint(0, 4096, n_requests))
    tr = Trace.of(kind=rng.randint(0, 2, n_requests),
                  bank=rng.randint(0, 4, n_requests),
                  row=row, delta=delta)
    sys = dataclasses.replace(JETSON_NANO, window=8)

    # candidate population: bucket-8 randoms + frfcfs, plus a handful of
    # wide (bucket-16) programs so the compile gate counts BUCKETS
    progs = [random_program(rng, name=f"cand{i}")
             for i in range(n_policies - 5)]
    progs.append(smcprog.frfcfs_program())
    while len(progs) < n_policies:
        p = random_program(rng, max_ops=14, name=f"wide{len(progs)}")
        if p.n_ops > 8:
            progs.append(p)
    buckets = sorted({smcprog.table_bucket(p.n_ops) for p in progs})

    # staged per-program baseline, cold: each program's content rides
    # its compile key, so every one pays a fresh XLA compile
    emulator.cache_clear()
    t0 = time.perf_counter()
    staged = [run(tr, dataclasses.replace(sys, policy=p), "ts")
              for p in progs[:n_baseline]]
    t_staged = time.perf_counter() - t0
    assert emulator.cache_stats()["misses"] == n_baseline, \
        "staged arm did not recompile per program"
    per_staged = t_staged / n_baseline

    # the policy axis, cold: one compile per table-length bucket
    emulator.cache_clear()
    t0 = time.perf_counter()
    recs = emulator.run_policies(tr, sys, progs, mode="ts",
                                 derive_cost=False)
    t_axis = time.perf_counter() - t0
    compiles = emulator.cache_stats()["misses"]
    per_axis = t_axis / len(progs)
    speedup = per_staged / max(per_axis, 1e-9)

    # bit-identity against the staged runs (axis pads t_resp to the
    # trace's length bucket exactly like the single-shot path)
    bitident = 1
    for p, a, b in zip(progs[:n_baseline], staged, recs):
        if int(a["exec_cycles"]) != int(b["exec_cycles"]) or \
                not np.array_equal(np.asarray(a["t_resp"]),
                                   np.asarray(b["t_resp"])):
            bitident = 0
            break

    return [
        ("policy_axis_n_policies", len(progs), f"{n_requests}_reqs"),
        ("policy_axis_buckets", len(buckets),
         "x".join(str(b) for b in buckets)),
        # gate enforcement (== buckets) lives in benchmarks/run.py
        ("policy_axis_compiles", compiles, "accept==buckets"),
        ("policy_axis_staged_per_policy_s", round(per_staged, 3),
         f"cold_{n_baseline}_programs"),
        ("policy_axis_batched_s", round(t_axis, 3),
         f"{len(progs)}_policies_cold"),
        ("policy_axis_batched_per_policy_s", round(per_axis, 5),
         "includes_bucket_compiles"),
        # gate enforcement (>= 5x) lives in benchmarks/run.py
        ("policy_axis_speedup_x", round(speedup, 2), "accept>=5x"),
        ("policy_axis_bitident", bitident,
         f"axis_vs_staged_{n_baseline}_programs"),
    ]


# ---------------- PR 8: fault injection + resumable campaigns ----------------

def bench_faults(n_requests=2000, n_traces=4, intensities=(0.5, 0.9),
                 study_requests=1500):
    """Fault-injection subsystem benchmark, three claims.

    (1) Zero-cost-off: ``faults=None`` must leave compile/group keys
    exactly as a config that never saw the fault subsystem, and the
    staged scan must be strictly SLIMMER than a fault-on lowering
    (asserted — if the off path ever stages fault ops, the texts
    converge). The gated ``faults_off_overhead_x`` row then bounds the
    runtime cost of the cheapest possible fault carry (a FaultModel
    with both error processes disabled — state threading only) at
    <= 1.05x the faults-off arm: the upper envelope of what
    attaching-but-disabling fault modeling can cost.

    (2) Checkpoint/resume: a checkpointed campaign re-run must load
    every finished group and recompute ZERO
    (``faults_ckpt_resume_recomputed``, gated == 0 in run.py), with
    bit-identical records.

    (3) The RowHammer mitigation study end-to-end: BER vs emulated
    slowdown for {unmitigated, PARA, TRR} x hammer intensities —
    the reliability/performance tradeoff rows the technique exists to
    produce."""
    import json as _json
    import os as _os
    import shutil as _shutil

    import jax.numpy as jnp

    from repro.core.faults import FaultModel
    from repro.core.techniques import RowHammerMitigationStudy

    rows = []
    rng = np.random.RandomState(41)
    trs = [Trace.of(kind=rng.randint(0, 2, n_requests),
                    bank=rng.randint(0, 16, n_requests),
                    row=rng.randint(0, 4096, n_requests),
                    delta=rng.randint(1, 8, n_requests))
           for _ in range(n_traces)]
    fm_on = FaultModel(seed=7, hammer_threshold=32, hammer_flip_fp=52000,
                       weak_fp=1200, retention_ticks=200)
    fm_disabled = FaultModel()           # carry threaded, zero error ops

    # (1a) key discipline: None is identical to never-attached; a real
    # model forks the group (campaigns never mix fault arms)
    n = trs[0].n
    keys_ok = (
        emulator.group_key(n, JETSON_NANO, "ts", None)
        == emulator.group_key(n, JETSON_NANO.with_faults(None), "ts", None)
        and emulator.group_key(n, JETSON_NANO, "ts", None)
        != emulator.group_key(n, JETSON_NANO.with_faults(fm_on), "ts", None))
    assert keys_ok, "faults=None perturbed the compile-key discipline"
    rows.append(("faults_off_compile_keys_equal", int(keys_ok), "accept==1"))

    # (1b) staged-program check: the fault-on lowering must be strictly
    # larger — if these converge, the off path is staging fault ops
    bucket = emulator._bucket(n)
    slots = emulator.slot_budget(bucket, trs[0].n_real)

    def lowered_lines(sysc):
        key = emulator.compile_key(bucket, 1, sysc, "ts", None, slots)
        r = emulator._batched_fn(key)
        dummies = [a() if callable(a) else jnp.zeros(a[0], a[1])
                   for a in r.avals]
        return len(r.jitted.lower(*dummies).as_text().splitlines())

    off_lines = lowered_lines(JETSON_NANO)
    on_lines = lowered_lines(JETSON_NANO.with_faults(fm_on))
    assert on_lines > off_lines, \
        f"fault-off scan ({off_lines} HLO lines) not slimmer than " \
        f"fault-on ({on_lines})"
    rows.append(("faults_off_hlo_lines", off_lines, "staged_scan"))
    rows.append(("faults_on_hlo_lines", on_lines, "must_exceed_off"))

    # (1c) runtime envelope: disabled-model carry vs no model at all
    sys_dis = JETSON_NANO.with_faults(fm_disabled)
    run_many(trs, JETSON_NANO, "ts")      # warm both executables
    run_many(trs, sys_dis, "ts")
    t_off, _ = _timed_median(lambda: run_many(trs, JETSON_NANO, "ts"))
    t_dis, _ = _timed_median(lambda: run_many(trs, sys_dis, "ts"))
    rows += [
        ("faults_none_s", round(t_off, 3), f"{n_traces}x{n_requests}_warm"),
        ("faults_disabled_model_s", round(t_dis, 3), "carry_only"),
        # gate enforcement (<= 1.05x) lives in benchmarks/run.py
        ("faults_off_overhead_x", round(t_dis / max(t_off, 1e-9), 3),
         "accept<=1.05x"),
    ]

    # (2) checkpoint/resume: finished groups load, nothing recomputes
    here = _os.path.dirname(_os.path.abspath(__file__))
    ck = _os.path.join(here, "..", "artifacts", "campaigns", "_bench_probe")
    _shutil.rmtree(ck, ignore_errors=True)  # a killed earlier run's leftovers
    try:
        def build():
            c = Campaign()
            for i, tr in enumerate(trs[:2]):
                c.add(tr, JETSON_NANO, mode="ts", i=i, arm="plain")
                c.add(tr, JETSON_NANO.with_faults(fm_on), mode="ts",
                      i=i, arm="faulty")
            return c

        first = build()
        r1 = first.run(checkpoint=ck)
        resumed = build()
        r2 = resumed.run(checkpoint=ck)
        assert resumed.last_run["computed"] == 0, resumed.last_run
        for a, b in zip(r1, r2):
            assert int(a["exec_cycles"]) == int(b["exec_cycles"])
            if "flips" in a:
                assert int(a["flips"]) == int(b["flips"])
        rows += [
            ("faults_ckpt_groups", first.last_run["groups"], "checkpointed"),
            ("faults_ckpt_resume_loaded", resumed.last_run["loaded"],
             "from_disk"),
            # gate enforcement (== 0) lives in benchmarks/run.py
            ("faults_ckpt_resume_recomputed", resumed.last_run["computed"],
             "accept==0"),
        ]
    finally:
        _shutil.rmtree(ck, ignore_errors=True)

    # (3) BER vs slowdown across mitigations x intensities
    study = RowHammerMitigationStudy(
        JETSON_NANO, fault_model=FaultModel(
            seed=7, hammer_threshold=48, hammer_flip_fp=52000))
    recs = study.evaluate(intensities=intensities,
                          n_requests=study_requests)
    for rec in recs:
        tag = f"i{int(round(rec['intensity'] * 100)):02d}"
        for name in study.programs:
            r = rec[name]
            rows.append((
                f"faults_study_{name}_{tag}_ber",
                round(r["bit_error_rate"], 6),
                _json.dumps({"flips": r["flips"],
                             "mitigations": r["mitigations"]},
                            separators=(",", ":"))))
            rows.append((
                f"faults_study_{name}_{tag}_slowdown_x",
                round(r["slowdown_vs_unmitigated"], 4),
                f"exec_cycles={r['exec_cycles']}"))
    hi = recs[-1]
    base_ber = hi[study.baseline]["bit_error_rate"]
    mitigated = [hi[nm]["bit_error_rate"] for nm in study.programs
                 if nm != study.baseline]
    assert base_ber > 0, "storm too weak: unmitigated arm never flipped"
    assert all(b < base_ber for b in mitigated), \
        f"mitigations did not reduce BER: base={base_ber}, {mitigated}"
    return rows


# ---------------- sweep service: multi-tenant shared engine ----------------

def bench_service(n_requests=8, round_pts=1, k_clients=4, rounds=60,
                  pairs=3):
    """Sweep-service multi-tenant throughput (ISSUE 9), three gated
    claims.

    (1) Shared-engine scaling (``service_scaling_x``, gated >= 0.7*K):
    K closed-loop clients hammering one ``SweepServer`` with same-group
    rounds of ``round_pts`` points each must reach at least 0.7*K the
    aggregate throughput of ONE client on its own server. On a
    single device this headroom can only come from cross-client
    coalescing: K concurrent rounds merge into one K*round_pts-point
    dispatch whose vmapped scan costs barely more than a round_pts one
    (batch amortization), so the shared server retires ~K rounds per
    dispatch wall. Each arm runs in its best configuration
    (``max_batch`` = its natural round size; both compile keys warmed
    before timing) — the comparison is K tenants SHARING a server vs a
    tenant OWNING one, not a rigged window.

    (2) Cross-client coalescing really happens
    (``service_clients_per_dispatch``, gated > 1.0): mean distinct
    clients per dispatch over the K-client phase.

    (3) No admission drops at default bounds (``service_rejected``,
    gated == 0): the closed-loop load must ride backpressure bounds
    without a single typed rejection.

    Arms alternate single/K ``pairs`` times (drift hits both), cyclic
    GC parked during timed regions as in ``_paired_ratio``; medians
    reported."""
    import gc
    import threading as _threading

    from repro.core.campaign import Point
    from repro.service import SweepClient, SweepServer

    rng = np.random.RandomState(0)

    def mk():
        return Trace.of(kind=rng.randint(0, 2, n_requests),
                        bank=rng.randint(0, 16, n_requests),
                        row=rng.randint(0, 4096, n_requests),
                        delta=rng.randint(1, 8, n_requests),
                        dep=rng.randint(0, 2, n_requests))

    pool = [[mk() for _ in range(round_pts)] for _ in range(k_clients)]

    def round_points(k):
        return [Point(t, JETSON_NANO, "ts") for t in pool[k]]

    def run_single():
        """One tenant owning a server: rounds flush at max_batch ==
        round_pts, no coalesce wait on its critical path."""
        with SweepServer(max_batch=round_pts,
                         coalesce_window_s=0.05) as srv:
            cli = SweepClient(server=srv, name="solo")
            cli.submit_points(round_points(0))
            cli.collect()                      # warm the round_pts key
            t0 = time.perf_counter()
            for _ in range(rounds):
                cli.submit_points(round_points(0))
                cli.collect()
            dt = time.perf_counter() - t0
        return rounds * round_pts / dt

    def run_k():
        """K tenants sharing one server: lockstep closed-loop rounds
        merge at max_batch == K*round_pts."""
        walls, errs = [], []
        barrier = _threading.Barrier(k_clients)
        with SweepServer(max_batch=k_clients * round_pts,
                         coalesce_window_s=0.005) as srv:
            def drive(k):
                try:
                    cli = SweepClient(server=srv, name=f"c{k}")
                    cli.submit_points(round_points(k))
                    cli.collect()              # warm the K*round_pts key
                    barrier.wait()
                    t0 = time.perf_counter()
                    for _ in range(rounds):
                        cli.submit_points(round_points(k))
                        cli.collect()
                    walls.append(time.perf_counter() - t0)
                except BaseException as e:  # pragma: no cover
                    errs.append(e)
            threads = [_threading.Thread(target=drive, args=(k,))
                       for k in range(k_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(600)
            st = srv.stats()
        if errs:
            raise errs[0]
        return k_clients * rounds * round_pts / max(walls), st

    def timed(f):
        gc.collect()
        gc.disable()
        try:
            return f()
        finally:
            gc.enable()

    # warm BOTH compile keys (round_pts and K*round_pts batch buckets)
    # through the exact key-derivation path the service dispatches use,
    # so no arm ever pays a compile inside a timed region
    run_many([t for c in pool for t in c], JETSON_NANO, "ts")
    run_many(pool[0], JETSON_NANO, "ts")

    singles, ks, coals, rej = [], [], [], 0
    for _ in range(pairs):
        singles.append(timed(run_single))
        tput_k, st = timed(run_k)
        ks.append(tput_k)
        coals.append(st["coalesce_ratio"])
        rej += int(st["rejected"])
    tput_s = sorted(singles)[len(singles) // 2]
    tput_k = sorted(ks)[len(ks) // 2]
    coal = sorted(coals)[len(coals) // 2]
    scaling = tput_k / max(tput_s, 1e-9)
    return [
        ("service_tput_single_pps", round(tput_s, 1),
         f"1_client_rounds_of_{round_pts}x{n_requests}req"),
        ("service_tput_k_pps", round(tput_k, 1),
         f"{k_clients}_clients_shared_server"),
        ("service_scaling_x", round(scaling, 2),
         f"accept>={0.7 * k_clients:.1f}_via_coalesced_batching"),
        ("service_clients_per_dispatch", round(coal, 2),
         "accept>1_mean_distinct_clients_per_dispatch"),
        ("service_rejected", rej, "accept==0_at_default_bounds"),
    ]


# ---------------- LM x EasyDRAM: the framework tie-in ----------------

def bench_lm_traces():
    """DRAM-level evaluation of LM serving traffic + RowClone KV fork.
    All arches' decode traces and the kv-fork pair run through batched
    campaign calls; the TRCD base/reduced arms for the whole arch set
    share one Campaign inside ``evaluate_traces``."""
    from repro.configs import get_config
    rows = []
    d = device()
    archs = ("qwen2_1_5b", "rwkv6_3b")
    arch_trs = [traces.lm_decode_trace(get_config(a), seq_len=4096, geo=GEO,
                                       max_requests=6000) for a in archs]
    base = run_many(arch_trs, JETSON_NANO, "ts")
    t = TRCDReduction(JETSON_NANO, d)
    trcd = t.evaluate_traces(arch_trs)
    for arch, r, rr in zip(archs, base, trcd):
        rows.append((f"lm_decode_trace_{arch}_cycles", int(r["exec_cycles"]),
                     f"reqs={r['n_requests']}"))
        rows.append((f"lm_decode_trace_{arch}_trcd_speedup",
                     round(rr["speedup"], 4), "x"))
    # KV-page fork via RowClone vs CPU copy (serving-side case study)
    tr_rc, _ = traces.kv_fork_trace(16, 8192, GEO, "rowclone", d)
    tr_cpu, _ = traces.kv_fork_trace(16, 8192, GEO, "cpu", d)
    fork = run_many([tr_cpu, tr_rc], JETSON_NANO, "ts")
    a, b = (int(r["exec_cycles"]) for r in fork)
    rows.append(("kv_fork_rowclone_speedup", round(a / max(b, 1), 2), "x"))
    return rows
