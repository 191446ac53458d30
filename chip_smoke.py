#!/usr/bin/env python3
"""Chip smoke test: drive the emulator's main paths once on a TPU and
check every result bit for bit.

  python chip_smoke.py              # one chip (default)
  python chip_smoke.py --chips 4    # four chips: the sharded campaign only

One chip runs five phases through the public entry points, each
compared with an independent path on the same chip:

* ``trcd`` — the paper's tRCD case study (Sec. 8): characterize the
  device, build the weak-row Bloom filter, then the base and reduced
  arms of 12 PolyBench kernels (6000 accesses each) through
  ``TRCDReduction`` -> ``Campaign`` -> ``run_many``; every point must
  equal the reference engine (``run_ref_many``).
* ``streaming`` — a 125k-request ``synthetic_stream`` through
  ``run_stream`` at chunk 16384; must equal single-shot ``run``.
* ``policies_faults`` — ``run_policies`` over FR-FCFS, FCFS and the
  RowHammer mitigation programs on a ``rowhammer_trace`` under a
  ``FaultModel``; must equal the staged per-program ``run``.
* ``service`` — an in-process ``SweepServer`` with one ``SweepClient``
  submitting the 12 base tRCD points; must equal the campaign.
* ``golden`` — the first kernels' tRCD points and the policy-axis
  points against values computed on the CPU
  (``chip_smoke_golden.json``; ``tests/test_golden.py`` regenerates
  them there).

``--chips 4`` runs one campaign of 72 points over three length
buckets, with and without Bloom filters, sharded across four devices,
and the same campaign unsharded on one device; the two must agree bit
for bit.

Lines starting ``smoke`` report smoke timings (wall seconds, compiles
included) and counts; they are not device metrics. The last line of
stdout is one JSON object, ``{"ok": true, "device": {...}}``, printed
only when every phase matched. Without a TPU the script exits non-zero
before any phase. The compile cache lives in
``$JAX_COMPILATION_CACHE_DIR`` when set, else
``<checkout>/artifacts/xla_cache``.
"""
import argparse
import hashlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
GOLDEN_PATH = os.path.join(ROOT, "chip_smoke_golden.json")
GOLDEN_KERNELS = 3          # leading tRCD kernels pinned to CPU values
TRCD_KERNELS = 12
TRCD_ACCESSES = 6000
STREAM_REQUESTS = 125_000
STREAM_CHUNK = 16384
RESULT_FIELDS = ("exec_cycles", "row_hits", "served", "t_resp", "t_issue")


class Mismatch(RuntimeError):
    """A result differed from the path it is checked against."""


def _log(msg: str) -> None:
    print(f"smoke {msg}", flush=True)


def check_equal(what: str, got: dict, want: dict, fields=RESULT_FIELDS,
                n: int = None) -> None:
    """Raise :class:`Mismatch` unless every field is bit-identical
    (per-request arrays compared over their first ``n`` entries)."""
    import numpy as np
    for f in fields:
        a, b = np.asarray(got[f]), np.asarray(want[f])
        if n is not None and a.ndim:
            a, b = a[:n], b[:n]
        if a.shape != b.shape or not np.array_equal(a, b):
            raise Mismatch(f"{what}: field {f!r} differs")


def t_resp_digest(t_resp, n: int) -> str:
    import numpy as np
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(t_resp)[:n], "<i4").tobytes()
    ).hexdigest()


def trcd_setup(n_kernels: int = TRCD_KERNELS):
    """The tRCD case study set-up (examples/trcd_case_study.py): the
    characterized ``TRCDReduction`` on JETSON_NANO, its safety check,
    and the PolyBench kernel traces (names, traces)."""
    from repro.core import traces
    from repro.core.dram import Geometry
    from repro.core.profiling import DeviceModel
    from repro.core.techniques import TRCDReduction
    from repro.core.timescale import JETSON_NANO

    geo = Geometry()
    t = TRCDReduction(JETSON_NANO, DeviceModel(geo))
    t.characterize()
    safety = t.safety_check()
    names, trs = [], []
    for i, kern in enumerate(traces.POLYBENCH[:n_kernels]):
        tr, _ = traces.polybench_trace(kern, geo, max_accesses=TRCD_ACCESSES,
                                       seed=i)
        if tr is not None:
            names.append(kern.name)
            trs.append(tr)
    return t, safety, names, trs


def policy_setup():
    """The policies-and-faults workload: a RowHammer storm under the
    mitigation study's fault model, and the programs swept over it
    (FR-FCFS, FCFS, and the study's mitigation arms)."""
    from repro.core import smcprog, traces
    from repro.core.faults import FaultModel
    from repro.core.timescale import JETSON_NANO

    fm = FaultModel(seed=7, hammer_threshold=48, hammer_flip_fp=52000)
    programs = [smcprog.frfcfs_program(), smcprog.fcfs_program()] + list(
        smcprog.mitigation_programs(
            para_fp=3277, trr_threshold=fm.hammer_threshold // 2).values())
    tr = traces.rowhammer_trace(2000, JETSON_NANO.geometry, intensity=0.9,
                                seed=0)
    return JETSON_NANO.with_faults(fm), programs, tr


def _golden_entry(r, n: int, extra=()) -> dict:
    out = {f: int(r[f]) for f in ("exec_cycles", "row_hits", "served")
           + tuple(extra)}
    out["t_resp_sha256"] = t_resp_digest(r["t_resp"], n)
    return out


def golden_values(names, trs, trcd_recs, programs, policy_tr, policy_recs,
                  n_kernels: int = GOLDEN_KERNELS) -> dict:
    """The points pinned to CPU values: ``trcd/<kernel>/<arm>`` for the
    first ``n_kernels`` kernels of the tRCD campaign and
    ``policy/<k>:<program>`` for every policy-axis point (with its
    ``flips`` and ``mitigations``). Each holds ``exec_cycles``,
    ``row_hits``, ``served`` and a sha256 of ``t_resp``."""
    out = {}
    for r in trcd_recs:
        i = r["i"]
        if i < n_kernels:
            out[f"trcd/{names[i]}/{r['arm']}"] = _golden_entry(r, trs[i].n)
    for k, (p, r) in enumerate(zip(programs, policy_recs)):
        out[f"policy/{k}:{p.name}"] = _golden_entry(
            r, policy_tr.n, ("flips", "mitigations"))
    return out


# ---------------------------------------------------------------- phases

def phase_trcd(ctx: dict) -> int:
    from repro.core import emulator

    t, safety, names, trs = trcd_setup()
    if safety["false_negatives"] != 0:
        raise Mismatch(f"bloom filter has false negatives: {safety}")
    recs = t.campaign(trs).run()
    spd = t.evaluate_traces(trs)
    for i, s in enumerate(spd):
        base, red = recs[2 * i], recs[2 * i + 1]
        if (s["base_cycles"], s["reduced_cycles"]) != (
                int(base["exec_cycles"]), int(red["exec_cycles"])):
            raise Mismatch(f"evaluate_traces disagrees with its campaign "
                           f"on {names[i]}")
    ref_base = emulator.run_ref_many(trs, t.sys, "ts")
    ref_red = emulator.run_ref_many(trs, t.sys, "ts", blooms=t.bloom_tuple)
    for i, name in enumerate(names):
        check_equal(f"trcd {name}/base", recs[2 * i], ref_base[i])
        check_equal(f"trcd {name}/reduced", recs[2 * i + 1], ref_red[i])
    ctx.update(sys=t.sys, names=names, trs=trs, recs=recs)
    mean = sum(s["speedup"] for s in spd) / len(spd)
    _log(f"trcd kernels={len(trs)} points={len(recs)} "
         f"mean_speedup={mean:.6f} fpr={safety['false_positive_rate']:.6f}")
    return len(recs)


def phase_streaming(ctx: dict) -> int:
    import numpy as np

    from repro.core import emulator, traces
    from repro.core.emulator import Trace
    from repro.core.timescale import JETSON_NANO

    seed = 11
    got = emulator.run_stream(
        lambda: traces.synthetic_stream(STREAM_REQUESTS, seed=seed),
        JETSON_NANO, chunk=STREAM_CHUNK, collect="full")
    parts = list(traces.synthetic_stream(STREAM_REQUESTS, seed=seed))
    whole = Trace.of(*(np.concatenate([getattr(p, f) for p in parts])
                       for f in ("kind", "bank", "row", "delta", "dep")))
    want = emulator.run(whole, JETSON_NANO)
    check_equal("streaming", got, want, n=whole.n)
    _log(f"streaming requests={whole.n} chunk={STREAM_CHUNK} "
         f"exec_cycles={int(got['exec_cycles'])}")
    return whole.n


def phase_policies_faults(ctx: dict) -> int:
    from repro.core import emulator

    sysf, programs, tr = policy_setup()
    axis = emulator.run_policies(tr, sysf, programs)
    flips = 0
    for p, got in zip(programs, axis):
        want = emulator.run(tr, sysf.with_policy(p))
        check_equal(f"policy {p.name}", got, want,
                    RESULT_FIELDS + ("flips", "mitigations"))
        flips += int(got["flips"])
    ctx.update(programs=programs, policy_tr=tr, policy_recs=axis)
    _log(f"policies_faults programs={len(programs)} requests={tr.n} "
         f"flips={flips}")
    return len(programs)


def phase_service(ctx: dict) -> int:
    from repro.service import SweepClient, SweepServer

    base = ctx["recs"][0::2]
    with SweepServer() as srv:
        cli = SweepClient(server=srv, name="smoke")
        for i, tr in enumerate(ctx["trs"]):
            cli.submit(tr, ctx["sys"], "ts", i=i)
        got = cli.collect()
        stats = srv.stats()
    for i, (g, w) in enumerate(zip(got, base)):
        check_equal(f"service {ctx['names'][i]}", g, w)
    _log(f"service points={len(got)} "
         f"dispatches={stats['dispatches']['count']}")
    return len(got)


def phase_golden(ctx: dict) -> int:
    with open(GOLDEN_PATH) as fh:
        want = json.load(fh)
    got = golden_values(ctx["names"], ctx["trs"], ctx["recs"],
                        ctx["programs"], ctx["policy_tr"], ctx["policy_recs"])
    if got != want:
        bad = sorted(k for k in set(got) | set(want)
                     if got.get(k) != want.get(k))
        raise Mismatch(f"golden: chip differs from the CPU values at {bad}")
    _log(f"golden points={len(got)} match=cpu")
    return len(got)


def phase_sharded(ctx: dict) -> int:
    """One campaign of 72 points over three length buckets (8 seeded
    traces per length x {no filter, one shared Bloom filter in ``nots``
    mode, a stacked filter per trace}), sharded over four devices,
    against the same campaign on one device."""
    from repro.core import emulator, traces
    from repro.core.campaign import Campaign, plan_groups

    t, _, _, _ = trcd_setup(0)
    bloom = t.bloom_tuple
    words, k, m_bits = bloom
    c = Campaign()
    for n in (1500, 3000, 6000):
        for seed in range(8):
            tr = next(traces.synthetic_stream(n, window=n, seed=seed))
            c.add(tr, t.sys, "ts", n=n, seed=seed, arm="base")
            # one filter object for the whole group: replicated
            c.add(tr, t.sys, "nots", bloom=bloom, n=n, seed=seed,
                  arm="shared")
            # a distinct filter object per point: the campaign stacks
            # them, and the words shard along the batch axis
            c.add(tr, t.sys, "ts", bloom=(words, k, m_bits), n=n,
                  seed=seed, arm="stacked")
    groups = plan_groups(c.points)
    shards = {emulator._shard_count(emulator._batch_bucket(len(idxs)))
              for idxs in groups.values()}
    if len(c) < 64 or shards != {4}:
        raise Mismatch(f"sharded campaign: {len(c)} points, shard counts "
                       f"{shards} (need >= 64 points, all 4-way)")
    old = emulator.set_sharding("off")
    try:
        one = c.run()
    finally:
        emulator.set_sharding(old)
    before = emulator.cache_stats()["misses"]
    sharded = c.run()
    if emulator.cache_stats()["misses"] - before != len(groups):
        raise Mismatch("the sharded run did not build its own executables")
    for i, (a, b) in enumerate(zip(sharded, one)):
        check_equal(f"sharded point {i} ({a['arm']}, n={a['n']})", a, b)
    _log(f"sharded points={len(c)} groups={len(groups)} shards=4")
    return len(c)


# ---------------------------------------------------------------- driver

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every one-chip phase; 4: only the sharded "
                         "campaign against its one-device run")
    args = ap.parse_args(argv)
    if args.chips == 1:
        # one chip even on a host that has more: libtpu then shows this
        # process a single device, so nothing shards
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from repro.utils import jax_compat
    cache_dir = jax_compat.enable_persistent_compile_cache()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (JAX reports {device}); refusing to run",
              file=sys.stderr)
        return 2
    if device["count"] != args.chips:
        print(f"chip_smoke: --chips {args.chips} needs exactly "
              f"{args.chips} visible devices, JAX reports {device}",
              file=sys.stderr)
        return 2
    _log(f"device {json.dumps(device)} compile_cache={cache_dir}")

    phases = ([phase_sharded] if args.chips == 4 else
              [phase_trcd, phase_streaming, phase_policies_faults,
               phase_service, phase_golden])
    from repro.core import emulator
    ctx: dict = {}
    for phase in phases:
        t0 = time.perf_counter()
        n = phase(ctx)
        _log(f"timing {phase.__name__[6:]} wall_s={time.perf_counter() - t0} "
             f"items={n} (smoke timing, compile included; not a metric)")
    stats = emulator.cache_stats()
    _log(f"cache_stats {json.dumps(stats, sort_keys=True)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
