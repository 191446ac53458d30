#!/usr/bin/env python3
"""Chip benchmark of the EasyDRAM emulator: one cell, one run.

  python3 bench/run.py --workload trcd.sweep --seed 7 --seconds 30 --trace 0

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<name>.json``); the mix names the call it drives
(``bench/calls/<kind>.py``). Set-up builds the system, the traffic and
the executables of every shape the cell uses, then the window starts
whole calls until ``--seconds`` have passed and counts every call it
started, to its end. ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` profiles the window and prints its per-layer
metrics (``bench/metrics/<name>.py``). Either way the points of one
call drawn from the seed are compared, field by field, with the plain
reference (``bench/lib/reference.py``) once the window has closed.

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and ``checks`` last); the last lines of stderr give each compared
number beside its limit. Without a TPU, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
for _p in (os.path.join(REPO, "src"), REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import registry, sut, trace_reduce  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def pin_devices(chips: int, trace: bool = False) -> None:
    """Before JAX starts: a one-chip cell sees one chip of the host, so
    the program's batch-axis sharding has nothing to shard over. A
    traced run records device time per XLA program, not per operation:
    the scan runs ~140 operations a slot, which op-level tracing turns
    into ~3 million events a second."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if trace:
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "")
            + " --xla_enable_hlo_trace=false").strip()
    if chips == 1:
        os.environ.setdefault("TPU_VISIBLE_CHIPS", "0")
        os.environ.setdefault("TPU_CHIPS_PER_PROCESS_BOUNDS", "1,1,1")
        os.environ.setdefault("TPU_PROCESS_BOUNDS", "1,1,1")


def tpu_devices(chips: int):
    """The cell's TPU devices, or None when JAX finds no TPU or another
    number of them."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError:
        return None
    if devs[0].platform != "tpu" or len(devs) != chips:
        return None
    return devs


def memory_peak(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def open_cell(reg, workload: str, seed: int, require_tpu: bool = True,
              cache: bool = True, trace: bool = False):
    """Set-up up to the window: the cell's devices, the compile cache,
    and a driver with every shape warm. Returns ``(call module,
    driver, devices)``, or None when the cell's TPU chips are missing
    (and ``require_tpu``)."""
    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    chips = int(cell["chips"])
    pin_devices(chips, trace)
    import jax
    devs = tpu_devices(chips)
    if devs is None:
        if require_tpu:
            print(f"bench: cell {workload} needs {chips} TPU chip(s); "
                  f"JAX reports {jax.devices()}", file=sys.stderr)
            return None
        devs = jax.devices()[:1]
    if cache:
        from repro.utils.jax_compat import enable_persistent_compile_cache
        enable_persistent_compile_cache(
            os.path.join(reg.repo, "artifacts", "bench_xla_cache"))
    call = reg.call(traffic["call"])
    driver = call.Driver(cfg, traffic, seed,
                         os.path.join(reg.repo, "artifacts", "bench_traces"))
    driver.warm()
    return call, driver, devs


def run_window(call, driver, seconds: float):
    """Start whole calls until ``seconds`` have passed; every call
    started runs to its end. A call that raises counts its points as
    failed. Returns ``(calls, t_start, t_end, failed)``, ``calls`` as
    ``[(t_submit, t_done, points)]``."""
    import jax
    calls, failed, k = [], 0, 0
    t_start = time.perf_counter()
    while time.perf_counter() - t_start < seconds:
        with jax.profiler.TraceAnnotation("traffic"):
            inputs = driver.inputs(k)
        t_sub = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(call.SPAN):
                points = driver.call(inputs)
        except Exception as e:  # a failed call is a result, not a crash
            print(f"bench: call {k} failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failed += driver.expected(inputs)
        else:
            calls.append((t_sub, time.perf_counter(), points))
        k += 1
    return calls, t_start, time.perf_counter(), failed


def compare(driver, calls, seed: int, broken: str = None):
    """Compare the points of one completed call, drawn from ``seed``,
    with the reference (or, with ``broken``, with the control: the
    reference with that guarantee dropped). Returns ``(mismatched,
    compared, call index)``."""
    import numpy as np
    if not calls:
        return 0, 0, None
    rng = np.random.default_rng([seed % (1 << 64), 0xC4EC])
    chosen = int(rng.integers(len(calls)))
    sample = driver.sample(calls[chosen][2], rng)
    mismatched = 0
    for p in sample:
        bad = sut.differs(p.result, driver.reference(p, broken))
        if bad:
            mismatched += 1
            if mismatched <= 3 and broken is None:
                print(f"bench: point {p.key} differs from the reference "
                      f"in {bad}", file=sys.stderr)
    return mismatched, len(sample), chosen


def main(argv=None, reg=None, require_tpu=True, cache=True,
         before_window=None) -> int:
    """One run. The keywords serve the CPU tests of the harness:
    ``require_tpu=False`` skips the look for a chip, ``cache=False``
    leaves JAX's compile cache alone, ``before_window`` is called once
    set-up is done."""
    args = parse(argv)
    reg = reg or registry.Registry()
    opened = open_cell(reg, args.workload, args.seed, require_tpu, cache,
                       bool(args.trace))
    if opened is None:
        return 2
    call, driver, devs = opened
    import jax
    from repro.core import emulator
    if before_window is not None:
        before_window()
    misses0 = emulator.cache_stats()["misses"]

    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(reg.repo, "artifacts", "bench_profile",
                                 args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - T0
    calls, t_start, t_end, failed = run_window(call, driver, args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    compiles = emulator.cache_stats()["misses"] - misses0
    peak = memory_peak(devs)

    points = [p for _, _, ps in calls for p in ps]
    requests = sum(p.n_real for p in points)
    latencies = [done - sub for sub, done, ps in calls for _ in ps]
    print(f"bench: {args.workload} seed={args.seed} calls={len(calls)} "
          f"points={len(points)} requests={requests} "
          f"window_s={t_end - t_start:.6f} setup_s={setup_s:.6f} "
          f"compiles_in_window={compiles}", file=sys.stderr)

    result = {"correct": None, "attempted": len(points) + failed,
              "failed": failed, "metrics": {}, "device": {
                  "platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs), "memory_peak_bytes": peak}}

    if args.trace:
        from bench.lib import xplane
        devices, spans, layout = xplane.extract(
            xplane.find(trace_dir), ("traffic", call.SPAN))
        shutil.rmtree(trace_dir, ignore_errors=True)
        print(f"bench: trace layout {json.dumps(layout)}", file=sys.stderr)
        red = trace_reduce.reduce(devices, spans) if spans else None
        if red is not None and red["idle_share"] is not None:
            result["device"]["busy_s"] = red["busy_mean_ns"] / 1e9
            result["device"]["window_s"] = red["window_ns"] / 1e9
            result["breakdown"] = {
                "device_ops": [[n, s / 1e9] for n, s in red["device_ops"]],
                "idle_gaps": [[n, s / 1e9] for n, s in red["idle_gaps"]]}
            for d, b in sorted(red["busy_ns"].items()):
                print(f"bench: device {d} busy_s={b / 1e9:.9f} idle_share="
                      f"{1 - b / red['window_ns']:.9f}", file=sys.stderr)
        ctx = {"trace": red, "requests": requests, "compiles": compiles}
        for m in reg.metrics("per_layer", args.workload):
            v = reg.metric_reader(m["name"]).read(ctx)
            if v is not None:
                result["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    elif calls:
        e2e = {
            "emu_req_per_s": requests / (t_end - t_start),
            "point_latency_p95_s": percentile(latencies, 95),
            "setup_s": setup_s,
        }
        for m in reg.metrics("end_to_end", args.workload):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}

    # ---- correctness, once the window has closed and memory is read
    t_ref = time.perf_counter()
    mismatched, compared, chosen = compare(driver, calls, args.seed)
    unfinished = sum(sut.unfinished(p) for p in points)
    result["failed"] = failed + unfinished
    checks = {
        "mismatched_points": {"value": mismatched, "limit": 0, "holds": "<="},
        "failed_points": {"value": result["failed"], "limit": 0, "holds": "<="},
        "compared_points": {"value": compared, "limit": 1, "holds": ">="},
    }
    result["correct"] = all(
        c["value"] <= c["limit"] if c["holds"] == "<=" else c["value"] >= c["limit"]
        for c in checks.values())
    result["checks"] = checks
    print(f"bench: reference over {compared} points of call {chosen} took "
          f"{time.perf_counter() - t_ref:.3f} s", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} {c['holds']} {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
