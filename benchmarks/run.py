"""Benchmark orchestrator: one section per paper table/figure.

Prints ``name,value,derived`` CSV rows. ``--quick`` trims trace sizes
for smoke use and exits non-zero if any section fails OR a perf gate
row is missing/out of range: the engine's steady-state speedup
(``sim_speed_steady_speedup_x``, >=2x warm-cache at N=4000 vs the
pre-optimization core) and the MC-policy-VM interpreter overhead
(``policy_sweep_interp_overhead_x``, <=1.3x vs the hard-coded
scheduler) — so it doubles as a CI smoke gate that catches throughput
regressions (``python -m benchmarks.run --quick``). ``--section <name>``
runs one section (e.g. ``sim_speed`` for the engine throughput gate,
``campaign_speed`` for the batched-vs-looped sweep comparison,
``policy_sweep`` for the policy-VM overhead gate and built-in grid).
``--out <path>`` additionally writes a machine-readable BENCH_<n>.json
(env fingerprint header + section rows + wall times + compile-cache
stats) so the perf trajectory is tracked and comparable across PRs and
environments; ``--quick`` defaults it to ``artifacts/BENCH_quick.json``.
PR 5 gate (``--quick``): the overlapped campaign executor must beat
the serial group loop >= 1.5x warm (``executor_speed_overlap_speedup_x``,
multicore hosts).
PR 7 gates (``--quick``, section ``streaming``): a 1M-request stream
through the constant-memory chunked-window driver must finish with
per-chunk throughput >= 0.9x the 8x4000 single-shot steady state
(``streaming_tput_ratio``), exactly ONE streaming compile key
(``streaming_compile_keys`` — length-independent by construction), and
peak RSS within ``STREAM_RSS_BUDGET_MB`` (``streaming_rss_mb``; the
budget is recorded in the BENCH json for trajectory comparison — a
length-dependent padded scan at this size would be gigabytes). The RSS
bound is enforced only in ``--section streaming`` runs: peak RSS is
process-wide, so other sections' allocations own it in a full run and
the row is informational there.
PR 8 gates (``--quick``, section ``faults``): ``faults=None`` must
leave compile/group keys untouched (``faults_off_compile_keys_equal``
== 1), the cheapest attached fault carry must cost <= 1.05x the
no-fault-model arm (``faults_off_overhead_x``), and a checkpointed
campaign re-run must recompute zero finished groups
(``faults_ckpt_resume_recomputed`` == 0).
ISSUE 9 gates (``--quick``, section ``service``): K=4 concurrent
clients sharing one ``SweepServer`` must keep >= 0.7*K the aggregate
throughput of a solo client on its own server
(``service_scaling_x`` — only reachable through cross-client
coalescing on a single device), dispatches must actually mix clients
(``service_clients_per_dispatch`` > 1), and zero points may be
rejected at the default admission bounds (``service_rejected`` == 0).
The reference run is ``--section service --out artifacts/BENCH_9.json``.
ISSUE 10 gates (``--quick``, section ``policy_axis``): a 256-candidate
policy sweep through the runtime-operand axis must compile once per
table-length BUCKET, not per program (``policy_axis_compiles`` ==
``policy_axis_buckets``), beat the PR-4 staged per-program loop >= 5x
per policy (``policy_axis_speedup_x``), stay bit-identical to the
staged path (``policy_axis_bitident`` == 1). The reference run is ``--section policy_axis --out
artifacts/BENCH_10.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

STEADY_ROW = "sim_speed_steady_speedup_x"
STEADY_GATE = 2.0
POLICY_ROW = "policy_sweep_interp_overhead_x"
POLICY_GATE = 1.3  # policy-VM scan must stay within 1.3x of hard-coded
EXEC_ROW = "executor_speed_overlap_speedup_x"
EXEC_GATE = 1.5    # overlapped executor vs serial group loop, warm cache
STREAM_RATIO_ROW = "streaming_tput_ratio"
STREAM_RATIO_GATE = 0.9   # stream vs 8x4000 single-shot steady throughput
STREAM_KEYS_ROW = "streaming_compile_keys"
STREAM_RSS_ROW = "streaming_rss_mb"
STREAM_RSS_BUDGET_MB = 2048  # whole-process peak; O(chunk) driver state
FAULTS_KEYS_ROW = "faults_off_compile_keys_equal"
FAULTS_OFF_ROW = "faults_off_overhead_x"
FAULTS_OFF_GATE = 1.05  # disabled fault carry vs no fault model at all
FAULTS_CKPT_ROW = "faults_ckpt_resume_recomputed"
SERVICE_K = 4              # clients in the shared-server arm
SERVICE_SCALING_ROW = "service_scaling_x"
SERVICE_SCALING_GATE = 0.7 * SERVICE_K  # K tenants sharing one engine
#                          must keep >= 0.7*K of a solo tenant's rate
#                          (cross-client coalescing + batch amortization)
SERVICE_COAL_ROW = "service_clients_per_dispatch"
SERVICE_REJ_ROW = "service_rejected"
PAXIS_COMPILES_ROW = "policy_axis_compiles"
PAXIS_BUCKETS_ROW = "policy_axis_buckets"
PAXIS_SPEEDUP_ROW = "policy_axis_speedup_x"
PAXIS_SPEEDUP_GATE = 5.0  # batched axis vs staged per-program loop
PAXIS_BITIDENT_ROW = "policy_axis_bitident"


def _env_header() -> dict:
    """Environment fingerprint for BENCH_<n>.json comparability: the
    same rows mean different things on a different jax/jaxlib, device
    topology (see ROADMAP perf note)."""
    import jax
    import jaxlib
    devs = jax.local_devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "device_count": len(devs),
        "device_kind": devs[0].device_kind if devs else "none",
        "platform": devs[0].platform if devs else "none",
        "cpu_count": os.cpu_count(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None,
                    help="write section rows + wall times + cache stats "
                         "as JSON (BENCH_<n>.json)")
    args = ap.parse_args()

    from benchmarks import kernels_bench, paper, roofline
    from repro.core import emulator

    sections = {
        "timescale": paper.bench_timescale_validation,          # Sec. 6
        "latency_profile": paper.bench_latency_profile,         # Fig. 8
        "rowclone_noflush": lambda: paper.bench_rowclone("noflush"),   # Fig. 10
        "rowclone_clflush": lambda: paper.bench_rowclone("clflush"),   # Fig. 11
        "trcd_profile": paper.bench_trcd_profile,               # Fig. 12
        "trcd_endtoend": (lambda: paper.bench_trcd_endtoend(8)) if args.quick
        else paper.bench_trcd_endtoend,                          # Fig. 13
        "sim_speed": paper.bench_sim_speed,                     # Fig. 14
        "campaign_speed": (lambda: paper.bench_campaign_speed(3))
        if args.quick else paper.bench_campaign_speed,          # run_many
        "policy_sweep": (lambda: paper.bench_policy_sweep(4, 400))
        if args.quick else paper.bench_policy_sweep,            # MC-policy VM
        "executor_speed": (lambda: paper.bench_executor_speed(6, 2000))
        if args.quick else paper.bench_executor_speed,          # PR 5 executor
        "streaming": paper.bench_streaming,                     # PR 7 driver
        "faults": (lambda: paper.bench_faults(
            n_requests=800, study_requests=600)) if args.quick
        else paper.bench_faults,                                # PR 8 faults
        "service": (lambda: paper.bench_service(rounds=40, pairs=3))
        if args.quick else paper.bench_service,                 # ISSUE 9 service
        "policy_axis": (lambda: paper.bench_policy_axis(
            n_requests=400, n_baseline=4)) if args.quick
        else paper.bench_policy_axis,                           # ISSUE 10 axis
        "lm_traces": paper.bench_lm_traces,                     # framework tie-in
        "kernels": kernels_bench.bench_kernels,
        "roofline": lambda: roofline.csv_rows(roofline.load_records("sp")),
    }
    if args.section:
        if args.section not in sections:
            ap.error(f"unknown section {args.section!r}; "
                     f"choose from: {', '.join(sections)}")
        sections = {args.section: sections[args.section]}

    out_path = args.out
    if out_path is None and args.quick and not args.section:
        # full smoke runs refresh the tracked perf-trajectory artifact;
        # filtered runs only write JSON where --out points
        out_path = os.path.join(os.path.dirname(__file__) or ".",
                                "..", "artifacts", "BENCH_quick.json")

    print("name,value,derived")
    report: dict = {"quick": args.quick, "argv": sys.argv[1:],
                    "env": _env_header(), "sections": {}}
    failures = 0
    gate_values: dict = {}
    for name, fn in sections.items():
        rows, error = [], None
        t0 = time.perf_counter()
        try:
            for row in fn():
                rows.append(tuple(row))
                print(",".join(str(x) for x in row))
        except Exception as e:  # pragma: no cover
            failures += 1
            error = f"{type(e).__name__}:{e}"
            print(f"{name},ERROR,{error}")
        dt = time.perf_counter() - t0
        for r in rows:
            if r[0] in (STEADY_ROW, POLICY_ROW, EXEC_ROW,
                        STREAM_RATIO_ROW, STREAM_KEYS_ROW, STREAM_RSS_ROW,
                        FAULTS_KEYS_ROW, FAULTS_OFF_ROW, FAULTS_CKPT_ROW,
                        SERVICE_SCALING_ROW, SERVICE_COAL_ROW,
                        SERVICE_REJ_ROW,
                        PAXIS_COMPILES_ROW, PAXIS_BUCKETS_ROW,
                        PAXIS_SPEEDUP_ROW, PAXIS_BITIDENT_ROW):
                gate_values[r[0]] = float(r[1])
        report["sections"][name] = {
            "rows": [list(r) for r in rows],
            "seconds": round(dt, 2),
            "error": error,
        }
        print(f"_section_{name}_seconds,{dt:.1f},wall", flush=True)
    steady_value = gate_values.get(STEADY_ROW)
    policy_value = gate_values.get(POLICY_ROW)

    # smoke gate: the steady-state engine speedup must be present and
    # at gate whenever the sim_speed section ran (bench_sim_speed also
    # asserts internally; this catches the row silently disappearing)
    if "sim_speed" in sections and not report["sections"]["sim_speed"]["error"]:
        if steady_value is None or steady_value < STEADY_GATE:
            failures += 1
            print(f"_steady_gate,FAIL,{STEADY_ROW}={steady_value}")
    # policy-VM gate: interpreting a scheduling program inside the scan
    # must stay within POLICY_GATE of the hard-coded scheduler
    if "policy_sweep" in sections \
            and not report["sections"]["policy_sweep"]["error"]:
        if policy_value is None or policy_value > POLICY_GATE:
            failures += 1
            print(f"_policy_gate,FAIL,{POLICY_ROW}={policy_value}")
    # executor gate: the overlapped group executor must beat the serial
    # PR 4 loop warm (only meaningful with >1 hardware thread)
    if "executor_speed" in sections \
            and not report["sections"]["executor_speed"]["error"]:
        from repro.core import executor
        exec_value = gate_values.get(EXEC_ROW)
        # overlap needs both hardware threads AND a multi-worker pool
        # (REPRO_EXEC_WORKERS=1 legitimately forces the serial loop)
        if (os.cpu_count() or 1) > 1 and executor.workers() > 1 \
                and (exec_value is None or exec_value < EXEC_GATE):
            failures += 1
            print(f"_executor_gate,FAIL,{EXEC_ROW}={exec_value}")
    # streaming gates: throughput parity with the single-shot steady
    # state, exactly one length-independent compile key, bounded RSS
    if "streaming" in sections \
            and not report["sections"]["streaming"]["error"]:
        ratio = gate_values.get(STREAM_RATIO_ROW)
        if ratio is None or ratio < STREAM_RATIO_GATE:
            failures += 1
            print(f"_streaming_gate,FAIL,{STREAM_RATIO_ROW}={ratio}")
        keys = gate_values.get(STREAM_KEYS_ROW)
        if keys is None or keys != 1:
            failures += 1
            print(f"_streaming_gate,FAIL,{STREAM_KEYS_ROW}={keys}")
        # ru_maxrss is process-wide high-water: sections that ran before
        # streaming (4 MiB rowclone traces, campaign sweeps) own the
        # peak in a full run, so the budget is only enforceable when
        # streaming runs alone (the BENCH_7.json protocol); the row
        # stays informational otherwise
        if args.section == "streaming":
            rss = gate_values.get(STREAM_RSS_ROW)
            if rss is None or rss > STREAM_RSS_BUDGET_MB:
                failures += 1
                print(f"_streaming_gate,FAIL,{STREAM_RSS_ROW}={rss}"
                      f">budget={STREAM_RSS_BUDGET_MB}")
        report["stream_rss_budget_mb"] = STREAM_RSS_BUDGET_MB
    # fault-subsystem gates: (a) faults=None must not perturb compile
    # keys; (b) the cheapest attached fault carry stays within 5% of no
    # fault model at all (the off path itself is byte-identical by key
    # discipline — bench_faults asserts the staged-HLO check); (c) a
    # checkpointed campaign re-run recomputes zero finished groups
    if "faults" in sections and not report["sections"]["faults"]["error"]:
        keys_eq = gate_values.get(FAULTS_KEYS_ROW)
        if keys_eq != 1:
            failures += 1
            print(f"_faults_gate,FAIL,{FAULTS_KEYS_ROW}={keys_eq}")
        off = gate_values.get(FAULTS_OFF_ROW)
        if off is None or off > FAULTS_OFF_GATE:
            failures += 1
            print(f"_faults_gate,FAIL,{FAULTS_OFF_ROW}={off}"
                  f">gate={FAULTS_OFF_GATE}")
        recomputed = gate_values.get(FAULTS_CKPT_ROW)
        if recomputed is None or recomputed != 0:
            failures += 1
            print(f"_faults_gate,FAIL,{FAULTS_CKPT_ROW}={recomputed}")
    # sweep-service gates: K tenants sharing one warm engine must keep
    # >= 0.7*K of a solo tenant's throughput (only reachable through
    # cross-client coalescing on a single device), dispatches must
    # actually mix clients, and the closed-loop load must ride the
    # default admission bounds without one typed rejection
    if "service" in sections and not report["sections"]["service"]["error"]:
        scaling = gate_values.get(SERVICE_SCALING_ROW)
        if scaling is None or scaling < SERVICE_SCALING_GATE:
            failures += 1
            print(f"_service_gate,FAIL,{SERVICE_SCALING_ROW}={scaling}"
                  f"<gate={SERVICE_SCALING_GATE}")
        coal = gate_values.get(SERVICE_COAL_ROW)
        if coal is None or coal <= 1.0:
            failures += 1
            print(f"_service_gate,FAIL,{SERVICE_COAL_ROW}={coal}<=1.0")
        rej = gate_values.get(SERVICE_REJ_ROW)
        if rej is None or rej != 0:
            failures += 1
            print(f"_service_gate,FAIL,{SERVICE_REJ_ROW}={rej}")

    # policy-axis gates (ISSUE 10): a 256-candidate sweep must compile
    # once per table-length BUCKET (not per program), beat the staged
    # per-program loop >= 5x per policy, and stay bit-identical to the
    # staged path
    if "policy_axis" in sections \
            and not report["sections"]["policy_axis"]["error"]:
        compiles = gate_values.get(PAXIS_COMPILES_ROW)
        buckets = gate_values.get(PAXIS_BUCKETS_ROW)
        if compiles is None or buckets is None or compiles != buckets:
            failures += 1
            print(f"_policy_axis_gate,FAIL,{PAXIS_COMPILES_ROW}={compiles}"
                  f"!=buckets={buckets}")
        speedup = gate_values.get(PAXIS_SPEEDUP_ROW)
        if speedup is None or speedup < PAXIS_SPEEDUP_GATE:
            failures += 1
            print(f"_policy_axis_gate,FAIL,{PAXIS_SPEEDUP_ROW}={speedup}"
                  f"<gate={PAXIS_SPEEDUP_GATE}")
        if gate_values.get(PAXIS_BITIDENT_ROW) != 1:
            failures += 1
            print(f"_policy_axis_gate,FAIL,{PAXIS_BITIDENT_ROW}="
                  f"{gate_values.get(PAXIS_BITIDENT_ROW)}")

    report["cache_stats"] = emulator.cache_stats()
    report["failures"] = failures
    print(f"_failures,{failures},smoke_gate")

    if out_path:
        out_path = os.path.abspath(out_path)
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"_report,{out_path},json")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
