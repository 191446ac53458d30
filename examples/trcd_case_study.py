"""Case study (paper Sec. 8): tRCD reduction — characterize the device,
build the weak-row Bloom filter, run PolyBench-like workloads end-to-end.

  PYTHONPATH=src python examples/trcd_case_study.py

Second runs start fast: XLA executables persist in
``$JAX_COMPILATION_CACHE_DIR`` (else ``<checkout>/artifacts/xla_cache``;
enable_persistent_compile_cache below), so a fresh process skips the
cold compiles; the base + reduced arms of the whole kernel suite then
run through the overlapped campaign executor.
"""
import warnings

warnings.filterwarnings("ignore")

# before the first compile: jax latches the cache decision there
from repro.utils.jax_compat import enable_persistent_compile_cache

enable_persistent_compile_cache()

import numpy as np

from repro.core import traces
from repro.core.dram import Geometry
from repro.core.profiling import DeviceModel
from repro.core.techniques import TRCDReduction
from repro.core.timescale import JETSON_NANO


def main():
    geo = Geometry()
    dev = DeviceModel(geo)
    print(f"device model: {100*(1-dev.weak_fraction()):.1f}% strong rows "
          f"(paper: 84.5%), min tRCD {dev.min_trcd_ns.min():.1f} ns")

    t = TRCDReduction(JETSON_NANO, dev)
    t.characterize()
    s = t.safety_check()
    print(f"bloom filter: false negatives={s['false_negatives']} (must be 0), "
          f"FPR={s['false_positive_rate']:.3%}")

    print(f"\n{'kernel':>14s} {'speedup':>8s}")
    names, trs = [], []
    for i, kern in enumerate(traces.POLYBENCH[:12]):
        tr, _ = traces.polybench_trace(kern, geo, max_accesses=6000, seed=i)
        if tr is None:
            continue
        names.append(kern.name)
        trs.append(tr)
    # base + reduced arms for every kernel in one batched campaign
    # (TRCDReduction.evaluate_traces -> Campaign -> emulator.run_many)
    speedups = []
    for name, r in zip(names, t.evaluate_traces(trs)):
        speedups.append(r["speedup"])
        print(f"{name:>14s} {r['speedup']:>7.3f}x")
    print(f"{'avg':>14s} {np.mean(speedups):>7.3f}x  (paper avg: 1.0275x)")


if __name__ == "__main__":
    main()
