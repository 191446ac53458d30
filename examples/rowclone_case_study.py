"""Case study (paper Sec. 7): RowClone end-to-end, with and without time
scaling — reproduces the paper's core finding that platforms that do not
faithfully model a modern CPU inflate DRAM-technique benefits.

  PYTHONPATH=src python examples/rowclone_case_study.py

Second runs start fast: XLA executables persist in
``$JAX_COMPILATION_CACHE_DIR`` (else ``<checkout>/artifacts/xla_cache``;
enable_persistent_compile_cache below), so a fresh process skips the
cold compiles, and the whole grid executes as one campaign through
the overlapped executor.
"""
import warnings

warnings.filterwarnings("ignore")

# before the first compile: jax latches the cache decision there
from repro.utils.jax_compat import enable_persistent_compile_cache

enable_persistent_compile_cache()

from repro.core.dram import Geometry
from repro.core.profiling import DeviceModel
from repro.core.techniques import Platform, RowClone
from repro.core.timescale import JETSON_NANO, PIDRAM_LIKE

TS_LINE = 4     # A57-class copy loop (cycles per 64B line)
NOTS_LINE = 20  # 50 MHz in-order rv64 copy loop


def main():
    rc = RowClone(JETSON_NANO, DeviceModel(Geometry()))
    systems = {
        "TS": Platform(JETSON_NANO, "ts", TS_LINE),          # EasyDRAM
        "NoTS": Platform(PIDRAM_LIKE, "nots", NOTS_LINE),    # PiDRAM-like
    }
    sizes = (65536, 1 << 20, 4 << 20)
    settings = ("noflush", "clflush")
    # the whole grid (sizes x setting x system x arm) is one batched
    # campaign: one compile and one dispatch per compile-key group
    res = RowClone.results(
        rc.campaign(sizes, ("copy",), settings, systems).run())
    for setting in settings:
        print(f"\n=== Copy, {setting} (speedup over CPU ld/st copy) ===")
        print(f"{'size':>10s} {'TS':>8s} {'NoTS':>8s} {'inflation':>10s}")
        for j, nb in enumerate(sizes):
            s_ts, s_no = (res[(j, "copy", setting, name)]["rowclone"]
                          .speedup_vs_cpu for name in systems)
            print(f"{nb:>10d} {s_ts:>7.1f}x {s_no:>7.1f}x {s_no/s_ts:>9.1f}x")
    print("\npaper: TS 15.0x vs NoTS 306.7x avg (copy, no-flush) -> ~20x "
          "inflation from not modeling the real CPU")


if __name__ == "__main__":
    main()
