"""Case study (paper Sec. 7): RowClone end-to-end, with and without time
scaling — reproduces the paper's core finding that platforms that do not
faithfully model a modern CPU inflate DRAM-technique benefits.

  PYTHONPATH=src python examples/rowclone_case_study.py

Second runs start fast: XLA executables persist in
``$JAX_COMPILATION_CACHE_DIR`` (else ``<checkout>/artifacts/xla_cache``;
enable_persistent_compile_cache below), so a fresh process skips the
cold compiles, and the size sweeps execute through the overlapped
campaign executor.
"""
import warnings

warnings.filterwarnings("ignore")

# before the first compile: jax latches the cache decision there
from repro.utils.jax_compat import enable_persistent_compile_cache

enable_persistent_compile_cache()

from repro.core.dram import Geometry
from repro.core.profiling import DeviceModel
from repro.core.techniques import RowClone
from repro.core.timescale import JETSON_NANO, PIDRAM_LIKE

TS_LINE = 4     # A57-class copy loop (cycles per 64B line)
NOTS_LINE = 20  # 50 MHz in-order rv64 copy loop


def main():
    dev = DeviceModel(Geometry())
    rc_ts = RowClone(JETSON_NANO, dev)        # EasyDRAM - Time Scaling
    rc_nots = RowClone(PIDRAM_LIKE, dev)      # PiDRAM-like - No Time Scaling

    sizes = (65536, 1 << 20, 4 << 20)
    for setting in ("noflush", "clflush"):
        print(f"\n=== Copy, {setting} (speedup over CPU ld/st copy) ===")
        print(f"{'size':>10s} {'TS':>8s} {'NoTS':>8s} {'inflation':>10s}")
        # the whole size sweep runs as one batched campaign per system
        # (emulator.run_many under the hood: one compile per group)
        a_all = rc_ts.evaluate_batch(sizes, "copy", setting, "ts",
                                     cpu_line_delta=TS_LINE)
        b_all = rc_nots.evaluate_batch(sizes, "copy", setting, "nots",
                                       cpu_line_delta=NOTS_LINE)
        for nb, a, b in zip(sizes, a_all, b_all):
            s_ts = a["rowclone"].speedup_vs_cpu
            s_no = b["rowclone"].speedup_vs_cpu
            print(f"{nb:>10d} {s_ts:>7.1f}x {s_no:>7.1f}x {s_no/s_ts:>9.1f}x")
    print("\npaper: TS 15.0x vs NoTS 306.7x avg (copy, no-flush) -> ~20x "
          "inflation from not modeling the real CPU")


if __name__ == "__main__":
    main()
