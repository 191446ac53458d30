"""The system under test, built from a configuration file: the
program's ``SystemConfig`` and, where the configuration names one, its
characterized weak-row Bloom filter. And the shared pieces of every
call: a point's record and its comparison with the plain reference."""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib import reference as ref

FIELDS = ("exec_cycles", "row_hits", "served", "dram_ticks",
          "smc_fpga_cycles", "t_issue", "t_resp")
SCALARS = FIELDS[:5]


def system(cfg: dict):
    from repro.core import dram
    from repro.core.timescale import SystemConfig
    timing = dict(cfg["timing"])
    if float(timing.pop("tck_ns")) != dram.TCK_NS:
        raise ValueError(f"the program's DRAM clock is {dram.TCK_NS} ns, "
                         f"the configuration states {cfg['timing']['tck_ns']}")
    return SystemConfig(**cfg["system"], timing=dram.Timing(**timing),
                        geometry=dram.Geometry(**cfg["geometry"]))


def trcd_study(cfg: dict, sysc):
    """``TRCDReduction`` over the configuration's device model, with its
    Bloom filter built (the characterization pass)."""
    from repro.core.profiling import DeviceModel
    from repro.core.techniques import TRCDReduction
    dm = cfg["device_model"]
    study = TRCDReduction(
        sysc, DeviceModel(sysc.geometry, seed=int(dm["seed"]),
                          weak_target=float(dm["weak_target"])),
        m_bits=int(cfg["bloom"]["m_bits"]), k=int(cfg["bloom"]["k"]))
    study.characterize()
    return study


def reference_bloom(cfg: dict) -> ref.Bloom:
    return ref.Bloom(ref.weak_rows(cfg), cfg["bloom"]["m_bits"],
                     cfg["bloom"]["k"])


@dataclasses.dataclass
class Point:
    """One completed point of a call: what went in, what came back."""
    key: tuple
    n_real: int
    trace: dict                 # the input trace's arrays
    result: dict                # the program's statistics (FIELDS)
    ref_args: dict = dataclasses.field(default_factory=dict)


def keep(rec: dict, n: int) -> dict:
    """The compared fields of a program record, per-request arrays cut
    to the trace's own length (batches pad with NOPs)."""
    out = {f: int(rec[f]) for f in SCALARS}
    for f in ("t_issue", "t_resp"):
        out[f] = np.array(np.asarray(rec[f])[:n], np.int64)
    return out


def unfinished(p: Point) -> bool:
    """A point the program did not emulate to its end."""
    return (p.result["served"] != p.n_real
            or bool((p.result["t_resp"] >= ref.BIG).any()))


def differs(got: dict, want: dict) -> list:
    """Names of the fields in which two records differ."""
    bad = [f for f in SCALARS if int(got[f]) != int(want[f])]
    for f in ("t_issue", "t_resp"):
        a, b = np.asarray(got[f]), np.asarray(want[f])
        if a.shape != b.shape or not np.array_equal(a, b):
            bad.append(f)
    return bad
