"""Call kind ``rowclone``: the RowClone study (Sec. 7) as users run it.
Each call is ``RowClone.campaign(...).run()``: every (size, workload,
setting, system) point of the mix in the cpu and the rowclone arm,
grouped and batched by the program's ``Campaign``. The traces and
their clonability profiling are built inside the call, over fresh
allocations drawn from (seed, call)."""
from __future__ import annotations

import numpy as np

from bench.lib import reference_rowclone as ref, sut

SPAN = "campaign.run"


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, trace_cache: str):
        from repro.core.dram import Geometry
        from repro.core.profiling import DeviceModel
        from repro.core.techniques import Platform, RowClone
        self.cfg, self.seed = cfg, seed
        self.systems = {}
        for name in traffic["systems"]:
            s = cfg["systems"][name]
            self.systems[name] = Platform(sut.system(dict(cfg, system=s["system"])),
                                          s["mode"], int(s["cpu_line_delta"]))
        dm = cfg["device_model"]
        device = DeviceModel(Geometry(**cfg["geometry"]), seed=int(dm["seed"]),
                             weak_target=float(dm["weak_target"]),
                             clone_fail_rate=float(dm["clone_fail_rate"]))
        self.study = RowClone(next(iter(self.systems.values())).sys, device)
        self.grid = (list(traffic["sizes"]), list(traffic["workloads"]),
                     list(traffic["settings"]))
        self.draw = {w: traffic["alloc_base_rows"][w] for w in self.grid[1]}
        self._ref = None

    def inputs(self, k: int) -> dict:
        """The call's allocations: a base row per workload."""
        rng = np.random.default_rng([self.seed % (1 << 64), k % (1 << 64),
                                     0xA110C])
        return {w: int(rng.integers(lo, hi)) for w, (lo, hi) in self.draw.items()}

    def warm(self) -> None:
        self.call(self.inputs(-1))

    def expected(self, inputs) -> int:
        """Points a call on ``inputs`` returns."""
        sizes, workloads, settings = self.grid
        return 2 * len(sizes) * len(workloads) * len(settings) * len(self.systems)

    def call(self, inputs: dict) -> list:
        c = self.study.campaign(*self.grid, self.systems, alloc_base_rows=inputs)
        points = []
        for p, r in zip(c.points, c.run()):
            n = p.trace.n
            points.append(sut.Point(
                key=(r["j"], r["workload"], r["setting"], r["system"], r["arm"]),
                n_real=n, trace=None, result=sut.keep(r, n),
                ref_args={"n_bytes": r["n_bytes"],
                          "base": inputs[r["workload"]]}))
        return points

    def sample(self, points: list, rng) -> list:
        return points

    def reference(self, p: sut.Point, broken: str = None) -> dict:
        """The point rebuilt and emulated by the plain reference from
        its parameters alone."""
        if self._ref is None:
            self._ref = ({n: ref.System(self.cfg, n) for n in self.systems},
                         ref.Device(self.cfg))
        systems, device = self._ref
        _, workload, setting, name, arm = p.key
        tr = ref.build(workload, arm, setting, p.ref_args["n_bytes"],
                       int(self.cfg["systems"][name]["cpu_line_delta"]),
                       p.ref_args["base"], self.cfg, device)
        return ref.emulate(tr, systems[name], guarantee_broken=broken)
