"""Call kind ``campaign``: the tRCD study as users run it. Each call is
``TRCDReduction.campaign(traces).run()``: every kernel of the mix (in
``variants`` fresh copies) with and without the weak-row Bloom filter,
grouped and batched by the program's ``Campaign``."""
from __future__ import annotations

from bench.lib import polybench, reference as ref, sut

SPAN = "campaign.run"


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, trace_cache: str):
        self.cfg, self.seed = cfg, seed
        self.sys = sut.system(cfg)
        self.study = sut.trcd_study(cfg, self.sys)
        suite = polybench.suite(traffic["max_accesses"], cfg["geometry"],
                                trace_cache)
        keep = traffic.get("kernels")
        self.kernels = [(i, tr) for i, tr in enumerate(suite)
                        if keep is None or i in keep]
        self.variants = int(traffic.get("variants", 1))
        self._ref = None

    def inputs(self, k: int) -> list:
        g = self.cfg["geometry"]
        return [((i, v), polybench.variant(tr, self.seed, k, v,
                                           g["n_banks"], g["n_rows"]))
                for i, tr in self.kernels for v in range(self.variants)]

    def warm(self) -> None:
        self.call(self.inputs(-1))

    def expected(self, inputs) -> int:
        """Points a call on ``inputs`` returns."""
        return 2 * len(inputs)

    def call(self, inputs: list) -> list:
        from repro.core.emulator import Trace
        recs = self.study.campaign([Trace.of(**x) for _, x in inputs]).run()
        points = []
        for j, (key, x) in enumerate(inputs):
            n = len(x["kind"])
            for a, arm in enumerate(("base", "reduced")):
                points.append(sut.Point(
                    key=key + (arm,), n_real=n, trace=x,
                    result=sut.keep(recs[2 * j + a], n),
                    ref_args={"bloom": arm == "reduced"}))
        return points

    def sample(self, points: list, rng) -> list:
        return points

    def reference(self, p: sut.Point, broken: str = None) -> dict:
        if self._ref is None:
            self._ref = (ref.System(self.cfg), sut.reference_bloom(self.cfg))
        sysr, bloom = self._ref
        return ref.emulate(p.trace, sysr, bloom if p.ref_args["bloom"] else None,
                           guarantee_broken=broken)
