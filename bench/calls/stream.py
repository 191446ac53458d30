"""Call kind ``stream``: long-trace replay through the streaming
driver. Each call is ``emulator.run_stream_many`` over every kernel's
trace at once (one lane per stream), in windows of ``chunk`` requests,
with ``collect="full"`` so every request's tags come back; the arm is
the reduced-tRCD one, so every activation probes the Bloom filter."""
from __future__ import annotations

from bench.lib import polybench, reference as ref, sut

SPAN = "run_stream_many"


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int, trace_cache: str):
        self.cfg, self.seed = cfg, seed
        self.sys = sut.system(cfg)
        self.bloom = sut.trcd_study(cfg, self.sys).bloom_tuple
        suite = polybench.suite(traffic["max_accesses"], cfg["geometry"],
                                trace_cache)
        keep = traffic.get("kernels")
        self.kernels = [(i, tr) for i, tr in enumerate(suite)
                        if keep is None or i in keep]
        self.chunk = int(traffic["chunk"])
        self._ref = None

    def inputs(self, k: int) -> list:
        g = self.cfg["geometry"]
        return [((i, k), polybench.variant(tr, self.seed, k, 0,
                                           g["n_banks"], g["n_rows"]))
                for i, tr in self.kernels]

    def warm(self) -> None:
        """One request per stream: the window executable of this batch
        is compiled and primed, and one window runs."""
        self.call([(key, {f: a[:1] for f, a in x.items()})
                   for key, x in self.inputs(-1)])

    def expected(self, inputs) -> int:
        """Points a call on ``inputs`` returns."""
        return len(inputs)

    def call(self, inputs: list) -> list:
        from repro.core import emulator
        from repro.core.emulator import Trace
        recs = emulator.run_stream_many(
            [Trace.of(**x) for _, x in inputs], self.sys, blooms=self.bloom,
            chunk=self.chunk, collect="full")
        return [sut.Point(key=key, n_real=len(x["kind"]), trace=x,
                          result=sut.keep(r, len(x["kind"])))
                for (key, x), r in zip(inputs, recs)]

    def sample(self, points: list, rng) -> list:
        return points

    def reference(self, p: sut.Point, broken: str = None) -> dict:
        if self._ref is None:
            self._ref = (ref.System(self.cfg), sut.reference_bloom(self.cfg))
        sysr, bloom = self._ref
        return ref.emulate(p.trace, sysr, bloom, guarantee_broken=broken)
