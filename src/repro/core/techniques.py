"""DRAM techniques as software-memory-controller extensions (Secs. 7-8).

Each technique is ~100 lines of plain Python/JAX over the engine — the
paper's accessibility claim, reproduced. ``RowClone`` handles the four
allocation constraints (alignment / granularity / subarray mapping /
coherence) with profiling-driven fallback; ``TRCDReduction`` runs the
two-stage characterize -> Bloom-filter flow and hands the filter to the
engine, which consults it on every row activation;
``SchedulingPolicyStudy`` sweeps software-defined scheduler programs
(``repro.core.smcprog``) across workloads with length-derived SMC costs;
``RowHammerMitigationStudy`` sweeps mitigation programs x hammer
intensities under the fault-injection model (``repro.core.faults``),
trading bit-error rate against emulated slowdown.

Evaluation goes through the batched campaign path
(``emulator.run_many`` / ``campaign.Campaign``): ``RowClone.campaign``
and ``TRCDReduction.campaign`` build a study's whole grid as one
Campaign, with one compile and one dispatch per compile-key group;
``evaluate_batch`` / ``evaluate_traces`` read their records, and the
single-point ``evaluate`` / ``evaluate_trace`` are batches of one.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence

import numpy as np

from repro.core import smcprog, spans, traces
from repro.core.campaign import Campaign, Point
from repro.core.bloom import BloomFilter
from repro.core.faults import FaultModel
from repro.core.profiling import DeviceModel
from repro.core.smcprog import PolicyProgram
from repro.core.timescale import SystemConfig


@dataclasses.dataclass
class RowCloneResult:
    mode: str
    setting: str
    n_bytes: int
    exec_cycles: int
    exec_seconds: float
    fallback_rows: int
    speedup_vs_cpu: float = 0.0


class Platform(NamedTuple):
    """One system of a RowClone study: its configuration, evaluation
    mode, and the per-line cost of the *modeled* CPU's copy loop in
    processor cycles (a 3-wide OoO core with 64B NEON moves retires far
    fewer cycles a line than a 50 MHz single-issue rv64; None keeps the
    trace generator's default)."""
    sys: SystemConfig
    mode: str = "ts"
    cpu_line_delta: Optional[int] = None


_CLONE_WORKLOADS = {"copy": traces.copy_workload,
                    "init": traces.init_workload}


class RowClone:
    """In-DRAM bulk copy/initialization (Sec. 7)."""

    def __init__(self, sys: SystemConfig, device: Optional[DeviceModel] = None):
        self.sys = sys
        self.geo = sys.geometry
        self.device = device or DeviceModel(self.geo)

    def campaign(self, sizes: Sequence[int],
                 workloads: Sequence[str] = ("copy", "init"),
                 settings: Sequence[str] = ("noflush", "clflush"),
                 systems: Optional[Mapping[str, Platform]] = None,
                 alloc_base_rows: Optional[Mapping[str, int]] = None,
                 ) -> Campaign:
        """The Sec. 7 grid as one :class:`Campaign`: every (size,
        workload, setting, system, arm) point, in that order, arm
        ``cpu`` (load/store per line) against ``rowclone`` (in-DRAM ops
        with profiled CPU fallback). ``systems`` maps names to
        :class:`Platform` s (default: this study's system, mode ``ts``);
        ``alloc_base_rows`` maps a workload to its allocation's base row
        (default: the generator's), so callers can draw fresh
        allocations. Records carry ``j`` (position in ``sizes``),
        ``n_bytes``, ``workload``, ``setting``, ``arm``, ``system`` and
        ``fallback_rows``. Traces and clonability profiling are built
        here, in the ``tech.traces`` span."""
        systems = {"sys": Platform(self.sys)} if systems is None \
            else dict(systems)
        for name, pf in systems.items():
            if pf.sys.geometry != self.geo:  # traces are laid out on self.geo
                raise ValueError(f"system {name!r} has another geometry "
                                 f"than the study's {self.geo}")
        alloc = alloc_base_rows or {}
        c = Campaign()
        with spans.span("tech.traces") as sp:
            for (j, nb), wl, setting, (name, pf), arm in itertools.product(
                    enumerate(sizes), workloads, settings, systems.items(),
                    ("cpu", "rowclone")):
                kw = {k: v for k, v in (("alloc_base_row", alloc.get(wl)),
                                        ("cpu_line_delta", pf.cpu_line_delta))
                      if v is not None}
                tr, meta = _CLONE_WORKLOADS[wl](nb, self.geo, arm, self.device,
                                                setting, **kw)
                c.add(tr, pf.sys, mode=pf.mode, j=j, n_bytes=nb, workload=wl,
                      setting=setting, arm=arm, system=name,
                      fallback_rows=meta["fallback_rows"])
            if sp:
                sp.add(points=len(c), fallback_rows=sum(
                    p.meta["fallback_rows"] for p in c.points))
        return c

    @staticmethod
    def results(records: Sequence[dict]) -> Dict[tuple, dict]:
        """:meth:`campaign` records by ``(j, workload, setting,
        system)``: ``{'cpu': RowCloneResult, 'rowclone':
        RowCloneResult}``, the RowClone arm's ``speedup_vs_cpu`` set."""
        out: Dict[tuple, dict] = {}
        for r in records:
            key = (r["j"], r["workload"], r["setting"], r["system"])
            out.setdefault(key, {})[r["arm"]] = RowCloneResult(
                mode=r["arm"], setting=r["setting"], n_bytes=r["n_bytes"],
                exec_cycles=int(r["exec_cycles"]),
                exec_seconds=r["exec_seconds"],
                fallback_rows=r["fallback_rows"])
        for d in out.values():
            d["rowclone"].speedup_vs_cpu = \
                d["cpu"].exec_cycles / max(d["rowclone"].exec_cycles, 1)
        return out

    def evaluate(self, n_bytes: int, workload: str = "copy",
                 setting: str = "noflush", mode_ts: str = "ts",
                 cpu_line_delta: int = None):
        """Returns {'cpu': RowCloneResult, 'rowclone': RowCloneResult}."""
        return self.evaluate_batch([n_bytes], workload, setting, mode_ts,
                                   cpu_line_delta)[0]

    def evaluate_batch(self, sizes: Sequence[int], workload: str = "copy",
                       setting: str = "noflush", mode_ts: str = "ts",
                       cpu_line_delta: int = None) -> List[dict]:
        """Sweep ``sizes`` for one workload, setting and mode through
        :meth:`campaign`: one {'cpu': ..., 'rowclone': ...} dict per
        size, in order."""
        sizes = list(sizes)
        res = self.results(self.campaign(
            sizes, (workload,), (setting,),
            {"sys": Platform(self.sys, mode_ts, cpu_line_delta)}).run())
        return [res[(j, workload, setting, "sys")] for j in range(len(sizes))]


class SchedulingPolicyStudy:
    """Scheduling policies as software — the paper's first key idea,
    turned into a technique-style sweep. A study takes a grid of
    :class:`~repro.core.smcprog.PolicyProgram` schedulers (default: all
    built-ins) and evaluates every (trace x policy x mode) point through
    one batched :class:`Campaign` — one compiled executable and one
    dispatch per program group.

    Two cost treatments, matching the paper's ts/nots axis:

    * ``derive_cost=True`` (default) — each program's SMC decision cost
      follows its length (``with_policy``), so ``nots`` records expose
      how a longer policy program slows the free-running system while
      ``ts`` records stay invariant to it (time scaling hides SMC
      slowness — the claim itself).
    * ``derive_cost=False`` — all programs keep ``sys``'s cost; results
      isolate pure scheduling quality.
    """

    def __init__(self, sys: SystemConfig,
                 programs: Optional[Sequence[PolicyProgram]] = None,
                 baseline: str = "frfcfs"):
        self.sys = sys
        self.programs = list(programs) if programs is not None \
            else list(smcprog.builtin_programs().values())
        if not self.programs:
            raise ValueError("need at least one policy program")
        names = [p.name for p in self.programs]
        if len(set(names)) != len(names):
            dupes = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(
                f"program names must be unique (results key on them), "
                f"got duplicates {dupes}")
        self.baseline = baseline

    def evaluate_traces(self, trs: Sequence, mode: str = "ts",
                        derive_cost: bool = True,
                        policy_axis: bool = True) -> List[Dict]:
        """Returns one dict per trace, in input order:
        ``{policy_name: {exec_cycles, row_hits, smc_cycles,
        speedup_vs_baseline}}``. ``policy_axis=True`` (default) rides
        the runtime policy operand — the whole program grid shares one
        compiled executable and one dispatch per trace-length bucket;
        ``policy_axis=False`` keeps the staged-constant path (one
        compile per program). Results are bit-identical either way."""
        c = Campaign()
        for i, tr in enumerate(trs):
            c.add_policy_grid(tr, self.sys, self.programs, mode=mode,
                              derive_cost=derive_cost,
                              policy_axis=policy_axis, i=i)
        recs = {(r["i"], r["policy"]): r for r in c.run()}
        cost = {p.name: p.smc_cycles() if derive_cost
                else self.sys.smc_cycles_per_decision for p in self.programs}
        out: List[Dict] = []
        for i in range(len(trs)):
            d = {}
            base = None
            if any(p.name == self.baseline for p in self.programs):
                base = int(recs[(i, self.baseline)]["exec_cycles"])
            for p in self.programs:
                r = recs[(i, p.name)]
                e = int(r["exec_cycles"])
                d[p.name] = {
                    "exec_cycles": e,
                    "row_hits": int(r["row_hits"]),
                    "smc_cycles": cost[p.name],
                    "speedup_vs_baseline":
                        (base / max(e, 1)) if base is not None else 1.0,
                }
            out.append(d)
        return out


class RowHammerMitigationStudy:
    """RowHammer mitigations as software-memory-controller programs,
    judged end-to-end under the fault-injection model (PR 8): each
    (mitigation program x hammer intensity) point replays a
    :func:`traces.rowhammer_trace` aggressor storm under one
    :class:`~repro.core.faults.FaultModel`, and the record pairs the
    resulting bit-error rate with the mitigation's emulated slowdown —
    the reliability-vs-performance tradeoff curve the paper's
    methodology exists to measure quickly.

    Programs default to :func:`smcprog.mitigation_programs`:
    ``frfcfs`` (no mitigation — the BER ceiling and the slowdown
    baseline), ``para`` (probabilistic neighbor refresh on row-miss
    activations) and ``trr`` (activation-counter-triggered refresh).
    ``derive_cost=True`` additionally charges each program's SMC
    decision cost by its length, so the slowdown axis includes the
    software controller overhead, not just the injected neighbor
    refreshes."""

    def __init__(self, sys: SystemConfig,
                 fault_model: Optional[FaultModel] = None,
                 programs: Optional[Dict[str, PolicyProgram]] = None,
                 baseline: str = "frfcfs"):
        self.sys = sys
        self.geo = sys.geometry
        self.fault_model = fault_model if fault_model is not None else \
            FaultModel(seed=7, hammer_threshold=48, hammer_flip_fp=52000)
        # default arms are tuned TO the fault model: TRR must trigger
        # below the hammer threshold or it never fires, and PARA at ~5%
        # per activation meaningfully resets a threshold-48 counter
        self.programs = dict(programs) if programs is not None \
            else smcprog.mitigation_programs(
                para_fp=3277,
                trr_threshold=max(1, self.fault_model.hammer_threshold // 2))
        if baseline not in self.programs:
            raise ValueError(
                f"baseline {baseline!r} not among programs "
                f"{sorted(self.programs)}")
        self.baseline = baseline

    def evaluate(self, intensities: Sequence[float] = (0.45, 0.9),
                 n_requests: int = 480, mode: str = "ts", seed: int = 0,
                 derive_cost: bool = True, policy_axis: bool = True,
                 **run_kw) -> List[dict]:
        """One record per intensity, in order: ``{'intensity': f,
        <program>: {bit_error_rate, flips, mitigations, exec_cycles,
        exec_seconds, slowdown_vs_unmitigated}}``. All points run as one
        batched campaign. ``policy_axis=True`` (default) carries each
        mitigation program as a runtime operand, so every (program x
        intensity) point sharing a table-length bucket shares ONE
        compiled executable and dispatch; ``policy_axis=False`` keeps
        the staged path (one compile per program). ``run_kw`` passes
        through to :meth:`Campaign.run` (``checkpoint=...`` resumes a
        killed sweep)."""
        import dataclasses as _dc
        c = Campaign()
        sysf = self.sys.with_faults(self.fault_model)
        for i, inten in enumerate(intensities):
            tr = traces.rowhammer_trace(n_requests, self.geo,
                                        intensity=float(inten),
                                        seed=seed + i)
            for name, prog in self.programs.items():
                if policy_axis:
                    cost = prog.smc_cycles() if derive_cost \
                        else int(self.sys.smc_cycles_per_decision)
                    # direct Point append: the dict key (not prog.name)
                    # labels the record, and mixed table buckets simply
                    # fork into per-bucket groups here
                    c.points.append(Point(
                        tr, sysf, mode, None, {"mitigation": name, "i": i},
                        policy=prog, policy_cost=cost))
                    continue
                sysc = self.sys.with_policy(prog) if derive_cost \
                    else _dc.replace(self.sys, policy=prog)
                c.add(tr, sysc.with_faults(self.fault_model), mode,
                      mitigation=name, i=i)
        recs = {(r["i"], r["mitigation"]): r for r in c.run(**run_kw)}
        out: List[dict] = []
        for i, inten in enumerate(intensities):
            base = int(recs[(i, self.baseline)]["exec_cycles"])
            d: dict = {"intensity": float(inten)}
            for name in self.programs:
                r = recs[(i, name)]
                d[name] = {
                    "bit_error_rate": float(r["bit_error_rate"]),
                    "flips": int(r["flips"]),
                    "mitigations": int(r["mitigations"]),
                    "exec_cycles": int(r["exec_cycles"]),
                    "exec_seconds": float(r["exec_seconds"]),
                    "slowdown_vs_unmitigated":
                        int(r["exec_cycles"]) / max(base, 1),
                }
            out.append(d)
        return out


class TRCDReduction:
    """Reduced-tRCD access via characterization + Bloom filter (Sec. 8)."""

    def __init__(self, sys: SystemConfig, device: Optional[DeviceModel] = None,
                 m_bits: int = 1 << 20, k: int = 4):
        self.sys = sys
        self.geo = sys.geometry
        self.device = device or DeviceModel(self.geo)
        self.m_bits = m_bits
        self.k = k
        self._bloom: Optional[BloomFilter] = None

    def characterize(self) -> BloomFilter:
        """Stage 1+2: profile rows (device model = the profiling requests'
        results), key the Bloom filter with weak rows."""
        weak = self.device.weak_rows()
        self._bloom = BloomFilter.build(weak, m_bits=self.m_bits, k=self.k)
        return self._bloom

    @property
    def bloom_tuple(self):
        if self._bloom is None:
            self.characterize()
        b = self._bloom
        return (b.bits, b.k, b.m_bits)

    def safety_check(self, n=100000, seed=1):
        """A false positive must map weak->nominal only: verify no weak row
        ever probes negative (zero false negatives by construction)."""
        weak = self.device.weak_rows()
        assert self._bloom is not None
        miss = (~self._bloom.contains(weak)).sum()
        rng = np.random.RandomState(seed)
        probe = rng.randint(0, self.geo.n_banks * self.geo.n_rows, n)
        truth = self.device.weak.reshape(-1)[probe]
        fpr = self._bloom.false_positive_rate(probe, truth)
        return {"false_negatives": int(miss), "false_positive_rate": float(fpr)}

    def evaluate_trace(self, trace, mode_ts: str = "ts"):
        """Run a workload with and without reduced-tRCD scheduling."""
        return self.evaluate_traces([trace], mode_ts)[0]

    def campaign(self, trs: Sequence, mode_ts: str = "ts") -> Campaign:
        """The base-vs-reduced grid: every trace with and without the
        Bloom filter (records carry ``i`` = trace index and ``arm``)."""
        bloom = self.bloom_tuple
        c = Campaign()
        for i, tr in enumerate(trs):
            c.add(tr, self.sys, mode=mode_ts, i=i, arm="base")
            c.add(tr, self.sys, mode=mode_ts, bloom=bloom, i=i, arm="reduced")
        return c

    def evaluate_traces(self, trs: Sequence, mode_ts: str = "ts") -> List[dict]:
        """Batched base-vs-reduced sweep: :meth:`campaign` in one run
        (one compile and one dispatch per length bucket: the base arm
        rides the reduced arm's dispatch with its filter mask off).
        Returns per-trace dicts in input order."""
        arms = {(r["i"], r["arm"]): int(r["exec_cycles"])
                for r in self.campaign(trs, mode_ts).run()}
        return [{
            "base_cycles": arms[(i, "base")],
            "reduced_cycles": arms[(i, "reduced")],
            "speedup": arms[(i, "base")] / max(arms[(i, "reduced")], 1),
        } for i in range(len(trs))]
