"""Batched emulation campaigns over (workload x system x mode x technique).

The paper's methodology (Secs. 6-8; PiDRAM / DRAM Bender share it) is
sweep-heavy: one DRAM technique is judged across many workloads, sizes,
system configs, and evaluation modes. Point-at-a-time evaluation pays a
fresh ``jax.jit`` compile of the ``2N+4``-step scan for every sweep
point; a :class:`Campaign` instead collects the whole grid, groups
points by compile key (trace-length bucket, ``SystemConfig``, mode,
Bloom-filter shape), executes each group as ONE vmapped
:func:`repro.core.emulator.run_many` call, and returns tidy per-point
records in submission order. Points without a filter join the filtered
points of their bucket, config and mode as lanes whose filter mask is
off (:func:`plan_groups`), so a base-vs-reduced grid is one dispatch
per bucket.

Usage::

    from repro.core.campaign import Campaign

    c = Campaign()
    for kern, tr in traces_by_kernel.items():
        c.add(tr, JETSON_NANO, mode="ts", workload=kern)
        c.add(tr, JETSON_NANO, mode="ts", bloom=bloom_tuple,
              workload=kern, technique="trcd")
    records = c.run()          # [{workload, technique, exec_cycles, ...}]

Results are bit-identical to looping ``emulator.run`` over the points —
the batch axis only vectorizes the same exact int32 arithmetic — but a
sweep compiles at most once per group and dispatches once per group.
Since PR 5 the groups themselves no longer execute serially either:
``run()`` prepares every group and hands the batch to
``repro.core.executor``, which overlaps host-side packing with device
compute and runs independent groups concurrently (``run(serial=True)``
keeps the old in-order loop for A/B). With more than one local device,
each group's batch axis additionally shards via ``shard_map``
(``emulator.set_sharding``).

Unbounded workloads are one more grid axis: ``add(stream, sys,
stream=True, chunk=...)`` accepts an iterable (or generator factory) of
``Trace`` windows and routes through ``emulator.run_stream_many`` — the
constant-memory chunked-window driver — so technique x workload sweeps
can replay production-scale traces next to padded micro-traces in one
campaign. Stream points group on ``(chunk, sys, mode, bloom-shape)``
with no length bucket at all.

Policy sweeps are one more grid axis: :meth:`Campaign.add_policy_grid`
fans a trace out across a set of :class:`repro.core.smcprog.PolicyProgram`
schedulers. By default (``policy_axis=True``) the programs ride the
runtime policy operand: every program whose packed table fits the same
length bucket shares ONE compile-key group and ONE vmapped dispatch —
256 same-bucket policies are one executable and one device call. The
PR 4 staged-constant path (one compile-key group per distinct program)
stays selectable with ``policy_axis=False`` for A/B.
"""
from __future__ import annotations

import dataclasses
import hashlib
import os
import pickle
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from repro.core import emulator, executor, spans
from repro.core.emulator import Trace
from repro.core.smcprog import PolicyProgram
from repro.core.timescale import SystemConfig


@dataclasses.dataclass
class Point:
    """One grid point. ``meta`` is carried through to the result.

    ``stream=True`` marks an unbounded point: ``trace`` is then a
    Trace, an iterable of Trace windows, or a zero-arg callable
    returning one, evaluated through the constant-memory
    ``emulator.run_stream_many`` path in windows of ``chunk``
    requests."""
    trace: Any
    sys: SystemConfig
    mode: str = "ts"
    bloom: Optional[tuple] = None       # (words_u32, k, m_bits)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    stream: bool = False
    chunk: Optional[int] = None         # stream window size (stream only)
    # runtime-operand policy axis (add_policy_grid(policy_axis=True)):
    # the program rides the dispatch as data, sys stays policy-free
    policy: Optional[PolicyProgram] = None
    policy_cost: Optional[int] = None   # smc_cycles_per_decision operand
    # memoized content_digest() — not part of identity/compares
    _digest: Optional[str] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def content_digest(self) -> str:
        """sha1 hex digest of this point's result-relevant content: the
        mode plus every trace array plus bloom words/params (meta is
        excluded — it is re-applied at merge time). Memoized on the
        point, so repeated :func:`_group_digest` calls — a second
        ``Campaign.run(checkpoint=...)``, or the sweep service's
        per-dispatch checkpoint path under load — hash each large trace
        exactly once instead of once per call. Points are treated as
        immutable after ``add``; mutating a trace in place after the
        first digest would go unnoticed (the same assumption the
        executor's ``pack`` closures already make). Stream points have
        no content address (one-shot iterators) and raise."""
        if self.stream:
            raise ValueError(
                "stream points have no content digest (their input is a "
                "one-shot iterator); checkpointing skips them")
        if self._digest is None:
            h = hashlib.sha1()
            h.update(self.mode.encode())
            for f in ("kind", "bank", "row", "delta", "dep"):
                h.update(np.ascontiguousarray(
                    np.asarray(getattr(self.trace, f), np.int32)).tobytes())
            if self.bloom is not None:
                h.update(np.ascontiguousarray(
                    np.asarray(self.bloom[0])).tobytes())
                h.update(repr((int(self.bloom[1]),
                               int(self.bloom[2]))).encode())
            if self.policy is not None:
                # packed table content + cost operand: two points with
                # the same trace but different runtime policies must
                # never share a checkpoint address
                from repro.core.smcprog import pack_program
                h.update(np.ascontiguousarray(
                    pack_program(self.policy)).tobytes())
                h.update(repr(int(self.policy_cost or 0)).encode())
            self._digest = h.hexdigest()
        return self._digest

    def group_key(self) -> tuple:
        # emulator.group_key is the single source of truth for bucket /
        # mode / bloom-shape normalization; slot budget and batch axis
        # are derived per group inside the run_many call
        if self.stream:
            # no length bucket by construction: streamed points group on
            # (chunk, sys, mode, bloom-shape) alone, whatever their size
            chunk = self.chunk or emulator.DEFAULT_STREAM_CHUNK
            return ("stream", chunk, self.sys,
                    emulator._norm_mode(self.mode),
                    emulator._bloom_shape(self.bloom))
        return emulator.group_key(self.trace.n, self.sys, self.mode,
                                  self.bloom, policy=self.policy)

    def coalesce_key(self) -> tuple:
        """:meth:`group_key` without the filter shape: the points that
        may share one dispatch, which :func:`plan_groups` then splits by
        filter shape. Stream points keep their group key (the window
        runner has no lane mask)."""
        if self.stream:
            return self.group_key()
        return emulator.group_key(self.trace.n, self.sys, self.mode, None,
                                  policy=self.policy)


def plan_groups(points: Sequence[Point]) -> Dict[tuple, List[int]]:
    """The dispatch groups of ``points``: group key -> point indices, in
    order of first appearance. Each point groups on its
    :meth:`Point.group_key`, except that an unfiltered non-stream point
    joins the filtered points of its :meth:`Point.coalesce_key` when
    those all have one filter shape; it then rides their dispatch with
    its lane's filter mask off. Where a coalesce key holds no filtered
    point, or several filter shapes, the unfiltered points keep a group
    of their own. ``Campaign.run`` and the sweep service both group by
    this rule."""
    shapes: Dict[tuple, set] = {}
    for p in points:
        if p.bloom is not None and not p.stream:
            shapes.setdefault(p.coalesce_key(), set()).add(p.group_key())
    groups: Dict[tuple, List[int]] = {}
    for i, p in enumerate(points):
        key = p.group_key()
        if p.bloom is None and not p.stream:
            filtered = shapes.get(p.coalesce_key(), ())
            if len(filtered) == 1:
                (key,) = filtered
        groups.setdefault(key, []).append(i)
    return groups


def group_blooms(pts: Sequence[Point]):
    """The ``blooms`` argument of one group's dispatch: None, one filter
    shared by every point, or the per-point list (None for an unfiltered
    point; :func:`repro.core.emulator.run_many` broadcasts one filter
    object and stacks distinct ones)."""
    blooms = [p.bloom for p in pts]
    return blooms[0] if all(b is blooms[0] for b in blooms) else blooms


def _group_digest(key: tuple, pts: Sequence[Point]) -> str:
    """Content address of one compile-key group's RESULTS: the group key
    (system config, mode, shapes — policy and fault models included via
    SystemConfig) plus every member point's memoized
    :meth:`Point.content_digest` (mode + trace arrays + bloom words),
    in group order. Two campaigns computing the same digest would
    produce bit-identical ``outs`` for the group — which is what makes
    checkpoint resume safe: a stale or foreign file can only collide by
    content, not by position. The per-point hashing is hoisted into the
    point (one O(trace) hash per point per process, however many
    ``run(checkpoint=...)`` calls or service drain-and-checkpoint
    passes re-derive the group path)."""
    h = hashlib.sha1()
    h.update(repr(key).encode())
    for p in pts:
        h.update(p.content_digest().encode())
    return h.hexdigest()[:16]


def _checkpointed(orig_finalize, outs: List[Optional[dict]], path: str):
    """Wrap a task's ``finalize`` so the group's result list is persisted
    the moment its last slot lands (atomically: tmp + rename — a kill
    mid-write leaves no half file, the group just recomputes). A group
    spanning several tasks saves once, from whichever task finishes
    last; concurrent finalizers can at worst both write identical bytes
    and ``os.replace`` keeps either one whole."""
    def finalize(out, ctx):
        orig_finalize(out, ctx)
        if all(o is not None for o in outs):
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                pickle.dump(outs, fh, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, path)
    return finalize


class Campaign:
    """Collect grid points, execute them in compile-key groups.

    ``add`` order is preserved in ``run()``'s output; extra keyword
    arguments to ``add`` (workload name, technique label, size, ...)
    come back verbatim on each record, which is what makes the output
    tidy-data-friendly for the paper-figure scripts.

    ``run(checkpoint=dir)`` persists each completed group's results
    incrementally and resumes a killed sweep with zero recomputation;
    ``run(on_error='quarantine')`` isolates failing grid points instead
    of abandoning the sweep. ``last_run`` reports what happened.
    """

    def __init__(self) -> None:
        self.points: List[Point] = []
        # stats of the most recent run(): group counts by outcome plus
        # the executor's TaskFailure records (empty before any run)
        self.last_run: Dict[str, Any] = {}

    def add(self, trace, sys: SystemConfig, mode: str = "ts",
            bloom: Optional[tuple] = None, stream: bool = False,
            chunk: Optional[int] = None, **meta) -> "Campaign":
        # a real exception, not an assert: grid-driving scripts run
        # under `python -O` too, where asserts vanish silently
        emulator.check_mode(mode)
        if not stream and not isinstance(trace, Trace):
            raise ValueError(
                f"non-stream points need a Trace, got "
                f"{type(trace).__name__}; pass stream=True for "
                f"iterables / generator factories")
        if chunk is not None and not stream:
            raise ValueError("chunk is a stream-point knob; pass stream=True")
        self.points.append(Point(trace, sys, mode, bloom, meta,
                                 stream=stream, chunk=chunk))
        return self

    def extend(self, traces: Sequence[Trace], sys: SystemConfig,
               mode: str = "ts", bloom: Optional[tuple] = None,
               metas: Optional[Sequence[dict]] = None) -> "Campaign":
        traces = list(traces)
        metas = [{}] * len(traces) if metas is None else list(metas)
        if len(metas) != len(traces):  # ValueError: survives python -O
            raise ValueError(
                f"metas ({len(metas)}) must match traces ({len(traces)})")
        for tr, m in zip(traces, metas):
            self.add(tr, sys, mode, bloom, **m)
        return self

    def add_policy_grid(self, trace: Trace, sys: SystemConfig,
                        programs: Sequence[PolicyProgram], mode: str = "ts",
                        derive_cost: bool = True, policy_axis: bool = True,
                        **meta) -> "Campaign":
        """Fan ``trace`` out across a grid of policy programs (one point
        per program; each record carries ``policy=<program name>`` plus
        ``meta``). ``derive_cost=True`` makes each program's decision
        cost follow its length (``sys.with_policy`` semantics) — the
        ``ts`` vs ``nots`` SMC-slowness experiment; ``derive_cost=False``
        keeps ``sys``'s cost for bit-comparable scheduling-only sweeps.

        ``policy_axis=True`` (default) rides the runtime policy operand:
        every program's packed table must fit one shared length bucket
        (``smcprog.table_bucket``), and the whole grid becomes ONE
        compile-key group — one executable, one vmapped dispatch,
        however many programs. Mixed buckets raise (name the offender,
        don't silently fork groups); split the grid by bucket or pass
        ``policy_axis=False`` for the PR 4 staged-constant path (one
        group — one XLA compile — per distinct program)."""
        emulator.check_mode(mode)
        names = [p.name for p in programs]
        if len(set(names)) != len(names):
            dupes = sorted({n for n in names if names.count(n) > 1})
            raise ValueError(
                f"policy grid needs unique program names (records key "
                f"on them), got duplicates {dupes}")
        if not isinstance(trace, Trace):
            raise ValueError(
                f"policy grids need a Trace, got {type(trace).__name__}")
        if "policy" in meta:
            raise ValueError(
                "meta key 'policy' is reserved for the program name")
        if not policy_axis:
            for prog in programs:
                sysc = sys.with_policy(prog) if derive_cost \
                    else dataclasses.replace(sys, policy=prog)
                self.add(trace, sysc, mode, policy=prog.name, **meta)
            return self
        from repro.core.smcprog import table_bucket
        buckets = {p.name: table_bucket(p.n_ops) for p in programs}
        lb = min(buckets.values(), default=None)
        for prog in programs:
            if buckets[prog.name] != lb:
                raise ValueError(
                    f"policy_axis=True needs one shared table-length "
                    f"bucket, but program {prog.name!r} ({prog.n_ops} "
                    f"ops) packs to bucket {buckets[prog.name]} while "
                    f"others pack to {lb}; split the grid by bucket or "
                    f"pass policy_axis=False")
        for prog in programs:
            cost = prog.smc_cycles() if derive_cost \
                else int(sys.smc_cycles_per_decision)
            self.points.append(Point(
                trace, sys, mode, None, {"policy": prog.name, **meta},
                policy=prog, policy_cost=cost))
        return self

    def __len__(self) -> int:
        return len(self.points)

    def run(self, serial: Optional[bool] = None,
            stream_collect: str = "aggregate",
            checkpoint: Optional[str] = None,
            on_error: str = "raise",
            timeout: Optional[float] = None,
            retries: Optional[int] = None) -> List[dict]:
        """Execute every point; one batched call per compile-key group.

        The default path prepares EVERY group up front (executable
        lookups settle on this thread, in group order — compile-cache
        counters are identical to the serial loop) and then runs them
        overlapped across the ``repro.core.executor`` worker pool: the
        host-side padding/packing of group k+1 proceeds while group k
        is inside XLA, and independent groups execute concurrently
        across cores. ``serial=True`` keeps the original in-order
        group loop for A/B;
        the default (None) also falls back to it for single-group
        campaigns or a 1-worker pool. Results are bit-identical either
        way, in ``add`` order: the emulator output dict plus the
        point's ``meta`` entries.

        Stream points (``add(..., stream=True)``) execute through the
        constant-memory window loop as their own tasks on the same
        pool; ``stream_collect`` picks their output shape ('aggregate'
        default — sweeps over unbounded traces should not retain
        per-request arrays; 'full' for exact t_resp/t_issue).

        Fault tolerance:

        * ``checkpoint=<dir>`` (e.g. ``artifacts/campaigns/mysweep``)
          persists each completed group's raw result list as
          ``group-<digest>.pkl`` the moment its task finalizes —
          incrementally, not at sweep end — where the digest is the
          group's full content address (:func:`_group_digest`). A rerun
          with the same directory loads finished groups, dispatches
          NOTHING for them, and produces bit-identical final records (a
          killed process resumes for free). Stream groups are never
          checkpointed: their inputs are one-shot iterators with no
          content address.
        * ``on_error='quarantine'`` isolates failures: a raising group
          is recorded (``last_run['failures']``) and its points come
          back as error records (``{'error', 'error_type', 'group',
          **meta}``) while every other group completes normally. The
          default ``'raise'`` raises the executor's aggregate
          :class:`repro.core.executor.ExecutionError` (after completed
          groups checkpointed — a poisoned sweep still makes resumable
          progress).
        * ``timeout`` / ``retries`` pass through to
          :func:`repro.core.executor.execute` (per-dispatch wall bound,
          bounded retry-with-backoff for transient failures).

        ``self.last_run`` gets ``{'groups', 'loaded', 'computed',
        'failed', 'failures'}`` either way.
        """
        if on_error not in ("raise", "quarantine"):
            raise ValueError(
                f"on_error must be 'raise' or 'quarantine', got {on_error!r}")
        with spans.span("emu.call") as sp:
            groups = plan_groups(self.points)
            if checkpoint is not None:
                os.makedirs(checkpoint, exist_ok=True)

            results: List[Optional[dict]] = [None] * len(self.points)
            tasks: List[Any] = []
            merges = []  # (campaign indices, points, group result list, tasks)
            loaded = 0
            for key, idxs in groups.items():
                pts = [self.points[i] for i in idxs]
                p0 = pts[0]
                ckpt_path = None
                if checkpoint is not None and not p0.stream:
                    ckpt_path = os.path.join(
                        checkpoint, f"group-{_group_digest(key, pts)}.pkl")
                    if os.path.exists(ckpt_path):
                        with open(ckpt_path, "rb") as fh:
                            outs = pickle.load(fh)
                        if len(outs) == len(pts) and all(
                                o is not None for o in outs):
                            loaded += 1
                            merges.append((idxs, pts, outs, []))
                            continue  # finished group: zero recompute
                blooms = group_blooms(pts)
                outs = [None] * len(pts)
                if p0.stream:
                    gtasks = emulator.prepare_stream_tasks(
                        [p.trace for p in pts], p0.sys, [p.mode for p in pts],
                        blooms, outs,
                        chunk=p0.chunk or emulator.DEFAULT_STREAM_CHUNK,
                        collect=stream_collect)
                else:
                    # policy groups never mix with staged/legacy points
                    # (their group_key carries a fifth, policy element)
                    pkw = {} if p0.policy is None else dict(
                        policies=[p.policy for p in pts],
                        policy_costs=[p.policy_cost for p in pts])
                    gtasks = emulator.prepare_tasks(
                        [p.trace for p in pts], p0.sys, [p.mode for p in pts],
                        blooms, outs, **pkw)
                if ckpt_path is not None:
                    for gt in gtasks:
                        gt.finalize = _checkpointed(gt.finalize, outs,
                                                    ckpt_path)
                tasks += gtasks
                merges.append((idxs, pts, outs, gtasks))

            failures = executor.execute(
                tasks, serial=serial, timeout=timeout, retries=retries,
                raise_on_error=False)
            fail_by_task = {id(f.task): f for f in failures}
            failed_groups = sum(
                1 for m in merges if any(id(t) in fail_by_task for t in m[3]))
            self.last_run = {
                "groups": len(groups), "loaded": loaded,
                "computed": len(groups) - loaded - failed_groups,
                "failed": failed_groups, "failures": failures,
            }
            if failures and on_error == "raise":
                raise executor.ExecutionError(failures)

            for idxs, pts, outs, gtasks in merges:
                gfail = next((fail_by_task[id(t)] for t in gtasks
                              if id(t) in fail_by_task), None)
                for p, i, out in zip(pts, idxs, outs):
                    if out is None:
                        # quarantined: the group's task raised (or timed
                        # out) before finalizing this point
                        e = gfail.error if gfail is not None else None
                        results[i] = {
                            "error": (str(e) if e is not None
                                      else "not computed"),
                            "error_type": type(e).__name__ if e is not None
                            else "Unknown",
                            "group": gfail.label if gfail is not None else "",
                            **p.meta}
                        continue
                    clash = set(out) & set(p.meta)
                    if clash:  # ValueError, not assert: survives python -O
                        raise ValueError(
                            f"meta keys shadow emulator result fields: "
                            f"{sorted(clash)}")
                    results[i] = {**out, **p.meta}
            spans.count_requests(sp, results)
        return results

    def n_groups(self) -> int:
        return len(plan_groups(self.points))
