"""The EasyDRAM engine: trace-driven, multi-domain, time-scaled emulation.

One fused ``lax.scan`` implements the whole request lifetime of Fig. 6:
processor issue (bounded-window in-order front end) -> hardware request
buffer -> SMC critical mode (visibility cutoff on the time-scaling
counter) -> scheduling decision -> DRAM-Bender-style command-batch
execution on the bank state machine -> response tagged with its consume
cycle -> counter advance.

The scheduling decision is software-defined: when ``sys.policy`` is a
:class:`repro.core.smcprog.PolicyProgram`, its instruction table is
interpreted inside the slot body by the branchless policy VM
(O(program-length * Q) extra work per slot, preserving the O(Q)
invariant below); otherwise the legacy hard-coded ``sys.scheduler``
FR-FCFS/FCFS branch runs. The built-in FR-FCFS/FCFS programs are
bit-identical to the legacy flag (tests/test_smcprog.py). The program's
content rides in the compile key through ``SystemConfig`` (programs
hash by table content), so policy sweeps group per program in
:func:`run_many` / ``Campaign``.

Each scan step performs one SMC scheduling slot (serve one visible
request, or an idle hop to the next arrival). All arithmetic is exact
int32 (DRAM ticks / processor cycles, fixed-point 1/4096 conversion);
results are bit-reproducible, which is what lets the Sec. 6 validation
assert exact invariance of time-scaled results to FPGA-side clocks.

Per-slot cost model (the O(Q) invariant)
----------------------------------------

The slot body does O(Q) + O(1) work, where Q = max(window, 2) is the
hardware-queue depth — NOT O(N) in the trace length: every state update
is a predicated point-scatter ``arr.at[i].set(where(pred, new, arr[i]))``
(a self-write when disabled), which XLA keeps in place on the scan carry,
and every read is a point gather. A whole trace therefore costs
O(slots * Q), linear in the trace, where the slot count is the exact
per-batch budget below. The pre-optimization engine (kept verbatim as
:func:`run_ref` / ``_run_core_ref`` for A/B tests) instead
paid full-length predicated selects per slot — O(bucket) work per slot,
O(bucket^2) per trace.

Slot budget
-----------

A real (non-NOP) request needs at most 2 slots (an idle hop that parks
the MC counter at its arrival, then its serve); NOPs (mid-trace or
trailing padding) resolve in the issue frontier at 4 per slot and never
enter the queue. (The idle hop is skipped outright while the hardware
queue is empty — e.g. during a mid-trace NOP run that drains it — so
the MC counter stays parked instead of saturating to BIG-1; the
pre-PR-4 engines saturated there and poisoned every later response.
Both engines carry the fix identically.) For a batch
group padded to ``bucket`` whose largest trace has R real requests, the
scan therefore runs

    slots = 2 * Rq + ceil((bucket - Rq) / 4) + 4,   Rq = R rounded up to
                                                    a bucket/4 granule

slots instead of the previous uniform ``2 * bucket + 4``. Rounding R up
to a coarse granule (and folding ``slots`` into the compile key) keeps
nearby batch shapes on one cached executable; the extra slots are no-ops
(the scan is idempotent once every request is served), so results are
bit-identical for any budget at or above the exact one — asserted by the
property tests against the reference engine.

Entry points:

* :func:`run` — one trace, one config, one mode. A thin wrapper over a
  batch of one.
* :func:`run_many` — a batched campaign step: pads every trace to one
  length bucket, stacks them on a leading axis, and ``jax.vmap``s the
  scan over that axis (optionally over per-trace Bloom filters too), so
  a whole sweep shares ONE compile and ONE device dispatch. Compiled
  executables are cached at module level keyed on
  ``(bucket, slots, batch, sys, mode, bloom-shape)`` — repeated sweeps
  never recompile (see :func:`cache_stats`; the cache is LRU-bounded,
  :func:`set_cache_capacity`). With more than one local device the
  padded batch axis is ``shard_map``-sharded across them
  (:func:`set_sharding`), and multi-group calls execute overlapped
  through ``repro.core.executor`` (``serial=True`` forces the in-order
  loop). Trace buffers are donated to the executable (they are rebuilt
  from host arrays each call). Results are bit-identical to per-trace
  :func:`run` in every combination. For grids that also vary
  ``SystemConfig`` / technique, drive this through
  :class:`repro.core.campaign.Campaign`. A fresh process can skip the
  cold compiles entirely via
  :func:`repro.utils.jax_compat.enable_persistent_compile_cache`.
* :func:`run_ref` / :func:`run_ref_many` — the pre-optimization
  O(bucket)-per-slot engine, kept only to pin bit-exactness and as
  the slow side of an A/B against the fast core (the chip benchmark is
  ``bench/run.py``).
* :func:`run_stream` / :func:`run_stream_many` — constant-memory
  streaming drivers for unbounded traces: the same slot body scans
  fixed-size windows of ``chunk`` requests while an explicit
  :class:`EmulatorState` carry (plus a ``halo`` of trailing trace
  context) threads across windows. Compile keys depend only on
  ``(chunk, halo, slots, batch, sys, mode, bloom-shape)`` — never on
  total trace length — so a 1M-request stream holds exactly ONE cache
  entry and runs in O(batch * window) device memory. Results are
  bit-identical to single-shot :func:`run` on any size both support
  (see the freeze-rule note on :func:`_stream_step_core`).

Importing this module does no device work (every module-level constant
is a host value), so a process can pick its backend and compile-cache
settings after the import.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import threading
import warnings
from typing import List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dram, executor, faults as faultmod, smcprog, spans
from repro.core.bloom import bloom_probe_jnp
from repro.core.dram import NOP, WRITE
from repro.core.timescale import SystemConfig

BIG = np.int32(2 ** 30)  # host constant: importing touches no device
FP = 4096  # fixed-point denominator for tick<->cycle conversion
# issue-frontier advances per scheduling slot; the streaming freeze rule
# and halo sizing are derived from it, so it is a named constant
_FRONTIER_UPTO = 4

# donation is best-effort by design (see _batched_fn); the per-call
# catch_warnings there is not thread-safe (process-global filter state),
# so overlapped group execution needs the filter installed up front too
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

def _mul_div(a, num, den):
    """Exact a * num // den without int32 overflow (num, den ~ 1e3..1e4)."""
    q = a // den
    r = a - q * den
    return q * num + (r * num) // den


def _policy_env(q_t, q_bank, q_row, qidx, visible, hit_now, kindj,
                bank_ready, dram_now, last_bank, n_banks: int, Q: int,
                fault_hct=None, fault_seed: int = 0):
    """Scheduling environment for the policy VM: one thunk per load op,
    each returning a [Q] int32 vector. :func:`smcprog.evaluate` calls
    only the thunks the program references (and each at most once), so
    an FR-FCFS program pays for exactly the two vectors the hard-coded
    scheduler already computed. Shared by both engine cores so the
    policy semantics cannot drift between them.

    ``fault_hct`` is the fault model's per-bank aggressor ACT counter
    vector (None on a perfect memory — then ``hammer_ct`` loads zeros
    and a TRR mitigation policy degrades to a no-op); ``fault_seed``
    keys the ``para_rand`` draws (see repro.core.faults.para_draw)."""
    is_write = lambda: (kindj[qidx] == WRITE).astype(jnp.int32)  # noqa: E731
    return {
        "age": lambda: q_t,
        "age_rel": lambda: q_t - jnp.min(jnp.where(visible, q_t, BIG)),
        "row_hit": lambda: hit_now.astype(jnp.int32),
        "bank": lambda: q_bank,
        "row": lambda: q_row,
        "is_write": is_write,
        "bank_busy": lambda: (bank_ready[q_bank] > dram_now).astype(jnp.int32),
        "rr_dist": lambda: (q_bank - last_bank - 1) % jnp.int32(n_banks),
        "qslot": lambda: jnp.arange(Q, dtype=jnp.int32),
        "write_pressure": lambda: jnp.zeros((Q,), jnp.int32) + jnp.sum(
            (visible & (is_write() != 0)).astype(jnp.int32)),
        "hammer_ct": lambda: (jnp.zeros((Q,), jnp.int32) if fault_hct is None
                              else fault_hct[q_bank]),
        "para_rand": lambda: faultmod.para_draw(
            fault_seed, q_bank, q_row, dram_now),
    }


@dataclasses.dataclass
class Trace:
    """Padded request trace. kind==NOP entries are ignored."""
    kind: np.ndarray    # int32 [N]
    bank: np.ndarray    # int32 [N]
    row: np.ndarray     # int32 [N]
    delta: np.ndarray   # int32 [N] proc cycles of compute before this request
    dep: np.ndarray     # int32 [N] 0 = window-only; d>0 = depends on resp[i-d]

    @property
    def n(self):
        return int(self.kind.shape[0])

    @property
    def n_real(self):
        """Non-NOP request count — input to :func:`slot_budget`."""
        return int((np.asarray(self.kind) != NOP).sum())

    @staticmethod
    def of(kind, bank, row, delta, dep=None):
        kind = np.asarray(kind, np.int32)
        z = np.zeros_like(kind)
        return Trace(kind=kind, bank=np.asarray(bank, np.int32),
                     row=np.asarray(row, np.int32),
                     delta=np.asarray(delta, np.int32),
                     dep=z if dep is None else np.asarray(dep, np.int32))

    def arrays(self):
        return (jnp.asarray(self.kind), jnp.asarray(self.bank),
                jnp.asarray(self.row), jnp.asarray(self.delta),
                jnp.asarray(self.dep))


@dataclasses.dataclass
class EmulatorState:
    """The complete scan carry of the emulation engine, as an explicit
    pytree (registered dataclass) instead of an ad-hoc dict.

    Everything the slot body threads from one scheduling slot to the
    next lives here: the DRAM bank state machine, per-request issue /
    response tags, the hardware request queue (request indices, -1 =
    free), the in-order issue pointer, the two clock domains
    (``mc_release`` in modeled proc cycles, ``dram_now`` in DRAM
    ticks), and the served/hit/SMC counters. The policy VM is pure per
    slot and Bloom words are read-only operands, so neither needs a
    carry slot. Because the carry is explicit it can be paused,
    serialized (:meth:`to_host` / :meth:`from_host`) and resumed — the
    mechanism the streaming drivers (:func:`run_stream`) use to thread
    one state across fixed-size trace windows. Index fields
    (``t_issue`` / ``t_resp`` / ``queue`` / ``ptr``) are window-local
    there; times stay absolute (int32 horizon ~2^30 cycles)."""
    bank: dict              # DRAM bank state (dram.init_bank_state)
    t_issue: jnp.ndarray    # int32 [N] issue tag per request
    t_resp: jnp.ndarray     # int32 [N] response tag (BIG = unserved)
    queue: jnp.ndarray      # int32 [Q] hardware request buffer
    ptr: jnp.ndarray        # int32 in-order issue pointer
    mc_release: jnp.ndarray  # time-scaling MC counter (proc cycles)
    dram_now: jnp.ndarray   # DRAM real-time frontier (ticks)
    hits: jnp.ndarray       # row-hit counter
    served_n: jnp.ndarray   # serve-slot counter
    smc_fpga_cycles: jnp.ndarray
    last_bank: jnp.ndarray  # bank of the last served request
    # fault-injection carry (repro.core.faults.init_fault_state): {} on a
    # perfect memory, which adds ZERO pytree leaves — the staged carry,
    # and therefore the compiled program, is byte-identical to a build
    # that never heard of faults
    faults: dict = dataclasses.field(default_factory=dict)

    @staticmethod
    def init(n: int, sys: SystemConfig) -> "EmulatorState":
        """Fresh single-shot state for an n-request trace."""
        return EmulatorState(
            bank=dram.init_bank_state(sys.geometry),
            t_issue=jnp.zeros((n,), jnp.int32),
            t_resp=jnp.full((n,), BIG, jnp.int32),
            queue=jnp.full((max(sys.window, 2),), -1, jnp.int32),
            ptr=jnp.int32(0), mc_release=jnp.int32(0),
            dram_now=jnp.int32(0), hits=jnp.int32(0),
            served_n=jnp.int32(0), smc_fpga_cycles=jnp.int32(0),
            last_bank=jnp.int32(-1),
            faults={} if sys.faults is None else faultmod.init_fault_state(
                sys.faults, sys.geometry.n_banks))

    def to_host(self) -> dict:
        """Serializable nested dict of NumPy arrays (device -> host)."""
        return jax.tree_util.tree_map(np.asarray, dataclasses.asdict(self))

    @staticmethod
    def from_host(d: dict) -> "EmulatorState":
        """Inverse of :meth:`to_host`."""
        return EmulatorState(**jax.tree_util.tree_map(jnp.asarray, dict(d)))


_EMU_STATE_FIELDS = ("bank", "t_issue", "t_resp", "queue", "ptr",
                     "mc_release", "dram_now", "hits", "served_n",
                     "smc_fpga_cycles", "last_bank", "faults")
jax.tree_util.register_dataclass(
    EmulatorState, data_fields=list(_EMU_STATE_FIELDS), meta_fields=[])


def _issue_frontier(t_issue, t_resp, queue, kindj, delta, dep, ptr, W,
                    upto=4, gate=None):
    """Advance the in-order issue pointer by up to ``upto`` requests,
    pushing them into free hardware-queue slots. ``queue`` holds request
    indices (-1 = free); occupancy can never exceed the window W because
    issue is in-order with W outstanding.

    O(1) work per advance: point gathers plus predicated point-scatters
    (``arr.at[i].set(where(can, new, arr[i]))`` — a self-write when the
    advance is disabled), never full-length selects. ``gate`` (a traced
    bool, streaming freeze) ANDs into every advance predicate, so a
    gated-off call is the identity at the same O(1) cost."""
    N = t_issue.shape[0]
    for _ in range(upto):
        j = ptr
        jc = jnp.clip(j, 0, N - 1)
        prev_issue = jnp.where(j > 0, t_issue[jnp.clip(j - 1, 0, N - 1)], 0)
        base = prev_issue + delta[jc]
        wj = j - W
        win_known = (wj < 0) | (t_resp[jnp.clip(wj, 0, N - 1)] < BIG)
        win_t = jnp.where(wj >= 0, t_resp[jnp.clip(wj, 0, N - 1)] + 1, 0)
        dj = j - dep[jc]
        dep_on = dep[jc] > 0
        dep_known = (~dep_on) | (dj < 0) | (t_resp[jnp.clip(dj, 0, N - 1)] < BIG)
        dep_t = jnp.where(dep_on & (dj >= 0), t_resp[jnp.clip(dj, 0, N - 1)] + 1, 0)
        free = queue < 0
        slot = jnp.argmax(free).astype(jnp.int32)
        is_nop = kindj[jc] == 4  # NOP padding: resolve instantly, skip queue
        can = (j < N) & win_known & dep_known & (jnp.any(free) | is_nop)
        if gate is not None:
            can = can & gate
        t_new = jnp.maximum(jnp.maximum(base, win_t), dep_t)
        t_issue = t_issue.at[jc].set(jnp.where(can, t_new, t_issue[jc]))
        t_resp = t_resp.at[jc].set(jnp.where(can & is_nop, t_new, t_resp[jc]))
        queue = queue.at[slot].set(jnp.where(can & ~is_nop, jc, queue[slot]))
        ptr = jnp.where(can, ptr + 1, ptr)
    return t_issue, t_resp, queue, ptr


def _make_slot_body(kindj, bankj, rowj, deltaj, depj, sys: SystemConfig,
                    mode: str, bloom_words, bloom_k: int, bloom_m: int,
                    gate=None, policy_table=None, policy_cost=None):
    """Build the per-slot transition ``EmulatorState -> EmulatorState``
    over one set of trace arrays. This is THE slot body: the single-shot
    scan (:func:`_run_core`) and the streaming windows
    (:func:`_stream_step_core`) both scan exactly this function, which
    is what makes streamed results bit-identical to single-shot by
    construction. ``sys`` / ``mode`` / ``bloom_k`` / ``bloom_m`` are
    Python-level constants baked into the compiled program; every state
    update is a predicated point gather/scatter — O(Q)+O(1) work per
    slot (see module docstring).

    ``gate`` is the streaming freeze hook: a callable ``state -> traced
    bool``. When it returns False the step is the exact identity — the
    gate ANDs into the frontier-advance and service predicates, so every
    point-scatter self-writes and every scalar keeps its old value. This
    is deliberately NOT a ``lax.cond`` around the body: under ``vmap`` a
    batched-predicate cond lowers to both branches plus a select over
    the whole O(L) carry per slot, which would demote the linear-time
    core back to quadratic. Predicate-threading keeps frozen slots at
    the same O(Q)+O(1) cost as live ones (and ``gate=None`` compiles to
    exactly the pre-streaming program).

    ``policy_table`` is the PR-10 runtime-operand scheduling path: a
    packed ``[bucket + 1, 4]`` int32 program
    (:func:`smcprog.pack_program`) arriving as a traced OPERAND, so one
    executable serves any program of the bucket — and vmapping it over a
    stacked axis evaluates a whole policy grid per dispatch. It takes
    precedence over both ``sys.policy`` (the staged-constant path) and
    the legacy scheduler flag. Because the program content is unknown at
    trace time, its decision cost rides along as an operand too:
    ``policy_cost`` is an int32 ``[2]`` vector ``(counter_inc,
    smc_latency_proc)`` — the per-decision SMC cycle-counter increment
    and the nots-mode free-running decision latency, exactly the two
    numbers the staged path bakes in from ``sys.smc_cycles_per_decision``
    (derived host-side by :func:`_policy_cost_pair`, so the int
    arithmetic is bit-identical)."""
    N = kindj.shape[0]
    t = sys.timing
    geo = sys.geometry
    W = sys.window
    frfcfs = sys.scheduler == "frfcfs"
    policy = sys.policy
    fm = sys.faults
    use_bloom = bloom_words is not None

    # proc cycles per DRAM tick, fixed-point /FP
    scale_num = jnp.int32(round((sys.proc_per_tick_fpga if mode == "nots"
                                 else sys.proc_per_tick_emu) * FP))
    # per-decision MC occupancy (decision *rate*) and per-response latency:
    # ts models the emulated HW MC; nots free-runs against the real SMC
    mc_lat = jnp.int32(0 if mode == "nots" else sys.hwmc_latency_proc)
    if policy_table is not None:
        # runtime-operand policy: SMC cost is per-policy data, not a
        # staged constant (ts-mode issue rate models the emulated HW MC
        # and stays policy-independent, exactly as in the staged path)
        smc_lat = policy_cost[1]
        mc_issue = smc_lat if mode == "nots" else jnp.int32(sys.hwmc_issue_proc)
        vis_slack = smc_lat if mode == "nots" else jnp.int32(0)
        counter_inc = policy_cost[0]
    else:
        mc_issue = jnp.int32(sys.smc_latency_fpga_proc if mode == "nots"
                             else sys.hwmc_issue_proc)
        # a slow SMC batches up whatever arrived while it was busy (nots)
        vis_slack = jnp.int32(sys.smc_latency_fpga_proc if mode == "nots"
                              else 0)
        counter_inc = sys.smc_cycles_per_decision + sys.smc_transfer_cycles
    Q = max(W, 2)

    def step(st: EmulatorState) -> EmulatorState:
        live = None if gate is None else gate(st)
        t_issue, t_resp, queue, ptr = _issue_frontier(
            st.t_issue, st.t_resp, st.queue, kindj, deltaj, depj, st.ptr, W,
            gate=live)

        # gather queued requests (O(Q), not O(N))
        qvalid = queue >= 0
        qidx = jnp.clip(queue, 0, N - 1)
        q_t = jnp.where(qvalid, t_issue[qidx], BIG)
        q_bank = bankj[qidx]
        q_row = rowj[qidx]

        cutoff = st.mc_release + vis_slack
        visible = qvalid & (q_t <= cutoff)
        do = jnp.any(visible)
        if live is not None:
            do = do & live

        # ---- scheduling decision (int32-safe two-level argmin) ----
        open_rows = st.bank["open_row"]
        hit_now = open_rows[q_bank] == q_row
        mit = None
        if policy_table is not None:
            # runtime-operand path: the table-driven VM interprets the
            # packed program operand (one executable per length bucket)
            qslot, mit = smcprog.select_slot_table(policy_table, _policy_env(
                q_t, q_bank, q_row, qidx, visible, hit_now, kindj,
                st.bank["ready"], st.dram_now, st.last_bank,
                geo.n_banks, Q, fault_hct=st.faults.get("hct"),
                fault_seed=0 if fm is None else fm.seed), visible)
        elif policy is not None:
            # software-defined path: the policy VM stages the program's
            # instruction table into branchless O(Q) vector ops here
            qslot, mit = smcprog.select_slot(policy, _policy_env(
                q_t, q_bank, q_row, qidx, visible, hit_now, kindj,
                st.bank["ready"], st.dram_now, st.last_bank,
                geo.n_banks, Q, fault_hct=st.faults.get("hct"),
                fault_seed=0 if fm is None else fm.seed), visible)
        else:
            key_all = jnp.where(visible, q_t, BIG)
            key_hit = jnp.where(visible & hit_now, q_t, BIG)
            slot_hit = jnp.argmin(key_hit).astype(jnp.int32)
            slot_old = jnp.argmin(key_all).astype(jnp.int32)
            use_hit = frfcfs & jnp.any(visible & hit_now)
            qslot = jnp.where(use_hit, slot_hit, slot_old)
        pick = qidx[qslot]

        # ---- DRAM service (command-batch executor) ----
        # decision happens when the MC is free AND the request has arrived
        decision_t = jnp.maximum(t_issue[pick], st.mc_release)
        dram_req_t = jnp.maximum(st.dram_now,
                                 _mul_div(decision_t, FP, jnp.maximum(scale_num, 1)))
        trcd_eff = jnp.int32(t.tRCD)
        if use_bloom:
            gid = (bankj[pick] * geo.n_rows + rowj[pick]).astype(jnp.uint32)
            weakp = bloom_probe_jnp(bloom_words, bloom_m, bloom_k, gid[None])[0]
            trcd_eff = jnp.where(weakp, jnp.int32(t.tRCD), jnp.int32(t.tRCD_reduced))
        nbs, t_done, hit = dram.service_request(
            st.bank, t, kindj[pick], bankj[pick], rowj[pick],
            dram_req_t, trcd_eff)

        # ---- time scaling: response consume-tag in modeled proc cycles.
        # t_done is absolute DRAM time; decisions pipeline at mc_issue rate
        # while each response additionally carries the MC pipeline latency.
        resp_t = _mul_div(t_done, scale_num, FP) + mc_lat
        resp_t = jnp.maximum(resp_t, decision_t + mc_issue)

        # bank state advances only at index b: merge the served bank's row
        # of the transition (plus the channel scalars) as predicated point
        # writes instead of whole-array selects
        b = bankj[pick]
        bs = st.bank
        bank = {
            "open_row": bs["open_row"].at[b].set(
                jnp.where(do, nbs["open_row"][b], bs["open_row"][b])),
            "ready": bs["ready"].at[b].set(
                jnp.where(do, nbs["ready"][b], bs["ready"][b])),
            "act_at": bs["act_at"].at[b].set(
                jnp.where(do, nbs["act_at"][b], bs["act_at"][b])),
            "bus_busy": jnp.where(do, nbs["bus_busy"], bs["bus_busy"]),
            "refs_done": jnp.where(do, nbs["refs_done"], bs["refs_done"]),
        }
        fstate = st.faults
        if fm is not None:
            # fault hook: advance the error model for the served request
            # and charge any fired neighbor refresh to the bank. Gated
            # at the Python level — fm=None stages not one extra op.
            fstate, extra = faultmod.apply_slot(
                fm, geo.n_rows, t.tREFI, dram.neighbor_refresh_ticks(t),
                fstate, do=do, hit=hit, bank=b, row=rowj[pick],
                kind=kindj[pick], t_start=dram_req_t,
                refreshed=do & (nbs["refs_done"] != bs["refs_done"]),
                mitigate=mit)
            bank["ready"] = bank["ready"].at[b].add(extra)
        t_resp = t_resp.at[pick].set(jnp.where(do, resp_t, t_resp[pick]))
        queue = queue.at[qslot].set(jnp.where(do, -1, queue[qslot]))
        # MC busy until the next decision slot; idle hop to the next
        # arrival when nothing is visible — but only when something is
        # queued: hopping on an empty queue (mid-trace NOP run) would
        # saturate the counter to BIG-1 and poison every later response
        # (the pre-PR-4 idle-hop quirk)
        nxt = jnp.min(q_t)
        may_hop = jnp.any(qvalid)
        if live is not None:  # frozen slots must not idle-hop either
            may_hop = may_hop & live
        idle = jnp.where(
            may_hop,
            jnp.maximum(st.mc_release, jnp.minimum(nxt, BIG - 1)),
            st.mc_release)
        return EmulatorState(
            bank=bank, t_issue=t_issue, t_resp=t_resp, queue=queue, ptr=ptr,
            mc_release=jnp.where(
                do, jnp.maximum(st.mc_release, decision_t + mc_issue), idle),
            dram_now=jnp.where(do, jnp.maximum(st.dram_now, dram_req_t),
                               st.dram_now),
            hits=st.hits + jnp.where(do & hit, 1, 0),
            served_n=st.served_n + jnp.where(do, 1, 0),
            smc_fpga_cycles=st.smc_fpga_cycles + jnp.where(
                do, counter_inc, 0),
            last_bank=jnp.where(do, bankj[pick], st.last_bank),
            faults=fstate)

    return step


def _run_core(kind, bank, row, delta, dep, sys: SystemConfig, mode: str,
              bloom_words, bloom_k: int, bloom_m: int,
              slots: Optional[int] = None,
              policy_table=None, policy_cost=None):
    """One trace's single-shot scan: a fresh :class:`EmulatorState`
    driven through the shared slot body (:func:`_make_slot_body`) for
    the ``slots`` budget. Pure traceable function (jit/vmap applied by
    the compile cache below). ``policy_table`` / ``policy_cost`` are the
    runtime-operand policy inputs (see :func:`_make_slot_body`)."""
    N = kind.shape[0]
    W = sys.window
    step = _make_slot_body(kind, bank, row, delta, dep, sys, mode,
                           bloom_words, bloom_k, bloom_m,
                           policy_table=policy_table,
                           policy_cost=policy_cost)
    length = (2 * N + 4) if slots is None else slots
    state, _ = jax.lax.scan(lambda st, _: (step(st), None),
                            EmulatorState.init(N, sys), None, length=length)
    # trailing frontier pass so post-memory compute counts
    t_issue, _, _, ptr = _issue_frontier(
        state.t_issue, state.t_resp, state.queue,
        kind, delta, dep, state.ptr, W, upto=8)
    valid = kind != NOP
    served_mask = state.t_resp < BIG
    last_resp = jnp.max(jnp.where(valid & served_mask, state.t_resp, 0))
    last_issue = jnp.max(jnp.where(valid, t_issue, 0))
    out = {
        "exec_cycles": jnp.maximum(last_resp, last_issue),
        "row_hits": state.hits,
        "served": state.served_n,
        "dram_ticks": state.dram_now,
        "smc_fpga_cycles": state.smc_fpga_cycles,
        "t_resp": state.t_resp,
        "t_issue": t_issue,
    }
    if sys.faults is not None:
        out.update(faultmod.fault_result_fields(state.faults))
    return out


# ---------------------------------------------------------------------------
# Reference engine: the pre-optimization core. O(bucket) work per slot
# (full-length predicated selects), uniform 2*bucket+4 budget. Kept ONLY
# to pin bit-exactness (tests/test_property.py) and as the slow side of
# an A/B against the fast core. Do not use for
# new work. Semantic changes are forbidden EXCEPT the ones the fast core
# must stay bit-identical under: the PR-4 policy-VM branch, the
# last_bank carry it reads, and the idle-hop empty-queue fix — all
# mirrored line-for-line from _run_core.
# ---------------------------------------------------------------------------


def _issue_frontier_ref(t_issue, t_resp, queue, kindj, delta, dep, ptr, W,
                        upto=4):
    N = t_issue.shape[0]
    for _ in range(upto):
        j = ptr
        jc = jnp.clip(j, 0, N - 1)
        prev_issue = jnp.where(j > 0, t_issue[jnp.clip(j - 1, 0, N - 1)], 0)
        base = prev_issue + delta[jc]
        wj = j - W
        win_known = (wj < 0) | (t_resp[jnp.clip(wj, 0, N - 1)] < BIG)
        win_t = jnp.where(wj >= 0, t_resp[jnp.clip(wj, 0, N - 1)] + 1, 0)
        dj = j - dep[jc]
        dep_on = dep[jc] > 0
        dep_known = (~dep_on) | (dj < 0) | (t_resp[jnp.clip(dj, 0, N - 1)] < BIG)
        dep_t = jnp.where(dep_on & (dj >= 0), t_resp[jnp.clip(dj, 0, N - 1)] + 1, 0)
        free = queue < 0
        slot = jnp.argmax(free).astype(jnp.int32)
        is_nop = kindj[jc] == 4
        can = (j < N) & win_known & dep_known & (jnp.any(free) | is_nop)
        t_new = jnp.maximum(jnp.maximum(base, win_t), dep_t)
        t_issue = jnp.where(can, t_issue.at[jc].set(t_new), t_issue)
        t_resp = jnp.where(can & is_nop, t_resp.at[jc].set(t_new), t_resp)
        queue = jnp.where(can & ~is_nop, queue.at[slot].set(jc), queue)
        ptr = jnp.where(can, ptr + 1, ptr)
    return t_issue, t_resp, queue, ptr


def _run_core_ref(kind, bank, row, delta, dep, sys: SystemConfig, mode: str,
                  bloom_words, bloom_k: int, bloom_m: int,
                  policy_table=None, policy_cost=None):
    N = kind.shape[0]
    t = sys.timing
    geo = sys.geometry
    W = sys.window
    frfcfs = sys.scheduler == "frfcfs"
    policy = sys.policy
    fm = sys.faults
    use_bloom = bloom_words is not None

    scale_num = jnp.int32(round((sys.proc_per_tick_fpga if mode == "nots"
                                 else sys.proc_per_tick_emu) * FP))
    mc_lat = jnp.int32(0 if mode == "nots" else sys.hwmc_latency_proc)
    if policy_table is not None:
        # runtime-operand policy cost, mirrored from _make_slot_body
        smc_lat = policy_cost[1]
        mc_issue = smc_lat if mode == "nots" else jnp.int32(sys.hwmc_issue_proc)
        vis_slack = smc_lat if mode == "nots" else jnp.int32(0)
        counter_inc = policy_cost[0]
    else:
        mc_issue = jnp.int32(sys.smc_latency_fpga_proc if mode == "nots"
                             else sys.hwmc_issue_proc)
        vis_slack = jnp.int32(sys.smc_latency_fpga_proc if mode == "nots"
                              else 0)
        counter_inc = sys.smc_cycles_per_decision + sys.smc_transfer_cycles

    Q = max(W, 2)
    state = {
        "bank": dram.init_bank_state(geo),
        "t_issue": jnp.zeros((N,), jnp.int32),
        "t_resp": jnp.full((N,), BIG, jnp.int32),
        "queue": jnp.full((Q,), -1, jnp.int32),
        "ptr": jnp.int32(0),
        "mc_release": jnp.int32(0),
        "dram_now": jnp.int32(0),
        "hits": jnp.int32(0),
        "served_n": jnp.int32(0),
        "smc_fpga_cycles": jnp.int32(0),
        "last_bank": jnp.int32(-1),
    }
    if fm is not None:
        state["faults"] = faultmod.init_fault_state(fm, geo.n_banks)

    kindj, bankj, rowj, deltaj, depj = kind, bank, row, delta, dep

    def slot(state, _):
        t_issue, t_resp = state["t_issue"], state["t_resp"]
        t_issue, t_resp, queue, ptr = _issue_frontier_ref(
            t_issue, t_resp, state["queue"], kindj, deltaj, depj,
            state["ptr"], W)

        qvalid = queue >= 0
        qidx = jnp.clip(queue, 0, N - 1)
        q_t = jnp.where(qvalid, t_issue[qidx], BIG)
        q_bank = bankj[qidx]
        q_row = rowj[qidx]

        cutoff = state["mc_release"] + vis_slack
        visible = qvalid & (q_t <= cutoff)
        do = jnp.any(visible)

        open_rows = state["bank"]["open_row"]
        hit_now = open_rows[q_bank] == q_row
        mit = None
        if policy_table is not None:
            # runtime-operand branch mirrored from _make_slot_body
            qslot, mit = smcprog.select_slot_table(policy_table, _policy_env(
                q_t, q_bank, q_row, qidx, visible, hit_now, kindj,
                state["bank"]["ready"], state["dram_now"],
                state["last_bank"], geo.n_banks, Q,
                fault_hct=state.get("faults", {}).get("hct"),
                fault_seed=0 if fm is None else fm.seed), visible)
        elif policy is not None:
            qslot, mit = smcprog.select_slot(policy, _policy_env(
                q_t, q_bank, q_row, qidx, visible, hit_now, kindj,
                state["bank"]["ready"], state["dram_now"],
                state["last_bank"], geo.n_banks, Q,
                fault_hct=state.get("faults", {}).get("hct"),
                fault_seed=0 if fm is None else fm.seed), visible)
        else:
            key_all = jnp.where(visible, q_t, BIG)
            key_hit = jnp.where(visible & hit_now, q_t, BIG)
            slot_hit = jnp.argmin(key_hit).astype(jnp.int32)
            slot_old = jnp.argmin(key_all).astype(jnp.int32)
            use_hit = frfcfs & jnp.any(visible & hit_now)
            qslot = jnp.where(use_hit, slot_hit, slot_old)
        pick = qidx[qslot]

        decision_t = jnp.maximum(t_issue[pick], state["mc_release"])
        dram_req_t = jnp.maximum(state["dram_now"],
                                 _mul_div(decision_t, FP, jnp.maximum(scale_num, 1)))
        trcd_eff = jnp.int32(t.tRCD)
        if use_bloom:
            gid = (bankj[pick] * geo.n_rows + rowj[pick]).astype(jnp.uint32)
            weakp = bloom_probe_jnp(bloom_words, bloom_m, bloom_k, gid[None])[0]
            trcd_eff = jnp.where(weakp, jnp.int32(t.tRCD), jnp.int32(t.tRCD_reduced))
        nbs, t_done, hit = dram.service_request(
            state["bank"], t, kindj[pick], bankj[pick], rowj[pick],
            dram_req_t, trcd_eff)

        resp_t = _mul_div(t_done, scale_num, FP) + mc_lat
        resp_t = jnp.maximum(resp_t, decision_t + mc_issue)

        state = dict(state)
        old_refs = state["bank"]["refs_done"]
        state["bank"] = jax.tree_util.tree_map(
            lambda a, b: jnp.where(do, b, a), state["bank"], nbs)
        if fm is not None:
            # fault hook mirrored from _make_slot_body (shared apply_slot
            # — the semantics live in repro.core.faults, not here)
            bsel = bankj[pick]
            fstate, extra = faultmod.apply_slot(
                fm, geo.n_rows, t.tREFI, dram.neighbor_refresh_ticks(t),
                state["faults"], do=do, hit=hit, bank=bsel,
                row=rowj[pick], kind=kindj[pick], t_start=dram_req_t,
                refreshed=do & (nbs["refs_done"] != old_refs),
                mitigate=mit)
            state["faults"] = fstate
            state["bank"]["ready"] = state["bank"]["ready"].at[bsel].add(extra)
        state["t_resp"] = jnp.where(do, t_resp.at[pick].set(resp_t), t_resp)
        queue = jnp.where(do, queue.at[qslot].set(-1), queue)
        state["dram_now"] = jnp.where(do, jnp.maximum(state["dram_now"], dram_req_t),
                                      state["dram_now"])
        state["hits"] = state["hits"] + jnp.where(do & hit, 1, 0)
        state["served_n"] = state["served_n"] + jnp.where(do, 1, 0)
        state["smc_fpga_cycles"] = state["smc_fpga_cycles"] + jnp.where(
            do, counter_inc, 0)
        state["last_bank"] = jnp.where(do, bankj[pick], state["last_bank"])
        # idle-hop fix mirrored from _run_core: never hop on an empty queue
        nxt = jnp.min(q_t)
        idle = jnp.where(
            jnp.any(qvalid),
            jnp.maximum(state["mc_release"], jnp.minimum(nxt, BIG - 1)),
            state["mc_release"])
        state["mc_release"] = jnp.where(
            do, jnp.maximum(state["mc_release"], decision_t + mc_issue), idle)
        state["t_issue"], state["queue"], state["ptr"] = t_issue, queue, ptr
        return state, None

    state, _ = jax.lax.scan(slot, state, None, length=2 * N + 4)
    t_issue, _, _, ptr = _issue_frontier_ref(
        state["t_issue"], state["t_resp"], state["queue"],
        kindj, deltaj, depj, state["ptr"], W, upto=8)
    valid = kindj != NOP
    served_mask = state["t_resp"] < BIG
    last_resp = jnp.max(jnp.where(valid & served_mask, state["t_resp"], 0))
    last_issue = jnp.max(jnp.where(valid, t_issue, 0))
    out = {
        "exec_cycles": jnp.maximum(last_resp, last_issue),
        "row_hits": state["hits"],
        "served": state["served_n"],
        "dram_ticks": state["dram_now"],
        "smc_fpga_cycles": state["smc_fpga_cycles"],
        "t_resp": state["t_resp"],
        "t_issue": t_issue,
    }
    if fm is not None:
        out.update(faultmod.fault_result_fields(state["faults"]))
    return out


def pad_trace(tr: Trace, n: int) -> Trace:
    """Pad with NOPs to length n (keeps jit caches warm across sizes)."""
    k = n - tr.n
    if k < 0:  # ValueError, not assert: survives python -O
        raise ValueError(
            f"cannot pad a trace of length {tr.n} down to {n}: the "
            f"target must be >= the trace length")
    z = np.zeros(k, np.int32)
    return Trace(kind=np.concatenate([tr.kind, z + 4]),
                 bank=np.concatenate([tr.bank, z]),
                 row=np.concatenate([tr.row, z]),
                 delta=np.concatenate([tr.delta, z]),
                 dep=np.concatenate([tr.dep, z]))


def _bucket(n: int) -> int:
    b = 32
    while b < n:
        b *= 2
    return b


def slot_budget(bucket: int, n_real: int) -> int:
    """Exact scan-slot budget for a batch group padded to ``bucket``
    whose largest trace has ``n_real`` non-NOP requests:

        2 * Rq + ceil((bucket - Rq) / 4) + 4

    with Rq = n_real rounded up to a ``max(bucket // 4, 8)`` granule
    (capped at bucket). Real requests cost at most 2 slots each (idle
    hop + serve, with issue piggybacking on earlier slots); NOPs resolve
    4 per slot in the frontier and never enter the queue. The budget is
    monotone in n_real, so the group max covers every member; surplus
    slots are no-ops, keeping results bit-identical to any larger
    budget (2*bucket+4 degenerate case included)."""
    g = max(bucket // 4, 8)
    rq = min(bucket, -(-n_real // g) * g)
    return 2 * rq + (bucket - rq + 3) // 4 + 4


def _batch_bucket(b: int) -> int:
    """Pad the batch axis to a power of two so sweeps of nearby sizes
    share one executable (padding rows are all-NOP traces)."""
    p = 1
    while p < b:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# Batched campaigns: module-level compile cache over vmapped executables.
# LRU-bounded (``REPRO_EMU_CACHE_CAP`` / :func:`set_cache_capacity`) so an
# unbounded sweep of distinct compile keys cannot retain every executable
# it ever built; evictions are counted in :func:`cache_stats`. A second
# *process* re-running the same sweep skips the XLA compile entirely when
# the persistent on-disk cache is enabled
# (:func:`repro.utils.jax_compat.enable_persistent_compile_cache`).
# ---------------------------------------------------------------------------

_COMPILE_CACHE: "collections.OrderedDict[tuple, object]" = \
    collections.OrderedDict()
_CACHE_LOCK = threading.Lock()
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_CACHE_CAP = max(1, executor._env_int("REPRO_EMU_CACHE_CAP", 128))

# batch-axis device sharding of run_many executables:
#   'auto'  — shard_map over local devices when >1 is present and the
#             padded batch axis divides across them; plain vmap otherwise
#   'off'   — never wrap in shard_map
#   'force' — always wrap, even over a single-device mesh (exercises the
#             shard_map code path on 1-device hosts; bit-identical)
_SHARD_MODES = ("auto", "off", "force")
_SHARD_MODE = os.environ.get("REPRO_EXEC_SHARD", "auto")


def set_sharding(mode: str) -> str:
    """Set the batch-axis sharding mode ('auto' | 'off' | 'force');
    returns the previous mode. Sharded and unsharded executables live
    under distinct cache keys, so toggling never returns a stale fn."""
    global _SHARD_MODE
    if mode not in _SHARD_MODES:
        raise ValueError(
            f"sharding mode must be one of {_SHARD_MODES}, got {mode!r}")
    old, _SHARD_MODE = _SHARD_MODE, mode
    return old


def _shard_count(batch: int) -> int:
    """Number of mesh devices for a padded batch axis of ``batch``:
    0 = no shard_map wrapper; >= 1 = wrap over that many devices (1 only
    under 'force'). The padded batch is a power of two, so the largest
    power-of-two device count that divides it is used."""
    if _SHARD_MODE == "off":
        return 0
    ndev = jax.local_device_count()
    n = 1
    while n * 2 <= ndev and batch % (n * 2) == 0:
        n *= 2
    if n == 1 and _SHARD_MODE != "force":
        return 0
    return n


def _norm_mode(mode: str) -> str:
    """'reference' compiles to the exact 'ts' program — that coincidence
    IS the paper's time-scaling claim — so they share one executable."""
    return "ts" if mode == "reference" else mode


def _is_bloom_triple(b) -> bool:
    """One (words_u32, k, m_bits) filter: words array + two scalars (as
    opposed to a per-trace sequence of such triples)."""
    return (len(b) == 3 and not isinstance(b[0], (tuple, list))
            and np.ndim(b[1]) == 0 and np.ndim(b[2]) == 0)


def _bloom_shape(blooms) -> Optional[tuple]:
    """Shape signature of a blooms argument: None, one shared (words, k,
    m_bits) filter, or a per-trace sequence of identically-shaped
    triples — shared-vs-stacked decided by content (like
    :func:`_normalize_blooms`), not container type."""
    if blooms is None:
        return None
    if _is_bloom_triple(blooms):
        return ("shared", int(np.asarray(blooms[0]).shape[0]),
                blooms[1], blooms[2])
    b0 = tuple(blooms[0])
    return ("stacked", int(np.asarray(b0[0]).shape[0]), b0[1], b0[2])


def _policy_rt_sys(sys: SystemConfig) -> SystemConfig:
    """Normalize a config for the runtime-operand policy path: the
    staged policy, the legacy scheduler flag, and the per-decision SMC
    cost are all dead in the traced program there (the table and its
    cost arrive as operands), so they are scrubbed from the compile /
    group key — configs differing only in those fields share ONE
    executable, which is the whole point of the policy axis."""
    return dataclasses.replace(sys, policy=None, scheduler="frfcfs",
                               smc_cycles_per_decision=0)


def _policy_cost_pair(sys: SystemConfig, cpd: int) -> tuple:
    """Host-side derivation of the runtime ``policy_cost`` operand for a
    policy whose ``smc_cycles_per_decision`` is ``cpd``: ``(counter_inc,
    smc_latency_proc)``, via the exact same Python-int / float rounding
    the staged path bakes into its constants (``smc_latency_fpga_proc``
    does float64 math — it must happen HERE, not in traced int32 ops,
    for bit-identity)."""
    csys = dataclasses.replace(sys, smc_cycles_per_decision=int(cpd))
    return (int(cpd) + int(sys.smc_transfer_cycles),
            int(csys.smc_latency_fpga_proc))


def _policy_shape(policy) -> Optional[tuple]:
    """Key element for the runtime policy axis: None (no policy
    operand) or ``("policy", table_bucket)`` — the padded table LENGTH
    is the only traced-shape property; content never reaches the key."""
    if policy is None:
        return None
    if isinstance(policy, smcprog.PolicyProgram):
        return ("policy", smcprog.table_bucket(policy.n_ops))
    return ("policy", int(policy))


def group_key(n: int, sys: SystemConfig, mode: str, blooms,
              policy=None) -> tuple:
    """Grouping key for one trace-length-n point: everything a batched
    executable is specialized on EXCEPT the batch axis and slot budget,
    which only exist once a group is assembled (run_many derives them
    per group). One source of truth with :func:`compile_key` for the
    bucket / mode / bloom-shape normalization — used by
    :class:`repro.core.campaign.Campaign`.

    ``policy`` (a :class:`smcprog.PolicyProgram` or a table bucket int)
    selects the runtime-operand policy axis: the key then normalizes
    ``sys`` (:func:`_policy_rt_sys`) and appends the table-length
    bucket, so any number of same-bucket programs — whatever their
    content or derived cost — land in ONE group."""
    if policy is None:
        return (_bucket(n), sys, _norm_mode(mode), _bloom_shape(blooms))
    return (_bucket(n), _policy_rt_sys(sys), _norm_mode(mode),
            _bloom_shape(blooms), _policy_shape(policy))


def compile_key(bucket: int, batch: int, sys: SystemConfig, mode: str,
                blooms, slots: Optional[int] = None,
                policy_bucket: Optional[int] = None) -> tuple:
    """Cache key for one batched executable (see :func:`_bloom_shape`
    for the ``blooms`` normalization). ``slots`` is the group's
    :func:`slot_budget` (None for the uniform-budget reference
    engine). ``sys`` carries the staged policy program, which hashes by
    instruction-table content (digest semantics): same-content programs
    share one executable, distinct programs fork the key — so a staged
    policy grid runs one batched dispatch per program.
    ``policy_bucket`` instead selects the runtime-operand policy axis
    (callers pass a :func:`_policy_rt_sys`-normalized ``sys`` with it):
    only the padded table LENGTH forks the key, so a whole grid of
    same-bucket programs shares one executable."""
    return (bucket, slots, _batch_bucket(batch), sys, _norm_mode(mode),
            _bloom_shape(blooms),
            None if policy_bucket is None else _policy_shape(policy_bucket))


def cache_stats() -> dict:
    """Executable-cache counters since the last :func:`cache_clear`:
    ``hits`` / ``misses`` (misses == in-process compiles) over
    :func:`run_many` lookups, ``evictions`` (LRU drops past
    ``capacity``), plus current ``size`` / ``capacity`` and the derived
    ``lookups`` (= hits + misses). ``persistent`` mirrors the on-disk
    XLA cache counters when
    :func:`repro.utils.jax_compat.enable_persistent_compile_cache` is
    active (all-zero otherwise).

    The snapshot is CONSISTENT: every LRU field is read in one
    ``_CACHE_LOCK`` region — the same lock every writer
    (``_batched_fn`` / ``_stream_fn`` lookups, ``set_cache_capacity``
    shrinks, ``cache_clear``) holds across its whole update — so a
    concurrent reader (a sweep-service stats poll while dispatchers
    resolve executables) can never observe a torn view: ``lookups ==
    hits + misses``, ``size <= capacity``, and
    ``size == misses - evictions`` (counters monotone between clears)
    all hold in any returned dict, which
    ``tests/test_service.py::test_cache_stats_consistent_under_threads``
    hammers from threads. Only ``persistent`` is sampled outside the
    lock — it belongs to jax's process-global cache, not this LRU."""
    from repro.utils import jax_compat
    with _CACHE_LOCK:
        out = dict(_CACHE_STATS)
        out["size"] = len(_COMPILE_CACHE)
        out["capacity"] = _CACHE_CAP
        out["lookups"] = out["hits"] + out["misses"]
    out["persistent"] = jax_compat.persistent_cache_stats()
    return out


def cache_clear() -> None:
    """Drop every cached executable and zero ALL counters (hits,
    misses, and the eviction counter added with the LRU bound)."""
    with _CACHE_LOCK:
        _COMPILE_CACHE.clear()
        for k in _CACHE_STATS:
            _CACHE_STATS[k] = 0


def set_cache_capacity(n: int) -> int:
    """Bound the in-memory executable cache to ``n`` entries (LRU);
    returns the previous capacity. Shrinking evicts immediately."""
    global _CACHE_CAP
    if n < 1:
        raise ValueError(f"cache capacity must be >= 1, got {n}")
    with _CACHE_LOCK:
        old, _CACHE_CAP = _CACHE_CAP, n
        while len(_COMPILE_CACHE) > _CACHE_CAP:
            _COMPILE_CACHE.popitem(last=False)
            _CACHE_STATS["evictions"] += 1
    return old


def _shard_wrap(fn, nshards: int, bshape, pshape=None):
    """Wrap a batched runner in ``shard_map`` over ``nshards`` local
    devices on the (leading) batch axis. Trace arrays shard; a shared
    Bloom filter replicates; stacked per-trace filters shard; stacked
    policy tables/costs (the runtime policy axis) shard. Inside
    each shard the wrapped fn sees a ``batch/nshards`` slice and vmaps
    over it exactly as in the unsharded path, so results concatenate to
    the bit-identical full batch. The body is a pure per-shard vmap
    with no collectives, so the varying-axis check is off: the scan's
    initial carry is built inside the body and is not batch-varying."""
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.utils import jax_compat
    mesh = Mesh(np.array(jax.local_devices()[:nshards]), ("batch",))
    spec = P("batch")
    if bshape is None:
        in_specs = (spec,) * 5
    else:
        in_specs = (spec,) * 5 + (spec if bshape[0] == "stacked" else P(),)
    if pshape is not None:
        in_specs = in_specs + (spec, spec)
    return jax_compat.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                out_specs=spec, check_vma=False)


class _CachedRunner:
    """One cached executable: a lazily-compiled jitted runner plus the
    argument shapes its compile key fixes.

    :meth:`prime` compiles it NOW, on the calling thread, by running an
    all-zeros dummy batch (all-NOP-free zero reads; one scan execution,
    noise next to the compile). ``prepare_tasks`` primes every resolved
    runner in group order on the caller's thread before any executor
    worker starts: tracing/lowering interleaved across worker threads
    makes jax's uid counters — and so the emitted StableHLO bytes and
    the persistent on-disk cache key — nondeterministic across
    processes (observed: one fresh disk entry per run)."""

    __slots__ = ("jitted", "avals", "primed")

    def __init__(self, jitted, avals):
        self.jitted = jitted
        self.avals = avals
        self.primed = False

    def prime(self) -> "_CachedRunner":
        # donation warning noise is suppressed by the module-level
        # filter (a per-call catch_warnings here would race: it mutates
        # process-global filter state while workers may be executing)
        if not self.primed:
            # an aval entry is (shape, dtype) for an all-zeros dummy, or
            # a zero-arg callable building a structured dummy (the
            # streaming runners pass their initial StreamState this way)
            self.jitted(*(a() if callable(a) else jnp.zeros(a[0], a[1])
                          for a in self.avals))
            self.primed = True
        return self

    def __call__(self, *args):
        return self.jitted(*args)


def _batched_fn(key: tuple, ref: bool = False):
    """Jitted vmapped runner for one compile key; built once per key,
    LRU-retained up to the cache capacity (a :class:`_CachedRunner`,
    compiled on first :meth:`~_CachedRunner.prime` or call). ``ref=True``
    builds the pre-optimization reference engine (no slot budget, no
    donation) on a separate cache entry. When batch-axis sharding
    applies (see :func:`set_sharding`), the runner is shard_mapped over
    the local devices — sharded and unsharded variants fork the cache
    key, so counter semantics are unchanged for a fixed device
    topology."""
    batch = key[2]
    nshards = _shard_count(batch)
    ckey = ("ref" if ref else "fast", nshards, key)
    # get-or-create is atomic: the lock is held across the whole build
    # (cheap — jit wrapping and Mesh construction; the XLA compile is
    # deferred to prime()/first call), so two threads racing on one key
    # can neither duplicate the entry nor skew the hit/miss counters
    with _CACHE_LOCK:
        fn = _COMPILE_CACHE.get(ckey)
        if fn is not None:
            _CACHE_STATS["hits"] += 1
            _COMPILE_CACHE.move_to_end(ckey)
            return fn
        _CACHE_STATS["misses"] += 1
        runner = _build_runner(key, ref, nshards)
        _COMPILE_CACHE[ckey] = runner
        while len(_COMPILE_CACHE) > _CACHE_CAP:
            _COMPILE_CACHE.popitem(last=False)
            _CACHE_STATS["evictions"] += 1
    return runner


def _build_runner(key: tuple, ref: bool, nshards: int) -> "_CachedRunner":
    """Construct the (lazily-compiled) runner for one cache key.
    Argument order after the five trace arrays: the Bloom words (when
    the key has a bloom shape), then the stacked policy tables + cost
    pairs (when it has a policy shape) — tables/costs always ride the
    batch axis (axis 0), one program per batch row."""
    _, slots, batch, sys, mode, bshape, pshape = key
    core = _run_core_ref if ref else _run_core
    extra = {} if ref else {"slots": slots}
    has_bloom = bshape is not None
    has_pol = pshape is not None
    if has_bloom:
        stacked, _, bk, bm = bshape
        words_axis = 0 if stacked == "stacked" else None
    axes = (0,) * 5 + ((words_axis,) if has_bloom else ()) \
        + ((0, 0) if has_pol else ())

    def one(k, b, r, d, dp, *rest):
        i = 0
        bloom_args = (None, 0, 1)
        if has_bloom:
            bloom_args = (rest[0], bk, bm)
            i = 1
        pol = ({"policy_table": rest[i], "policy_cost": rest[i + 1]}
               if has_pol else {})
        return core(k, b, r, d, dp, sys, mode, *bloom_args, **extra, **pol)

    def fn(*args):
        return jax.vmap(one, in_axes=axes)(*args)

    if nshards:
        fn = _shard_wrap(fn, nshards, bshape, pshape)

    # trace arrays are freshly staged from host memory every call, so the
    # executable may reuse their buffers for its outputs (bloom words can
    # be caller-shared jnp arrays -> not donated); donation is best-effort
    # by design, so the inputs-not-aliased warning is pure noise
    jitted = jax.jit(fn) if ref else jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4))
    bucket, bb = key[0], _batch_bucket(batch)
    avals = [((bb, bucket), jnp.int32)] * 5
    if bshape is not None:
        wshape = (bshape[1],) if bshape[0] == "shared" else (bb, bshape[1])
        avals = avals + [(wshape, jnp.uint32)]
    if has_pol:
        avals = avals + [((bb, pshape[1] + 1, 4), jnp.int32),
                         ((bb, 2), jnp.int32)]
    return _CachedRunner(jitted, avals)


def _finalize(out_row: dict, padded: Trace, sys: SystemConfig,
              mode: str) -> dict:
    """Per-trace derived metrics — identical math to the original
    single-trace ``run`` so batched results stay drop-in compatible."""
    out = {kk: np.asarray(v) for kk, v in out_row.items()}
    out["exec_seconds"] = sys.cycles_to_seconds(out["exec_cycles"], mode)
    out["mode"] = mode
    out["n_requests"] = int((padded.kind != NOP).sum())
    lat = out["t_resp"] - out["t_issue"]
    ok = (padded.kind != NOP) & (out["t_resp"] < int(BIG))
    out["avg_load_latency_cycles"] = float(lat[ok].mean()) if ok.any() else 0.0
    if "flips" in out:  # fault model attached: flips per served request
        out["bit_error_rate"] = float(out["flips"]) / max(int(out["served"]), 1)
    return out


def _normalize_blooms(blooms, n: int):
    """blooms: None | one (words, k, m_bits) filter (any sequence type)
    | a per-trace sequence of identically-shaped filter triples. ->
    None | shared tuple | list of tuples (no mixed None: group
    upstream). Shared-vs-per-trace is decided by content, not container
    type, so a list-typed single filter still broadcasts."""
    if blooms is None:
        return None
    blooms = list(blooms)
    if _is_bloom_triple(blooms):
        return tuple(blooms)
    blooms = [tuple(b) for b in blooms]
    # real exceptions, not asserts: these guard public entry points
    # (run_many / run_stream_many / Campaign) and must survive python -O
    if len(blooms) != n:
        raise ValueError(
            f"per-trace blooms ({len(blooms)}) must match len(traces) ({n})")
    b0 = blooms[0]
    if not all(_is_bloom_triple(b) and b[1] == b0[1] and b[2] == b0[2]
               and np.asarray(b[0]).shape == np.asarray(b0[0]).shape
               for b in blooms):
        raise ValueError(
            "per-trace blooms must share (words-shape, k, m_bits); use "
            "Campaign to mix bloom/no-bloom points in one grid")
    return blooms


def check_mode(mode: str) -> str:
    """Validate one evaluation mode; a real ValueError (not an assert
    — asserts vanish under ``python -O``) carrying the offending value.
    Single source of truth for every mode guard (``run`` / ``run_many``
    / ``Campaign.add`` / ``Campaign.add_policy_grid``)."""
    if mode not in ("ts", "nots", "reference"):
        raise ValueError(
            f"mode must be one of ('ts', 'nots', 'reference'), got {mode!r}")
    return mode


def _check_modes(modes: Sequence[str], n: int) -> List[str]:
    modes = list(modes)
    if len(modes) != n:
        raise ValueError(
            f"per-trace modes ({len(modes)}) must match len(traces) ({n})")
    for m in modes:
        check_mode(m)
    return modes


def _normalize_policies(policies, policy_costs, sys: SystemConfig, n: int):
    """policies: None | per-trace sequence of PolicyProgram (the
    runtime policy axis — one program PER TRACE ROW; run the same trace
    against P programs by repeating it P times, which is what
    :func:`run_policies` does). policy_costs: None (every row keeps
    ``sys.smc_cycles_per_decision``, matching a staged
    ``dataclasses.replace(sys, policy=p)``) | per-trace sequence of
    smc_cycles_per_decision ints (pass ``p.smc_cycles()`` to match
    staged ``sys.with_policy(p)``). Returns None or (programs, costs)."""
    if policies is None:
        if policy_costs is not None:
            raise ValueError("policy_costs requires policies")
        return None
    policies = list(policies)
    if len(policies) != n:
        raise ValueError(
            f"per-trace policies ({len(policies)}) must match "
            f"len(traces) ({n})")
    for p in policies:
        if not isinstance(p, smcprog.PolicyProgram):
            raise TypeError(
                f"policies must be smcprog.PolicyProgram, got "
                f"{type(p).__name__}")
        p.validate()
    if policy_costs is None:
        costs = [int(sys.smc_cycles_per_decision)] * n
    else:
        costs = [int(c) for c in policy_costs]
        if len(costs) != n:
            raise ValueError(
                f"per-trace policy_costs ({len(costs)}) must match "
                f"len(traces) ({n})")
    return policies, costs


def prepare_tasks(traces: Sequence[Trace], sys: SystemConfig,
                  mode: Union[str, Sequence[str]], blooms,
                  results: List[Optional[dict]], ref: bool = False,
                  policies=None, policy_costs=None,
                  ) -> List[executor.GroupTask]:
    """Plan one :func:`run_many`-style call into executable
    :class:`repro.core.executor.GroupTask`s WITHOUT running them.

    Grouping, executable-cache resolution (``_batched_fn`` — so
    ``cache_stats`` counters settle deterministically on the caller's
    thread, in group order), and slot budgeting happen here; the
    host-side padding/stacking and the device dispatch are deferred
    into each task's ``pack``/``run``, which is what lets the
    campaign executor overlap group k+1's packing with group k's
    compute. Each task finalizes into its own ``results`` slots
    (``results`` must be a list of ``len(traces)`` Nones).

    With ``policies`` (see :func:`_normalize_policies`) each trace row
    carries its own packed program + cost pair down the batch axis —
    the runtime policy axis: grouping gains the table-length bucket,
    ``sys`` is key-normalized (:func:`_policy_rt_sys`), and one
    executable per (trace-bucket, mode, table-bucket) evaluates the
    whole grid, however many distinct programs it holds.
    """
    tctx = spans.context()
    with spans.span("emu.plan"):
        traces = list(traces)
        n = len(traces)
        modes = _check_modes([mode] * n if isinstance(mode, str) else mode, n)
        blooms = _normalize_blooms(blooms, n)
        pol = _normalize_policies(policies, policy_costs, sys, n)

        groups: dict = {}  # (bucket, normalized mode, table bucket) -> [idx]
        for i, tr in enumerate(traces):
            lb = None if pol is None else smcprog.table_bucket(pol[0][i].n_ops)
            groups.setdefault(
                (_bucket(tr.n), _norm_mode(modes[i]), lb), []).append(i)

        tasks: List[executor.GroupTask] = []
        for (bucket, gmode, lb), idxs in groups.items():
            reals = [traces[i].n_real for i in idxs]
            slots = None if ref else slot_budget(bucket, max(reals))
            gsys = sys if lb is None else _policy_rt_sys(sys)
            key = compile_key(bucket, len(idxs), gsys, gmode, blooms, slots, lb)
            fn = _batched_fn(key, ref=ref).prime()

            def pack(idxs=idxs, bucket=bucket, lb=lb):
                padded = [pad_trace(traces[i], bucket) for i in idxs]
                bb = _batch_bucket(len(idxs))
                if bb > len(idxs):  # all-NOP filler rows, discarded below
                    filler = Trace.of(np.full(bucket, 4), np.zeros(bucket),
                                      np.zeros(bucket), np.zeros(bucket))
                    padded += [filler] * (bb - len(idxs))
                stacked = [jnp.asarray(np.stack([getattr(p, f) for p in padded]))
                           for f in ("kind", "bank", "row", "delta", "dep")]
                if blooms is None:
                    args = tuple(stacked)
                elif isinstance(blooms, tuple):
                    args = (*stacked, jnp.asarray(blooms[0]))
                else:
                    words = np.stack([np.asarray(blooms[i][0]) for i in idxs])
                    if bb > len(idxs):
                        words = np.concatenate(
                            [words, np.repeat(words[:1], bb - len(idxs), axis=0)])
                    args = (*stacked, jnp.asarray(words))
                if lb is not None:
                    tables = np.stack(
                        [smcprog.pack_program(pol[0][i], lb) for i in idxs])
                    cost = np.asarray(
                        [_policy_cost_pair(sys, pol[1][i]) for i in idxs],
                        np.int32)
                    if bb > len(idxs):  # filler rows repeat row 0 (discarded)
                        tables = np.concatenate(
                            [tables,
                             np.repeat(tables[:1], bb - len(idxs), axis=0)])
                        cost = np.concatenate(
                            [cost, np.repeat(cost[:1], bb - len(idxs), axis=0)])
                    args = (*args, jnp.asarray(tables), jnp.asarray(cost))
                return args, padded

            def finalize(out, padded, idxs=idxs):
                for j, i in enumerate(idxs):
                    row = {kk: v[j] for kk, v in out.items()}
                    results[i] = _finalize(row, padded[j], sys, modes[i])

            ptag = "" if lb is None else f":pol{lb}"
            bb = _batch_bucket(len(idxs))
            tasks.append(executor.GroupTask(
                fn=fn, pack=pack, finalize=finalize,
                label=f"b{bucket}x{len(idxs)}:{gmode}{ptag}",
                cost=(slots or 2 * bucket + 4) * bb,
                counts={"slots": slots or 2 * bucket + 4, "lanes": bb,
                        "requests": sum(reals),
                        "shards": max(_shard_count(bb), 1)},
                trace_ctx=tctx))
    return tasks


def _execute_entry_point(tasks, serial) -> None:
    """Execute for the library entry points (run_many/run_stream_many):
    a single failed task re-raises its ORIGINAL exception — validation
    errors like a dep_max violation keep their type and message — and
    only a genuine multi-failure raises the executor's aggregate
    :class:`repro.core.executor.ExecutionError`. Campaign.run() goes
    through :func:`repro.core.executor.execute` directly and always
    sees the full failure records."""
    fails = executor.execute(tasks, serial=serial, raise_on_error=False)
    if fails:
        if len(fails) == 1:
            raise fails[0].error
        raise executor.ExecutionError(fails)


def _run_grouped(traces: Sequence[Trace], sys: SystemConfig,
                 mode: Union[str, Sequence[str]], blooms,
                 ref: bool, serial: Optional[bool] = None,
                 policies=None, policy_costs=None) -> List[dict]:
    """Shared grouped-execution path for :func:`run_many` (exact slot
    budgets) and :func:`run_ref_many` (uniform reference budgets):
    plan into group tasks, then execute — overlapped across the
    executor's worker pool when more than one group is present, or
    strictly in-order under ``serial=True``. Bit-identical either way
    (the executor only changes wall-clock interleaving)."""
    traces = list(traces)
    results: List[Optional[dict]] = [None] * len(traces)
    with spans.span("emu.call") as sp:
        tasks = prepare_tasks(traces, sys, mode, blooms, results, ref=ref,
                              policies=policies, policy_costs=policy_costs)
        _execute_entry_point(tasks, serial)
        spans.count_requests(sp, results)
    return results


def run_many(traces: Sequence[Trace], sys: SystemConfig,
             mode: Union[str, Sequence[str]] = "ts",
             blooms=None, serial: Optional[bool] = None,
             policies=None, policy_costs=None) -> List[dict]:
    """Evaluate many traces under one ``SystemConfig`` in batched calls.

    ``mode`` is one of 'ts' | 'nots' | 'reference', or a per-trace
    sequence of them. ``blooms`` is None, one shared ``(words, k,
    m_bits)`` tuple, or a per-trace list of identically-shaped tuples
    (stacked and vmapped alongside the traces).

    Traces are grouped by ``(length-bucket, mode)``; each group pads to
    its bucket, pads the batch axis to a power of two with all-NOP
    traces, computes its exact :func:`slot_budget` from the largest
    member, and executes as ONE vmapped, jit-cached call (trace buffers
    donated; batch axis sharded across local devices when present —
    see :func:`set_sharding`). Multi-group calls overlap host packing
    with device compute across the ``repro.core.executor`` worker pool;
    ``serial=True`` forces the in-order loop (bit-identical, for A/B).
    Returns one dict per input trace, in input order, bit-identical to
    ``run(trace, sys, mode, bloom)``.

    ``policies`` / ``policy_costs`` select the runtime policy axis: one
    :class:`smcprog.PolicyProgram` per trace row, packed into a stacked
    table operand so same-table-bucket rows share ONE executable
    regardless of program content (see :func:`_normalize_policies` for
    the cost semantics and :func:`run_policies` for the
    one-trace-many-programs convenience form). Bit-identical to
    attaching each program via ``sys.policy`` staged constants.
    """
    return _run_grouped(traces, sys, mode, blooms, ref=False, serial=serial,
                        policies=policies, policy_costs=policy_costs)


def run_ref_many(traces: Sequence[Trace], sys: SystemConfig,
                 mode: Union[str, Sequence[str]] = "ts",
                 blooms=None, serial: Optional[bool] = None,
                 policies=None, policy_costs=None) -> List[dict]:
    """The pre-optimization engine over the same grouped/batched path:
    O(bucket) work per slot, uniform ``2*bucket+4`` budget. Kept for
    bit-exactness property tests and the sim_speed steady-state A/B.
    Supports the runtime policy axis like :func:`run_many` (the
    reference engine mirrors the table-VM branch line for line)."""
    return _run_grouped(traces, sys, mode, blooms, ref=True, serial=serial,
                        policies=policies, policy_costs=policy_costs)


def run_policies(trace: Trace, sys: SystemConfig,
                 programs: Sequence[smcprog.PolicyProgram],
                 mode: str = "ts", bloom: Optional[tuple] = None,
                 derive_cost: bool = True,
                 serial: Optional[bool] = None) -> List[dict]:
    """Evaluate ONE trace under many candidate policies in vmapped
    policy-axis dispatches: the trace is repeated down the batch axis
    with one packed program per row, so a 256-program sweep compiles
    once per distinct table-length bucket (<= 3 for sanely-sized
    programs) instead of once per program — the scaling wall of the
    staged-constant path (ROADMAP item 5).

    ``derive_cost=True`` charges each program its length-derived SMC
    decision cost (``prog.smc_cycles()`` — matching
    ``sys.with_policy(prog)``); False keeps ``sys``'s existing cost
    (matching ``dataclasses.replace(sys, policy=prog)``). Returns one
    result dict per program, in input order, bit-identical to the
    equivalent staged-constant runs."""
    programs = list(programs)
    costs = ([p.smc_cycles() for p in programs] if derive_cost
             else [sys.smc_cycles_per_decision] * len(programs))
    return run_many([trace] * len(programs), sys, mode=mode, blooms=bloom,
                    serial=serial, policies=programs, policy_costs=costs)


def run(trace: Trace, sys: SystemConfig, mode: str = "ts",
        bloom: Optional[tuple] = None) -> dict:
    """mode: 'ts' | 'nots' | 'reference'. bloom: (words_u32, k, m_bits).

    'reference' is the Sec. 6 RTL reference system: a hardware memory
    controller at the modeled clock. Its math must coincide with 'ts' —
    that coincidence (validated in tests) IS the paper's
    time-scaling accuracy claim.

    A thin wrapper over a :func:`run_many` batch of one — single-trace
    and campaign paths share one compiled-program cache.
    """
    return run_many([trace], sys, mode=mode, blooms=bloom)[0]


def run_ref(trace: Trace, sys: SystemConfig, mode: str = "ts",
            bloom: Optional[tuple] = None) -> dict:
    """Single-trace wrapper over :func:`run_ref_many` (see there)."""
    return run_ref_many([trace], sys, mode=mode, blooms=bloom)[0]


# ---------------------------------------------------------------------------
# Streaming chunked-window driver: constant memory, length-independent
# compile keys, bit-identical to single-shot.
#
# The trace is consumed in windows of L = halo + chunk requests. Each
# window step (a) shifts the carried arrays left by ``chunk`` (retiring
# the ``chunk`` oldest entries, whose tags are provably final — see
# below) and appends the fresh chunk, (b) runs the SHARED slot body
# (:func:`_make_slot_body`) for a fixed per-window slot budget, with one
# twist: a slot is executed only while ``ptr <= L - _FRONTIER_UPTO``
# (the *freeze rule*), else it is an identity step. Freezing whole slots
# — rather than letting the frontier run off the window's edge — means
# the streamed slot sequence is exactly the single-shot slot sequence
# with identity steps inserted, so every carried value is bit-identical
# by induction; the inserted no-ops cost nothing but wall-clock.
#
# Finality of the retired prefix: after a window's scan, the freeze rule
# guarantees ptr > L - _FRONTIER_UPTO, in-order issue bounds unserved
# requests to indices >= ptr - window, and the halo satisfies
# halo >= _FRONTIER_UPTO + window — so every entry below ``chunk`` is
# issued AND served, and the window can emit its [0, chunk) slice as
# final output (window k covers global [k*chunk - halo, (k+1)*chunk -
# halo); the first ``halo`` emitted entries are the virtual warm-up
# prefix and are dropped by the accumulator). The window that exhausts
# the trace group ships with ``final=1``, lifting the freeze: its own
# scan drains every carried entry (the slot budget covers a full fresh
# chunk plus the halo, and chunk >= halo bounds the tail), and the
# consumer keeps its whole [0, L) emission instead of the [0, chunk)
# slice — no separate flush dispatch, same executable, same key.
#
# The carried halo holds the trailing ``halo = _FRONTIER_UPTO +
# max(window, dep_max)`` requests: the deepest lookback the frontier
# performs is max(window, dep) behind an issue point, and at a window
# handoff up to _FRONTIER_UPTO - 1 entries may sit unissued behind
# ``ptr``. The initial (virtual) halo is all-NOP with t_issue = 0 and
# t_resp = -1, so the frontier's lookback terms ``t_resp[j-k] + 1``
# evaluate to 0 — exactly the out-of-range defaults the single-shot
# engine uses for j - k < 0.
#
# Times stay ABSOLUTE int32 (only indices are rebased by -chunk at each
# shift), so a stream saturates at ~2^30 modeled cycles — a documented
# horizon, checked at the accumulator (RuntimeError on wrap), not a
# silent truncation.
# ---------------------------------------------------------------------------

DEFAULT_STREAM_CHUNK = 4096   # requests per window
DEFAULT_STREAM_DEP = 8        # max dep lookback admitted into a stream


@dataclasses.dataclass
class StreamState:
    """One stream's full inter-window carry: the :class:`EmulatorState`
    plus the window's trace arrays (the tail ``halo`` of which is the
    context the next window needs). A registered pytree, so the
    streaming runner donates and rebuilds it in place each window."""
    emu: EmulatorState
    kind: jnp.ndarray     # int32 [L]
    bank: jnp.ndarray     # int32 [L]
    row: jnp.ndarray      # int32 [L]
    delta: jnp.ndarray    # int32 [L]
    dep: jnp.ndarray      # int32 [L]


jax.tree_util.register_dataclass(
    StreamState,
    data_fields=["emu", "kind", "bank", "row", "delta", "dep"],
    meta_fields=[])


def stream_halo(sys: SystemConfig, dep_max: int = DEFAULT_STREAM_DEP) -> int:
    """Carried-context length: the issue frontier looks back at most
    ``max(window, dep)`` entries, plus up to ``_FRONTIER_UPTO - 1``
    unissued entries may trail the pointer at a window handoff (and the
    freeze slack is ``_FRONTIER_UPTO``)."""
    return _FRONTIER_UPTO + max(int(sys.window), int(dep_max))


def stream_slot_budget(chunk: int, sys: SystemConfig) -> int:
    """Per-window slot budget: at most ``chunk + _FRONTIER_UPTO - 1``
    requests become issuable in one window (the fresh chunk plus carried
    unissued entries), each costing at most 2 slots (idle hop + serve),
    plus queue-drain and freeze slack. The same budget covers the
    freeze-lifted final window — fresh chunk (2*chunk) plus carried
    queued entries (2*max(window, 2)) plus unissued stragglers and
    slack (12) — so the tail drains with no extra dispatch. Surplus
    slots freeze into identity steps, so any budget at or above the
    exact one is bit-identical (same argument as :func:`slot_budget`)."""
    return 2 * chunk + 2 * max(int(sys.window), 2) + 12


def stream_compile_key(chunk: int, batch: int, sys: SystemConfig, mode: str,
                       blooms=None,
                       dep_max: int = DEFAULT_STREAM_DEP,
                       policy_bucket: Optional[int] = None) -> tuple:
    """Cache key of one streaming window executable. Everything here is
    bounded by configuration — chunk, halo, slot budget, padded batch,
    system config, normalized mode, bloom shape, policy table-length
    bucket — and NOTHING depends on total trace length: a 1M-request
    stream and a 10k-request stream on the same config share one entry
    (the ``cache_stats`` regression in tests/test_streaming.py pins
    this). ``policy_bucket`` selects the runtime policy axis (callers
    pass a :func:`_policy_rt_sys`-normalized ``sys`` with it)."""
    return ("stream", int(chunk), stream_halo(sys, dep_max),
            stream_slot_budget(chunk, sys), _batch_bucket(batch), sys,
            _norm_mode(mode), _bloom_shape(blooms),
            None if policy_bucket is None else _policy_shape(policy_bucket))


def _stream_init(chunk: int, halo: int, sys: SystemConfig,
                 batch: Optional[int] = None) -> StreamState:
    """Window-0 carry: an all-virtual window (NOP trace, t_issue=0,
    t_resp=-1 — see the section comment) with ``ptr = L`` so the first
    shift lands the pointer exactly on the first real request. With
    ``batch``, every leaf gains a leading batch axis."""
    L = chunk + halo
    emu = EmulatorState.init(L, sys)
    emu = dataclasses.replace(emu, t_resp=jnp.full((L,), -1, jnp.int32),
                              ptr=jnp.int32(L))
    z = jnp.zeros((L,), jnp.int32)
    ss = StreamState(emu=emu, kind=jnp.full((L,), NOP, jnp.int32),
                     bank=z, row=z, delta=z, dep=z)
    if batch is None:
        return ss
    return jax.tree_util.tree_map(lambda a: jnp.stack([a] * batch), ss)


def _stream_step_core(ss: StreamState, ck, cb, cr, cd, cdep, final,
                      sys: SystemConfig, mode: str, bloom_words,
                      bloom_k: int, bloom_m: int, chunk: int, slots: int,
                      policy_table=None, policy_cost=None):
    """One window step (see the section comment for the correctness
    argument): shift by ``chunk``, scan the freeze-gated shared slot
    body for ``slots`` steps, and emit the whole [0, L) carry.
    ``final`` is a traced scalar (an operand, NOT a compile-key
    constant): the last real window sets it to lift the freeze so its
    own scan drains the entire tail in-budget — no separate flush
    dispatch (requires chunk >= halo, enforced by the driver, so the
    final window's emission covers every still-carried entry)."""
    C = chunk
    L = ss.kind.shape[0]
    kind = jnp.concatenate([ss.kind[C:], ck])
    bank = jnp.concatenate([ss.bank[C:], cb])
    row = jnp.concatenate([ss.row[C:], cr])
    delta = jnp.concatenate([ss.delta[C:], cd])
    dep = jnp.concatenate([ss.dep[C:], cdep])
    e = ss.emu
    emu = dataclasses.replace(
        e,
        t_issue=jnp.concatenate([e.t_issue[C:], jnp.zeros((C,), jnp.int32)]),
        t_resp=jnp.concatenate([e.t_resp[C:], jnp.full((C,), BIG, jnp.int32)]),
        # queue entries and the pointer are window-local indices: rebase
        # (carried live entries are >= C — they sit in the halo)
        queue=jnp.where(e.queue >= 0, e.queue - C, e.queue),
        ptr=e.ptr - C)

    # freeze rule: a slot only executes while the frontier cannot run off
    # the loaded window (or during the lifted flush). Threaded through the
    # body's predicates — NOT a lax.cond, which vmap would lower to both
    # branches + an O(L) select over the carry per slot (see
    # _make_slot_body); frozen slots cost the same O(Q)+O(1) as live ones.
    live_cut = jnp.int32(L - _FRONTIER_UPTO)
    lifted = final != 0
    step = _make_slot_body(kind, bank, row, delta, dep, sys, mode,
                           bloom_words, bloom_k, bloom_m,
                           gate=lambda st: lifted | (st.ptr <= live_cut),
                           policy_table=policy_table,
                           policy_cost=policy_cost)
    emu, _ = jax.lax.scan(lambda st, _: (step(st), None), emu, None,
                          length=slots)
    # emit the full [0, L) carry every window: the consumer slices
    # [0, chunk) for interior windows and keeps everything for the
    # final (freeze-lifted) one — constant shapes, ONE executable
    out = (kind, emu.t_issue, emu.t_resp, emu.ptr)
    return StreamState(emu=emu, kind=kind, bank=bank, row=row,
                       delta=delta, dep=dep), out


def _build_stream_runner(key: tuple) -> "_CachedRunner":
    """Construct the (lazily-compiled) window-step runner for one
    streaming cache key: :func:`_stream_step_core` vmapped over the
    padded batch axis, jitted with the carried :class:`StreamState` and
    the freshly-staged chunk arrays donated (constant device memory —
    each window rebuilds the carry in place). Post-``is_final``
    argument order matches :func:`_build_runner`: Bloom words (when
    keyed), then stacked policy tables + cost pairs (when keyed)."""
    _, C, H, SL, bb, sys, mode, bshape, pshape = key
    has_bloom = bshape is not None
    has_pol = pshape is not None
    if has_bloom:
        stacked, _, bk, bm = bshape
        words_axis = 0 if stacked == "stacked" else None
    axes = (0,) * 6 + ((words_axis,) if has_bloom else ()) \
        + ((0, 0) if has_pol else ())

    def fn(ss, ck, cb, cr, cd, cdep, is_final, *rest):
        def one(s, a, b, c, d, e, *r):
            i = 0
            bloom_args = (None, 0, 1)
            if has_bloom:
                bloom_args = (r[0], bk, bm)
                i = 1
            pol = ({"policy_table": r[i], "policy_cost": r[i + 1]}
                   if has_pol else {})
            return _stream_step_core(s, a, b, c, d, e, is_final,
                                     sys, mode, *bloom_args, C, SL, **pol)
        return jax.vmap(one, in_axes=axes)(ss, ck, cb, cr, cd, cdep, *rest)

    jitted = jax.jit(fn, donate_argnums=(0, 1, 2, 3, 4, 5))
    avals = [lambda: _stream_init(C, H, sys, batch=bb)] + \
        [((bb, C), jnp.int32)] * 5 + [((), jnp.int32)]
    if bshape is not None:
        wshape = (bshape[1],) if bshape[0] == "shared" else (bb, bshape[1])
        avals = avals + [(wshape, jnp.uint32)]
    if has_pol:
        avals = avals + [((bb, pshape[1] + 1, 4), jnp.int32),
                         ((bb, 2), jnp.int32)]
    return _CachedRunner(jitted, avals)


def _stream_fn(key: tuple) -> "_CachedRunner":
    """Get-or-build the streaming runner for ``key`` in the SAME
    module-level LRU as the batched executables (same lock, same
    hit/miss/eviction counters — the ``"stream"`` tag namespaces the
    keys)."""
    with _CACHE_LOCK:
        fn = _COMPILE_CACHE.get(key)
        if fn is not None:
            _CACHE_STATS["hits"] += 1
            _COMPILE_CACHE.move_to_end(key)
            return fn
        _CACHE_STATS["misses"] += 1
        runner = _build_stream_runner(key)
        _COMPILE_CACHE[key] = runner
        while len(_COMPILE_CACHE) > _CACHE_CAP:
            _COMPILE_CACHE.popitem(last=False)
            _CACHE_STATS["evictions"] += 1
    return runner


def _nop_fields(k: int) -> tuple:
    z = np.zeros(k, np.int32)
    return (np.full(k, NOP, np.int32), z, z, z, z)


class _Chunker:
    """Re-buffer an arbitrary stream of :class:`Trace` windows into
    exact ``chunk``-sized int32 field blocks, NOP-padding past the end.
    Accepts a single Trace, an iterable of Traces, or a zero-arg
    callable returning one (a generator factory). Holds O(chunk +
    largest yielded window) host memory — never the whole stream."""

    __slots__ = ("it", "chunk", "dep_max", "parts", "buffered",
                 "exhausted", "n")

    def __init__(self, stream, chunk: int, dep_max: int):
        if isinstance(stream, Trace):
            stream = (stream,)
        elif callable(stream):
            stream = stream()
        self.it = iter(stream)
        self.chunk = chunk
        self.dep_max = dep_max
        self.parts: list = []    # pending (kind, bank, row, delta, dep)
        self.buffered = 0
        self.exhausted = False
        self.n = 0               # total requests pulled (incl. user NOPs)

    @property
    def done(self) -> bool:
        return self.exhausted and self.buffered == 0

    def _pull(self) -> None:
        try:
            tr = next(self.it)
        except StopIteration:
            self.exhausted = True
            return
        if not isinstance(tr, Trace):
            raise TypeError(
                f"streams must yield Trace windows, got {type(tr).__name__}")
        dep = np.asarray(tr.dep, np.int32)
        if dep.size and (int(dep.max()) > self.dep_max or int(dep.min()) < 0):
            raise ValueError(
                f"stream window has dep={int(dep.max())} outside "
                f"[0, dep_max={self.dep_max}]; raise dep_max (grows the "
                f"carried halo) or re-author the trace")
        self.parts.append(tuple(
            np.asarray(getattr(tr, f), np.int32)
            for f in ("kind", "bank", "row", "delta", "dep")))
        self.buffered += tr.n
        self.n += tr.n

    def next_block(self) -> tuple:
        """The next ``chunk`` requests as (kind, bank, row, delta, dep)
        arrays; all-NOP once the stream is exhausted."""
        while self.buffered < self.chunk and not self.exhausted:
            self._pull()
        fields: list = [[] for _ in range(5)]
        need = self.chunk
        while need and self.parts:
            part = self.parts[0]
            take = min(need, part[0].shape[0])
            for f, arr in zip(fields, part):
                f.append(arr[:take])
            if take == part[0].shape[0]:
                self.parts.pop(0)
            else:
                self.parts[0] = tuple(arr[take:] for arr in part)
            self.buffered -= take
            need -= take
        if need:
            for f, p in zip(fields, _nop_fields(need)):
                f.append(p)
        return tuple(np.concatenate(f) if len(f) != 1 else f[0]
                     for f in fields)


class _StreamAccum:
    """Per-stream output accumulator over emitted window blocks.

    ``collect='aggregate'`` keeps O(1) state: int64-exact latency sums
    plus running maxima (for int32-range values np.mean's float64
    pairwise sum is exact too, so the reported mean is identical to the
    full-mode one). ``collect='full'`` additionally retains every
    emitted block and reassembles exact per-request ``t_issue`` /
    ``t_resp`` arrays — drop-in comparable with single-shot
    :func:`run`, at O(stream length) host memory."""

    __slots__ = ("collect", "halo", "blocks", "n_requests", "lat_sum",
                 "last_resp", "last_issue")

    def __init__(self, collect: str, halo: int):
        self.collect = collect
        self.halo = halo
        self.blocks: list = []
        self.n_requests = 0
        self.lat_sum = 0
        self.last_resp = 0
        self.last_issue = 0

    def feed(self, kind_blk, issue_blk, resp_blk) -> None:
        valid = kind_blk != NOP  # virtual-halo and padding entries are NOP
        if valid.any():
            resp = resp_blk[valid].astype(np.int64)
            issue = issue_blk[valid].astype(np.int64)
            if (resp >= int(BIG)).any() or (resp < 0).any():
                raise RuntimeError(
                    "streaming invariant violated: a retired window slice "
                    "holds an unserved or time-wrapped request (t_resp "
                    "outside [0, 2^30)) — slot budget or int32 time "
                    "horizon exceeded")
            self.n_requests += int(valid.sum())
            self.lat_sum += int((resp - issue).sum())
            self.last_resp = max(self.last_resp, int(resp.max()))
            self.last_issue = max(self.last_issue, int(issue.max()))
        if self.collect == "full":
            self.blocks.append((np.asarray(kind_blk),
                                np.asarray(issue_blk),
                                np.asarray(resp_blk)))

    def result(self, n: int, hits: int, served: int, dram_ticks: int,
               smc: int, sys: SystemConfig, mode: str) -> dict:
        if served != self.n_requests:
            raise RuntimeError(
                f"streaming invariant violated: {served} serve slots vs "
                f"{self.n_requests} retired non-NOP requests")
        exec_cycles = max(self.last_resp, self.last_issue)
        out = {
            "exec_cycles": np.int32(exec_cycles),
            "row_hits": np.int32(hits),
            "served": np.int32(served),
            "dram_ticks": np.int32(dram_ticks),
            "smc_fpga_cycles": np.int32(smc),
            "exec_seconds": sys.cycles_to_seconds(exec_cycles, mode),
            "mode": mode,
            "n_requests": self.n_requests,
        }
        if self.collect == "full":
            H = self.halo
            kind = np.concatenate([b[0] for b in self.blocks])[H:H + n]
            t_issue = np.concatenate([b[1] for b in self.blocks])[H:H + n]
            t_resp = np.concatenate([b[2] for b in self.blocks])[H:H + n]
            lat = t_resp - t_issue
            ok = (kind != NOP) & (t_resp < int(BIG))
            out["avg_load_latency_cycles"] = \
                float(lat[ok].mean()) if ok.any() else 0.0
            out["t_resp"] = t_resp
            out["t_issue"] = t_issue
        else:
            out["avg_load_latency_cycles"] = \
                self.lat_sum / self.n_requests if self.n_requests else 0.0
        return out


def prepare_stream_tasks(streams: Sequence, sys: SystemConfig,
                         mode: Union[str, Sequence[str]], blooms,
                         results: List[Optional[dict]],
                         chunk: int = DEFAULT_STREAM_CHUNK,
                         dep_max: int = DEFAULT_STREAM_DEP,
                         collect: str = "full",
                         policies=None, policy_costs=None,
                         ) -> List["executor.StreamTask"]:
    """Plan a :func:`run_stream_many` call into executable
    :class:`repro.core.executor.StreamTask`s WITHOUT running them —
    the streaming analogue of :func:`prepare_tasks`: grouping (by
    normalized mode only — there is no length bucket, that is the
    point — plus the policy table bucket when the runtime policy axis
    rides along), runner resolution and priming on the caller's thread,
    and closures that feed windows / consume emitted blocks / finalize
    per-stream records into disjoint ``results`` slots. ``policies`` /
    ``policy_costs`` are per-STREAM (one program per stream row, see
    :func:`_normalize_policies`); the packed tables are per-group
    constants appended to every window's arguments."""
    tctx = spans.context()
    with spans.span("emu.plan"):
        streams = list(streams)
        n = len(streams)
        modes = _check_modes([mode] * n if isinstance(mode, str) else mode, n)
        blooms = _normalize_blooms(blooms, n)
        pol = _normalize_policies(policies, policy_costs, sys, n)
        H = stream_halo(sys, dep_max)
        if not isinstance(chunk, (int, np.integer)) or isinstance(chunk, bool) \
                or chunk < H:
            raise ValueError(
                f"stream chunk must be an int >= halo ({H} = {_FRONTIER_UPTO} "
                f"+ max(window={sys.window}, dep_max={dep_max})) so the final "
                f"window drains the whole tail in-budget, got {chunk!r}")
        if collect not in ("full", "aggregate"):
            raise ValueError(
                f"collect must be 'full' or 'aggregate', got {collect!r}")
        chunk = int(chunk)
        SL = stream_slot_budget(chunk, sys)
        L = chunk + H

        groups: dict = {}
        for i in range(n):
            lb = None if pol is None else smcprog.table_bucket(pol[0][i].n_ops)
            groups.setdefault((_norm_mode(modes[i]), lb), []).append(i)

        tasks: List[executor.StreamTask] = []
        for (gmode, lb), idxs in groups.items():
            gsys = sys if lb is None else _policy_rt_sys(sys)
            key = stream_compile_key(chunk, len(idxs), gsys, gmode, blooms,
                                     dep_max, lb)
            fn = _stream_fn(key).prime()
            bb = _batch_bucket(len(idxs))
            if blooms is None:
                wargs = ()
            elif isinstance(blooms, tuple):
                wargs = (jnp.asarray(blooms[0]),)
            else:
                words = np.stack([np.asarray(blooms[i][0]) for i in idxs])
                if bb > len(idxs):
                    words = np.concatenate(
                        [words, np.repeat(words[:1], bb - len(idxs), axis=0)])
                wargs = (jnp.asarray(words),)
            if lb is not None:  # per-group policy operands, shared by windows
                tables = np.stack(
                    [smcprog.pack_program(pol[0][i], lb) for i in idxs])
                cost = np.asarray(
                    [_policy_cost_pair(sys, pol[1][i]) for i in idxs], np.int32)
                if bb > len(idxs):
                    tables = np.concatenate(
                        [tables, np.repeat(tables[:1], bb - len(idxs), axis=0)])
                    cost = np.concatenate(
                        [cost, np.repeat(cost[:1], bb - len(idxs), axis=0)])
                wargs = wargs + (jnp.asarray(tables), jnp.asarray(cost))

            def pack(idxs=idxs, bb=bb):
                ctx = {
                    "chunkers": [_Chunker(streams[i], chunk, dep_max)
                                 for i in idxs],
                    "accs": [_StreamAccum(collect, H) for _ in idxs],
                    # index of the freeze-lifted final window; written by
                    # windows() BEFORE that window's args are queued, so the
                    # (possibly prefetching) consumer always sees it in time
                    "final_idx": None,
                    "fed": 0,
                }
                return _stream_init(chunk, H, sys, batch=bb), ctx

            def windows(ctx, bb=bb, wargs=wargs):
                # the window whose assembly exhausts every chunker is the
                # final one: it ships with the freeze LIFTED (final=1) and
                # drains the whole tail in-budget — no separate flush
                # dispatch (SL covers a full fresh chunk plus the carried
                # halo, the exact worst case). Each window comes with its
                # fresh non-NOP requests, for the dispatch span's count.
                chunkers = ctx["chunkers"]
                filler = _nop_fields(chunk)
                k = 0
                while not all(c.done for c in chunkers):
                    blocks = [c.next_block() for c in chunkers]
                    fresh = sum(int(np.count_nonzero(b[0] != NOP))
                                for b in blocks)
                    blocks += [filler] * (bb - len(blocks))
                    final = all(c.done for c in chunkers)
                    if final:
                        ctx["final_idx"] = k
                    yield tuple(
                        jnp.asarray(np.stack([b[i] for b in blocks]))
                        for i in range(5)) + (jnp.int32(final),) + wargs, fresh
                    k += 1
                if k == 0:  # every stream empty: one all-NOP final window
                    ctx["final_idx"] = 0
                    blocks = [filler] * bb
                    yield tuple(
                        jnp.asarray(np.stack([b[i] for b in blocks]))
                        for i in range(5)) + (jnp.int32(1),) + wargs, 0

            def consume(out, ctx, idxs=idxs):
                kind_blk, issue_blk, resp_blk, ptr = out
                final = ctx["final_idx"] == ctx["fed"]
                ctx["fed"] += 1
                # interior windows retire exactly [0, chunk); the final one
                # keeps its whole [0, L) carry (tail included — that is the
                # flush)
                keep = L if final else chunk
                for j, acc in enumerate(ctx["accs"]):
                    acc.feed(kind_blk[j, :keep], issue_blk[j, :keep],
                             resp_blk[j, :keep])
                if not final:
                    lag = ptr[:len(idxs)] <= (L - _FRONTIER_UPTO)
                    if lag.any():
                        raise RuntimeError(
                            f"streaming invariant violated: issue frontier "
                            f"fell behind the window "
                            f"(ptr={ptr[:len(idxs)].tolist()}, window={L}, "
                            f"slots={SL}) — slot budget too small")

            def finalize(final_state, ctx, idxs=idxs):
                e = final_state.emu
                hits = np.asarray(e.hits)
                served = np.asarray(e.served_n)
                dram_now = np.asarray(e.dram_now)
                smc = np.asarray(e.smc_fpga_cycles)
                # the fault carry rides EmulatorState through every window
                # untouched by the shift, so the final window's state IS the
                # whole stream's flip record (bit-identical to single-shot)
                fhost = (None if sys.faults is None else
                         jax.tree_util.tree_map(np.asarray, e.faults))
                for j, i in enumerate(idxs):
                    results[i] = ctx["accs"][j].result(
                        ctx["chunkers"][j].n, int(hits[j]), int(served[j]),
                        int(dram_now[j]), int(smc[j]), sys, modes[i])
                    if fhost is not None:
                        frow = {kk: v[j] for kk, v in fhost.items()}
                        results[i].update(faultmod.fault_result_fields(frow))
                        results[i]["bit_error_rate"] = \
                            int(frow["vptr"]) / max(int(served[j]), 1)

            ptag = "" if lb is None else f":pol{lb}"
            tasks.append(executor.StreamTask(
                fn=fn, pack=pack, windows=windows, consume=consume,
                finalize=finalize,
                label=f"stream:c{chunk}x{len(idxs)}:{gmode}{ptag}",
                cost=SL * bb, trace_ctx=tctx,
                # the window runner is never shard_mapped: one device
                counts={"slots": SL, "lanes": bb, "shards": 1}))
    return tasks


def run_stream_many(streams: Sequence, sys: SystemConfig,
                    mode: Union[str, Sequence[str]] = "ts", blooms=None,
                    chunk: int = DEFAULT_STREAM_CHUNK,
                    dep_max: int = DEFAULT_STREAM_DEP,
                    collect: str = "full",
                    serial: Optional[bool] = None,
                    policies=None, policy_costs=None) -> List[dict]:
    """Evaluate many UNBOUNDED traces under one ``SystemConfig`` in
    lockstep constant-memory windows.

    Each stream is a :class:`Trace`, an iterable of Trace windows, or a
    zero-arg callable returning one (a generator factory) — total
    length need not be known, and with an iterator input it is never
    materialized. Streams sharing a normalized mode batch into ONE
    window executable whose compile key (:func:`stream_compile_key`)
    is independent of trace length; exhausted streams idle on NOP
    windows until the whole group drains, and the window that exhausts
    the group ships with the freeze lifted so its own scan retires
    every tail — no extra flush dispatch. Device memory is
    O(batch * (chunk + halo));
    host memory is O(chunk) per stream with ``collect='aggregate'``
    (exact int64 aggregates only) or O(length) with the default
    ``collect='full'`` (adds exact per-request ``t_resp`` /
    ``t_issue``).

    Results are bit-identical to single-shot :func:`run` /
    :func:`run_many` on any trace both paths support, for every chunk
    size >= the halo — pinned by tests/test_streaming.py and the
    hypothesis property in tests/test_property.py. ``dep_max`` bounds
    admissible ``dep`` lookbacks (it sizes the carried halo); times
    saturate at the int32 horizon (~2^30 modeled cycles), checked at
    the accumulator."""
    streams = list(streams)
    results: List[Optional[dict]] = [None] * len(streams)
    with spans.span("emu.call") as sp:
        tasks = prepare_stream_tasks(streams, sys, mode, blooms, results,
                                     chunk=chunk, dep_max=dep_max,
                                     collect=collect, policies=policies,
                                     policy_costs=policy_costs)
        _execute_entry_point(tasks, serial)
        spans.count_requests(sp, results)
    return results


def run_stream(stream, sys: SystemConfig, mode: str = "ts",
               bloom: Optional[tuple] = None,
               chunk: int = DEFAULT_STREAM_CHUNK,
               dep_max: int = DEFAULT_STREAM_DEP,
               collect: str = "full") -> dict:
    """Single-stream wrapper over :func:`run_stream_many` (see there)."""
    return run_stream_many([stream], sys, mode=mode, blooms=bloom,
                           chunk=chunk, dep_max=dep_max,
                           collect=collect)[0]
