"""Software-defined SMC scheduling policies: a branchless MC-policy VM.

EasyDRAM's first key idea is that DRAM scheduling policies are *software*
running on a programmable memory controller (SMC) — not RTL. This module
reproduces that idea in jax_pallas terms: a scheduling policy is a tiny
program over a fixed register IR, authored in ~20 lines of Python with
:class:`PolicyBuilder`, assembled into a dense int32 instruction table
(:class:`PolicyProgram`), and evaluated *inside* the emulator's scan slot
body over the Q visible hardware-queue slots.

Execution model
---------------

Two execution paths share one semantics:

* **Staged constant** (PR 4): the table is a compile-time constant of
  the jitted emulator program; its content rides in the compile key
  through ``SystemConfig`` (a :class:`PolicyProgram` is hashed/compared
  by table content, not by name, so two same-content programs share one
  cached executable). The evaluator (:func:`evaluate`) unrolls a fixed
  ``len(table)``-trip loop over the rows at staging time and emits
  straight-line, branch-free vector arithmetic over the Q queue slots —
  an interpreter while tracing, a branchless dataflow program at run
  time.
* **Runtime operand** (PR 10): the table is packed into a dense int32
  array (:func:`pack_program`, padded to a :func:`table_bucket` length
  so only the BUCKET — never the content — reaches the compile key) and
  interpreted by :func:`evaluate_table`, a branchless table-driven VM:
  each row gathers its operands dynamically and selects among every
  opcode's candidate result. One compiled executable then evaluates ANY
  program of that bucket — and ``jax.vmap`` over stacked packed tables
  evaluates hundreds of candidate policies per dispatch
  (``emulator.run_policies``). Bit-identical to the staged path by
  construction: identical int32 candidate arithmetic, exact selects.

Every instruction is O(Q) int32 work either way, so a policy adds
O(L * Q) per scheduling slot and preserves the engine's O(Q)+O(1)
per-slot invariant (L = program length / bucket, a small constant; the
runtime VM pays a constant-factor premium — all opcode candidates per
row — which the policy axis amortizes across the batch).

A program produces a per-slot ``score`` (int32, lower = served first)
and an optional ``boost`` mask (nonzero = preferred class). Selection is
the same two-level argmin the hard-coded scheduler used: the oldest-
score request among boosted visible slots if any, else among all visible
slots — which is what makes the built-in :func:`frfcfs_program` /
:func:`fcfs_program` *bit-identical* to the legacy ``sys.scheduler``
string flag (pinned in tests/test_smcprog.py).

Cost model
----------

The SMC is slow — that slowness is the very thing time scaling hides, so
it must be modeled, not ignored. A program's decision cost is derived
from its length: ``smc_cycles() = base_cycles + cycles_per_op * len``
(override with ``smc_cycles_override`` to pin a calibrated number).
``SystemConfig.with_policy(prog)`` folds that cost into
``smc_cycles_per_decision``, so a ``ts`` vs ``nots`` sweep of one
policy grid is a first-class experiment: ``ts`` results are invariant
to program length (the paper's claim), ``nots`` results degrade with it
(the inaccuracy the paper quantifies). Attaching a program with plain
``dataclasses.replace(sys, policy=prog)`` keeps the config's existing
cost — that is what the bit-identity tests use.

Quickstart — a custom policy in ~20 lines::

    from repro.core.smcprog import PolicyBuilder
    from repro.core.timescale import JETSON_NANO
    from repro.core.emulator import run

    b = PolicyBuilder()
    age = b.score_age()            # arrival time, lower = older
    hit = b.score_row_hit()        # 1 where the bank's open row matches
    busy = b.mask_bank_busy()      # 1 where the request's bank is busy
    # serve oldest, but penalize requests on busy banks by 64 cycles,
    # and prefer row hits whenever any are visible
    score = b.add(age, b.mul(busy, b.const(64)))
    prog = b.build(score=score, boost=hit, name="hit-first-idle-banks")

    sysc = JETSON_NANO.with_policy(prog)     # cost derived from length
    out = run(trace, sysc, "ts")
    print(prog.smc_cycles(), prog.digest, prog.describe())

Quickstart — 256 candidate policies, ONE compiled dispatch (the
runtime-operand axis; table content is data, only the length bucket
rides the compile key), then a short autotune run::

    from repro.core import emulator
    from repro.core.policysearch import random_program, search
    import numpy as np

    rng = np.random.RandomState(0)
    progs = [random_program(rng, name=f"cand{i}") for i in range(256)]
    recs = emulator.run_policies(trace, JETSON_NANO, progs, mode="ts")
    best = min(recs, key=lambda r: float(r["avg_load_latency_cycles"]))

    res = search(trace, JETSON_NANO, generations=5, population=16)
    print(res.summary())           # tuned-vs-baseline table
    print(res.best.describe())     # the winning schedule, one dispatch
                                   # per generation under the hood

(Full walkthrough: ``examples/policy_lab.py``.)

Sweeping a grid of policies goes through
:meth:`repro.core.campaign.Campaign.add_policy_grid` — by default one
vmapped policy-axis dispatch per (trace, mode) with programs sharing a
table bucket; ``policy_axis=False`` selects the staged per-program
path. Built-ins: :func:`frfcfs_program`,
:func:`fcfs_program`, :func:`bank_round_robin_program`,
:func:`open_page_program`, :func:`closed_page_program`,
:func:`write_drain_program` (see :func:`builtin_programs`).
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

# Same sentinel value as repro.core.emulator.BIG, as a host value: a
# module-level jnp constant would initialize the JAX backend at import
# time, before the process has picked its backend and compile cache.
BIG = 2 ** 30

# ---------------------------------------------------------------------------
# Opcodes. Loads read one named input vector of the scheduling environment
# (length Q, int32); ALU ops combine previously-computed values. Booleans
# are int32 0/1. All arithmetic wraps in int32 (document, don't guard).
# ---------------------------------------------------------------------------

OP_CONST = 0           # imm -> broadcast constant
# environment loads
OP_AGE = 1             # request arrival time (proc cycles; lower = older)
OP_AGE_REL = 2         # age minus the oldest *visible* age (small ints)
OP_ROW_HIT = 3         # 1 where the bank's open row matches the request row
OP_BANK = 4            # request bank index
OP_ROW = 5             # request row index
OP_IS_WRITE = 6        # 1 where the request is a WRITE
OP_BANK_BUSY = 7       # 1 where the request's bank is busy at the DRAM frontier
OP_RR_DIST = 8         # cyclic bank distance from the last served bank
OP_QSLOT = 9           # hardware-queue slot index 0..Q-1
OP_WRITE_PRESSURE = 10  # count of visible writes, broadcast to all slots
OP_HAMMER_CT = 11      # request bank's aggressor ACT counter (faults model)
OP_PARA_RAND = 12      # per-slot uniform 16-bit draw in [0, 65536) (PARA)
# ALU
OP_ADD = 16
OP_SUB = 17
OP_MUL = 18
OP_MIN = 19
OP_MAX = 20
OP_AND = 21            # bitwise (use on 0/1 masks)
OP_OR = 22
OP_NOT = 23            # (a == 0) -> 0/1
OP_EQ = 24
OP_LT = 25
OP_GE = 26
OP_SELECT = 27         # a != 0 ? b : imm-indexed?  (c, a, b) -> see builder

_LOAD_NAMES = {
    OP_AGE: "age", OP_AGE_REL: "age_rel", OP_ROW_HIT: "row_hit",
    OP_BANK: "bank", OP_ROW: "row", OP_IS_WRITE: "is_write",
    OP_BANK_BUSY: "bank_busy", OP_RR_DIST: "rr_dist", OP_QSLOT: "qslot",
    OP_WRITE_PRESSURE: "write_pressure",
    OP_HAMMER_CT: "hammer_ct", OP_PARA_RAND: "para_rand",
}
_OP_NAMES = {v: k for k, v in globals().items() if k.startswith("OP_")}
_UNARY = {OP_NOT}
_BINARY = {OP_ADD, OP_SUB, OP_MUL, OP_MIN, OP_MAX, OP_AND, OP_OR,
           OP_EQ, OP_LT, OP_GE}
_INT32_MIN, _INT32_MAX = -(2 ** 31), 2 ** 31 - 1


@dataclasses.dataclass(frozen=True)
class Reg:
    """Handle to one SSA value of one builder. Opaque to callers."""
    idx: int
    owner: int = dataclasses.field(repr=False, compare=False, default=0)


@dataclasses.dataclass(frozen=True)
class PolicyProgram:
    """An assembled policy: a dense int32 instruction table in SSA form.

    ``table`` rows are ``(opcode, a, b, imm)``; row *i* defines value
    *i*, operands ``a``/``b`` reference earlier rows. ``score_reg`` /
    ``boost_reg`` name the output values (``boost_reg == -1`` = no
    boost class). Equality and hashing are by *semantic* content —
    ``name`` and the cost-model fields are excluded — so the emulator
    compile cache and Campaign grouping are content-addressed (same
    table = one executable).
    """
    table: Tuple[Tuple[int, int, int, int], ...]
    score_reg: int
    boost_reg: int = -1
    # optional mitigation output: nonzero on the SERVED slot triggers a
    # targeted neighbor refresh on its bank (RowHammer defense) — the
    # engine charges dram.neighbor_refresh_ticks and resets the bank's
    # aggressor counter. -1 = the policy never mitigates (all pre-fault
    # programs), which keeps select_slot's trace byte-identical.
    mitigate_reg: int = -1
    # cost-model fields never enter the emulation semantics (with_policy
    # copies the cost onto SystemConfig.smc_cycles_per_decision, which
    # IS compared), so like `name` they are excluded from eq/hash —
    # same-table programs share one compile-key group
    base_cycles: int = dataclasses.field(default=300, compare=False)
    cycles_per_op: int = dataclasses.field(default=25, compare=False)
    smc_cycles_override: Optional[int] = dataclasses.field(
        default=None, compare=False)
    name: str = dataclasses.field(default="policy", compare=False)

    @property
    def n_ops(self) -> int:
        return len(self.table)

    def smc_cycles(self) -> int:
        """SMC cycles per scheduling decision — the program-length cost
        model (``base + per_op * len``), or the calibrated override."""
        if self.smc_cycles_override is not None:
            return int(self.smc_cycles_override)
        return int(self.base_cycles + self.cycles_per_op * self.n_ops)

    @property
    def digest(self) -> str:
        """Content digest (table + outputs); what the compile key sees.
        mitigate_reg joins the repr only when set, so every pre-fault
        program keeps its historical digest."""
        sem = (self.table, self.score_reg, self.boost_reg)
        if self.mitigate_reg >= 0:
            sem = sem + (self.mitigate_reg,)
        return hashlib.sha1(repr(sem).encode()).hexdigest()[:12]

    def uses(self, opcode: int) -> bool:
        return any(row[0] == opcode for row in self.table)

    def validate(self) -> "PolicyProgram":
        """Structural check; errors carry the table row index AND the
        decoded op name (``row 3 (op_add): ...``) so search-generated
        invalid programs point straight at the offending instruction."""
        n = len(self.table)
        if not 0 <= self.score_reg < n:
            raise ValueError(f"score_reg {self.score_reg} out of range "
                             f"for a {n}-row table")
        if not -1 <= self.boost_reg < n:
            raise ValueError(f"boost_reg {self.boost_reg} out of range "
                             f"for a {n}-row table")
        if not -1 <= self.mitigate_reg < n:
            raise ValueError(f"mitigate_reg {self.mitigate_reg} out of "
                             f"range for a {n}-row table")
        for i, (op, a, b, imm) in enumerate(self.table):
            nm = _OP_NAMES.get(op, f"op{op}").lower()
            if op != OP_CONST and op not in _LOAD_NAMES \
                    and op not in _UNARY and op not in _BINARY \
                    and op != OP_SELECT:
                raise ValueError(f"row {i}: unknown opcode {op}")
            refs = (() if op == OP_CONST or op in _LOAD_NAMES
                    else (a,) if op in _UNARY
                    else (a, b) if op in _BINARY else (a, b, imm))
            for r in refs:
                if not 0 <= r < i:
                    raise ValueError(
                        f"row {i} ({nm}): operand {r} is not an earlier "
                        f"value")
            if op == OP_CONST and not _INT32_MIN <= imm <= _INT32_MAX:
                raise ValueError(f"row {i} ({nm}): imm {imm} not int32")
        return self

    def describe(self) -> str:
        """Human-readable disassembly (one line per instruction)."""
        lines = [f"{self.name}: {self.n_ops} ops, "
                 f"{self.smc_cycles()} smc-cycles/decision, "
                 f"digest {self.digest}"]
        for i, (op, a, b, imm) in enumerate(self.table):
            nm = _OP_NAMES.get(op, f"op{op}").lower()[3:]
            if op == OP_CONST:
                arg = str(imm)
            elif op in _LOAD_NAMES:
                arg = ""
            elif op in _UNARY:
                arg = f"v{a}"
            elif op == OP_SELECT:
                arg = f"v{a} ? v{b} : v{imm}"
            else:
                arg = f"v{a}, v{b}"
            out = []
            if i == self.score_reg:
                out.append("score")
            if i == self.boost_reg:
                out.append("boost")
            if i == self.mitigate_reg:
                out.append("mitigate")
            tag = ("   -> " + "+".join(out)) if out else ""
            arg = f" {arg}" if arg else ""
            lines.append(f"  v{i} = {nm}{arg}{tag}")
        return "\n".join(lines)


class PolicyBuilder:
    """Author a :class:`PolicyProgram` op by op (SSA; each method
    returns a :class:`Reg` naming its result). See the module docstring
    for a complete example."""

    def __init__(self) -> None:
        self._rows: list = []

    def _emit(self, op: int, a: int = 0, b: int = 0, imm: int = 0) -> Reg:
        self._rows.append((op, a, b, imm))
        return Reg(len(self._rows) - 1, id(self))

    def _r(self, reg: Reg) -> int:
        if not isinstance(reg, Reg) or reg.owner != id(self):
            raise ValueError(f"{reg!r} is not a register of this builder")
        return reg.idx

    # ---- environment loads (the semantic ops of the issue) ----
    def score_age(self) -> Reg:
        """Arrival time in proc cycles: ``argmin`` over it = FCFS."""
        return self._emit(OP_AGE)

    def age_rel(self) -> Reg:
        """Age relative to the oldest visible request (small values —
        safe to combine with multiplied terms without int32 overflow)."""
        return self._emit(OP_AGE_REL)

    def score_row_hit(self) -> Reg:
        """1 where the request hits its bank's open row, else 0."""
        return self._emit(OP_ROW_HIT)

    def bank(self) -> Reg:
        return self._emit(OP_BANK)

    def row(self) -> Reg:
        return self._emit(OP_ROW)

    def is_write(self) -> Reg:
        return self._emit(OP_IS_WRITE)

    def mask_bank_busy(self) -> Reg:
        """1 where the request's bank is still busy at the DRAM
        frontier (its ready tick lies in the future), else 0."""
        return self._emit(OP_BANK_BUSY)

    def rr_distance(self) -> Reg:
        """Cyclic distance from the last served bank: 0 = the next bank
        round-robin order would pick, n_banks-1 = the bank just served."""
        return self._emit(OP_RR_DIST)

    def qslot(self) -> Reg:
        return self._emit(OP_QSLOT)

    def write_pressure(self) -> Reg:
        """Number of visible writes, broadcast to every slot."""
        return self._emit(OP_WRITE_PRESSURE)

    def hammer_count(self) -> Reg:
        """The request bank's aggressor ACT counter (see
        repro.core.faults). All-zero when no FaultModel is attached, so
        counter-based TRR degrades to a no-op on a perfect memory."""
        return self._emit(OP_HAMMER_CT)

    def para_rand(self) -> Reg:
        """Per-slot uniform draw in [0, 65536), deterministically keyed
        on (fault seed, bank, row, decision time) — compare against a
        16-bit fixed-point constant for a PARA coin flip."""
        return self._emit(OP_PARA_RAND)

    def prefer_writes_drain(self, threshold: int = 2) -> Reg:
        """Write-drain mask: 1 on write requests while at least
        ``threshold`` writes are visible (batch writes to amortize bus
        turnarounds), else 0. A macro over 4 IR instructions."""
        wp = self.write_pressure()
        thr = self.const(threshold)
        drain = self.ge(wp, thr)
        return self.and_(self.is_write(), drain)

    # ---- ALU ----
    def const(self, value: int) -> Reg:
        return self._emit(OP_CONST, imm=int(value))

    def add(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_ADD, self._r(a), self._r(b))

    def sub(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_SUB, self._r(a), self._r(b))

    def mul(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_MUL, self._r(a), self._r(b))

    def min_(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_MIN, self._r(a), self._r(b))

    def max_(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_MAX, self._r(a), self._r(b))

    def and_(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_AND, self._r(a), self._r(b))

    def or_(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_OR, self._r(a), self._r(b))

    def not_(self, a: Reg) -> Reg:
        return self._emit(OP_NOT, self._r(a))

    def eq(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_EQ, self._r(a), self._r(b))

    def lt(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_LT, self._r(a), self._r(b))

    def ge(self, a: Reg, b: Reg) -> Reg:
        return self._emit(OP_GE, self._r(a), self._r(b))

    def select(self, cond: Reg, a: Reg, b: Reg) -> Reg:
        """``cond != 0 ? a : b`` elementwise."""
        return self._emit(OP_SELECT, self._r(cond), self._r(a),
                          imm=self._r(b))

    def build(self, score: Reg, boost: Optional[Reg] = None,
              mitigate: Optional[Reg] = None,
              name: str = "policy", base_cycles: int = 300,
              cycles_per_op: int = 25,
              smc_cycles: Optional[int] = None) -> PolicyProgram:
        """Assemble. ``score`` is minimized among visible requests;
        ``boost`` (optional 0/1 mask) marks a preferred class served
        first whenever any member is visible; ``mitigate`` (optional 0/1
        mask) triggers a neighbor refresh when the served slot has it
        set. ``smc_cycles`` pins the decision cost instead of deriving
        it from program length."""
        return PolicyProgram(
            table=tuple(self._rows), score_reg=self._r(score),
            boost_reg=-1 if boost is None else self._r(boost),
            mitigate_reg=-1 if mitigate is None else self._r(mitigate),
            base_cycles=base_cycles, cycles_per_op=cycles_per_op,
            smc_cycles_override=smc_cycles, name=name).validate()


# ---------------------------------------------------------------------------
# Evaluator: staged inside the emulator's scan slot body. ``env`` maps
# load names to zero-arg thunks returning [Q] int32 vectors; thunks are
# evaluated at most once, and only for the loads the program references.
# ---------------------------------------------------------------------------


def evaluate(prog: PolicyProgram, env: Dict):
    """Run ``prog`` over the scheduling environment. Returns
    ``(score, boost, mitigate)`` — [Q] int32 vectors (boost is all-zero
    when the program declared no boost register; mitigate is None when
    no mitigate register, so legacy programs stage zero extra ops)."""
    cache: Dict[str, object] = {}

    def load(nm):
        if nm not in cache:
            cache[nm] = jnp.asarray(env[nm]()).astype(jnp.int32)
        return cache[nm]

    vals = []
    for op, a, b, imm in prog.table:
        if op == OP_CONST:
            v = jnp.full_like(load("qslot"), jnp.int32(imm))
        elif op in _LOAD_NAMES:
            v = load(_LOAD_NAMES[op])
        elif op == OP_ADD:
            v = vals[a] + vals[b]
        elif op == OP_SUB:
            v = vals[a] - vals[b]
        elif op == OP_MUL:
            v = vals[a] * vals[b]
        elif op == OP_MIN:
            v = jnp.minimum(vals[a], vals[b])
        elif op == OP_MAX:
            v = jnp.maximum(vals[a], vals[b])
        elif op == OP_AND:
            v = vals[a] & vals[b]
        elif op == OP_OR:
            v = vals[a] | vals[b]
        elif op == OP_NOT:
            v = (vals[a] == 0).astype(jnp.int32)
        elif op == OP_EQ:
            v = (vals[a] == vals[b]).astype(jnp.int32)
        elif op == OP_LT:
            v = (vals[a] < vals[b]).astype(jnp.int32)
        elif op == OP_GE:
            v = (vals[a] >= vals[b]).astype(jnp.int32)
        elif op == OP_SELECT:
            v = jnp.where(vals[a] != 0, vals[b], vals[imm])
        else:  # pragma: no cover - validate() rejects these
            raise ValueError(f"unknown opcode {op}")
        vals.append(v.astype(jnp.int32))
    score = vals[prog.score_reg]
    boost = (vals[prog.boost_reg] if prog.boost_reg >= 0
             else jnp.zeros_like(score))
    mit = vals[prog.mitigate_reg] if prog.mitigate_reg >= 0 else None
    return score, boost, mit


def select_slot(prog: PolicyProgram, env: Dict, visible):
    """Pick the queue slot to serve: two-level argmin over the program's
    score — boosted visible requests first (when any), else all visible.
    Identical selection structure to the legacy hard-coded scheduler,
    which is what makes :func:`frfcfs_program` / :func:`fcfs_program`
    bit-identical to the ``sys.scheduler`` string path. Scores are
    clamped to ``BIG - 1`` so a user program can never out-score the
    invisible-slot sentinel and redirect the argmin to a garbage slot.

    Returns ``(qslot, mitigate)``: the selected slot, and the selected
    slot's mitigate flag (scalar bool) or None for legacy programs —
    None keeps the staged trace byte-identical to pre-fault builds."""
    score, boost, mit = evaluate(prog, env)
    score = jnp.minimum(score, BIG - 1)
    key_all = jnp.where(visible, score, BIG)
    boost_on = visible & (boost != 0)
    key_boost = jnp.where(boost_on, score, BIG)
    slot_boost = jnp.argmin(key_boost).astype(jnp.int32)
    slot_all = jnp.argmin(key_all).astype(jnp.int32)
    qslot = jnp.where(jnp.any(boost_on), slot_boost, slot_all)
    return qslot, (None if mit is None else mit[qslot] != 0)


# ---------------------------------------------------------------------------
# Runtime-operand path (PR 10): pack a program into a dense int32 array
# and interpret it with a table-driven VM. Only the PADDED LENGTH of the
# table (its bucket) is a traced-shape property; the content is a plain
# runtime operand, so one compiled emulator evaluates any program of a
# bucket — and a vmap over stacked tables evaluates a whole policy grid.
# ---------------------------------------------------------------------------

# Smallest bucket: all built-ins fit in 8 rows, and a floor keeps the
# number of distinct buckets (== distinct compile keys) tiny.
TABLE_BUCKET_FLOOR = 8

# Environment loads in opcode order — row `op - OP_AGE` of the stacked
# env matrix. Contiguity of OP_AGE..OP_PARA_RAND is load-bearing here.
_ENV_ORDER = tuple(_LOAD_NAMES[op] for op in range(OP_AGE, OP_PARA_RAND + 1))
N_LOADS = len(_ENV_ORDER)


def table_bucket(n_ops: int) -> int:
    """Padded table length for an ``n_ops``-row program: the next power
    of two, floored at :data:`TABLE_BUCKET_FLOOR`. The bucket — never
    the content — rides the compile key."""
    if n_ops < 1:
        raise ValueError(f"n_ops must be >= 1, got {n_ops}")
    b = TABLE_BUCKET_FLOOR
    while b < n_ops:
        b *= 2
    return b


def pack_program(prog: PolicyProgram,
                 bucket: Optional[int] = None) -> np.ndarray:
    """Pack a validated program into the runtime-operand layout: an
    int32 ``[bucket + 1, 4]`` array whose row 0 is the header
    ``(n_ops, score_reg, boost_reg, mitigate_reg)`` and rows 1.. are the
    instruction table padded with ``(OP_CONST, 0, 0, 0)`` no-ops (they
    execute — producing zeros no live row references — so the VM needs
    no length gate)."""
    prog.validate()
    lb = table_bucket(prog.n_ops) if bucket is None else int(bucket)
    if prog.n_ops > lb:
        raise ValueError(
            f"program {prog.name!r} has {prog.n_ops} ops; bucket {lb} "
            f"is too small (needs {table_bucket(prog.n_ops)})")
    out = np.zeros((lb + 1, 4), np.int32)
    out[0] = (prog.n_ops, prog.score_reg, prog.boost_reg,
              prog.mitigate_reg)
    for i, row in enumerate(prog.table):
        out[i + 1] = row
    return out


def eval_table_rows(rows, envm):
    """The table-driven VM core: interpret ``rows`` ([L, 4] int32
    instructions) over ``envm`` ([N_LOADS, Q] int32 stacked environment)
    and return all SSA values as [L, Q] int32. Branchless — every row
    computes every opcode's candidate and selects by opcode — so it
    traces to a fixed dataflow program regardless of table content.
    Candidate arithmetic matches :func:`evaluate` op for op (int32
    wraparound included), which is what makes the runtime path
    bit-identical to the staged path. :func:`evaluate_table` is its
    one caller (single source of semantics)."""
    L = rows.shape[0]
    q = envm.shape[1]

    def body(i, vals):
        op = rows[i, 0]
        a = jnp.clip(rows[i, 1], 0, L - 1)
        b = jnp.clip(rows[i, 2], 0, L - 1)
        imm = rows[i, 3]
        va = vals[a]
        vb = vals[b]
        vc = vals[jnp.clip(imm, 0, L - 1)]
        # OP_CONST is the default arm (also the padding no-op).
        v = jnp.zeros((q,), jnp.int32) + imm
        is_load = (op >= OP_AGE) & (op <= OP_PARA_RAND)
        v = jnp.where(is_load,
                      envm[jnp.clip(op - OP_AGE, 0, N_LOADS - 1)], v)
        for code, cand in (
                (OP_ADD, va + vb),
                (OP_SUB, va - vb),
                (OP_MUL, va * vb),
                (OP_MIN, jnp.minimum(va, vb)),
                (OP_MAX, jnp.maximum(va, vb)),
                (OP_AND, va & vb),
                (OP_OR, va | vb),
                (OP_NOT, (va == 0).astype(jnp.int32)),
                (OP_EQ, (va == vb).astype(jnp.int32)),
                (OP_LT, (va < vb).astype(jnp.int32)),
                (OP_GE, (va >= vb).astype(jnp.int32)),
                (OP_SELECT, jnp.where(va != 0, vb, vc)),
        ):
            v = jnp.where(op == code, cand, v)
        return vals.at[i].set(v.astype(jnp.int32))

    return jax.lax.fori_loop(0, L, body, jnp.zeros((L, q), jnp.int32))


def evaluate_table(table, env: Dict):
    """Runtime-operand counterpart of :func:`evaluate`: run a packed
    ``[L + 1, 4]`` table (header + rows, :func:`pack_program` layout)
    over the scheduling environment. Returns ``(score, boost, mitigate)``
    [Q] int32 vectors; unlike the staged path, mitigate is always a
    vector (all-zero when the program declared none) — the table content
    is not known at trace time, and an always-False mitigate flag is
    numerically identical to None in ``faults.apply_slot``. Evaluates
    every environment thunk (the stacked env matrix is shared across the
    whole policy axis, so the cost amortizes)."""
    table = jnp.asarray(table, jnp.int32)
    hdr = table[0]
    rows = table[1:]
    lb = rows.shape[0]
    envm = jnp.stack([jnp.asarray(env[nm]()).astype(jnp.int32)
                      for nm in _ENV_ORDER])
    vals = eval_table_rows(rows, envm)
    score = vals[jnp.clip(hdr[1], 0, lb - 1)]
    zero = jnp.zeros_like(score)
    boost = jnp.where(hdr[2] >= 0, vals[jnp.clip(hdr[2], 0, lb - 1)], zero)
    mit = jnp.where(hdr[3] >= 0, vals[jnp.clip(hdr[3], 0, lb - 1)], zero)
    return score, boost, mit


def select_slot_table(table, env: Dict, visible):
    """Runtime-operand counterpart of :func:`select_slot`: identical
    two-level argmin (clamp, boosted-first, else all-visible). Returns
    ``(qslot, mitigate_flag)`` where the flag is a traced scalar bool —
    always present, always False for programs without a mitigate
    register (bit-identical to the staged path's None, see
    ``faults.apply_slot``)."""
    score, boost, mit = evaluate_table(table, env)
    score = jnp.minimum(score, BIG - 1)
    key_all = jnp.where(visible, score, BIG)
    boost_on = visible & (boost != 0)
    key_boost = jnp.where(boost_on, score, BIG)
    slot_boost = jnp.argmin(key_boost).astype(jnp.int32)
    slot_all = jnp.argmin(key_all).astype(jnp.int32)
    qslot = jnp.where(jnp.any(boost_on), slot_boost, slot_all)
    return qslot, mit[qslot] != 0


# ---------------------------------------------------------------------------
# Built-in programs.
# ---------------------------------------------------------------------------


def frfcfs_program() -> PolicyProgram:
    """FR-FCFS: oldest-first, row hits first. Bit-identical to the
    legacy ``scheduler='frfcfs'`` flag (tests/test_smcprog.py)."""
    b = PolicyBuilder()
    return b.build(score=b.score_age(), boost=b.score_row_hit(),
                   name="frfcfs")


def fcfs_program() -> PolicyProgram:
    """FCFS: strictly oldest-first. Bit-identical to the legacy
    ``scheduler='fcfs'`` flag."""
    b = PolicyBuilder()
    return b.build(score=b.score_age(), name="fcfs")


def bank_round_robin_program() -> PolicyProgram:
    """Cycle banks after the last served bank; age (relative, so the
    scaled term can't overflow int32) breaks ties within a bank."""
    b = PolicyBuilder()
    rr = b.rr_distance()
    age = b.min_(b.age_rel(), b.const((1 << 20) - 1))
    score = b.add(b.mul(rr, b.const(1 << 20)), age)
    return b.build(score=score, name="bank-rr")


def open_page_program() -> PolicyProgram:
    """Open-page: like FR-FCFS but only boosts hits on banks that are
    already idle — a hit on a busy bank waits its turn by age."""
    b = PolicyBuilder()
    hit_idle = b.and_(b.score_row_hit(), b.not_(b.mask_bank_busy()))
    return b.build(score=b.score_age(), boost=hit_idle, name="open-page")


def closed_page_program() -> PolicyProgram:
    """Closed-page: no row-hit preference — drain conflicts early by
    boosting row misses. (The bank state machine still keeps rows open;
    this isolates the *scheduling* component of a closed-page MC.)"""
    b = PolicyBuilder()
    return b.build(score=b.score_age(), boost=b.not_(b.score_row_hit()),
                   name="closed-page")


def write_drain_program(threshold: int = 2) -> PolicyProgram:
    """Age-ordered with write-drain mode: once ``threshold`` writes are
    visible, writes are served first until the backlog drops."""
    b = PolicyBuilder()
    return b.build(score=b.score_age(),
                   boost=b.prefer_writes_drain(threshold),
                   name=f"write-drain{threshold}")


def builtin_programs() -> Dict[str, PolicyProgram]:
    """All built-ins keyed by name — the default policy-sweep grid."""
    progs = [frfcfs_program(), fcfs_program(), bank_round_robin_program(),
             open_page_program(), closed_page_program(),
             write_drain_program()]
    return {p.name: p for p in progs}


# ---------------------------------------------------------------------------
# RowHammer mitigation policies: FR-FCFS scheduling plus a mitigate
# output. Kept OUT of builtin_programs() — the default policy-sweep
# grid (and its tests) is mitigation-free; sweeps come in through
# techniques.RowHammerMitigationStudy / mitigation_programs().
# ---------------------------------------------------------------------------


def para_program(p_fp: int = 655) -> PolicyProgram:
    """PARA: on every row activation (a served row *miss*), refresh the
    neighbors with probability ``p_fp``/65536 (default ~1%). Stateless —
    no counters — which is PARA's selling point; the cost is paying the
    refresh tax on well-behaved traffic too."""
    if not 0 <= p_fp <= 65536:
        raise ValueError(f"p_fp is 16-bit fixed point, got {p_fp}")
    b = PolicyBuilder()
    hit = b.score_row_hit()
    coin = b.lt(b.para_rand(), b.const(p_fp))
    return b.build(score=b.score_age(), boost=hit,
                   mitigate=b.and_(coin, b.not_(hit)),
                   name=f"para{p_fp}")


def trr_program(trr_threshold: int = 512) -> PolicyProgram:
    """Counter-based TRR: refresh the neighbors when the request bank's
    aggressor ACT counter reaches ``trr_threshold``. Deterministic and
    cheap when traffic is benign; choose the threshold below the chip's
    hammer threshold or the mitigation fires too late."""
    if trr_threshold < 1:
        raise ValueError(f"trr_threshold must be >= 1, got {trr_threshold}")
    b = PolicyBuilder()
    return b.build(score=b.score_age(), boost=b.score_row_hit(),
                   mitigate=b.ge(b.hammer_count(), b.const(trr_threshold)),
                   name=f"trr{trr_threshold}")


def mitigation_programs(para_fp: int = 655,
                        trr_threshold: int = 512) -> Dict[str, PolicyProgram]:
    """The RowHammer-mitigation sweep arms, keyed by name: unmitigated
    FR-FCFS baseline + PARA + counter-based TRR."""
    progs = [frfcfs_program(), para_program(para_fp),
             trr_program(trr_threshold)]
    return {p.name: p for p in progs}
