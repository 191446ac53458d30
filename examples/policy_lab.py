"""Policy lab: author a DRAM scheduling policy in ~20 lines, cost it,
sweep it against the built-ins, fan 256 candidate policies through ONE
compiled dispatch, and autotune a schedule that beats FR-FCFS — end to
end through the batched Campaign machinery.

EasyDRAM's first key idea is that scheduling policies are *software* on
a programmable memory controller. Here that is literal: a policy is a
:class:`repro.core.smcprog.PolicyProgram` — a dense int32 instruction
table a branchless VM interprets inside the emulator's scan — and its
SMC decision cost is derived from its length. Since PR 10 the table is
also a *runtime operand*: programs sharing a table-length bucket share
one compiled executable, and a vmapped policy axis evaluates a whole
candidate population per device dispatch — which is what makes the
closing autotuning demo (``core.policysearch``) affordable.

  PYTHONPATH=src python examples/policy_lab.py
"""
import numpy as np

from repro.core import emulator, smcprog
from repro.core.campaign import Campaign
from repro.core.emulator import Trace
from repro.core.policysearch import random_program, search
from repro.core.smcprog import PolicyBuilder
from repro.core.timescale import JETSON_NANO


def make_trace(n=2400, seed=7):
    """Bursty multi-bank traffic: 8-deep request bursts, 60% to one hot
    row — enough visible requests per decision that policy choice
    matters."""
    rng = np.random.RandomState(seed)
    delta = np.where(np.arange(n) % 8 == 0, 400, 0)
    row = np.where(rng.rand(n) < 0.6, 7, rng.randint(0, 4096, n))
    return Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, 4, n),
                    row=row, delta=delta)


def custom_policy():
    """A custom policy in ~20 lines: serve oldest first, prefer row
    hits on idle banks, and drain writes in batches of three — the kind
    of policy that needs RTL surgery on a hardware MC and is a page of
    Python here."""
    b = PolicyBuilder()
    age = b.score_age()
    hit = b.score_row_hit()
    busy = b.mask_bank_busy()
    drain = b.prefer_writes_drain(threshold=3)
    # boost class: row hits on idle banks, or writes during drain mode
    boost = b.or_(b.and_(hit, b.not_(busy)), drain)
    # penalize touching a busy bank by 32 cycles of effective age
    score = b.add(age, b.mul(busy, b.const(32)))
    return b.build(score=score, boost=boost, name="lab-custom")


def costed_sweep(tr):
    prog = custom_policy()
    print("=== custom policy, costed ===")
    print(prog.describe())

    grid = list(smcprog.builtin_programs().values()) + [prog]
    c = Campaign()
    for mode in ("ts", "nots"):
        # each program's SMC decision cost derives from its length —
        # the slowness ts hides. lab-custom (14 ops) packs to table
        # bucket 16 while the built-ins share bucket 8, and the policy
        # axis refuses to mix buckets silently — so this heterogeneous
        # grid takes the staged per-program path explicitly
        c.add_policy_grid(tr, JETSON_NANO, grid, mode=mode,
                          mode_label=mode, policy_axis=False)
    print(f"\n{len(c)} points in {c.n_groups()} compile groups "
          f"(one batched dispatch each)")
    recs = {(r["mode_label"], r["policy"]): r for r in c.run()}

    print(f"\n{'policy':>12s} {'smc_cyc':>8s} {'ts_cycles':>10s} "
          f"{'nots_cycles':>12s} {'row_hits':>8s}")
    for p in grid:
        ts, nots = recs[("ts", p.name)], recs[("nots", p.name)]
        print(f"{p.name:>12s} {p.smc_cycles():>8d} "
              f"{int(ts['exec_cycles']):>10d} "
              f"{int(nots['exec_cycles']):>12d} {int(ts['row_hits']):>8d}")
    print("\nts results ignore program length (time scaling hides SMC "
          "slowness);\nnots results grow with it — the ~20x modeling gap "
          "the paper quantifies.")


def policy_axis_sweep(tr, n_policies=256):
    """256 candidate policies through ONE executable: the runtime
    policy operand means table CONTENT is data, only the table-length
    bucket rides the compile key."""
    print(f"\n=== {n_policies}-policy sweep, one dispatch ===")
    rng = np.random.RandomState(0)
    progs = [random_program(rng, name=f"cand{i}")
             for i in range(n_policies - 1)]
    progs.append(smcprog.frfcfs_program())
    emulator.cache_clear()
    recs = emulator.run_policies(tr, JETSON_NANO, progs, mode="ts")
    stats = emulator.cache_stats()
    lat = [float(r["avg_load_latency_cycles"]) for r in recs]
    best = int(np.argmin(lat))
    print(f"{len(progs)} policies -> {stats['misses']} XLA compile(s); "
          f"best {progs[best].name} at {lat[best]:.2f} avg load-latency "
          f"cycles (frfcfs: {lat[-1]:.2f})")


def write_heavy_trace(n=360, seed=7):
    """Write-heavy traffic with hard bank conflicts (4 banks, small row
    space) — a workload where oldest-first row-hit scheduling is NOT
    optimal, so the search has real room over frfcfs."""
    rng = np.random.RandomState(seed)
    return Trace.of(kind=(rng.random_sample(n) < 0.6).astype(np.int32),
                    bank=rng.randint(0, 4, n), row=rng.randint(0, 64, n),
                    delta=rng.randint(1, 4, n),
                    dep=(rng.random_sample(n) < 0.3).astype(np.int32))


def autotune():
    """Evolutionary search over the op space; every generation scores
    its candidates with one vmapped dispatch."""
    print("\n=== autotune vs frfcfs (write-heavy workload) ===")
    res = search(write_heavy_trace(), JETSON_NANO,
                 generations=5, population=16, seed=0)
    print(res.summary())
    print(f"best-vs-baseline improvement: x{res.improvement:.4f}")
    print("\nwinning schedule:")
    print(res.best.describe())


def main():
    tr = make_trace()
    costed_sweep(tr)
    policy_axis_sweep(tr)
    autotune()


if __name__ == "__main__":
    main()
