"""The MC-policy VM (`repro.core.smcprog`): assembler validation,
content-addressed digests and cost model, bit-identity of the built-in
FR-FCFS/FCFS programs with the legacy `sys.scheduler` flag, policy
grids through Campaign, behavioral divergence of the built-ins, the
corrected idle-hop behavior, and the fast-scan late-call guard."""
import dataclasses

import numpy as np
import pytest

from repro.core import emulator, smcprog
from repro.core.campaign import Campaign
from repro.core.emulator import BIG, Trace, run, run_many
from repro.core.smcprog import PolicyBuilder, PolicyProgram
from repro.core.techniques import SchedulingPolicyStudy
from repro.core.timescale import JETSON_NANO


def grid_trace(n=45, seed=5):
    """All request kinds (incl. mid-trace NOPs and RowClone ops) and
    random deps — the TestSlotBudget grid workload."""
    rng = np.random.RandomState(seed)
    return Trace.of(kind=rng.randint(0, 5, n), bank=rng.randint(0, 16, n),
                    row=rng.randint(0, 4096, n),
                    delta=rng.randint(0, 24, n), dep=rng.randint(0, 3, n))


def bursty_trace(n=120, seed=3, n_banks=4):
    """8-deep request bursts: several requests visible per decision, so
    scheduling policy has real choices."""
    rng = np.random.RandomState(seed)
    delta = np.where(np.arange(n) % 8 == 0, 400, 0)
    row = np.where(rng.rand(n) < 0.6, 7, rng.randint(0, 4096, n))
    return Trace.of(kind=rng.randint(0, 2, n), bank=rng.randint(0, n_banks, n),
                    row=row, delta=delta)


class TestAssembler:
    def test_build_and_describe(self):
        b = PolicyBuilder()
        p = b.build(score=b.add(b.score_age(),
                                b.mul(b.mask_bank_busy(), b.const(64))),
                    boost=b.score_row_hit(), name="demo")
        assert p.n_ops == 6
        text = p.describe()
        assert "demo" in text and "score" in text and "boost" in text

    def test_foreign_register_rejected(self):
        b1, b2 = PolicyBuilder(), PolicyBuilder()
        r = b1.score_age()
        with pytest.raises(ValueError, match="not a register"):
            b2.build(score=r)

    def test_validate_rejects_bad_programs(self):
        with pytest.raises(ValueError, match="score_reg"):
            PolicyProgram(table=((smcprog.OP_AGE, 0, 0, 0),),
                          score_reg=3).validate()
        with pytest.raises(ValueError, match="unknown opcode"):
            PolicyProgram(table=((99, 0, 0, 0),), score_reg=0).validate()
        with pytest.raises(ValueError, match="earlier value"):
            # operand references itself (not an earlier SSA value)
            PolicyProgram(table=((smcprog.OP_ADD, 0, 0, 0),),
                          score_reg=0).validate()

    def test_content_addressed_equality(self):
        a = smcprog.frfcfs_program()
        b = dataclasses.replace(smcprog.frfcfs_program(), name="renamed")
        assert a == b and hash(a) == hash(b)   # name is display-only
        assert a.digest == b.digest
        # cost-model fields never enter the emulation: same group too
        c = dataclasses.replace(a, smc_cycles_override=999, base_cycles=1)
        assert a == c and hash(a) == hash(c)
        assert a != smcprog.fcfs_program()
        assert a.digest != smcprog.fcfs_program().digest

    def test_cost_model(self):
        p = smcprog.fcfs_program()
        assert p.smc_cycles() == p.base_cycles + p.cycles_per_op * p.n_ops
        pinned = dataclasses.replace(p, smc_cycles_override=777)
        assert pinned.smc_cycles() == 777
        sysc = JETSON_NANO.with_policy(p)
        assert sysc.policy == p
        assert sysc.smc_cycles_per_decision == p.smc_cycles()
        # attaching without with_policy keeps the config's cost
        kept = dataclasses.replace(JETSON_NANO, policy=p)
        assert kept.smc_cycles_per_decision == \
            JETSON_NANO.smc_cycles_per_decision


class TestBitIdentity:
    """Acceptance: built-in FR-FCFS and FCFS programs are bit-identical
    to the legacy `sys.scheduler` flag across the TestSlotBudget grid —
    responses, issue times, and SMC cycle counters included."""

    @pytest.mark.parametrize("mode,window,sched", [
        ("ts", 1, "frfcfs"), ("nots", 4, "frfcfs"),
        ("reference", 2, "fcfs"), ("ts", 4, "fcfs")])
    def test_program_matches_legacy_flag(self, mode, window, sched):
        tr = grid_trace()
        prog = (smcprog.frfcfs_program() if sched == "frfcfs"
                else smcprog.fcfs_program())
        sys_leg = dataclasses.replace(JETSON_NANO, window=window,
                                      scheduler=sched)
        sys_prog = dataclasses.replace(sys_leg, policy=prog)
        a = run(tr, sys_leg, mode)
        b = run(tr, sys_prog, mode)
        for k in ("exec_cycles", "row_hits", "served", "dram_ticks",
                  "smc_fpga_cycles"):
            assert int(a[k]) == int(b[k]), k
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], b["t_issue"])

    def test_run_equals_run_many_equals_run_ref(self):
        tr = grid_trace(seed=9)
        sys_prog = dataclasses.replace(JETSON_NANO,
                                       policy=smcprog.frfcfs_program())
        a = run(tr, sys_prog, "ts")
        b = run_many([tr, tr], sys_prog, "ts")[1]
        c = emulator.run_ref(tr, sys_prog, "ts")
        for k in ("exec_cycles", "row_hits", "served", "dram_ticks",
                  "smc_fpga_cycles"):
            assert int(a[k]) == int(b[k]) == int(c[k]), k
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_resp"], c["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], c["t_issue"])

    def test_ts_invariant_to_policy_cost(self):
        """Time scaling hides SMC slowness: deriving the decision cost
        from program length must not move ts results — and must move
        nots results (that is the modeling gap the policy axis opens)."""
        tr = grid_trace(seed=13)
        prog = smcprog.frfcfs_program()
        kept = dataclasses.replace(JETSON_NANO, policy=prog)
        derived = JETSON_NANO.with_policy(prog)
        assert derived.smc_cycles_per_decision != \
            kept.smc_cycles_per_decision
        assert int(run(tr, kept, "ts")["exec_cycles"]) \
            == int(run(tr, derived, "ts")["exec_cycles"])
        slow = JETSON_NANO.with_policy(
            dataclasses.replace(prog, smc_cycles_override=4000))
        assert int(run(tr, slow, "nots")["exec_cycles"]) \
            > int(run(tr, derived, "nots")["exec_cycles"])


class TestPolicyGrid:
    """Acceptance: a grid of >= 4 programs runs through Campaign in one
    batched dispatch per compile-key group (content-addressed)."""

    def test_grid_one_dispatch_per_program(self):
        """The staged-constant (legacy) path: policy_axis=False keeps
        one compile-key group — one compile, one dispatch — per
        program. The runtime-axis default's contract (one group per
        table-length bucket) is pinned in tests/test_policy_axis.py."""
        programs = list(smcprog.builtin_programs().values())
        assert len(programs) >= 4
        trs = [bursty_trace(seed=s) for s in (0, 1)]
        c = Campaign()
        for i, tr in enumerate(trs):
            c.add_policy_grid(tr, JETSON_NANO, programs, i=i,
                              policy_axis=False)
        assert c.n_groups() == len(programs)
        emulator.cache_clear()
        recs = c.run()
        stats = emulator.cache_stats()
        assert stats["misses"] == len(programs)
        assert stats["hits"] == 0
        assert len(recs) == len(programs) * len(trs)
        assert {r["policy"] for r in recs} == {p.name for p in programs}
        for r in recs:
            assert int(r["served"]) == trs[0].n

    def test_same_content_programs_share_group(self):
        fresh1, fresh2 = smcprog.fcfs_program(), dataclasses.replace(
            smcprog.fcfs_program(), name="fcfs-clone")
        tr = bursty_trace(seed=2)
        c = (Campaign()
             .add(tr, dataclasses.replace(JETSON_NANO, policy=fresh1))
             .add(tr, dataclasses.replace(JETSON_NANO, policy=fresh2)))
        assert c.n_groups() == 1
        r = c.run()
        assert int(r[0]["exec_cycles"]) == int(r[1]["exec_cycles"])

    def test_duplicate_names_rejected(self):
        """Grid records key on program names: two distinct programs
        under one (e.g. the default) name would silently collide."""
        b1, b2 = PolicyBuilder(), PolicyBuilder()
        progs = [b1.build(score=b1.score_age()),
                 b2.build(score=b2.score_row_hit())]
        # ValueError, not AssertionError: the guard must survive python -O
        with pytest.raises(ValueError, match="unique"):
            Campaign().add_policy_grid(bursty_trace(), JETSON_NANO, progs)
        with pytest.raises(ValueError, match="unique"):
            SchedulingPolicyStudy(JETSON_NANO, programs=progs)

    def test_policy_study(self):
        study = SchedulingPolicyStudy(
            dataclasses.replace(JETSON_NANO, window=8))
        out = study.evaluate_traces([bursty_trace()])
        assert len(out) == 1
        d = out[0]
        assert set(d) == set(smcprog.builtin_programs())
        assert d["frfcfs"]["speedup_vs_baseline"] == 1.0
        assert d["bank-rr"]["smc_cycles"] > d["fcfs"]["smc_cycles"]


class TestBuiltinBehaviors:
    """The built-ins must actually schedule differently on traffic with
    visible-queue choices (bursty, hot-row, multi-bank)."""

    def _run(self, prog, tr):
        # with_policy on the window-8 base: same compile keys as the
        # SchedulingPolicyStudy points, so these tests share executables
        sysc = dataclasses.replace(JETSON_NANO, window=8).with_policy(prog)
        return run(tr, sysc, "ts")

    def test_frfcfs_harvests_more_hits_than_fcfs(self):
        tr = bursty_trace()
        fr = self._run(smcprog.frfcfs_program(), tr)
        fc = self._run(smcprog.fcfs_program(), tr)
        assert int(fr["row_hits"]) > int(fc["row_hits"])
        assert int(fr["exec_cycles"]) <= int(fc["exec_cycles"])

    def test_closed_page_sheds_hits(self):
        tr = bursty_trace()
        fr = self._run(smcprog.frfcfs_program(), tr)
        cp = self._run(smcprog.closed_page_program(), tr)
        assert int(cp["row_hits"]) < int(fr["row_hits"])

    def test_all_builtins_complete(self):
        tr = bursty_trace(seed=11)
        for p in smcprog.builtin_programs().values():
            r = self._run(p, tr)
            assert int(r["served"]) == tr.n, p.name
            assert (np.asarray(r["t_resp"])[:tr.n] < int(BIG)).all(), p.name


class TestIdleHopFix:
    """Re-baselined mid-trace NOP behavior: the idle hop is skipped on
    an empty hardware queue, so a NOP run no longer saturates
    mc_release and poisons later responses."""

    def test_mid_trace_nops_fully_served(self):
        rng = np.random.RandomState(7)
        n = 60
        kind = rng.randint(0, 2, n)
        kind[10:18] = 4
        kind[30:33] = 4
        tr = Trace.of(kind=kind, bank=rng.randint(0, 16, n),
                      row=rng.randint(0, 4096, n),
                      delta=rng.randint(0, 6, n), dep=rng.randint(0, 2, n))
        real = kind != 4
        a = run(tr, JETSON_NANO, "ts")
        assert int(a["served"]) == int(real.sum())
        assert (np.asarray(a["t_resp"])[:n][real] < int(BIG)).all()
        # both engines carry the fix identically
        b = emulator.run_ref(tr, JETSON_NANO, "ts")
        np.testing.assert_array_equal(a["t_resp"], b["t_resp"])
        np.testing.assert_array_equal(a["t_issue"], b["t_issue"])


@pytest.mark.parametrize("module", ["repro.core.timescale",
                                    "repro.core.emulator",
                                    "repro.core.campaign"])
def test_import_leaves_backend_down(module):
    """Importing the config layer, the engine or the campaign driver
    creates no device value: the JAX backend stays uninitialized, so a
    process can still choose its backend and compile cache after the
    import (and a process that never computes never holds the chip)."""
    import os
    import subprocess
    import sys as _sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"))
    code = (f"import {module}\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, list(xla_bridge._backends)\n")
    proc = subprocess.run([_sys.executable, "-c", code], cwd=root,
                          capture_output=True, text=True, env=env)
    assert proc.returncode == 0, proc.stderr
