"""Plain reference of the emulator's semantics, for deciding `correct`.

One request trace at a time, in Python integers, slot by slot: the
in-order issue front end (up to four issues per slot, at most
``window`` outstanding, ``dep`` look-backs), the memory controller's
pick among the requests it can see (FR-FCFS), DDR4 bank timing with all-bank refresh
catch-up, the reduced-tRCD access of rows the weak-row Bloom filter
clears, and the time-scaled response tags. It is written from the
configuration's stated semantics and imports nothing of the program:
the weak-row map and the Bloom filter are rebuilt here from the
configuration's seeds.

``guarantee_broken`` names one guarantee of the configuration to drop,
for the control that has to come out as not correct:
``"weak_rows_nominal"`` gives weak rows the reduced tRCD as well.
"""
from __future__ import annotations

import numpy as np

BIG = 1 << 30
FP = 4096
READ, WRITE = 0, 1
M32 = 0xFFFFFFFF


# ---------------------------------------------------------------- system

class System:
    """The numbers one configuration file states, resolved to the
    integer constants the emulation uses (mode ``ts``)."""

    def __init__(self, cfg: dict):
        s, t, g = cfg["system"], cfg["timing"], cfg["geometry"]
        self.window = int(s["window"])
        self.Q = max(self.window, 2)
        self.t = {k: int(v) for k, v in t.items() if k != "tck_ns"}
        self.n_banks, self.n_rows = int(g["n_banks"]), int(g["n_rows"])
        ghz = float(s["f_proc_emu_ghz"])
        self.scale = int(round(ghz * float(t["tck_ns"]) * FP))
        self.mc_lat = int(round(float(s["hwmc_latency_ns"]) * ghz))
        self.mc_issue = max(int(round(float(s["hwmc_issue_ns"]) * ghz)), 1)
        self.counter_inc = int(s["smc_cycles_per_decision"]) + \
            int(s["smc_transfer_cycles"])


# ------------------------------------------------- weak rows, Bloom filter

def weak_rows(cfg: dict) -> np.ndarray:
    """Global ids (bank * n_rows + row) of the weak rows of the seeded
    device model the configuration names: a per-row score of bank
    effect + smoothed region effect + noise, with the top
    ``weak_target`` share weak."""
    g, d = cfg["geometry"], cfg["device_model"]
    nb, nr, region = int(g["n_banks"]), int(g["n_rows"]), int(g["subarray_rows"])
    rng = np.random.RandomState(int(d["seed"]))
    bank_eff = rng.normal(0.0, 0.6, size=(nb, 1))
    reg = rng.normal(0.0, 1.0, size=(nb, nr // region))
    kern = np.array([0.25, 0.5, 1.0, 0.5, 0.25])
    reg = np.stack([np.convolve(v, kern, mode="same") for v in reg])
    score = bank_eff + np.repeat(reg, region, axis=1) + \
        rng.normal(0.0, 0.35, size=(nb, nr))
    weak = score > np.quantile(score, 1.0 - float(d["weak_target"]))
    b, r = np.nonzero(weak)
    return b.astype(np.int64) * nr + r


_MULS = (0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F, 0x165667B1,
         0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2D)


def _mix(x, mul: int):
    x = x ^ (x >> 16)
    x = (x * mul) & M32
    x = x ^ (x >> 13)
    x = (x * 0x2B2AE3D5) & M32
    return x ^ (x >> 16)


class Bloom:
    """The weak-row Bloom filter: ``k`` multiplicative hashes into
    ``m_bits`` bits. A row that probes positive is served at nominal
    tRCD; only rows that probe negative get the reduced tRCD."""

    def __init__(self, keys, m_bits: int, k: int):
        self.m, self.k = int(m_bits), int(k)
        bits = np.zeros(self.m, np.uint8)
        keys = np.asarray(keys).astype(np.uint64) & np.uint64(M32)
        for i in range(self.k):     # _mix on a whole array of keys at once
            bits[_mix(keys, _MULS[i]) & np.uint64(self.m - 1)] = 1
        self.bits = bytearray(bits)
        self.memo = {}

    def __contains__(self, key: int) -> bool:
        hit = self.memo.get(key)
        if hit is None:
            hit = all(self.bits[_mix(key & M32, _MULS[i]) & (self.m - 1)]
                      for i in range(self.k))
            self.memo[key] = hit
        return hit


def _argmin(keys) -> int:
    best, at = keys[0], 0
    for i in range(1, len(keys)):
        if keys[i] < best:
            best, at = keys[i], i
    return at


# ------------------------------------------------------------- emulation

def emulate(tr: dict, sysr: System, bloom: Bloom = None,
            guarantee_broken: str = None) -> dict:
    """Emulate one trace (dict of kind/bank/row/delta/dep arrays of
    READ and WRITE requests) to completion under FR-FCFS. Returns the
    simulated statistics: exec_cycles, row_hits, served, dram_ticks,
    smc_fpga_cycles and the per-request t_issue / t_resp."""
    kind = np.asarray(tr["kind"]).tolist()
    if not set(kind) <= {READ, WRITE}:
        raise ValueError("the reference emulates READ and WRITE requests only")
    bank = np.asarray(tr["bank"]).tolist()
    row = np.asarray(tr["row"]).tolist()
    delta = np.asarray(tr["delta"]).tolist()
    dep = np.asarray(tr["dep"]).tolist()
    N = len(kind)
    W, Q, nr = sysr.window, sysr.Q, sysr.n_rows
    t = sysr.t
    tRCD, tRCD_red, tCL, tRP, tRAS = t["tRCD"], t["tRCD_reduced"], \
        t["tCL"], t["tRP"], t["tRAS"]
    tWR, tBL, tRFC, tREFI = t["tWR"], t["tBL"], t["tRFC"], t["tREFI"]
    nominal_for_weak = guarantee_broken != "weak_rows_nominal"
    scale, mc_lat, mc_issue = sysr.scale, sysr.mc_lat, sysr.mc_issue

    open_row = [-1] * sysr.n_banks
    ready = [0] * sysr.n_banks
    act_at = [0] * sysr.n_banks
    bus_busy = refs_done = 0
    t_issue = [0] * N
    t_resp = [BIG] * N
    queue = [-1] * Q
    ptr = mc_release = dram_now = hits = served = 0
    budget = 4 * N + 16
    slots = 0
    while ptr < N or any(x >= 0 for x in queue):
        slots += 1
        if slots > budget:
            raise RuntimeError("reference emulation did not finish")
        # ---- issue front end: up to four in-order issues per slot
        for _ in range(4):
            j = ptr
            if j >= N:
                break
            wj = j - W
            if wj >= 0 and t_resp[wj] >= BIG:
                break
            d = dep[j]
            dj = j - d
            if d > 0 and dj >= 0 and t_resp[dj] >= BIG:
                break
            if -1 not in queue:
                break
            t_new = (t_issue[j - 1] if j > 0 else 0) + delta[j]
            if wj >= 0:
                t_new = max(t_new, t_resp[wj] + 1)
            if d > 0 and dj >= 0:
                t_new = max(t_new, t_resp[dj] + 1)
            t_issue[j] = t_new
            queue[queue.index(-1)] = j
            ptr += 1
        # ---- the controller's view of its queue
        idx = [x if x >= 0 else 0 for x in queue]
        q_t = [t_issue[i] if x >= 0 else BIG for i, x in zip(idx, queue)]
        vis = [x >= 0 and qt <= mc_release for x, qt in zip(queue, q_t)]
        if not any(vis):
            if any(x >= 0 for x in queue):   # idle hop to the next arrival
                mc_release = max(mc_release, min(min(q_t), BIG - 1))
            continue
        q_bank = [bank[i] for i in idx]
        q_row = [row[i] for i in idx]
        hit_now = [open_row[b] == r for b, r in zip(q_bank, q_row)]
        keys = [qt if v and h else BIG for qt, v, h in zip(q_t, vis, hit_now)]
        if any(v and h for v, h in zip(vis, hit_now)):
            qs = _argmin(keys)            # the oldest visible row hit
        else:
            qs = _argmin([qt if v else BIG for qt, v in zip(q_t, vis)])
        pick = idx[qs]
        # ---- serve it on the bank
        decision_t = max(t_issue[pick], mc_release)
        now = max(dram_now, decision_t * FP // max(scale, 1))
        k, b, r = kind[pick], bank[pick], row[pick]
        trcd = tRCD
        if bloom is not None:
            weak = (b * nr + r) in bloom
            trcd = tRCD if (weak and nominal_for_weak) else tRCD_red
        refs_due = max(now // tREFI - refs_done, 0)
        start = max(now, ready[b]) + refs_due * tRFC
        hit = open_row[b] == r
        if open_row[b] < 0:
            act_start = start
        else:
            act_start = max(start, act_at[b] + tRAS) + tRP
        col_start = start if hit else act_start + trcd
        t_done = bus_busy = max(col_start + tCL, bus_busy) + tBL
        bank_next = t_done + tWR if k == WRITE else t_done
        if not hit:
            act_at[b] = act_start
        open_row[b] = r
        ready[b] = bank_next
        refs_done += refs_due
        resp = max(t_done * scale // FP + mc_lat, decision_t + mc_issue)
        t_resp[pick] = resp
        queue[qs] = -1
        mc_release = max(mc_release, decision_t + mc_issue)
        dram_now = max(dram_now, now)
        hits += hit
        served += 1
    last = max(t_resp + t_issue + [0])
    return {"exec_cycles": last, "row_hits": hits, "served": served,
            "dram_ticks": dram_now,
            "smc_fpga_cycles": served * sysr.counter_inc,
            "t_issue": np.asarray(t_issue, np.int64),
            "t_resp": np.asarray(t_resp, np.int64)}
