"""Plain reference of the RowClone study (EasyDRAM Sec. 7), for deciding
`correct` in the cells of configuration ``rowclone-sec7``.

Two parts, both written from the configuration's stated semantics and
importing nothing of the program:

* :func:`build` lays out one point's request trace: a copy or an
  initialization of ``n_bytes``, by the CPU (a load and a store, or a
  store, per 64 B line) or by RowClone (one in-DRAM op per 8 KiB row,
  each waiting for the previous one, with a CPU fallback for a row no
  profiled candidate can clone), after the ``clflush`` write-backs where
  the setting asks for them. Clonability is the device model's seeded
  per-(bank, source, destination) hash.
* :func:`emulate` replays one trace, slot by slot, in Python integers:
  the in-order issue front end (up to four issues per slot, at most
  ``window`` outstanding, ``dep`` look-backs), FR-FCFS among the
  requests the controller can see, DDR4 bank timing with all-bank
  refresh catch-up, the RowClone ACT-PRE-ACT sequence, and the response
  tags of mode ``ts`` (time scaling: the modeled processor clock and
  hardware controller) or ``nots`` (the FPGA processor free-running
  against the software controller's real latency).

Departures from the plain reading of the paper, each the program's
documented semantics, kept here so the comparison is exact:

* The controller's FR-FCFS pick counts a queued RowClone op whose row is
  open as a row hit (it looks at rows, not kinds); serving it then
  closes and reopens the row all the same (``rc_full_sequence``).
* Refresh is one all-bank catch-up, charged before the next request
  reaches any bank.
* The ``clflush`` write-backs of a copy go to the source rows of the
  RowClone layout in both arms.

``guarantee_broken`` names one guarantee of the configuration to drop,
for the control that has to come out as not correct:
``"rc_full_sequence"`` charges a RowClone op as a plain activation at
tRCD instead of the whole tRC_CLONE sequence.
"""
from __future__ import annotations

import numpy as np

BIG = 1 << 30
FP = 4096
READ, WRITE, RC_COPY, RC_INIT = 0, 1, 2, 3
M64 = (1 << 64) - 1


# ---------------------------------------------------------------- system

class System:
    """The numbers one system of the configuration states, resolved to
    the integer constants the emulation uses in its mode."""

    def __init__(self, cfg: dict, name: str):
        entry = cfg["systems"][name]
        s, t = entry["system"], cfg["timing"]
        self.mode = entry["mode"]
        self.window = int(s["window"])
        self.Q = max(self.window, 2)
        self.t = {k: int(v) for k, v in t.items() if k != "tck_ns"}
        self.n_banks = int(cfg["geometry"]["n_banks"])
        ghz, tck = float(s["f_proc_emu_ghz"]), float(t["tck_ns"])
        fpga_mhz = float(s["f_proc_fpga_mhz"])
        self.counter_inc = int(s["smc_cycles_per_decision"]) + \
            int(s["smc_transfer_cycles"])
        # the software controller's decision time, in cycles of the
        # free-running FPGA processor
        smc_ns = self.counter_inc / (float(s["f_mc_fpga_mhz"]) * 1e-3)
        smc_lat = int(round(smc_ns * fpga_mhz * 1e-3))
        if self.mode == "nots":
            self.scale = int(round(fpga_mhz * 1e-3 * tck * FP))
            self.mc_lat, self.mc_issue, self.slack = 0, smc_lat, smc_lat
        elif self.mode == "ts":
            self.scale = int(round(ghz * tck * FP))
            self.mc_lat = int(round(float(s["hwmc_latency_ns"]) * ghz))
            self.mc_issue = max(int(round(float(s["hwmc_issue_ns"]) * ghz)), 1)
            self.slack = 0
        else:
            raise ValueError(f"mode must be ts or nots, got {self.mode!r}")


# ------------------------------------------------------------ the traces

class Device:
    """Clonability of (bank, source row, destination row) pairs on the
    configuration's seeded device: within one subarray, and passing the
    seeded profiling hash at ``clone_fail_rate``."""

    def __init__(self, cfg: dict):
        d = cfg["device_model"]
        self.seed = int(d["seed"])
        self.fail = float(d["clone_fail_rate"])
        self.sa = int(cfg["geometry"]["subarray_rows"])

    def clonable(self, bank: int, src: int, dst: int) -> bool:
        if src == dst or src // self.sa != dst // self.sa:
            return False
        h = 0x9E3779B97F4A7C15
        x = (bank * 1000003) ^ (src * 8191) ^ (dst * 131071) ^ self.seed
        x = (x * h) & M64
        x ^= x >> 29
        x = (x * h) & M64
        x ^= x >> 32
        return x / float(2 ** 64) >= self.fail


def build(workload: str, arm: str, setting: str, n_bytes: int,
          cpu_line_delta: int, base: int, cfg: dict,
          device: Device) -> dict:
    """One point's trace: dict of kind/bank/row/delta/dep arrays.
    ``base`` is the allocation's base row."""
    g = cfg["geometry"]
    nb, nr, sa = int(g["n_banks"]), int(g["n_rows"]), int(g["subarray_rows"])
    row_b, line_b = int(g["row_bytes"]), int(g["line_bytes"])
    lines, rows = max(n_bytes // line_b, 1), max(n_bytes // row_b, 1)
    per_row = row_b // line_b
    out = []    # (kind, bank, row, delta, dep)

    def at(r):  # a global row-buffer index, interleaved over the banks
        return r % nb, r // nb % nr

    if workload == "copy":
        def src(i):  # the RowClone layout: source rows two apart per bank
            return i % nb, (base + 2 * (i // nb)) % nr
        if setting == "clflush":
            out += [(WRITE, *src(i * line_b // row_b), 2, 0)
                    for i in range(lines)]
        if arm == "cpu":
            for i in range(lines):
                ri = i * line_b // row_b
                out.append((READ, *at(base + ri), cpu_line_delta, 0))
                out.append((WRITE, *at(base + 2 * rows + nb // 2 + 1 + ri),
                            cpu_line_delta, 0))
            return _arrays(out)
        for i in range(rows):
            bank, s = src(i)
            lo = s // sa * sa
            dst = next((d for d in (lo + (s - lo + k) % sa for k in range(1, 9))
                        if device.clonable(bank, s, d)), s + 1)
            if device.clonable(bank, s, dst):
                out.append((RC_COPY, bank, dst, 12, 1))
            else:
                out += [(kd, bank, r, cpu_line_delta, 0)
                        for _ in range(per_row)
                        for kd, r in ((READ, s), (WRITE, dst))]
        return _arrays(out)

    if workload != "init":
        raise ValueError(f"workload must be copy or init, got {workload!r}")
    if setting == "clflush":
        out += [(WRITE, *at(base + i), 1, 0) for i in range(rows)]
    if arm == "cpu":
        out += [(WRITE, *at(base + i * line_b // row_b), cpu_line_delta, 0)
                for i in range(lines)]
        return _arrays(out)
    for i in range(rows):
        bank, d = at(base + i)
        lo = d // sa * sa   # one source row per subarray, four candidates
        if any(device.clonable(bank, lo + k, d) for k in range(4)):
            out.append((RC_INIT, bank, d, 12, 1))
        else:
            out += [(WRITE, bank, d, cpu_line_delta, 0)] * per_row
    return _arrays(out)


def _arrays(reqs) -> dict:
    a = np.asarray(reqs, np.int64).reshape(-1, 5).T.astype(np.int32)
    return dict(zip(("kind", "bank", "row", "delta", "dep"), a))


# ------------------------------------------------------------- emulation

def _argmin(keys) -> int:
    best, at = keys[0], 0
    for i in range(1, len(keys)):
        if keys[i] < best:
            best, at = keys[i], i
    return at


def emulate(tr: dict, sysr: System, guarantee_broken: str = None) -> dict:
    """Emulate one trace of READ, WRITE, RC_COPY and RC_INIT requests
    to completion under FR-FCFS. Returns the simulated statistics:
    exec_cycles, row_hits, served, dram_ticks, smc_fpga_cycles and the
    per-request t_issue / t_resp."""
    kind = np.asarray(tr["kind"]).tolist()
    if not set(kind) <= {READ, WRITE, RC_COPY, RC_INIT}:
        raise ValueError("the reference emulates READ, WRITE, RC_COPY and "
                         "RC_INIT requests only")
    bank = np.asarray(tr["bank"]).tolist()
    row = np.asarray(tr["row"]).tolist()
    delta = np.asarray(tr["delta"]).tolist()
    dep = np.asarray(tr["dep"]).tolist()
    N = len(kind)
    W, Q = sysr.window, sysr.Q
    t = sysr.t
    tRCD, tCL, tRP, tRAS = t["tRCD"], t["tCL"], t["tRP"], t["tRAS"]
    tWR, tBL, tRFC, tREFI = t["tWR"], t["tBL"], t["tRFC"], t["tREFI"]
    # the RowClone sequence: ACT(src)-PRE-ACT(dst) from the activation
    t_clone = tRCD if guarantee_broken == "rc_full_sequence" \
        else t["tRC_CLONE"]
    scale, mc_lat, mc_issue = sysr.scale, sysr.mc_lat, sysr.mc_issue
    slack = sysr.slack

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
        # ---- the controller's view of its queue: in mode nots a slow
        # software controller also sees what arrived while it decided
        idx = [x if x >= 0 else 0 for x in queue]
        q_t = [t_issue[i] if x >= 0 else BIG for i, x in zip(idx, queue)]
        vis = [x >= 0 and qt <= mc_release + slack
               for x, qt in zip(queue, q_t)]
        if not any(vis):
            if any(x >= 0 for x in queue):   # idle hop to the next arrival
                mc_release = max(mc_release, min(min(q_t), BIG - 1))
            continue
        hit_now = [open_row[bank[i]] == row[i] for i in idx]
        if any(v and h for v, h in zip(vis, hit_now)):
            # the oldest visible request to an open row
            qs = _argmin([qt if v and h else BIG
                          for qt, v, h in zip(q_t, vis, hit_now)])
        else:
            qs = _argmin([qt if v else BIG for qt, v in zip(q_t, vis)])
        pick = idx[qs]
        # ---- serve it on the bank
        decision_t = max(t_issue[pick], mc_release)
        now = max(dram_now, decision_t * FP // max(scale, 1))
        k, b, r = kind[pick], bank[pick], row[pick]
        clone = k in (RC_COPY, RC_INIT)
        refs_due = max(now // tREFI - refs_done, 0)
        start = max(now, ready[b]) + refs_due * tRFC
        hit = open_row[b] == r and not clone
        if open_row[b] < 0:
            act_start = start
        else:
            act_start = max(start, act_at[b] + tRAS) + tRP
        if clone:       # no data burst: the bus is left alone
            t_done = bank_next = act_start + t_clone
        else:
            col_start = start if hit else act_start + tRCD
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
