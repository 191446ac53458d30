"""The benchmark's own copy of the PolyBench-like DRAM traffic.

A copy, not an import, of the suite table, the address-stream builder
(``traces.polybench_stream``), the 512 KiB 8-way LRU last-level cache
(``cachesim.filter_stream``) and the address mapping
(``traces.dram_trace_from_stream``), so that a later change to the
program's generators cannot move the yardstick. ``tests/`` pins the
output to the program's generator and to digests stored with the
benchmark. The LLC here is a per-set ordered dict: it gives the same
misses and write-backs, in the same order, as the program's numpy LRU,
about ten times faster.

Arrays are plain numpy: ``kind, bank, row, delta, dep`` (int32), the
layout of ``repro.core.emulator.Trace``.
"""
from __future__ import annotations

import collections
import hashlib
import json
import os
import zlib

import numpy as np

READ, WRITE, NOP = 0, 1, 4
FIELDS = ("kind", "bank", "row", "delta", "dep")

# (name, ((n_bytes, stride, passes), ...), compute_per_access, dep)
KERNELS = (
    ("gemm", ((1 << 21, 64, 2), (1 << 21, 64, 2), (1 << 20, 64, 1)), 48, 0),
    ("2mm", ((1 << 21, 64, 2), (1 << 21, 64, 2), (1 << 21, 64, 2)), 40, 0),
    ("3mm", ((1 << 21, 64, 3), (1 << 21, 64, 2), (1 << 21, 64, 2)), 40, 0),
    ("atax", ((1 << 22, 64, 2), (1 << 16, 64, 4)), 10, 0),
    ("bicg", ((1 << 22, 64, 2), (1 << 16, 64, 4)), 10, 0),
    ("mvt", ((1 << 22, 64, 2), (1 << 16, 64, 2)), 10, 0),
    ("gemver", ((1 << 22, 64, 3), (1 << 16, 64, 2)), 14, 0),
    ("gesummv", ((1 << 22, 64, 2), (1 << 16, 64, 2)), 8, 0),
    ("syrk", ((1 << 21, 64, 2), (1 << 20, 64, 2)), 36, 0),
    ("syr2k", ((1 << 21, 64, 3), (1 << 20, 64, 2)), 32, 0),
    ("trmm", ((1 << 21, 64, 2),), 30, 0),
    ("symm", ((1 << 21, 64, 2), (1 << 20, 64, 2)), 34, 0),
    ("cholesky", ((1 << 21, 64, 2),), 26, 1),
    ("lu", ((1 << 21, 64, 3),), 24, 1),
    ("ludcmp", ((1 << 21, 64, 3), (1 << 16, 64, 2)), 24, 1),
    ("trisolv", ((1 << 20, 64, 2), (1 << 16, 64, 2)), 8, 1),
    ("durbin", ((1 << 15, 64, 8),), 12, 1),
    ("gramschmidt", ((1 << 21, 64, 3),), 28, 1),
    ("correlation", ((1 << 21, 64, 3),), 22, 0),
    ("covariance", ((1 << 21, 64, 3),), 22, 0),
    ("jacobi-1d", ((1 << 21, 64, 4),), 6, 0),
    ("jacobi-2d", ((1 << 21, 64, 4),), 8, 0),
    ("seidel-2d", ((1 << 21, 64, 4),), 10, 1),
    ("heat-3d", ((1 << 21, 64, 4),), 10, 0),
    ("fdtd-2d", ((1 << 21, 64, 4),), 9, 0),
    ("adi", ((1 << 21, 64, 4),), 14, 1),
    ("doitgen", ((1 << 21, 64, 2), (1 << 16, 64, 4)), 20, 0),
    ("deriche", ((1 << 21, 64, 4),), 12, 0),
)
_BLOCKED = ("gemm", "2mm", "3mm", "syrk", "syr2k", "symm")


def address_stream(index: int, max_accesses: int, seed: int):
    """CPU-level (addresses, is_write) of kernel ``index``: round-robin
    interleaved strided passes over its arrays, blocked kernels
    revisiting tiles three times."""
    name, arrays, _, _ = KERNELS[index]
    rng = np.random.RandomState(seed + zlib.crc32(name.encode()) % 1000)
    streams = []
    base = 0
    for nb, stride, passes in arrays:
        lines = nb // stride
        for _ in range(passes):
            idx = np.arange(lines)
            if name in _BLOCKED:
                tile = max(lines // 16, 1)
                idx = np.concatenate([np.tile(np.arange(i, min(i + tile, lines)), 3)
                                      for i in range(0, lines, tile)])
            streams.append(base + idx * stride)
        base += nb * 2
    n = min(max_accesses, sum(len(s) for s in streams))
    out = np.empty(n, np.int64)
    k = len(streams)
    for j, s in enumerate(streams):
        pos = np.arange(j, n, k)
        out[pos] = s[np.arange(len(pos)) % len(s)]
    writes = rng.rand(n) < 0.3
    return out, writes


def llc_filter(addrs, writes, size_bytes=512 * 1024, ways=8, line=64):
    """Misses and dirty write-backs of a write-allocate LRU cache, in
    the order the memory sees them (a write-back before the miss that
    evicted it)."""
    n_sets = size_bytes // (ways * line)
    sets = [collections.OrderedDict() for _ in range(n_sets)]
    out_a, out_w = [], []
    for a, w in zip(np.asarray(addrs).tolist(), np.asarray(writes).tolist()):
        la = a // line
        s = sets[la % n_sets]
        if la in s:
            s.move_to_end(la)
            if w:
                s[la] = True
            continue
        if len(s) >= ways:
            old, dirty = s.popitem(last=False)
            if dirty:
                out_a.append(old * line)
                out_w.append(True)
        s[la] = bool(w)
        out_a.append(a)
        out_w.append(False)
    return np.asarray(out_a, np.int64), np.asarray(out_w, bool)


def dram_trace(addrs, writes, delta, dep, n_banks=16, n_rows=32768,
               row_bytes=8192) -> dict:
    """Row-interleaved XOR bank mapping to (kind, bank, row, delta, dep)."""
    rbuf = np.asarray(addrs, np.int64) // row_bytes
    n = len(rbuf)
    return {
        "kind": np.where(writes, WRITE, READ).astype(np.int32),
        "bank": ((rbuf ^ (rbuf >> 4)) % n_banks).astype(np.int32),
        "row": ((rbuf // n_banks) % n_rows).astype(np.int32),
        "delta": np.full(n, delta, np.int32),
        "dep": np.full(n, dep, np.int32),
    }


def kernel_trace(index: int, max_accesses: int, geometry: dict) -> dict:
    """The LLC-filtered DRAM trace of kernel ``index``, generated with
    the examples' seed (the kernel's index in the suite)."""
    a, w = address_stream(index, max_accesses, seed=index)
    da, dw = llc_filter(a, w)
    _, _, delta, dep = KERNELS[index]
    return dram_trace(da, dw, delta, dep, geometry["n_banks"],
                      geometry["n_rows"], geometry["row_bytes"])


def digest(tr: dict) -> str:
    h = hashlib.sha256()
    for f in FIELDS:
        h.update(np.ascontiguousarray(tr[f], "<i4").tobytes())
    return h.hexdigest()


def suite(max_accesses: int, geometry: dict, cache_dir: str = None) -> list:
    """All kernels' base traces, in suite order. With ``cache_dir``,
    each trace is kept there as ``.npz`` keyed by (kernel, max_accesses,
    generator seed, geometry) and read back by later runs."""
    out = []
    gkey = json.dumps(geometry, sort_keys=True)
    for i, (name, *_rest) in enumerate(KERNELS):
        path = None
        if cache_dir:
            key = hashlib.sha256(
                f"polybench-v1|{name}|{max_accesses}|{i}|{gkey}".encode()
            ).hexdigest()[:20]
            path = os.path.join(cache_dir, f"{name}-{max_accesses}-{key}.npz")
            if os.path.exists(path):
                with np.load(path) as z:
                    out.append({f: z[f] for f in FIELDS})
                continue
        tr = kernel_trace(i, max_accesses, geometry)
        if path:
            os.makedirs(cache_dir, exist_ok=True)
            tmp = f"{path}.part.npz"
            np.savez(tmp, **tr)
            os.replace(tmp, path)
        out.append(tr)
    return out


def variant(tr: dict, seed: int, call: int, index: int, n_banks: int,
            n_rows: int) -> dict:
    """A fresh trace with the same shape of work, drawn from (seed, call,
    index): banks permuted, rows XOR-ed, and the first request delayed
    by up to 16383 cycles of compute, which moves the run against the
    refresh schedule. Sizes, compute gaps, dependences and every
    same-row/same-bank relation are kept, so each variant costs the
    emulator the same scan slots, while its answers (refresh
    alignment, weak-row probes) differ from every other variant's."""
    rng = np.random.default_rng([seed % (1 << 64), call % (1 << 64),
                                 index % (1 << 64)])
    perm = rng.permutation(n_banks).astype(np.int32)
    mask = np.int32(rng.integers(0, n_rows))
    out = dict(tr)
    out["bank"] = perm[tr["bank"]]
    out["row"] = (tr["row"] ^ mask).astype(np.int32)
    out["delta"] = tr["delta"].copy()
    out["delta"][:1] += np.int32(rng.integers(0, 1 << 14))
    return out
