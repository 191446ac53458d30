"""Bloom filter over weak DRAM rows (RAIDR-style, Sec. 8.2 of the paper).

Host-built (numpy) from the characterization pass, probed inside the
software memory controller on every row activation. Keys are weak rows,
so a false positive only means a weak-timing row gets *nominal* tRCD —
never an unsafe reduced access. The engine probes with
:func:`bloom_probe_jnp` inside its scan.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

_MULS = np.array([0x85EBCA6B, 0xC2B2AE35, 0x27D4EB2F, 0x165667B1,
                  0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2D], np.uint32)


def _mix(x: np.ndarray, mul: int) -> np.ndarray:
    x = x.astype(np.uint32)
    x ^= x >> np.uint32(16)
    x = (x * np.uint32(mul)) & np.uint32(0xFFFFFFFF)
    x ^= x >> np.uint32(13)
    x = (x * np.uint32(0x2B2AE3D5)) & np.uint32(0xFFFFFFFF)
    x ^= x >> np.uint32(16)
    return x


@dataclasses.dataclass
class BloomFilter:
    bits: np.ndarray       # uint32 words, len = m_bits // 32
    m_bits: int
    k: int

    @staticmethod
    def build(keys: np.ndarray, m_bits: int = 1 << 20, k: int = 4) -> "BloomFilter":
        assert m_bits % 32 == 0 and (m_bits & (m_bits - 1)) == 0
        words = np.zeros(m_bits // 32, np.uint32)
        keys = np.asarray(keys, np.uint32)
        for i in range(k):
            idx = _mix(keys, int(_MULS[i])) & np.uint32(m_bits - 1)
            np.bitwise_or.at(words, idx >> np.uint32(5),
                             np.uint32(1) << (idx & np.uint32(31)))
        return BloomFilter(bits=words, m_bits=m_bits, k=k)

    def contains(self, keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(keys, np.uint32)
        out = np.ones(keys.shape, bool)
        for i in range(self.k):
            idx = _mix(keys, int(_MULS[i])) & np.uint32(self.m_bits - 1)
            bit = (self.bits[idx >> np.uint32(5)] >> (idx & np.uint32(31))) & np.uint32(1)
            out &= bit.astype(bool)
        return out

    def false_positive_rate(self, probes: np.ndarray, truth: np.ndarray) -> float:
        pos = self.contains(probes)
        fp = pos & ~truth
        denom = max(int((~truth).sum()), 1)
        return float(fp.sum()) / denom


def bloom_probe_jnp(words: jnp.ndarray, m_bits: int, k: int, keys: jnp.ndarray):
    """Pure-jnp probe (emulator + kernel oracle). keys: uint32 [N] -> bool [N]."""
    keys = keys.astype(jnp.uint32)
    out = jnp.ones(keys.shape, bool)
    for i in range(k):
        x = keys
        x = x ^ (x >> 16)
        x = x * jnp.uint32(int(_MULS[i]))
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0x2B2AE3D5)
        x = x ^ (x >> 16)
        idx = x & jnp.uint32(m_bits - 1)
        bit = (words[idx >> 5] >> (idx & 31)) & 1
        out = out & bit.astype(bool)
    return out
