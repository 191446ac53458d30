"""Kernel micro-benchmarks: wall time of the XLA reference path on CPU
(the Pallas kernels themselves run in interpret mode here, so wall time
is meaningless for them — their perf story lives in the roofline, and
their correctness in tests/test_kernels.py)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _time(f, *args, iters=5):
    f(*args)[0].block_until_ready() if isinstance(f(*args), tuple) else \
        jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(f(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def bench_kernels():
    rows = []
    from repro.models.attention import _sdpa_chunked
    B, S, H, hd = 1, 2048, 8, 64
    q = jnp.ones((B, S, H, hd), jnp.bfloat16)
    k = jnp.ones((B, S, 2, hd), jnp.bfloat16)
    v = jnp.ones((B, S, 2, hd), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: _sdpa_chunked(q, k, v, True, hd ** -0.5))
    rows.append(("xla_chunked_attn_2k_us", round(_time(f, q, k, v), 1),
                 "flash-kernel twin"))

    from repro.core.bloom import BloomFilter, bloom_probe_jnp
    import numpy as np
    bf = BloomFilter.build(np.arange(1000, dtype=np.uint32), m_bits=1 << 20, k=4)
    keys = jnp.arange(1 << 16, dtype=jnp.uint32)
    words = jnp.asarray(bf.bits)
    g = jax.jit(lambda w, kk: bloom_probe_jnp(w, 1 << 20, 4, kk))
    rows.append(("bloom_probe_64k_keys_us", round(_time(g, words, keys), 1),
                 "jnp path"))

    x = jnp.ones((1024, 4096), jnp.bfloat16)
    h = jax.jit(lambda x: x + 0)  # copy through XLA
    rows.append(("bulk_copy_8MB_us", round(_time(h, x), 1), "HBM-bound op"))

    return rows
