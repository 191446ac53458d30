"""Pure-jnp oracles for every Pallas kernel (allclose targets)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, causal=True):
    """q: [BHq, Sq, hd]; k, v: [BHkv, Sk, hd] (GQA by ratio)."""
    BH, Sq, hd = q.shape
    BK, Sk, _ = k.shape
    G = BH // BK
    qf = q.astype(jnp.float32) * (hd ** -0.5)
    kf = jnp.repeat(k.astype(jnp.float32), G, axis=0)
    vf = jnp.repeat(v.astype(jnp.float32), G, axis=0)
    s = jnp.einsum("bqd,bkd->bqk", qf, kf)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, vf).astype(q.dtype)


def rowclone_copy_ref(x):
    return x

