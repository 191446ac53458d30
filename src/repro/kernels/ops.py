"""Jit'd public wrappers for the Pallas kernels.

On CPU (this container) the kernels execute in interpret mode — the
kernel body runs as traced Python for correctness validation; on TPU the
same calls compile to Mosaic. ``flash_attention`` takes the model-layout
[B, S, H, hd] tensors and handles the GQA head flattening + the
long-context fallback to the chunked-XLA path.
"""
from __future__ import annotations

import jax

from repro.kernels import flash_attention as _fa
from repro.kernels import rowclone_copy as _rc

_INTERPRET = jax.default_backend() == "cpu"
_MAX_KV_VMEM = 8192  # Sk beyond this falls back to the chunked XLA path


def flash_attention(q, k, v, causal=True):
    """q: [B,S,H,hd]; k,v: [B,S,KV,hd] -> [B,S,H,hd]."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    if k.shape[1] > _MAX_KV_VMEM or Sq % 128:
        from repro.models.attention import _sdpa_chunked
        return _sdpa_chunked(q, k, v, causal, hd ** -0.5)
    # GQA layout: group q heads by kv head so kernel i//G indexing works
    G = H // KV
    qr = (q.transpose(0, 2, 1, 3)
          .reshape(B, KV, G, Sq, hd).reshape(B * KV * G, Sq, hd))
    kr = k.transpose(0, 2, 1, 3).reshape(B * KV, k.shape[1], hd)
    vr = v.transpose(0, 2, 1, 3).reshape(B * KV, v.shape[1], hd)
    o = _fa.flash_attention_bhsd(qr, kr, vr, causal=causal,
                                 interpret=_INTERPRET)
    return (o.reshape(B, KV, G, Sq, hd).reshape(B, H, Sq, hd)
            .transpose(0, 2, 1, 3))


def rowclone_copy(x):
    return _rc.rowclone_copy(x, interpret=_INTERPRET)

