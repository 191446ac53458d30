"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret mode on CPU = kernel body executed exactly)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),    # MHA
    (2, 256, 8, 2, 64),    # GQA 4:1
    (1, 256, 8, 8, 128),   # MHA hd=128
    (1, 128, 4, 1, 256),   # MQA hd=256 (gemma-style)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, S, H, KV, hd, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(B * S + H), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    o = ops.flash_attention(q, k, v, causal=causal)
    G = H // KV
    qr = q.transpose(0, 2, 1, 3).reshape(B, KV, G, S, hd).reshape(-1, S, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(-1, S, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(-1, S, hd)
    oref = (ref.flash_attention_ref(qr, kr, vr, causal)
            .reshape(B, KV, G, S, hd).reshape(B, H, S, hd).transpose(0, 2, 1, 3))
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(oref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("shape", [(8, 128), (64, 512), (33, 257), (1, 8192)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_rowclone_copy_sweep(shape, dtype):
    x = jnp.arange(np.prod(shape)).reshape(shape).astype(dtype)
    y = ops.rowclone_copy(x)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref.rowclone_copy_ref(x)))
