"""Public wrapper for flash-decode: layout, padding, backend selection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_kernel
from repro.kernels.decode_attention.ref import decode_attention_ref


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *, impl: str = "auto",
                     block_kv: int = 512) -> jax.Array:
    """q: (B, H, D); k/v: (B, S, K, D); kv_len: (B,).  Returns (B, H, D).

    impl: auto | pallas | pallas_interpret | ref
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    return _decode_attention(q, k, v, kv_len, impl=impl, block_kv=block_kv)


@functools.partial(jax.jit, static_argnames=("impl", "block_kv"))
def _decode_attention(q, k, v, kv_len, *, impl, block_kv):
    if impl == "ref":
        return decode_attention_ref(q, k, v, kv_len)

    B, H, D = q.shape
    S, K = k.shape[1], k.shape[2]
    G = H // K
    bkv = min(block_kv, S)
    pad = (-S) % bkv
    kt = jnp.moveaxis(k, 1, 2)                           # (B, K, S, D)
    vt = jnp.moveaxis(v, 1, 2)
    if pad:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad), (0, 0)))
    qg = q.reshape(B, K, G, D)
    out = decode_attention_kernel(qg, kt, vt, kv_len, block_kv=bkv,
                                  interpret=(impl == "pallas_interpret"))
    return out.reshape(B, H, D)
