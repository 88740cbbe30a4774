"""jit'd public wrapper: GQA-aware flash attention.

``flash_attention(q, k, v)`` with q [B,S,H,d], k/v [B,T,KV,d*] broadcasts KV
heads to query heads, flattens (B, H) into the kernel's grid dim and restores
the layout.  Lowered for a TPU the compiled kernel runs; on any other
platform the kernel body runs in interpret mode — the code path the tests
validate (``repro.kernels.dispatch``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import kernel_call
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_kernel


@partial(jax.jit, static_argnames=("causal", "bq", "bk"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, bq: int = 256,
                    bk: int = 256) -> jax.Array:
    B, S, H, d = q.shape
    T, KV = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(B * H, T, dv)
    o = kernel_call(flash_attention_kernel, qf, kf, vf, causal=causal,
                    bq=bq, bk=bk)
    return o.reshape(B, H, S, dv).transpose(0, 2, 1, 3)
