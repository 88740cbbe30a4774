"""jit'd wrapper: hash keys -> columns, run kernel, classify hot keys.

Device hashing uses natural uint32 multiply-shift wraparound (x64 is
unavailable on device by default); the host CountMinFilter uses prime-mod
hashing — the two sketches share SEMANTICS (saturating counters, aging,
all-rows >= T classification), not hash values, and each is validated
against its own oracle."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.cms_sketch.cms_sketch import cms_update_kernel
from repro.kernels.dispatch import kernel_call


def columns_for(keys: jax.Array, a: jax.Array, b: jax.Array,
                width: int) -> jax.Array:
    """keys [B] -> cols [d, B] via uint32 multiply-shift wraparound."""
    k = keys.astype(jnp.uint32)
    h = a[:, None].astype(jnp.uint32) * k[None, :] \
        + b[:, None].astype(jnp.uint32)
    h = h ^ (h >> jnp.uint32(16))
    return (h % jnp.uint32(width)).astype(jnp.int32)


@partial(jax.jit, static_argnames=("threshold", "max_count"))
def cms_update_and_classify(keys, counters, a, b, *, threshold: int = 20,
                            max_count: int = 255):
    """Batched equivalent of CountMinFilter.update_and_classify (no aging;
    the caller right-shifts ``counters`` every aging interval).
    Returns (new_counters, hot [B] bool)."""
    cols = columns_for(keys, a, b, counters.shape[1])
    new_counters, est = kernel_call(cms_update_kernel, cols, counters,
                                    max_count=max_count)
    hot = (est >= threshold).all(axis=0)
    return new_counters, hot
