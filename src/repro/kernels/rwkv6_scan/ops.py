"""jit'd wrapper for the RWKV6 scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.dispatch import kernel_call
from repro.kernels.rwkv6_scan.rwkv6_scan import rwkv6_scan_kernel


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, w, u, *, chunk: int = 64):
    return kernel_call(rwkv6_scan_kernel, r, k, v, w, u, chunk=chunk)
