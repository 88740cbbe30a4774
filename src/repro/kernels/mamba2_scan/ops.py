"""jit'd wrapper for the SSD chunk-scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.dispatch import kernel_call
from repro.kernels.mamba2_scan.mamba2_scan import mamba2_scan_kernel


@partial(jax.jit, static_argnames=("chunk",))
def mamba2_scan(x, dt, A, Bm, Cm, *, chunk: int = 64):
    return kernel_call(mamba2_scan_kernel, x, dt, A, Bm, Cm, chunk=chunk)
