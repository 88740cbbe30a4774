"""jit'd wrappers: slot-indirect page gather / scatter."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import kernel_call
from repro.kernels.page_gather.page_gather import (page_gather_kernel,
                                                   page_scatter_kernel)


@jax.jit
def page_gather(slots, pages):
    """slots [N]; pages [n_slots, page, d] -> [N, page, d]."""
    return kernel_call(page_gather_kernel, slots.astype(jnp.int32), pages)


@jax.jit
def page_scatter(slots, blocks, pages):
    """pages[slots[i]] = blocks[i]; returns the updated pool."""
    return kernel_call(page_scatter_kernel, slots.astype(jnp.int32), blocks,
                       pages)
