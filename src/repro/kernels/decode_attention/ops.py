"""jit'd wrapper: GQA paged decode attention with head broadcasting."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import \
    paged_decode_attention_kernel
from repro.kernels.dispatch import kernel_call


@jax.jit
def paged_decode_attention(q, k_pages, v_pages, page_table, seq_lens):
    """q [B,H,d] (single token per sequence); pages [slots, page, d*]."""
    return kernel_call(paged_decode_attention_kernel, q, k_pages, v_pages,
                       page_table.astype(jnp.int32),
                       seq_lens.astype(jnp.int32))
