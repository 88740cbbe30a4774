"""Kernel dispatch by the platform a program is lowered for.

Every Pallas kernel here has one call site per wrapper.  The choice
between the compiled kernel and its interpreted body is made when the
enclosing jitted program is LOWERED (``jax.lax.platform_dependent``), not
by a caller-set flag: lowering for a TPU keeps only the compiled kernel
(``tpu_custom_call`` in the HLO, no interpreter, no reference op), and
lowering for any other platform keeps only the interpreted body.  A
program compiled ahead of time for a described TPU topology on a CPU
host therefore takes the TPU branch, which is what the real-width
compile tests rely on.
"""
from __future__ import annotations

import functools

import jax


def kernel_call(kernel, *args, **static):
    """``kernel(*args, **static)`` compiled for the TPU when lowered for a
    TPU; the same kernel body in Pallas interpret mode elsewhere."""
    return jax.lax.platform_dependent(
        *args,
        tpu=functools.partial(kernel, interpret=False, **static),
        default=functools.partial(kernel, interpret=True, **static))
