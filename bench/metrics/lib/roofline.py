"""Bytes a kernel's algorithm must move, from its shapes, and its share
of the memory roofline.  The counts follow the algorithm, not the
padded layout the compiler chooses for it."""
from __future__ import annotations

from math import prod
from typing import Optional

from metrics.lib.hlo import BYTES, tpu_custom_call, kernel_of


def probe_bytes(B: int, ways: int, rows: int = 1) -> int:
    """``tac_probe``: the ``[B]`` query keys, the directory ``[rows,
    ways]`` (all int32) and the ``[B]`` first-match ways written back."""
    return 4 * B + 4 * rows * ways + 4 * B


def page_bytes(n: int, row_bytes: int) -> int:
    """``page_gather`` or ``page_scatter`` of n rows: the int32 slot ids,
    n rows read and n rows written (the scatter writes into the pool in
    place; the rest of the pool is not touched)."""
    return 4 * n + 2 * n * row_bytes


def op_bytes(text: str) -> Optional[int]:
    """Bytes of one kernel op, from the shapes in its HLO text."""
    kind = kernel_of(text)
    if kind is None:
        return None
    c = tpu_custom_call(text)
    if kind == "tac_probe":
        (_, (B, _)), (_, (rows, ways)) = c.operands
        return probe_bytes(B, ways, rows)
    # the rows moved: the gather's result, the scatter's blocks
    dt, dims = c.result[0] if kind == "page_gather" else c.operands[1]
    return page_bytes(dims[0], prod(dims[1:]) * BYTES[dt])


def share_pct(nbytes: float, seconds: float, bytes_per_s: float) -> float:
    """Least time for ``nbytes`` at the peak bandwidth over the measured
    time, in percent."""
    return 100.0 * nbytes / bytes_per_s / seconds
