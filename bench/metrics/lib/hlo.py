"""Which Pallas kernel a device op is, read from the op's HLO text as
the TPU trace names it (``%x = s32[256,1]{...} custom-call(...),
custom_call_target="tpu_custom_call"``), and the logical shapes of its
operands and result.  The kernels carry no name of their own in the
trace, so they are told apart by their signatures:

* ``tac_probe``: int32 ``[B, 1]`` from the ``[B, 1]`` query keys and an
  int32 directory ``[rows, ways]``;
* ``page_gather``: ``[N, page, d]`` rows from the ``[N]`` slot ids and
  the pool ``[slots, page, d]``;
* ``page_scatter``: the pool ``[slots, page, d]`` from the slot ids, the
  ``[N, page, d]`` blocks and the pool.
"""
from __future__ import annotations

import re
from typing import List, NamedTuple, Optional, Tuple

_SHAPE = re.compile(
    r"\b(pred|s8|s16|s32|s64|u8|u16|u32|u64|bf16|f16|f32|f64)\[([\d,]*)\]")
BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
         "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
         "f64": 8}

Shape = Tuple[str, Tuple[int, ...]]


class Call(NamedTuple):
    result: List[Shape]
    operands: List[Shape]


def _shapes(text: str) -> List[Shape]:
    return [(dt, tuple(int(d) for d in dims.split(",") if d))
            for dt, dims in _SHAPE.findall(text)]


def tpu_custom_call(text: str) -> Optional[Call]:
    if 'custom_call_target="tpu_custom_call"' not in text \
            or " custom-call(" not in text:
        return None
    head, tail = text.split(" custom-call(", 1)
    result = head.split(" = ", 1)[-1]
    operands = tail.split("), custom_call_target=", 1)[0]
    return Call(_shapes(result), _shapes(operands))


def kernel_of(text: str) -> Optional[str]:
    c = tpu_custom_call(text)
    if c is None or len(c.result) != 1:
        return None
    (rdt, rdims), ops = c.result[0], c.operands
    if len(ops) == 2 and rdt == "s32" and len(rdims) == 2 and \
            rdims[1] == 1 and ops[1][0] == "s32" and len(ops[1][1]) == 2:
        return "tac_probe"
    if len(ops) == 2 and len(rdims) == 3 and ops[0][0] == "s32" and \
            len(ops[0][1]) == 1 and len(ops[1][1]) == 3 and \
            rdims[0] == ops[0][1][0] and rdims[1:] == ops[1][1][1:]:
        return "page_gather"
    if len(ops) == 3 and len(rdims) == 3 and ops[2][1] == rdims and \
            ops[0][0] == "s32" and len(ops[0][1]) == 1:
        return "page_scatter"
    return None
