"""Batched TAC directory probe as a Pallas TPU kernel.

The device-resident Timestamp-Aware Cache stores its directory as
(n_buckets x ways) key slots.  A batch of B state-access keys is probed
in one launch against directory ROWS: either the one shared row of a
fully-associative directory (``[1, ways]``, every ``FusedPlane``) or one
row per query, the query's hashed bucket gathered by the caller
(``[B, ways]``, the set-associative serving arena).

The grid walks the ways axis in lane-aligned tiles, so a row of 2^20
ways never has to fit in VMEM: each step compares the whole query
column ``[B, 1]`` against one ``[rows, tile]`` block on the VPU and
folds the tile's first matching way into a resident ``[B, 1]``
accumulator with a min — the earliest match across tiles wins, exactly
the reference's ``argmax`` over the full row.  Every block is either
lane-aligned or spans its whole array dimension, which is what the TPU
compiler's (8, 128) tiling rule asks of a BlockSpec.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 2048                      # ways per grid step (a multiple of 128)


def _kernel(q_ref, keys_ref, way_ref, *, tile: int, ways: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        way_ref[...] = jnp.full(way_ref.shape, ways, jnp.int32)

    match = keys_ref[...] == q_ref[...]                  # [B, tile]
    idx = jax.lax.broadcasted_iota(jnp.int32, match.shape, 1) + j * tile
    # a ragged last tile reads past the row: mask those lanes out
    cand = jnp.where(match & (idx < ways), idx, ways)
    way_ref[...] = jnp.minimum(way_ref[...],
                               jnp.min(cand, axis=1, keepdims=True))


def tac_probe_kernel(qkeys: jax.Array, rows: jax.Array, *,
                     interpret: bool = False) -> jax.Array:
    """qkeys [B] int32; rows [1, ways] (shared) or [B, ways] (per query)
    int32 directory keys, -1 = empty.  Returns way [B] int32: the first
    way whose key equals the query, -1 on a miss."""
    B = qkeys.shape[0]
    n_rows, ways = rows.shape
    tile = ways if ways <= TILE else TILE
    way = pl.pallas_call(
        functools.partial(_kernel, tile=tile, ways=ways),
        grid=(pl.cdiv(ways, tile),),
        in_specs=[pl.BlockSpec((B, 1), lambda j: (0, 0)),
                  pl.BlockSpec((n_rows, tile), lambda j: (0, j))],
        out_specs=pl.BlockSpec((B, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(qkeys.astype(jnp.int32).reshape(B, 1), rows)[:, 0]
    return jnp.where(way < ways, way, -1)
