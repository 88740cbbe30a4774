"""jit'd wrappers: hash, probe, gather."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import kernel_call
from repro.kernels.page_gather.page_gather import page_gather_kernel
from repro.kernels.tac_probe.tac_probe import tac_probe_kernel

_A, _B, _P = 2654435761, 40503, 2 ** 31 - 1


def bucket_of(keys: jax.Array, n_buckets: int) -> jax.Array:
    h = (keys.astype(jnp.uint32) * jnp.uint32(_A)) ^ jnp.uint32(_B)
    return (h % jnp.uint32(n_buckets)).astype(jnp.int32)


def _probe(qkeys, bucket_keys):
    """(bucket [B], way [B], -1 = miss).  A fully-associative directory
    is probed as its one shared row; otherwise each query's bucket row
    is gathered first."""
    qkeys = qkeys.astype(jnp.int32)
    buckets = bucket_of(qkeys, bucket_keys.shape[0])
    rows = bucket_keys if bucket_keys.shape[0] == 1 \
        else bucket_keys[buckets]
    return buckets, kernel_call(tac_probe_kernel, qkeys, rows)


def _values(bucket_vals, buckets, way):
    """Value row of each hit's slot, zeros for misses."""
    vals = bucket_vals[buckets, jnp.maximum(way, 0)]
    return jnp.where((way >= 0)[:, None], vals, jnp.zeros_like(vals))


@jax.jit
def tac_probe(qkeys, bucket_keys, bucket_vals):
    """Returns (values [B, D], hit [B] int32, way [B] int32, -1 = miss)."""
    buckets, way = _probe(qkeys, bucket_keys)
    return _values(bucket_vals, buckets, way), \
        (way >= 0).astype(jnp.int32), way


@jax.jit
def tac_probe_counted(qkeys, bucket_keys, bucket_vals):
    """Probe + device-side tallies for the observability plane
    (DESIGN.md §12): returns ``(values, hit, way, counts)`` where
    ``counts`` is an int32 ``[2]`` vector of (n_hit, n_conflict) reduced
    on device in the same launch — a CONFLICT is a miss whose bucket is
    already full, i.e. admitting the key would evict.  One device->host
    transfer surfaces both tallies instead of a host-side scan of the
    per-query hit vector."""
    buckets, way = _probe(qkeys, bucket_keys)
    hit = (way >= 0).astype(jnp.int32)
    full = jnp.all(bucket_keys[buckets] != -1, axis=1)
    miss = hit == 0
    counts = jnp.stack([hit.sum(), (miss & full).sum()]).astype(jnp.int32)
    return _values(bucket_vals, buckets, way), hit, way, counts


@jax.jit
def tac_probe_gather(qkeys, bucket_keys, pages):
    """Composed probe -> page gather (DESIGN.md §14): the directory probe
    and the payload pull run in ONE traced program instead of two island
    launches — the probe's (bucket, way) resolves to a flat slot id that
    feeds ``page_gather_kernel``'s scalar-prefetch index_map directly.

    ``pages`` is ``[n_slots + 1, page, d]``: the LAST row is a zeroed
    scratch slot that miss lanes alias, so their gathered rows decode as
    "absent" without any host-side masking.  Returns
    ``(rows [B, page, d], hit [B] bool, slots [B] int32 flat)``.
    """
    ways = bucket_keys.shape[1]
    buckets, way = _probe(qkeys, bucket_keys)
    hit = way >= 0
    trash = pages.shape[0] - 1
    slots = jnp.where(hit, buckets * ways + jnp.maximum(way, 0),
                      trash).astype(jnp.int32)
    rows = kernel_call(page_gather_kernel, slots, pages)
    return rows, hit, slots
