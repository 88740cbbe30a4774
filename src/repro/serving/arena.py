"""Device-resident paged state arena (DESIGN.md §6).

The physical page pool lives in fixed device slots (one or more parallel
pools — e.g. K pages and V pages — sharing slot indices); the device TAC
(``repro.core.tac_jax``) is its page table.  All APIs are BATCHED: a probe,
admit, stage or victim-gather over N pages is one fused device op, never a
per-page Python loop.

Admission reuses the TAC's eviction rule (min-timestamp way within the
key's bucket); dirty victims are surfaced — with their page contents
gathered BEFORE restaging overwrites the slots — so the caller (the tiered
store / scheduler) can write them back asynchronously.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tac_jax
from repro.kernels.page_gather.ops import page_gather, page_scatter
from repro.kernels.tac_probe.ops import (bucket_of, tac_probe,
                                         tac_probe_counted)
from repro.obs import NULL_COUNTER


class Admitted(NamedTuple):
    slots: np.ndarray           # [N] flat physical slot per admitted key
    evicted_keys: np.ndarray    # [N] displaced key (-1 = none)
    evicted_dirty: np.ndarray   # [N] displaced key's dirty bit
    evicted_blocks: Dict[str, jax.Array]  # victim page contents per pool,
    #                             gathered pre-staging; rows align with slots


class PagedStateArena:
    """Fixed-slot page pool with a TAC page table.

    ``pools`` maps pool name -> ((page, d), dtype); every pool holds
    ``n_buckets * ways`` physical pages addressed by the same slot ids.
    """

    def __init__(self, n_buckets: int, ways: int,
                 pools: Dict[str, Tuple[Tuple[int, int], Any]]):
        self.n_buckets = n_buckets
        self.ways = ways
        self.n_slots = n_buckets * ways
        self.tac = tac_jax.init(n_buckets, ways, 1)
        self.pools: Dict[str, jax.Array] = {
            name: jnp.zeros((self.n_slots, *shape), dtype)
            for name, (shape, dtype) in pools.items()}
        self.hits = 0
        self.misses = 0
        self.conflicts = 0
        self.admits = 0
        self.evictions = 0
        self.dirty_evictions = 0
        self.staged_pages = 0
        self._c_hits = self._c_misses = self._c_conflicts = NULL_COUNTER

    def bind_registry(self, registry) -> None:
        """Publish device probe tallies into a MetricsRegistry
        (DESIGN.md §12)."""
        self._c_hits = registry.counter("serving.arena.probe.hits")
        self._c_misses = registry.counter("serving.arena.probe.misses")
        self._c_conflicts = registry.counter(
            "serving.arena.probe.conflicts")

    # -------------------------------------------------------------- probing
    def probe(self, keys: jax.Array, now_ts: Optional[jax.Array] = None,
              count: bool = True) -> Tuple[np.ndarray, np.ndarray]:
        """Batched residency probe.  Returns (hit [N] bool, slots [N] int32,
        -1 for misses).  With ``now_ts`` the probe is an ACCESS: hit
        timestamps are refreshed (max with now).  ``count=False`` keeps
        polling/hint probes out of the hit-rate stats (a parked request is
        probed every scheduler tick; counting those would turn the hit rate
        into a poll-frequency artifact)."""
        keys = jnp.asarray(keys, jnp.int32)
        if keys.shape[0] == 0:                # empty batch: nothing to probe
            return (np.zeros((0,), bool), np.zeros((0,), np.int32))
        if count:
            # counted variant: hit/conflict tallies reduced ON DEVICE in
            # the same launch feed the registry (DESIGN.md §12)
            _, hit_d, way, tallies = tac_probe_counted(
                keys, self.tac.keys, self.tac.vals)
        else:
            _, hit_d, way = tac_probe(keys, self.tac.keys, self.tac.vals)
        bucket_d = bucket_of(keys, self.n_buckets)
        if now_ts is not None:                # access: refresh hit ts
            safe = jnp.maximum(way, 0)
            cur = self.tac.ts[bucket_d, safe]
            new_ts = self.tac.ts.at[bucket_d, safe].max(
                jnp.where(hit_d.astype(bool),
                          jnp.asarray(now_ts, jnp.float32), cur))
            self.tac = self.tac._replace(ts=new_ts)
        hit = np.asarray(hit_d).astype(bool)
        bucket = np.asarray(bucket_d)
        slots = np.where(hit, bucket * self.ways + np.asarray(way), -1)
        if count:
            n_hit, n_conflict = (int(x) for x in np.asarray(tallies))
            self.hits += n_hit
            self.misses += len(hit) - n_hit
            self.conflicts += n_conflict
            self._c_hits.inc(n_hit)
            self._c_misses.inc(len(hit) - n_hit)
            self._c_conflicts.inc(n_conflict)
        return hit, slots.astype(np.int32)

    def count_access(self, hits: int, misses: int) -> None:
        """Explicit hit-rate bookkeeping for callers that probe with
        ``count=False`` and decide afterwards what constituted an access."""
        self.hits += int(hits)
        self.misses += int(misses)
        self._c_hits.inc(int(hits))
        self._c_misses.inc(int(misses))

    def page_table(self, keys: jax.Array) -> Tuple[np.ndarray, jax.Array]:
        """keys [B, P] -> (hit [B, P], table [B, P] slot ids) for
        ``paged_decode_attention`` — one batched probe for all sequences."""
        keys = jnp.asarray(keys, jnp.int32)
        B, P = keys.shape
        hit, slots = self.probe(keys.reshape(-1))
        return hit.reshape(B, P), jnp.asarray(slots.reshape(B, P))

    def renew(self, keys: jax.Array, ts: jax.Array) -> None:
        """Hint for already-resident pages: bump predicted relevance."""
        keys = jnp.asarray(keys, jnp.int32)
        if keys.shape[0] == 0:
            return
        self.tac = tac_jax.renew(self.tac, keys,
                                 jnp.asarray(ts, jnp.float32))

    # ------------------------------------------------------------- admission
    def admit(self, keys: jax.Array, ts: jax.Array,
              dirty: Optional[jax.Array] = None) -> Admitted:
        """Batched multi-key admission via ``tac_jax.admit_batch``.  Chooses
        slots (evicting min-ts ways), gathers victim page contents before
        they can be overwritten, and returns everything the caller needs to
        stage new pages and write dirty victims back."""
        keys = jnp.asarray(keys, jnp.int32)
        if keys.shape[0] == 0:                # empty batch: nothing to admit
            return Admitted(np.zeros((0,), np.int32),
                            np.zeros((0,), np.int32),
                            np.zeros((0,), bool), {})
        res = tac_jax.admit_batch(
            self.tac, keys, jnp.asarray(ts, jnp.float32), None,
            None if dirty is None else jnp.asarray(dirty, bool))
        self.tac = res.state
        slots = np.asarray(res.slots)
        ev_k = np.asarray(res.evicted_keys)
        ev_d = np.asarray(res.evicted_dirty)
        # victim contents: gather the chosen slots BEFORE staging overwrites
        # them (rows where evicted_keys == -1 are garbage; callers filter).
        # Only DIRTY victims are ever written back, so all-clean eviction
        # rounds skip the gather entirely
        evicted_blocks = {name: page_gather(jnp.asarray(slots), pool)
                          for name, pool in self.pools.items()} \
            if bool(((ev_k >= 0) & ev_d).any()) else {}
        self.admits += len(slots)
        self.evictions += int((ev_k >= 0).sum())
        self.dirty_evictions += int((ev_d & (ev_k >= 0)).sum())
        return Admitted(slots.astype(np.int32), ev_k, ev_d, evicted_blocks)

    def stage(self, slots: jax.Array,
              blocks: Dict[str, jax.Array]) -> None:
        """Scatter N staged pages into their physical slots (one kernel
        launch per pool)."""
        slots = jnp.asarray(slots, jnp.int32)
        if slots.shape[0] == 0:
            return
        for name, blk in blocks.items():
            self.pools[name] = page_scatter(slots, blk.astype(
                self.pools[name].dtype), self.pools[name])
        self.staged_pages += int(slots.shape[0])

    def gather(self, slots: jax.Array) -> Dict[str, jax.Array]:
        """Batched read of N physical pages from every pool."""
        slots = jnp.asarray(slots, jnp.int32)
        return {name: page_gather(slots, pool)
                for name, pool in self.pools.items()}

    # ------------------------------------------------------------- migration
    def export_where(self, pred) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray, Dict[str, jax.Array]]:
        """Migration drain (DESIGN.md §9): pop every resident entry whose key
        satisfies ``pred`` (vectorized numpy predicate) out of the page
        table, gather its page contents (one batched ``page_gather`` per
        pool), and return (keys, ts, dirty, blocks) with timestamps and
        dirty bits preserved — the destination re-admits with the same
        eviction priority via ``admit(keys, ts, dirty)`` + ``stage``."""
        exp = tac_jax.export_mask(self.tac, pred(np.asarray(self.tac.keys)))
        self.tac = exp.state
        blocks = self.gather(jnp.asarray(exp.slots)) if len(exp.keys) else {}
        return exp.keys, exp.ts, exp.dirty, blocks

    # ----------------------------------------------------------- dirty state
    def mark_dirty(self, keys: jax.Array) -> None:
        """Decode mutated these pages in place: flag them for write-back."""
        keys = jnp.asarray(keys, jnp.int32)
        if keys.shape[0] == 0:
            return
        self.tac = tac_jax.set_dirty(self.tac, keys, True)

    def flush_dirty(self) -> Tuple[np.ndarray, Dict[str, jax.Array]]:
        """Checkpoint/shutdown: return (keys, page contents) of every dirty
        resident page and clear the dirty bits."""
        dirty = np.asarray(self.tac.dirty)
        keys = np.asarray(self.tac.keys)
        mask = dirty & (keys >= 0)
        if not mask.any():
            return np.zeros((0,), np.int32), {}
        b, w = np.nonzero(mask)
        slots = (b * self.ways + w).astype(np.int32)
        blocks = self.gather(jnp.asarray(slots))
        self.tac = self.tac._replace(dirty=jnp.zeros_like(self.tac.dirty))
        return keys[mask], blocks

    # --------------------------------------------------------------- metrics
    def stats(self) -> Dict[str, float]:
        tot = self.hits + self.misses
        return {"arena_hits": self.hits, "arena_misses": self.misses,
                "arena_hit_rate": self.hits / tot if tot else 0.0,
                "arena_conflicts": self.conflicts,
                "arena_admits": self.admits,
                "arena_evictions": self.evictions,
                "arena_dirty_evictions": self.dirty_evictions,
                "arena_staged_pages": self.staged_pages}
