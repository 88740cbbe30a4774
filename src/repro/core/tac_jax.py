"""Device-side Timestamp-Aware Cache: fixed-slot, functional, jittable.

The accelerator twin of ``repro.core.tac``: state rows live in
(n_buckets x ways) slots; eviction picks the min-timestamp way within the
key's bucket (set-associative; with n_buckets=1 it is exactly the paper's
fully-associative min-ts policy — the equivalence test in
tests/test_tac_jax.py checks eviction-order agreement with the Python TAC).
Lookups go through the ``tac_probe`` Pallas kernel.  Admissions come in two
flavours: ``admit`` scans the batch sequentially (reference semantics:
duplicate keys in one batch must see each other's effects), and
``admit_batch`` vectorizes — keys in distinct buckets land in ONE fused
update, same-bucket collisions resolve in batch order over conflict rounds,
and the chosen slot + displaced key/dirty bit are reported per key (the
serving arena's write-back path needs them).
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.page_gather.page_gather import (page_gather_kernel,
                                                   page_scatter_kernel)
from repro.kernels.page_gather.ref import page_gather_ref
from repro.kernels.tac_probe.ops import bucket_of, tac_probe, \
    tac_probe_gather
from repro.kernels.tac_probe.ref import tac_probe_ref


class TACState(NamedTuple):
    keys: jax.Array        # [n_buckets, ways] int32, -1 = empty
    ts: jax.Array          # [n_buckets, ways] fp32
    vals: jax.Array        # [n_buckets, ways, D]
    dirty: jax.Array       # [n_buckets, ways] bool


def init(n_buckets: int, ways: int, d: int,
         dtype=jnp.float32) -> TACState:
    return TACState(
        keys=jnp.full((n_buckets, ways), -1, jnp.int32),
        ts=jnp.full((n_buckets, ways), -jnp.inf, jnp.float32),
        vals=jnp.zeros((n_buckets, ways, d), dtype),
        dirty=jnp.zeros((n_buckets, ways), bool))


def lookup(state: TACState, qkeys: jax.Array, now_ts: jax.Array
           ) -> Tuple[jax.Array, jax.Array, TACState]:
    """Batched probe+gather; refreshes timestamps of hits (max with now)."""
    vals, hit, way = tac_probe(qkeys, state.keys, state.vals)
    b = bucket_of(qkeys, state.keys.shape[0])
    safe_way = jnp.maximum(way, 0)
    cur = state.ts[b, safe_way]
    new_ts = state.ts.at[b, safe_way].max(
        jnp.where(hit.astype(bool), now_ts, cur))
    return vals, hit.astype(bool), state._replace(ts=new_ts)


def renew(state: TACState, keys: jax.Array, hint_ts: jax.Array) -> TACState:
    """Bump predicted relevance of cached keys (hint for a cached entry)."""
    _, hit, way = tac_probe(keys, state.keys, state.vals)
    b = bucket_of(keys, state.keys.shape[0])
    safe = jnp.maximum(way, 0)
    cur = state.ts[b, safe]
    new_ts = state.ts.at[b, safe].max(
        jnp.where(hit.astype(bool), hint_ts, cur))
    return state._replace(ts=new_ts)


def admit(state: TACState, keys: jax.Array, ts: jax.Array,
          vals: jax.Array, dirty: jax.Array = None) -> TACState:
    """Insert a batch (prefetched or freshly computed state).  Sequential
    over the batch so duplicate buckets compose; each insert overwrites a
    matching key if present, else evicts the bucket's min-ts way."""
    if dirty is None:
        dirty = jnp.zeros(keys.shape, bool)
    n_buckets = state.keys.shape[0]

    def one(st: TACState, inp):
        k, t, v, d = inp
        b = bucket_of(k[None], n_buckets)[0]
        bkeys = st.keys[b]
        bts = st.ts[b]
        match = bkeys == k
        way = jnp.where(match.any(), jnp.argmax(match), jnp.argmin(bts))
        # overwrite semantics match TimestampAwareCache.insert (ts replaced)
        new_ts = t
        return TACState(
            keys=st.keys.at[b, way].set(k),
            ts=st.ts.at[b, way].set(new_ts),
            vals=st.vals.at[b, way].set(v.astype(st.vals.dtype)),
            dirty=st.dirty.at[b, way].set(d)), None

    state, _ = jax.lax.scan(one, state, (keys, ts, vals, dirty))
    return state


class AdmitResult(NamedTuple):
    state: TACState
    slots: jax.Array          # [B] int32 flat slot (bucket * ways + way)
    evicted_keys: jax.Array   # [B] int32 displaced key, -1 = none/overwrite
    evicted_dirty: jax.Array  # [B] bool  dirty bit of the displaced key


@jax.jit
def admit_batch(state: TACState, keys: jax.Array, ts: jax.Array,
                vals: jax.Array = None, dirty: jax.Array = None
                ) -> AdmitResult:
    """Vectorized multi-key admit.

    Keys hashing to DISTINCT buckets are admitted in one fused update (no
    ``lax.scan`` over the batch); keys colliding in a bucket are resolved in
    batch order over conflict rounds (``lax.while_loop``, trip count = max
    same-bucket multiplicity, 1 for collision-free batches).  Semantics are
    exactly sequential ``admit``: overwrite a matching key, else evict the
    bucket's min-ts way.

    Returns the new state plus, per admitted key, the flat slot it landed in
    and the key/dirty-bit it displaced (-1/False when the way was empty or
    held the same key) — callers owning slot-addressed payloads (the paged
    arena) use these to write dirty victims back before re-staging.
    """
    B = keys.shape[0]
    n_buckets, ways = state.keys.shape
    if vals is None:
        vals = jnp.zeros((B, state.vals.shape[-1]), state.vals.dtype)
    if dirty is None:
        dirty = jnp.zeros((B,), bool)
    b = bucket_of(keys, n_buckets)
    # occurrence rank within each bucket, in batch order
    same_before = (b[:, None] == b[None, :]) & \
        jnp.tril(jnp.ones((B, B), bool), k=-1)
    rank = same_before.sum(axis=1).astype(jnp.int32)
    n_rounds = rank.max() + 1

    def round_body(carry):
        r, st, slots, ev_k, ev_d = carry
        active = rank == r
        bkeys = st.keys[b]                               # [B, ways]
        bts = st.ts[b]
        match = bkeys == keys[:, None]
        hit = match.any(axis=1)
        way = jnp.where(hit, jnp.argmax(match, axis=1),
                        jnp.argmin(bts, axis=1)).astype(jnp.int32)
        old_key = jnp.take_along_axis(bkeys, way[:, None], 1)[:, 0]
        old_dirty = st.dirty[b, way]
        # masked scatter: active lanes have unique buckets this round, so a
        # one-hot add is an exact set and duplicate-index order never matters
        act_i = active.astype(jnp.int32)
        cnt = jnp.zeros((n_buckets, ways), jnp.int32).at[b, way].add(act_i)
        mask = cnt > 0
        grid_k = jnp.zeros((n_buckets, ways), jnp.int32) \
            .at[b, way].add(jnp.where(active, keys, 0))
        grid_t = jnp.zeros((n_buckets, ways), jnp.float32) \
            .at[b, way].add(jnp.where(active, ts, 0.0))
        grid_d = jnp.zeros((n_buckets, ways), jnp.int32) \
            .at[b, way].add(jnp.where(active, dirty.astype(jnp.int32), 0))
        grid_v = jnp.zeros_like(st.vals).at[b, way].add(
            jnp.where(active[:, None], vals.astype(st.vals.dtype), 0))
        st = TACState(
            keys=jnp.where(mask, grid_k, st.keys),
            ts=jnp.where(mask, grid_t, st.ts),
            vals=jnp.where(mask[..., None], grid_v, st.vals),
            dirty=jnp.where(mask, grid_d > 0, st.dirty))
        slots = jnp.where(active, b * ways + way, slots)
        displaced = active & ~hit & (old_key >= 0)
        ev_k = jnp.where(displaced, old_key, ev_k)
        ev_d = jnp.where(displaced, old_dirty, ev_d)
        return r + 1, st, slots, ev_k, ev_d

    init = (jnp.int32(0), state, jnp.zeros((B,), jnp.int32),
            jnp.full((B,), -1, jnp.int32), jnp.zeros((B,), bool))
    _, state, slots, ev_k, ev_d = jax.lax.while_loop(
        lambda c: c[0] < n_rounds, round_body, init)
    return AdmitResult(state, slots, ev_k, ev_d)


# ----------------------------------------------------------- sharded plane
# Key ownership in the sharded state plane (DESIGN.md §9): non-negative
# int32 keys are assigned to shards by modulo, which agrees with the
# engine-side ``hash_partition`` for ints (CPython hash(i) == i for small
# non-negative ints), so a hint routed host-side and a page admitted
# device-side land at the same owner.

def shard_of(keys: jax.Array, n_shards: int) -> jax.Array:
    """Owning shard per key (device-side twin of ``hash_partition``)."""
    return jnp.mod(jnp.asarray(keys, jnp.int32), n_shards)


def shard_mask(keys: jax.Array, shard_id: int, n_shards: int) -> jax.Array:
    """True where ``shard_id`` owns the key."""
    return shard_of(keys, n_shards) == shard_id


def probe_owned(state: TACState, keys: jax.Array, shard_id: int,
                n_shards: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Shard-local probe: foreign keys (misrouted in the shard plane) are
    forced to miss so a stray probe can never refresh another shard's
    entries.  Returns (vals, hit, owned) — callers count ``~owned`` lanes
    as misroutes, not misses."""
    keys = jnp.asarray(keys, jnp.int32)
    owned = shard_mask(keys, shard_id, n_shards)
    vals, hit, _ = tac_probe(keys, state.keys, state.vals)
    return vals, hit.astype(bool) & owned, owned


def admit_owned(state: TACState, keys: jax.Array, ts: jax.Array,
                shard_id: int, n_shards: int, vals: jax.Array = None,
                dirty: jax.Array = None) -> Tuple[AdmitResult, int]:
    """Shard-local admit: drops keys the shard does not own before the
    batched admit (a misrouted admit would orphan a page — no hint or probe
    would ever find it on this shard again).  Host-side filter (shapes are
    data-dependent); returns (AdmitResult over the owned subset, n_dropped).
    """
    keys = jnp.asarray(keys, jnp.int32)
    owned = np.asarray(shard_mask(keys, shard_id, n_shards))
    n_dropped = int((~owned).sum())
    if n_dropped == 0:
        return admit_batch(state, keys, ts, vals, dirty), 0
    idx = np.nonzero(owned)[0]
    sub = lambda a: None if a is None else jnp.asarray(a)[idx]
    if len(idx) == 0:
        empty = AdmitResult(state, jnp.zeros((0,), jnp.int32),
                            jnp.zeros((0,), jnp.int32),
                            jnp.zeros((0,), bool))
        return empty, n_dropped
    return admit_batch(state, sub(keys), sub(ts), sub(vals),
                       sub(dirty)), n_dropped


def evict_expired(state: TACState, watermark: float,
                  retention: Any = 0.0) -> Tuple[TACState, jax.Array]:
    """Watermark-driven bulk reclaim (DESIGN.md §10, §11): invalidate
    every occupied slot whose EXPIRY time lies strictly behind
    ``watermark``.

    Device-side primitive mirroring the engine's pane purge
    (``WindowedStatefulOp._purge_pane``) and interval-key expiry
    (``IntervalJoinOp._purge_key``) for a future windowed/join serving
    path — not yet wired into the scheduler.  The expiry time is
    ``ts + retention``:

      * ``retention == 0`` (default) — the slot timestamp IS the expiry
        deadline (window panes admitted with their fire deadline, §10);
      * ``retention > 0`` — slots admitted at their insertion/access
        timestamp expire at their INTERVAL END instead (interval-join
        entries whose matchability outlives the access that admitted
        them, §11).  ``retention`` may be a scalar (one bound for the
        whole cache) or a ``[n_buckets, ways]`` array (per-slot bounds,
        e.g. side-dependent ``hi`` vs ``−lo``).

    Allowed lateness is folded into ``watermark`` by the caller.  Dirty
    bits are cleared along with the slots: expired state is purged, not
    written back, so callers that still need the data must flush BEFORE
    the watermark passes.  Returns (state, number of slots reclaimed).
    """
    expiry = state.ts + jnp.asarray(retention, state.ts.dtype)
    expired = (state.keys >= 0) & (expiry < watermark)
    return TACState(
        keys=jnp.where(expired, -1, state.keys),
        ts=jnp.where(expired, -jnp.inf, state.ts),
        vals=state.vals,
        dirty=jnp.where(expired, False, state.dirty)), expired.sum()


# --------------------------------------------------------------- migration
class Exported(NamedTuple):
    state: TACState           # source state with the entries cleared
    keys: np.ndarray          # [M] exported keys
    ts: np.ndarray            # [M] their timestamps (preserved end-to-end)
    vals: np.ndarray          # [M, D] their value rows
    dirty: np.ndarray         # [M] their dirty bits
    slots: np.ndarray         # [M] flat source slots (page-payload gather)


def export_mask(state: TACState, mask: np.ndarray) -> Exported:
    """Migration drain: pop every resident entry selected by ``mask`` (a
    host boolean over keys, e.g. a key range or ``shard_mask``) out of the
    cache, preserving timestamps and dirty bits so the destination re-admits
    them with the SAME eviction priority (Megaphone-style fluid migration
    moves state, not recency).  Host-side: migrations are rare, bulk, and
    off the tuple path."""
    keys = np.asarray(state.keys)
    sel = (keys >= 0) & np.asarray(mask)
    if not sel.any():
        return Exported(state, np.zeros((0,), np.int32),
                        np.zeros((0,), np.float32),
                        np.zeros((0, state.vals.shape[-1]), np.float32),
                        np.zeros((0,), bool), np.zeros((0,), np.int32))
    b, w = np.nonzero(sel)
    slots = (b * state.keys.shape[1] + w).astype(np.int32)
    out = Exported(
        state._replace(
            keys=state.keys.at[b, w].set(-1),
            ts=state.ts.at[b, w].set(-jnp.inf),
            dirty=state.dirty.at[b, w].set(False)),
        keys[sel].astype(np.int32),
        np.asarray(state.ts)[sel].astype(np.float32),
        np.asarray(state.vals)[sel],
        np.asarray(state.dirty)[sel],
        slots)
    return out


def import_entries(state: TACState, keys: np.ndarray, ts: np.ndarray,
                   vals: np.ndarray = None,
                   dirty: np.ndarray = None) -> AdmitResult:
    """Migration re-admit at the destination shard: a batched admit that
    keeps the exported timestamps (NOT the migration time — a prefetched
    page whose hint ts lies in the future must stay protected after the
    move, DESIGN.md §9)."""
    keys = jnp.asarray(keys, jnp.int32)
    if keys.shape[0] == 0:
        return AdmitResult(state, jnp.zeros((0,), jnp.int32),
                           jnp.zeros((0,), jnp.int32),
                           jnp.zeros((0,), bool))
    return admit_batch(state, keys, jnp.asarray(ts, jnp.float32),
                       None if vals is None else jnp.asarray(vals),
                       None if dirty is None else jnp.asarray(dirty, bool))


def flush_dirty(state: TACState) -> Tuple[TACState, Exported]:
    """Barrier-time dirty export (DESIGN.md §7): the device twin of
    ``TimestampAwareCache.flush_dirty``.  Returns every DIRTY resident
    row (keys, timestamps, values, flat slots — the write-back batch the
    checkpoint persists) and the state with those dirty bits CLEARED;
    unlike the migration drain (``export_mask``) the entries stay
    resident — a checkpoint snapshots state, it does not evict it.
    Host-side like the other bulk paths: checkpoints are rare and run
    off the tuple path."""
    dirty = np.asarray(state.dirty) & (np.asarray(state.keys) >= 0)
    if not dirty.any():
        return state, Exported(state, np.zeros((0,), np.int32),
                               np.zeros((0,), np.float32),
                               np.zeros((0, state.vals.shape[-1]),
                                        np.float32),
                               np.zeros((0,), bool),
                               np.zeros((0,), np.int32))
    b, w = np.nonzero(dirty)
    slots = (b * state.keys.shape[1] + w).astype(np.int32)
    new_state = state._replace(dirty=state.dirty.at[b, w].set(False))
    exp = Exported(new_state,
                   np.asarray(state.keys)[dirty].astype(np.int32),
                   np.asarray(state.ts)[dirty].astype(np.float32),
                   np.asarray(state.vals)[dirty],
                   np.ones((int(dirty.sum()),), bool),
                   slots)
    return new_state, exp


def set_dirty(state: TACState, keys: jax.Array,
              value: bool = True) -> TACState:
    """Flip the dirty bit of resident keys (no-op for missing keys).

    Miss lanes alias way 0 of their bucket, so the scatter must be
    idempotent under duplicate indices: ``.at[].set`` with a stale value
    could clobber a hit lane's update (unspecified duplicate order) —
    ``.at[].max``/``.at[].min`` with a neutral element cannot."""
    _, hit, way = tac_probe(keys, state.keys, state.vals)
    hit = hit.astype(bool)
    b = bucket_of(keys, state.keys.shape[0])
    safe = jnp.maximum(way, 0)
    d_int = state.dirty.astype(jnp.int32)
    if value:
        d_int = d_int.at[b, safe].max(jnp.where(hit, 1, 0))
    else:
        d_int = d_int.at[b, safe].min(jnp.where(hit, 0, 1))
    return state._replace(dirty=d_int > 0)


# ------------------------------------------------------- fused hot path §14
# The device data plane of the fused execution mode (DESIGN.md §14): the
# stateful-operator inner loop — probe → gather → operator compute →
# scatter write-back — compiled into ONE jitted program per operator
# config.  The payload pool is ``pages [n_slots + 1, 1, V + 1]``: channel
# 0 is a presence flag (0 = the pane was never written; decodes to the
# Python side's ``None``), channels 1..V the value vector, and the LAST
# row a zeroed scratch slot that miss/read/padding lanes alias so their
# scatters are inert.  The host shadow directory (streaming/fused.py)
# owns eviction ORDER and slot assignment; the device directory
# (``TACState.keys``) is authoritative for MEMBERSHIP and the pool for
# payloads — both only change through the entry points below, so they
# agree by construction.

# The fused entry points below are LATENCY-critical: one call per engine
# batch, plus one per single-key cold-path op.  Which code runs their
# probe, gather and scatter is decided when the jitted program is LOWERED
# (``jax.lax.platform_dependent``), never by a caller:
#
#   * lowered for a TPU, the Pallas kernels run compiled
#     (``tac_probe_gather``, ``page_gather_kernel``,
#     ``page_scatter_kernel``) — no interpreter, no reference op;
#   * lowered for any other platform (the CPU test suite), the kernels'
#     pure-jnp reference ops run fused into the surrounding XLA program.
#     The Pallas interpreter would emulate the kernel grid step by step,
#     orders of magnitude slower, and the semantics are bit-identical:
#     tests/test_kernels.py holds kernel and reference to each other.

def _probe_gather_ref(keys, dir_keys, dir_vals, pages):
    n_buckets, ways = dir_keys.shape
    trash = pages.shape[0] - 1
    if n_buckets == 1:
        # fully-associative fast path (every FusedPlane directory):
        # membership is a broadcast compare against the one bucket, and
        # first-match resolves via iota-min — argmax lowers ~3x slower
        # on the CPU backend, and the directory-vals gather the generic
        # probe does is dead weight here (payloads live in the pool)
        match = dir_keys[0][None, :] == keys[:, None]
        iota = jnp.arange(ways, dtype=jnp.int32)
        way = jnp.min(jnp.where(match, iota, ways), axis=1)
        hit = way < ways
        slots = jnp.where(hit, jnp.minimum(way, ways - 1),
                          trash).astype(jnp.int32)
    else:
        buckets = bucket_of(keys, n_buckets)
        _, hiti, way = tac_probe_ref(keys.astype(jnp.int32), buckets,
                                     dir_keys, dir_vals)
        hit = hiti.astype(bool)
        slots = jnp.where(hit, buckets * ways + jnp.maximum(way, 0),
                          trash).astype(jnp.int32)
    return page_gather_ref(slots, pages), hit, slots


def _probe_gather(keys, state: "TACState", pages):
    return jax.lax.platform_dependent(
        keys, state.keys, state.vals, pages,
        tpu=lambda k, dk, dv, p: tac_probe_gather(k, dk, p),
        default=_probe_gather_ref)


def _gather(slots, pages):
    return jax.lax.platform_dependent(
        slots, pages, tpu=page_gather_kernel, default=page_gather_ref)


def _scatter_ref(slots, blocks, pages):
    # last-write-wins matching the kernel's grid order: non-final writes
    # to a duplicated slot redirect to the scratch row (the pool's last
    # row, which fused callers keep zeroed / overwrite before reading)
    B = slots.shape[0]
    idx = jnp.arange(B)
    later = (slots[None, :] == slots[:, None]) & \
        (idx[None, :] > idx[:, None])
    eff = jnp.where(later.any(axis=1), pages.shape[0] - 1, slots)
    return pages.at[eff].set(blocks)


def _scatter(slots, blocks, pages):
    return jax.lax.platform_dependent(
        slots, blocks, pages, tpu=page_scatter_kernel,
        default=_scatter_ref)


# Host <-> device interface (§14): on the chip each host array a call
# hands the device, and each result array the host reads back, costs a
# fixed fraction of a millisecond whatever its size, against tens of
# microseconds of device work per call.  So every fused entry point packs
# its per-lane inputs into ONE int32 slab on the host, and ``fused_step``
# returns its per-lane outputs as ONE int32 slab the host reads in one
# transfer.  float32 columns cross as their bit patterns
# (``ndarray.view`` on the host, ``lax.bitcast_convert_type`` in the
# program), bools as 0/1: every value arrives bit-exact.  The public
# entry points keep their per-array signatures (and ``.lower``): the
# packing is a host-side wrapper around one jitted program each.

def _bits(x) -> np.ndarray:
    """float32 values as their int32 bit patterns (a view, not a cast)."""
    return np.asarray(x, np.float32).view(np.int32)


def _pack(*cols) -> np.ndarray:
    """Per-lane columns ([B] or [B, k]) side by side in one int32 slab."""
    return np.column_stack(cols).astype(np.int32, copy=False)


def _slab_spec(like, width: int) -> jax.ShapeDtypeStruct:
    """The slab an entry point's ``.lower`` stands in for its columns:
    rows from ``like``, on ``like``'s sharding where it has one."""
    return jax.ShapeDtypeStruct((like.shape[0], width), jnp.int32,
                                sharding=getattr(like, "sharding", None))


def _f32(cols) -> jax.Array:
    return jax.lax.bitcast_convert_type(cols, jnp.float32)


def _program(name: str):
    """jit ``fn`` as the one device module ``jit_<name>``."""
    def named(fn):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)
    return named


def _unpack_step_out(out: np.ndarray) -> dict:
    """Decode ``fused_step``'s host slab ``[B + 1, V + 3]``: per lane
    ``hit, slots, present, new_vals bits``; ``tallies`` in the last row."""
    lanes = out[:-1]
    return {"hit": lanes[:, 0] != 0, "slots": lanes[:, 1],
            "present": lanes[:, 2] != 0,
            "new_vals": lanes[:, 3:].view(np.float32),
            "tallies": out[-1, :2]}


@jax.tree_util.register_pytree_node_class
class FusedStep:
    """One fused batch's results.  ``state`` and ``pages`` stay on the
    device; ``out`` is the per-lane output slab, still on the device
    until the first read of a lane field, which copies it to the host
    ONCE (``read``) and decodes:

      hit       [B] bool   (padding lanes forced False)
      slots     [B] int32  flat slot; scratch for miss/padding
      new_vals  [B, V]     value AFTER this lane's update,
                           prefix-composed over earlier same-key lanes
      present   [B] bool   presence flag after this lane
      tallies   [2] int32  (hits, misses) over valid lanes
    """
    FIELDS = ("hit", "slots", "new_vals", "present", "tallies")

    def __init__(self, state: TACState, pages: jax.Array, out: jax.Array,
                 host: dict = None):
        self.state, self.pages, self.out = state, pages, out
        self._host = host

    def read(self) -> dict:
        """The lane fields on the host: one transfer, decoded once."""
        if self._host is None:
            self._host = _unpack_step_out(np.asarray(self.out))
        return self._host

    hit = property(lambda self: self.read()["hit"])
    slots = property(lambda self: self.read()["slots"])
    new_vals = property(lambda self: self.read()["new_vals"])
    present = property(lambda self: self.read()["present"])
    tallies = property(lambda self: self.read()["tallies"])

    def _replace(self, **kw) -> "FusedStep":
        """A copy with fields replaced, as ``NamedTuple._replace``; a
        replaced lane field reads back as given."""
        state = kw.pop("state", self.state)
        pages = kw.pop("pages", self.pages)
        if set(kw) - set(self.FIELDS):
            raise ValueError(f"no FusedStep fields {sorted(kw)}")
        host = {**self.read(), **kw} if kw else self._host
        return FusedStep(state, pages, self.out, host)

    def tree_flatten(self):
        return (self.state, self.pages, self.out, self._host), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def _fused_step(state, pages, keys, ts, weights, fire, valid, kind):
    """The step's device compute over per-lane arrays; returns
    ``(state, pages, hit, slots, new_vals, present, tallies)``."""
    B = keys.shape[0]
    n_buckets, ways = state.keys.shape
    trash = pages.shape[0] - 1
    rows, hit, slots = _probe_gather(keys, state, pages)
    hit = hit & valid
    slots = jnp.where(hit, slots, trash)
    safe_b = jnp.where(hit, slots // ways, 0)
    safe_w = jnp.where(hit, slots % ways, 0)
    # timestamp refresh on hits (advisory fp32 copy; the fp64 eviction
    # order lives in the host shadow, §14)
    new_ts = state.ts.at[safe_b, safe_w].max(
        jnp.where(hit, ts, -jnp.inf))
    g = rows[:, 0, 1:]                         # [B, V] current value
    f = rows[:, 0, 0] > 0.5                    # [B] presence
    if kind == "read":
        upd = jnp.zeros_like(hit)
    else:
        upd = hit & ~fire
    same = keys[:, None] == keys[None, :]
    M = same & upd[None, :] & jnp.tril(jnp.ones((B, B), bool))
    hasupd = M.any(axis=1)
    if kind == "max":
        m = jnp.where(M[:, :, None], weights[None, :, :],
                      -jnp.inf).max(axis=1)
        new_v = jnp.maximum(jnp.where(f[:, None], g, -jnp.inf), m)
    else:                                      # sum (count = sum of ones)
        # HIGHEST: the TPU's default matmul precision rounds f32 operands
        # to bf16, exact only for unit weights
        new_v = jnp.where(f[:, None], g, 0.0) + jnp.matmul(
            M.astype(weights.dtype), weights,
            precision=jax.lax.Precision.HIGHEST)
    present = f | hasupd
    new_v = jnp.where(present[:, None], new_v, 0.0)
    dirty = state.dirty
    if kind != "read":
        blocks = jnp.concatenate(
            [present[:, None].astype(pages.dtype),
             new_v.astype(pages.dtype)], axis=1)[:, None, :]
        wslots = jnp.where(upd, slots, trash)
        pages = _scatter(wslots, blocks, pages)
        # the scratch row must stay "absent" for future miss gathers
        pages = pages.at[trash].set(0.0)
        d_int = state.dirty.astype(jnp.int32).at[safe_b, safe_w].max(
            jnp.where(upd, 1, 0))
        dirty = d_int > 0
    tallies = jnp.stack([hit.sum(), (valid & ~hit).sum()]
                        ).astype(jnp.int32)
    return (state._replace(ts=new_ts, dirty=dirty), pages,
            hit, slots, new_v, present, tallies)


def _step_program(kind: str):
    @_program(f"fused_step_{kind}")
    def step(state, pages, slab):
        # slab [B, V + 4]: keys | ts bits | weights bits (V) | fire | valid
        V = slab.shape[1] - 4
        state, pages, hit, slots, new_v, present, tallies = _fused_step(
            state, pages, slab[:, 0], _f32(slab[:, 1]),
            _f32(slab[:, 2:V + 2]), slab[:, V + 2] != 0,
            slab[:, V + 3] != 0, kind)
        # out [B + 1, V + 3]: hit | slots | present | new_vals bits (V),
        # then one row holding the tallies
        lanes = jnp.concatenate(
            [hit[:, None].astype(jnp.int32), slots[:, None],
             present[:, None].astype(jnp.int32),
             jax.lax.bitcast_convert_type(new_v.astype(jnp.float32),
                                          jnp.int32)], axis=1)
        tail = jnp.zeros((1, V + 3), jnp.int32).at[0, :2].set(tallies)
        return state, pages, jnp.concatenate([lanes, tail], axis=0)
    return step


_FUSED_STEPS = {k: _step_program(k) for k in ("sum", "max", "read")}


def _step_slab(keys, ts, weights, fire, valid) -> np.ndarray:
    B = np.shape(keys)[0]
    return _pack(keys, _bits(ts), _bits(weights).reshape(B, -1), fire,
                 valid)


def fused_step(state: TACState, pages: jax.Array, keys: jax.Array,
               ts: jax.Array, weights: jax.Array, fire: jax.Array,
               valid: jax.Array, *, kind: str = "sum") -> FusedStep:
    """One fused batch over the resident working set.

    ``kind`` picks the operator compute: ``sum`` (count is sum of ones),
    ``max``, or ``read`` (no state update, read-only enrichment).  Each
    kind is its own jitted program named after it (``fused_step_sum``,
    ``fused_step_max``, ``fused_step_read``), so a device trace tells
    the planes of one engine apart; ``fused_step.lower(..., kind=)``
    lowers the kind's program.  ``weights`` is ``[B, V]``; ``fire``
    lanes read the pane without updating it.  The five lane arrays go to
    the device as one slab and the lane results come back as one
    (``FusedStep``).

    Duplicate keys in one batch compose EXACTLY as the interpreted
    sequential loop: lane i's ``new_vals`` folds in every earlier
    same-key update lane (lower-triangular mask), and the scatter's
    last-write-wins grid order leaves the final composed value in the
    pool.  The batching contract (streaming/fused.py) never mixes a fire
    lane and an update lane of the same key in one batch.

    Miss lanes are NOT admitted here — the host parks their tuples and
    admissions arrive later through ``fused_admit`` (the asynchronous
    fetch path, DESIGN.md §2) — so a miss lane's only trace is its tally.
    """
    return FusedStep(*_FUSED_STEPS[kind](
        state, pages, _step_slab(keys, ts, weights, fire, valid)))


def _lower_fused_step(state, pages, keys, ts, weights, fire, valid, *,
                      kind: str = "sum"):
    return _FUSED_STEPS[kind].lower(
        state, pages, _slab_spec(keys, weights.shape[1] + 4))


fused_step.lower = _lower_fused_step


def _fused_admit(state, pages, slots, keys, ts, rows, present, dirty):
    """The admit's device compute over per-lane arrays."""
    n_buckets, ways = state.keys.shape
    b, w = slots // ways, slots % ways
    victim_rows = _gather(slots, pages)
    blocks = jnp.concatenate(
        [present[:, None].astype(pages.dtype),
         rows.astype(pages.dtype)], axis=1)[:, None, :]
    new_pages = _scatter(slots, blocks, pages)
    # duplicate pads spill their non-final writes into the scratch row;
    # it must read as "absent" for future miss/padding gathers
    new_pages = new_pages.at[-1].set(0.0)
    st = TACState(
        keys=state.keys.at[b, w].set(keys.astype(jnp.int32)),
        ts=state.ts.at[b, w].set(ts.astype(jnp.float32)),
        vals=state.vals,
        dirty=state.dirty.at[b, w].set(dirty))
    return st, new_pages, victim_rows


@_program("fused_admit")
def _admit_program(state: TACState, pages: jax.Array, slab: jax.Array):
    # slab [B, V + 5]: slots | keys | ts bits | present | dirty | rows (V)
    return _fused_admit(state, pages, slab[:, 0], slab[:, 1],
                        _f32(slab[:, 2]), _f32(slab[:, 5:]),
                        slab[:, 3] != 0, slab[:, 4] != 0)


def fused_admit(state: TACState, pages: jax.Array, slots: jax.Array,
                keys: jax.Array, ts: jax.Array, rows: jax.Array,
                present: jax.Array, dirty: jax.Array):
    """Admit at HOST-CHOSEN slots (the shadow directory resolved victims
    and free slots; a slot may repeat only as an IDENTICAL padding
    duplicate of an earlier lane — chunked flushes pad to fixed jit
    shapes that way).  Gathers the pre-overwrite victim rows first — a
    dirty victim's value feeds the eviction buffer for asynchronous
    write-back — then scatters the new rows and updates the device
    directory.  The six lane arrays go to the device as one slab.
    Returns ``(state, pages, victim_rows [B, 1, V+1])``."""
    B = np.shape(slots)[0]
    return _admit_program(state, pages, _pack(
        slots, keys, _bits(ts), present, dirty, _bits(rows).reshape(B, -1)))


def _lower_fused_admit(state, pages, slots, keys, ts, rows, present,
                       dirty):
    return _admit_program.lower(state, pages,
                                _slab_spec(slots, rows.shape[1] + 5))


fused_admit.lower = _lower_fused_admit


def _drop_slots(state, slots, valid):
    """The directory clear's device compute over per-lane arrays."""
    ways = state.keys.shape[1]
    b, w = slots // ways, slots % ways
    imax = jnp.iinfo(jnp.int32).max
    keys = state.keys.at[b, w].min(
        jnp.where(valid, jnp.int32(-1), imax))
    ts = state.ts.at[b, w].min(
        jnp.where(valid, -jnp.inf, jnp.inf))
    d_int = state.dirty.astype(jnp.int32).at[b, w].min(
        jnp.where(valid, 0, 1))
    return state._replace(keys=keys, ts=ts, dirty=d_int > 0)


@_program("drop_slots")
def _drop_program(state: TACState, slab: jax.Array) -> TACState:
    # slab [B, 2]: slots | valid
    return _drop_slots(state, slab[:, 0], slab[:, 1] != 0)


def drop_slots(state: TACState, slots: jax.Array,
               valid: jax.Array) -> TACState:
    """Clear directory entries at host-chosen slots (window-pane purges,
    drops).  Padding lanes (``valid`` False) alias slot 0, so the
    clears use masked min/max scatters that are idempotent no-ops for
    them.  Pool rows are left stale: a cleared slot can no longer be
    probed, and the next ``fused_admit`` overwrites the row.  Both lane
    arrays go to the device as one slab."""
    return _drop_program(state, _pack(slots, valid))


def _lower_drop_slots(state, slots, valid):
    return _drop_program.lower(state, _slab_spec(slots, 2))


drop_slots.lower = _lower_drop_slots


@jax.jit
def gather_rows(pages: jax.Array, slots: jax.Array) -> jax.Array:
    """Pull payload rows at flat slots (single-key adapter reads)."""
    return _gather(slots, pages)
