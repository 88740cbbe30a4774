"""Per-kernel validation: shape/dtype sweeps vs the ref.py pure-jnp oracles
(kernels execute in interpret mode — Python on CPU — per the brief)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

RNG = jax.random.PRNGKey(0)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 2e-5


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("S,H,KV,d,bq,bk", [
    (128, 4, 2, 32, 64, 64),
    (256, 2, 2, 64, 64, 128),
    (128, 4, 1, 16, 128, 32),      # MQA, uneven blocks
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(S, H, KV, d, bq, bk, dtype, causal):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    B = 2
    q = jax.random.normal(RNG, (B, S, H, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, d), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=bq, bk=bk)
    G = H // KV
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), G, 1).reshape(B * H, S, d)
    ref = attention_ref(qf, kf, vf, causal=causal) \
        .reshape(B, H, S, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


# ------------------------------------------------------ paged decode attention
@pytest.mark.parametrize("B,H,d,page,P", [
    (3, 8, 32, 16, 4),
    (2, 4, 64, 32, 2),
    (4, 16, 16, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, H, d, page, P, dtype):
    from repro.kernels.decode_attention.ops import paged_decode_attention
    from repro.kernels.decode_attention.ref import paged_decode_ref
    slots = B * P + 3
    q = jax.random.normal(RNG, (B, H, d), dtype)
    kp = jax.random.normal(jax.random.PRNGKey(1), (slots, page, d), dtype)
    vp = jax.random.normal(jax.random.PRNGKey(2), (slots, page, d), dtype)
    pt = jax.random.permutation(jax.random.PRNGKey(3),
                                slots)[:B * P].reshape(B, P)
    lens = jax.random.randint(jax.random.PRNGKey(4), (B,), 1, P * page + 1)
    out = paged_decode_attention(q, kp, vp, pt, lens)
    ref = paged_decode_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


# -------------------------------------------------------------------- tac probe
@pytest.mark.parametrize("nb,ways,D,B", [(16, 8, 64, 32), (8, 4, 128, 16),
                                         (32, 16, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tac_probe(nb, ways, D, B, dtype):
    from repro.kernels.tac_probe.ops import bucket_of, tac_probe
    from repro.kernels.tac_probe.ref import tac_probe_ref
    rng = np.random.RandomState(0)
    bkeys = rng.choice(10_000, size=(nb, ways), replace=False) \
        .astype(np.int32)
    bvals = rng.randn(nb, ways, D).astype(np.float32)
    qk = np.where(np.arange(B) % 2 == 0,
                  rng.randint(1, 100_000, B), -(7 + np.arange(B))) \
        .astype(np.int32)
    bks = np.asarray(bucket_of(jnp.asarray(qk), nb))
    next_way = {}
    planted = 0
    for i in range(0, B, 2):          # plant hits in the hashed bucket
        wslot = next_way.get(bks[i], 0)
        if wslot < ways:
            bkeys[bks[i], wslot] = qk[i]
            next_way[bks[i]] = wslot + 1
            planted += 1
    bvals_j = jnp.asarray(bvals).astype(dtype)
    out_v, out_h, out_w = tac_probe(jnp.asarray(qk), jnp.asarray(bkeys),
                                    bvals_j)
    ref_v, ref_h, ref_w = tac_probe_ref(jnp.asarray(qk), jnp.asarray(bks),
                                        jnp.asarray(bkeys), bvals_j)
    assert (np.asarray(out_h) == np.asarray(ref_h)).all()
    assert (np.asarray(out_w) == np.asarray(ref_w)).all()
    np.testing.assert_allclose(np.asarray(out_v, np.float32),
                               np.asarray(ref_v, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))
    assert int(out_h.sum()) >= planted


# ------------------------------------------------------------------ cms sketch
@pytest.mark.parametrize("d,w,B", [(4, 256, 64), (2, 512, 128), (4, 128, 32)])
def test_cms_sketch(d, w, B):
    from repro.kernels.cms_sketch.ops import (cms_update_and_classify,
                                              columns_for)
    from repro.kernels.cms_sketch.ref import cms_update_ref
    rng = np.random.RandomState(1)
    a = jnp.asarray(rng.randint(1, 2 ** 31, d), dtype=jnp.uint32)
    b = jnp.asarray(rng.randint(0, 2 ** 31, d), dtype=jnp.uint32)
    keys = np.concatenate([np.full(20, 42), rng.randint(0, 1000, B - 20)])
    rng.shuffle(keys)
    keys = keys.astype(np.int32)
    counters0 = jnp.zeros((d, w), jnp.int32)
    new_c, hot = cms_update_and_classify(jnp.asarray(keys), counters0, a, b,
                                         threshold=5)
    cols = np.asarray(columns_for(jnp.asarray(keys), a, b, w))
    ref_c, ref_est = cms_update_ref(cols, np.zeros((d, w), np.int32))
    assert (np.asarray(new_c) == ref_c).all()
    assert (np.asarray(hot) == (ref_est >= 5).all(axis=0)).all()
    # the heavy hitter must be classified hot by its last occurrence
    last42 = np.where(keys == 42)[0][-1]
    assert bool(hot[last42])


def test_cms_sketch_saturation_and_aging_protocol():
    from repro.kernels.cms_sketch.ops import cms_update_and_classify
    d, w = 2, 64
    a = jnp.asarray([3, 7], dtype=jnp.uint32)
    b = jnp.asarray([1, 5], dtype=jnp.uint32)
    counters = jnp.full((d, w), 250, jnp.int32)
    keys = jnp.asarray(np.full(32, 9, np.int32))
    new_c, hot = cms_update_and_classify(keys, counters, a, b, threshold=10)
    assert int(new_c.max()) <= 255                 # saturating
    aged = new_c >> 1                              # caller-side aging
    assert int(aged.max()) <= 127


# ------------------------------------------------------------------ ssm scans
@pytest.mark.parametrize("S,P,N,chunk", [(128, 16, 8, 32), (64, 32, 16, 64),
                                         (256, 8, 4, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba2_scan(S, P, N, chunk, dtype):
    from repro.kernels.mamba2_scan.ops import mamba2_scan
    from repro.kernels.mamba2_scan.ref import mamba2_scan_ref
    BH = 3
    x = jax.random.normal(RNG, (BH, S, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(jax.random.PRNGKey(1),
                                           (BH, S))).astype(dtype)
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(2), (BH,)) * 0.5)
    Bm = jax.random.normal(jax.random.PRNGKey(3), (BH, S, N), dtype)
    Cm = jax.random.normal(jax.random.PRNGKey(4), (BH, S, N), dtype)
    out = mamba2_scan(x, dt, A, Bm, Cm, chunk=chunk)
    ref = mamba2_scan_ref(x, dt, A, Bm, Cm)
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-9
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))) / scale
    assert rel < (5e-2 if dtype == jnp.bfloat16 else 1e-4), rel


@pytest.mark.parametrize("S,N,chunk", [(128, 8, 32), (64, 16, 64),
                                       (96, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan(S, N, chunk, dtype):
    from repro.kernels.rwkv6_scan.ops import rwkv6_scan
    from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref
    BH = 3
    r = jax.random.normal(RNG, (BH, S, N), dtype)
    k = (jax.random.normal(jax.random.PRNGKey(5), (BH, S, N)) * 0.3) \
        .astype(dtype)
    v = jax.random.normal(jax.random.PRNGKey(6), (BH, S, N), dtype)
    w = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(7),
                                         (BH, S, N))).astype(dtype)
    u = (jax.random.normal(jax.random.PRNGKey(8), (BH, N)) * 0.1) \
        .astype(dtype)
    out = rwkv6_scan(r, k, v, w, u, chunk=chunk)
    ref = rwkv6_scan_ref(r, k, v, w, u)
    scale = float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) + 1e-9
    rel = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32)))) / scale
    assert rel < (5e-2 if dtype == jnp.bfloat16 else 1e-4), rel


@pytest.mark.parametrize("kind", ["sum", "max", "read"])
def test_fused_step_composition(kind):
    """fused_step = tac_probe_gather ∘ operator compute ∘ page_scatter in
    one program; duplicate keys must compose exactly as a sequential
    per-lane loop (DESIGN.md §14)."""
    from repro.core import tac_jax
    W, V, B = 8, 2, 6
    state = tac_jax.init(1, W, 1)
    pages = jnp.zeros((W + 1, 1, V + 1), jnp.float32)
    # admit keys 0..3 at slots 0..3 with seed values
    seed = np.arange(1, 4 * V + 1, dtype=np.float32).reshape(4, V)
    state, pages, _ = tac_jax.fused_admit(
        state, pages, jnp.arange(4, dtype=jnp.int32),
        jnp.arange(4, dtype=jnp.int32),
        jnp.zeros(4, jnp.float32), jnp.asarray(seed),
        jnp.ones(4, bool), jnp.zeros(4, bool))
    # batch: dup key 1 (composes), key 2 fire (reads only), key 7 miss,
    # one padding lane
    keys = jnp.asarray([1, 1, 2, 7, 1, -2], jnp.int32)
    ts = jnp.full(B, 5.0, jnp.float32)
    wts = jnp.asarray(
        np.arange(1, B * V + 1, dtype=np.float32).reshape(B, V))
    fire = jnp.asarray([0, 0, 1, 0, 0, 0], bool)
    valid = jnp.asarray([1, 1, 1, 1, 1, 0], bool)
    out = tac_jax.fused_step(state, pages, keys, ts, wts, fire, valid,
                             kind=kind)
    hit = np.asarray(out.hit)
    assert hit.tolist() == [True, True, True, False, True, False]
    assert np.asarray(out.tallies).tolist() == [4, 1]
    # sequential reference over the same lanes
    vals = {k: seed[k].copy() for k in range(4)}
    ref = []
    for i in range(B):
        k = int(keys[i])
        if not hit[i]:
            ref.append(np.zeros(V, np.float32))
            continue
        if kind != "read" and not bool(fire[i]):
            w = np.asarray(wts[i])
            vals[k] = np.maximum(vals[k], w) if kind == "max" \
                else vals[k] + w
        ref.append(vals[k].copy())
    np.testing.assert_allclose(np.asarray(out.new_vals), np.stack(ref),
                               rtol=1e-6)
    # pool holds the final composed value; scratch row stays absent
    pool = np.asarray(out.pages)
    expect = seed[1] if kind == "read" else vals[1]
    np.testing.assert_allclose(pool[1, 0, 1:], expect, rtol=1e-6)
    assert pool[-1].sum() == 0.0
    # fire lane never dirties; update lanes do (except read kind)
    dirty = np.asarray(out.state.dirty)[0]
    assert not dirty[2]
    assert bool(dirty[1]) == (kind != "read")
    # drop then re-probe: membership cleared, pool row stale-but-dead
    st2 = tac_jax.drop_slots(out.state, jnp.asarray([1, 0], jnp.int32),
                             jnp.asarray([True, False], bool))
    out2 = tac_jax.fused_step(st2, out.pages, keys, ts, wts, fire, valid,
                              kind=kind)
    assert np.asarray(out2.hit).tolist() == [False, False, True, False,
                                             False, False]


def test_fused_step_sum_exact_for_fractional_weights():
    """Sum weights that bf16 cannot hold compose in f32: the in-batch
    prefix sums equal a sequential f32 fold, which a matmul at the TPU's
    default precision (f32 operands rounded to bf16) would miss."""
    from repro.core import tac_jax
    W, V, B = 8, 3, 16
    state = tac_jax.init(1, W, 1)
    pages = jnp.zeros((W + 1, 1, V + 1), jnp.float32)
    seed = np.asarray([[0.1, 1e3 + 0.3, -2.7]], np.float32)
    state, pages, _ = tac_jax.fused_admit(
        state, pages, jnp.zeros(1, jnp.int32), jnp.asarray([5], jnp.int32),
        jnp.zeros(1, jnp.float32), jnp.asarray(seed), jnp.ones(1, bool),
        jnp.zeros(1, bool))
    rng = np.random.RandomState(3)
    wts = (rng.randn(B, V) * 10 + 1 / 3).astype(np.float32)
    out = tac_jax.fused_step(state, pages, jnp.full(B, 5, jnp.int32),
                             jnp.ones(B, jnp.float32), jnp.asarray(wts),
                             jnp.zeros(B, bool), jnp.ones(B, bool),
                             kind="sum")
    acc, ref = seed[0].copy(), []
    for w in wts:
        acc = acc + w
        ref.append(acc.copy())
    np.testing.assert_allclose(np.asarray(out.new_vals), np.stack(ref),
                               rtol=1e-6, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out.pages)[0, 0, 1:], ref[-1],
                               rtol=1e-6, atol=1e-4)


# ------------------------------------- fused entry points: one slab each way
def _seeded_pool(W, V):
    """A directory of W slots holding keys 0..5 at slots 0..5, one of them
    absent (never written), with fractional and negative values."""
    from repro.core import tac_jax
    state = tac_jax.init(1, W, 1)
    pages = jnp.zeros((W + 1, 1, V + 1), jnp.float32)
    seed = (np.arange(6 * V, dtype=np.float32).reshape(6, V) - 4.5) / 3
    return tac_jax.fused_admit(
        state, pages, np.arange(6, dtype=np.int32),
        np.arange(6, dtype=np.int32),
        np.asarray([0.5, -np.inf, 2.0, 1.0, 3.5, 0.25], np.float32), seed,
        np.asarray([1, 1, 0, 1, 1, 1], bool), np.zeros(6, bool))[:2]


def _bits_equal(a, b):
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    assert a.shape == b.shape and a.dtype == b.dtype
    assert np.array_equal(a.view(np.uint8), b.view(np.uint8))


def _slab_and_per_array(program, W=16, V=2):
    """(entry point's outputs, the same compute jitted with one argument
    per lane array) as flat lists of arrays, over lanes that cover
    padding (PAD_KEY, invalid or duplicate-padded), -inf timestamps,
    fractional and negative values, fire lanes and duplicate keys."""
    import functools
    from repro.core import tac_jax
    state, pages = _seeded_pool(W, V)
    rng = np.random.RandomState(5)
    if program.startswith("fused_step"):
        kind = program.split(".")[1]
        keys = np.asarray([3, 3, 5, 9, 3, 4, 5, 0, 1, -2, -2, 3], np.int32)
        B = len(keys)
        ts = np.asarray([1.5, -np.inf, 2.0, 7.25, 3.0, -np.inf, 0.5, 4.0,
                         1.0, 0.0, 0.0, 2.5], np.float32)
        w = (rng.randn(B, V) * 3 + 1 / 3).astype(np.float32)
        fire = np.isin(keys, (4, 0))
        valid = keys != -2
        args = (keys, ts, w, fire, valid)
        out = tac_jax.fused_step(state, pages, *args, kind=kind)
        got = [out.state, out.pages, out.hit, out.slots, out.new_vals,
               out.present, out.tallies]
        ref = jax.jit(functools.partial(tac_jax._fused_step, kind=kind))(
            state, pages, *map(jnp.asarray, args))
        assert out.hit.any() and not out.hit.all()
    elif program == "fused_admit":
        n, Wc = 5, 8                   # a chunk padded by its first record
        slots = np.asarray([7, 2, 9, 0, 12] + [7] * (Wc - n), np.int32)
        keys = np.asarray([40, 41, 42, 43, 44] + [40] * (Wc - n), np.int32)
        ts = np.asarray([2.5, -np.inf, 0.75, 9.0, 1.0] + [2.5] * (Wc - n),
                        np.float32)
        rows = (rng.randn(Wc, V) - 1 / 7).astype(np.float32)
        rows[n:] = rows[0]
        present = np.asarray([1, 0, 1, 1, 1] + [1] * (Wc - n), bool)
        dirty = np.asarray([0, 1, 1, 0, 1] + [0] * (Wc - n), bool)
        args = (slots, keys, ts, rows, present, dirty)
        got = tac_jax.fused_admit(state, pages, *args)
        ref = jax.jit(tac_jax._fused_admit)(state, pages,
                                            *map(jnp.asarray, args))
    else:
        slots = np.asarray([4, 1, 0, 0, 0, 0], np.int32)   # 0: padding
        valid = np.asarray([1, 1, 0, 0, 0, 0], bool)
        got = tac_jax.drop_slots(state, slots, valid)
        ref = jax.jit(tac_jax._drop_slots)(state, jnp.asarray(slots),
                                           jnp.asarray(valid))
    return jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)


@pytest.mark.parametrize("program", ["fused_step.sum", "fused_step.max",
                                     "fused_step.read", "fused_admit",
                                     "drop_slots"])
def test_slab_entry_point_is_bit_identical_to_per_array(program):
    """Packing the lanes into one int32 slab (floats by bit pattern) and
    unpacking the step's one output slab changes no bit of any result:
    state, pool, and every per-lane output."""
    got, ref = _slab_and_per_array(program)
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        _bits_equal(g, r)


@pytest.mark.parametrize("program", ["fused_step", "fused_admit",
                                     "drop_slots"])
def test_lowered_entry_point_takes_one_lane_slab(program):
    """Each lowered program takes the state (and pool) plus ONE array of
    lanes; the step gives the state and pool back plus ONE array."""
    from repro.core import tac_jax
    W, V, B = 16, 2, 8
    state = tac_jax.init(1, W, 1)
    pages = jnp.zeros((W + 1, 1, V + 1), jnp.float32)
    i32, f32 = np.zeros(B, np.int32), np.zeros(B, np.float32)
    flags, rows = np.zeros(B, bool), np.zeros((B, V), np.float32)
    n_state = len(jax.tree_util.tree_leaves(state))
    if program == "fused_step":
        low = tac_jax.fused_step.lower(state, pages, i32, f32, rows, flags,
                                       flags, kind="sum")
        width, fixed = V + 4, n_state + 1
        out = jax.tree_util.tree_leaves(low.out_info)
        assert len(out) == n_state + 2
        assert (out[-1].shape, out[-1].dtype) == ((B + 1, V + 3), jnp.int32)
    elif program == "fused_admit":
        low = tac_jax.fused_admit.lower(state, pages, i32, i32, f32, rows,
                                        flags, flags)
        width, fixed = V + 5, n_state + 1
    else:
        low = tac_jax.drop_slots.lower(state, i32, flags)
        width, fixed = 2, n_state
    args = jax.tree_util.tree_leaves(low.args_info)
    assert len(args) == fixed + 1
    assert (args[-1].shape, args[-1].dtype) == ((B, width), jnp.int32)
    assert f"module @jit_{program}" in low.as_text()
