"""Fused paged-attention op invariants (PR 11).

The op-level half of the fused-kernel contract (the engine-level
token pins live in tests/test_paged_kv.py): the blockwise ``lax``
formulation and the Pallas kernel (interpreter mode here; the real
Mosaic lowering is compiled for a described v5e in
tests/test_chip_compile.py and run by chip_smoke.py) must
match the gather reference to float accumulation noise on every query
shape the engine produces (decode s=1, fused prefill s>1, ragged
per-row positions, bucket-padded rows whose positions overshoot the
logical capacity) — and, the bandwidth claim itself, must provably
never READ a block outside a row's live set: pool rows no live block
maps to are poisoned with NaN and the fused outputs must not change.
(The gather reference deliberately fails that poison test — it reads
everything and masks, which is the formulation this kernel exists to
replace.)
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pa = importlib.import_module(
    "tensorflowonspark_tpu.ops.paged_attention")


def _case(seed, b=3, s_q=1, n=4, d=16, pool=11, bs=8, mb=4):
    """Random pools (flat, ``[pool, bs, n * d]``: the op's contract)
    + per-row tables and positions; every row's table entries are
    distinct allocated rows (no scratch aliasing) so the live-set
    accounting in the poison test is exact."""
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, s_q, n, d), jnp.float32)
    kp = jnp.asarray(rng.randn(pool, bs, n * d), jnp.float32)
    vp = jnp.asarray(rng.randn(pool, bs, n * d), jnp.float32)
    table = np.stack([rng.choice(np.arange(1, pool), size=mb,
                                 replace=False) for _ in range(b)])
    # each row at its own depth; positions cover first/mid/last block
    base = rng.randint(0, mb * bs - s_q, size=b)
    pos = base[:, None] + np.arange(s_q)[None, :]
    return q, kp, vp, jnp.asarray(table, jnp.int32), \
        jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("s_q", [1, 8])
def test_blockwise_matches_gather_reference(s_q):
    for seed in range(3):
        q, kp, vp, table, pos = _case(seed, s_q=s_q)
        ref = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
        blk = pa.paged_attention(q, kp, vp, table, pos,
                                 impl="blockwise")
        np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("s_q", [1, 8])
def test_pallas_interpret_matches_gather_reference(s_q):
    q, kp, vp, table, pos = _case(7, s_q=s_q)
    ref = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
    pal = pa.paged_attention(q, kp, vp, table, pos, impl="pallas",
                             interpret=True)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("n,d", [(4, 16), (3, 24)])
def test_lane_sliced_heads_match_gather_off_the_lane_tile(n, d):
    """The kernel reads head ``h`` as lanes ``[h*D, (h+1)*D)`` of a
    flat block. At 4 heads of 16 a block row is 64 lanes, at 3 heads
    of 24 it is 72 and no head starts on anything round: neither is a
    multiple of the chip's 128. Every head must still come from ITS
    lanes: V of head h is put on a scale of 10**h, so a head read
    from a neighbour's lanes is off by a factor, not by accumulation
    noise."""
    q, kp, vp, table, pos = _case(13, s_q=8, n=n, d=d)
    mags = 10.0 ** np.arange(n)
    vp = vp * jnp.repeat(jnp.asarray(mags, jnp.float32), d)
    ref = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
    pal = pa.paged_attention(q, kp, vp, table, pos, impl="pallas",
                             interpret=True)
    per_head = mags[None, None, :, None]
    np.testing.assert_allclose(np.asarray(pal) / per_head,
                               np.asarray(ref) / per_head,
                               atol=2e-6, rtol=2e-6)
    # the oracle keeps heads apart too: head h is on head h's scale
    seen = np.abs(np.asarray(ref)).max(axis=(0, 1, 3))
    assert np.all(seen > 0.1 * mags) and np.all(seen < 10 * mags), seen


@pytest.mark.parametrize("s_q", [1, 8])
def test_int8_scales_on_scores_match_dequantized_gather(s_q):
    """On int8 pools the kernel never scales K or V: it lays a head's
    scales on its scores and its probabilities (``q.(c*s) = (q.c)*s``,
    ``p@(c*s) = (p*s)@c``). The same products in another order, so it
    must agree to accumulation noise with the plain form: the pools
    dequantized whole by ``dequantize_kv``, then ``_gather`` on the
    floats. Heads on scales a factor of ten apart, so a head under a
    neighbour's scale is off by that factor."""
    q, kp, vp, table, pos = _case(17, s_q=s_q)
    n, d = q.shape[2:]
    mags = 10.0 ** np.arange(n)
    vp = vp * jnp.repeat(jnp.asarray(mags, jnp.float32), d)
    (qk, sk), (qv, sv) = (_quantize_pool(p, q) for p in (kp, vp))
    plain_k, plain_v = (
        pa.dequantize_kv(c.reshape(s.shape + (d,)), s).reshape(c.shape)
        for c, s in ((qk, sk), (qv, sv)))
    ref = pa._gather(q, plain_k, plain_v, table, pos, d ** -0.5)
    pal = pa.paged_attention(q, qk, qv, table, pos, impl="pallas",
                             interpret=True, k_scale=sk, v_scale=sv)
    per_head = mags[None, None, :, None]
    np.testing.assert_allclose(np.asarray(pal) / per_head,
                               np.asarray(ref) / per_head,
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("impl", [None, "gather", "blockwise", "pallas"])
def test_pool_with_heads_apart_is_refused(impl):
    """No formulation takes a ``[P, block, N, D]`` pool: reshaping it
    on entry IS the whole-pool relayout copy the flat pool exists to
    avoid. The error names the shape asked for and the shape given."""
    q, kp, vp, table, pos = _case(5)
    four_d = kp.shape[:2] + q.shape[2:]
    with pytest.raises(ValueError) as err:
        pa.paged_attention(q, kp.reshape(four_d), vp.reshape(four_d),
                           table, pos, impl=impl)
    assert "[P, 8, 64]" in str(err.value)
    assert str(four_d) in str(err.value)


def test_overshooting_pad_rows_match_reference():
    """Bucket-padded prefill rows carry positions PAST the logical
    capacity (their writes went to scratch); the fused formulations
    must clamp to the table width exactly like the gather view does —
    same (garbage, discarded) outputs for pad rows, same (real)
    outputs for live rows."""
    q, kp, vp, table, pos = _case(11, s_q=8, mb=3)
    pos = pos.at[2].set(20 + jnp.arange(8))  # rows 20..27 > L-1 = 23
    ref = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
    blk = pa.paged_attention(q, kp, vp, table, pos, impl="blockwise")
    pal = pa.paged_attention(q, kp, vp, table, pos, impl="pallas",
                             interpret=True)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("impl", ["blockwise", "pallas"])
def test_fused_never_reads_dead_blocks(impl):
    """THE bandwidth claim, falsifiably: poison every pool row outside
    the rows' live block sets with NaN — one read of a dead block
    would turn the whole output NaN (0 * NaN is NaN, so even a fully
    masked read poisons). Fused outputs must be bitwise-unchanged.
    The gather reference reads everything and masks, so it cannot
    pass this — which is exactly the transient-traffic difference the
    fused kernel exists for."""
    q, kp, vp, table, pos = _case(3)
    bs = kp.shape[1]
    kw = {"interpret": True} if impl == "pallas" else {}
    clean = pa.paged_attention(q, kp, vp, table, pos, impl=impl, **kw)
    live = set()
    for bi in range(q.shape[0]):
        nblk = (int(np.max(np.asarray(pos)[bi])) + bs) // bs
        live |= set(int(x) for x in np.asarray(table)[bi, :nblk])
    kpo = np.asarray(kp).copy()
    vpo = np.asarray(vp).copy()
    for row in range(kp.shape[0]):
        if row not in live:
            kpo[row] = np.nan
            vpo[row] = np.nan
    assert len(live) < kp.shape[0], "case must leave dead rows"
    out = pa.paged_attention(q, jnp.asarray(kpo), jnp.asarray(vpo),
                             table, pos, impl=impl, **kw)
    assert bool(jnp.all(jnp.isfinite(out)))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


def _int8_case(seed, **kw):
    """A float case quantized into int8 pools + per-head scales — all
    three impls dequantize the SAME codes, so their outputs must agree
    to accumulation noise (the int8 parity contract; the float-vs-int8
    ERROR is the engine-level agreement test's business)."""
    q, kp, vp, table, pos = _case(seed, **kw)
    qk, sk = _quantize_pool(kp, q)
    qv, sv = _quantize_pool(vp, q)
    return q, qk, qv, sk, sv, table, pos


def _quantize_pool(pool, q):
    """Per-head codes of a flat float pool, flat again, and their
    ``[pool, bs, n]`` scales — what models/decoder.py's write does."""
    codes, scales = pa.quantize_kv(
        pool.reshape(pool.shape[:2] + q.shape[2:]))
    return codes.reshape(pool.shape), scales


def test_quantize_kv_round_trip_exact():
    """The exact-round-trip fixed point: requantizing the dequantized
    grid reproduces codes AND scales bitwise (the absmax element maps
    to ±127 exactly), zero vectors quantize to zero codes under scale
    1.0, and the numpy mirror in paging.BlockPool agrees bitwise with
    the device op."""
    from tensorflowonspark_tpu import paging

    rng = np.random.RandomState(0)
    x = rng.randn(5, 8, 4, 16).astype(np.float32)
    x[1, 2, 3] = 0.0  # an all-zero head vector
    q1, s1 = pa.quantize_kv(jnp.asarray(x))
    deq = pa.dequantize_kv(q1, s1)
    q2, s2 = pa.quantize_kv(deq)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    assert np.asarray(s1)[1, 2, 3] == 1.0
    assert not np.asarray(q1)[1, 2, 3].any()
    # max quantization error is bounded by scale/2 per element
    err = np.abs(np.asarray(deq) - x)
    assert np.all(err <= np.asarray(s1)[..., None] / 2 + 1e-7)
    # host mirror == device op, bitwise
    hq, hs = paging.BlockPool.quantize(x)
    np.testing.assert_array_equal(hq, np.asarray(q1))
    np.testing.assert_array_equal(hs, np.asarray(s1))
    np.testing.assert_array_equal(
        paging.BlockPool.dequantize(hq, hs), np.asarray(deq))
    # and for float64 input: both sides must cast BEFORE dividing, or
    # the double-rounded scale shifts codes by ±1 between runtimes
    x64 = rng.randn(3, 4, 16)
    hq64, hs64 = paging.BlockPool.quantize(x64)
    dq64, ds64 = pa.quantize_kv(jnp.asarray(x64))
    np.testing.assert_array_equal(hq64, np.asarray(dq64))
    np.testing.assert_array_equal(hs64, np.asarray(ds64))


@pytest.mark.parametrize("s_q", [1, 4])
def test_int8_blockwise_and_pallas_match_gather(s_q):
    """int8 parity across formulations: gather dequantizes the
    materialized view, blockwise and the Pallas kernel (interpret —
    the tier-1 path for the in-kernel dequant) one block at a time;
    same codes, same scales, so outputs agree to accumulation
    noise."""
    for seed in range(3):
        q, qk, qv, sk, sv, table, pos = _int8_case(seed, s_q=s_q)
        ref = pa.paged_attention(q, qk, qv, table, pos, impl="gather",
                                 k_scale=sk, v_scale=sv)
        blk = pa.paged_attention(q, qk, qv, table, pos,
                                 impl="blockwise", k_scale=sk,
                                 v_scale=sv)
        pal = pa.paged_attention(q, qk, qv, table, pos, impl="pallas",
                                 interpret=True, k_scale=sk,
                                 v_scale=sv)
        np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-6, rtol=2e-6)


def test_int8_scales_validated_and_close_to_float():
    """One-sided scales are a loud error, and the dequantized
    attention lands close to the float original (the per-head absmax
    grid is fine enough that attention outputs move by quantization
    noise, not structure)."""
    q, kp, vp, table, pos = _case(9)
    qk, sk = _quantize_pool(kp, q)
    qv, sv = _quantize_pool(vp, q)
    with pytest.raises(ValueError, match="together"):
        pa.paged_attention(q, qk, qv, table, pos, k_scale=sk)
    ref = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
    i8 = pa.paged_attention(q, qk, qv, table, pos, impl="gather",
                            k_scale=sk, v_scale=sv)
    np.testing.assert_allclose(np.asarray(i8), np.asarray(ref),
                               atol=0.08, rtol=0.08)


def test_auto_dispatch_and_bad_impl():
    """Off-TPU the auto path IS the blockwise formulation (bitwise);
    unknown impls fail loudly."""
    q, kp, vp, table, pos = _case(5)
    auto = pa.paged_attention(q, kp, vp, table, pos)
    blk = pa.paged_attention(q, kp, vp, table, pos, impl="blockwise")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(blk))
    with pytest.raises(ValueError, match="impl"):
        pa.paged_attention(q, kp, vp, table, pos, impl="banana")


def test_jit_and_traced_operands():
    """The engine calls the op inside jitted step fns with traced
    tables/positions — pin that the blockwise formulation (a
    fori_loop whose trip count is traced on wide tables) traces and
    compiles clean."""
    q, kp, vp, table, pos = _case(6, s_q=1)
    fn = jax.jit(lambda *a: pa.paged_attention(*a, impl="blockwise"))
    ref = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
    np.testing.assert_allclose(np.asarray(fn(q, kp, vp, table, pos)),
                               np.asarray(ref), atol=2e-6, rtol=2e-6)


# -- the kernel's grid is a work list of live blocks (PR 34) ------------

WORK_LISTS = {
    # nblk [rows, q tiles], table width
    "ragged_rows_of_one_and_a_full_row": ([[1], [3], [1], [4], [2]], 4),
    "two_tiles_a_row": ([[2, 1, 3], [1, 1, 4]], 4),
    "every_row_idle": ([[1], [1], [1]], 5),
    "every_row_full": ([[4, 4], [4, 4]], 4),
    "one_pair": ([[7]], 80),
}


@pytest.mark.parametrize("name", sorted(WORK_LISTS))
def test_work_list_is_the_live_blocks_of_each_pair_in_order(name):
    """``_work_list`` alone: ``sum(nblk)`` steps; each (row, q tile)
    pair's steps contiguous, the pairs in order; a pair's slots
    ``0..nblk-1``; bit 1 on its first step, bit 0 on its last (both on
    the one step of an idle row). The entries past the live ones, one
    at the least (the chip's pipeline looks an entry ahead of the step
    it runs), are never taken and repeat the last live step, so they
    stay in bounds."""
    nblk, width = WORK_LISTS[name]
    flat = np.asarray(nblk, np.int32).reshape(-1)
    steps, pair, code = jax.jit(pa._work_list, static_argnums=1)(
        jnp.asarray(flat), width)
    steps, pair, code = int(steps), np.asarray(pair), np.asarray(code)
    assert pair.dtype == code.dtype == np.int32
    assert pair.shape == code.shape == (flat.size * width + 1,)
    assert steps < pair.size
    assert steps == flat.sum()
    want_pair = np.repeat(np.arange(flat.size), flat)
    want_slot = np.concatenate([np.arange(n) for n in flat])
    np.testing.assert_array_equal(pair[:steps], want_pair)
    np.testing.assert_array_equal(code[:steps] >> 2, want_slot)
    np.testing.assert_array_equal(code[:steps] & 2 != 0, want_slot == 0)
    np.testing.assert_array_equal(code[:steps] & 1 != 0,
                                  want_slot == flat[want_pair] - 1)
    assert np.all(pair[steps:] == pair[steps - 1])
    assert np.all(code[steps:] == code[steps - 1])


def _mixed_batch(kind, seed=19, s_q=1, n=4, kv=4, d=16, bs=8, mb=6):
    """Three rows as a serving step mixes them: an idle slot (cursor 0
    over the scratch table, row 0 of the pool), a row inside its first
    block, and a row that fills its table to the last position."""
    rng = np.random.RandomState(seed)
    dtype = jnp.bfloat16 if kind == "bf16_gqa" else jnp.float32
    pool = 2 * mb + 1
    q = jnp.asarray(rng.randn(3, s_q, n, d), dtype)
    kp = jnp.asarray(rng.randn(pool, bs, kv * d), dtype)
    vp = jnp.asarray(rng.randn(pool, bs, kv * d), dtype)
    table = np.zeros((3, mb), np.int32)
    table[1:] = 1 + rng.permutation(2 * mb).reshape(2, mb)
    start = np.array([0, bs - s_q - 1, mb * bs - s_q])
    pos = start[:, None] + np.arange(s_q)[None, :]
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("kind", ["f32", "bf16_gqa", "int8"])
def test_pallas_walks_an_idle_a_one_block_and_a_full_row(kind):
    """The kernel (interpreter) against ``gather`` on a batch whose
    rows take 1, 1 and ``table width`` steps: float32 with equal heads,
    bfloat16 with 4 K/V heads under 32 query heads at 4 positions a
    row (the block step's shape), and int8 pools. The bfloat16 case is
    held to the oracle on the same values in float32: the kernel sums
    in float32 and rounds its output once."""
    if kind == "bf16_gqa":
        q, kp, vp, table, pos = _mixed_batch(kind, s_q=4, n=32, kv=4)
        want = pa.paged_attention(
            q.astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), table, pos, impl="gather")
        tol = dict(atol=1e-2, rtol=1e-2)
    else:
        q, kp, vp, table, pos = _mixed_batch(kind, s_q=1)
        tol = dict(atol=2e-6, rtol=2e-6)
    scales = {}
    if kind == "int8":
        (kp, sk), (vp, sv) = (_quantize_pool(p, q) for p in (kp, vp))
        scales = dict(k_scale=sk, v_scale=sv)
    if kind != "bf16_gqa":
        want = pa.paged_attention(q, kp, vp, table, pos, impl="gather",
                                  **scales)
    got = pa.paged_attention(q, kp, vp, table, pos, impl="pallas",
                             interpret=True, **scales)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)
    # the steps it took: one for the idle row, one for the row in its
    # first block, the whole table for the full row
    nblk = pa._nblocks(pos, kp.shape[1], table.shape[1])
    assert nblk.tolist() == [1, 1, table.shape[1]]


@pytest.mark.parametrize("starts", [(10, 0), (40, 20)])
def test_prefill_tiles_of_one_row_see_different_depths(starts):
    """160 query rows are two tiles of 128 (the second padded): each
    tile walks the blocks ITS deepest query sees, so the two pairs of a
    row take different numbers of steps, and the rows differ too."""
    s_q, bs, mb, n, d = 160, 16, 13, 2, 16
    rng = np.random.RandomState(23)
    q = jnp.asarray(rng.randn(2, s_q, n, d), jnp.float32)
    kp = jnp.asarray(rng.randn(2 * mb + 1, bs, n * d), jnp.float32)
    vp = jnp.asarray(rng.randn(2 * mb + 1, bs, n * d), jnp.float32)
    table = jnp.asarray(1 + rng.permutation(2 * mb).reshape(2, mb),
                        jnp.int32)
    pos = jnp.asarray(np.asarray(starts)[:, None] + np.arange(s_q)[None, :],
                      jnp.int32)
    depth = pa._nblocks(
        jnp.pad(pos, ((0, 0), (0, 96))).reshape(2, 2, 128), bs, mb)
    assert len(set(np.asarray(depth).reshape(-1).tolist())) == 4, depth
    assert int(depth.max()) <= mb
    want = pa.paged_attention(q, kp, vp, table, pos, impl="gather")
    got = pa.paged_attention(q, kp, vp, table, pos, impl="pallas",
                             interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


def test_pallas_takes_as_many_steps_as_blocks_are_live(monkeypatch):
    """The grid's one bound is ``sum(nblk)``: counted where the kernel
    is launched, on a batch whose three rows see 1, 2 and 4 of 4 table
    slots."""
    from jax.experimental import pallas as pl

    seen = []
    call = pl.pallas_call

    def spy(kernel, *, grid_spec, **kw):
        seen.append(grid_spec.grid)
        return call(kernel, grid_spec=grid_spec, **kw)

    monkeypatch.setattr(pl, "pallas_call", spy)
    q, kp, vp, table, _ = _case(2)
    pos = jnp.asarray([[0], [9], [31]], jnp.int32)
    pa.paged_attention(q, kp, vp, table, pos, impl="pallas", interpret=True)
    (grid,) = seen
    assert len(grid) == 1 and int(grid[0]) == 1 + 2 + 4
