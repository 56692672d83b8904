"""Fused paged attention: attend through a block table, no gather.

The device half of the paged KV cache (PR 8) stores K/V in a shared
pool ``[pool_rows, block_size, heads * head_dim]`` per layer (heads
flattened into the lanes, see "Pool layout" below), with each batch
row reaching its sequence through a ``block_table`` row. PR 8's
attention was the XLA *gather* formulation: materialize the logical
``[B, L, heads, dim]`` view (``pool[table]``) every step, then attend —
resident memory is paged, but transient compute memory is not, so
per-step bandwidth scales with the table width (max context), not with
the tokens actually live.

This module is the fused formulation (PR 11): attention consumes the
pool and the block table DIRECTLY, streaming one block at a time
through the online-softmax recurrence (the flash pattern,
ops/flash_attention.py), and visiting only the blocks a row actually
occupies — per-step traffic scales with LIVE tokens. Three
implementations share one contract:

- ``impl="pallas"`` — the TPU kernel. Its grid is a WORK LIST
  (:func:`_work_list`, PR 34): one axis whose bound, a traced scalar,
  is the number of live (row, query tile, block) triples, and nothing
  else is stepped over. The list — each step's (row, tile) pair, its
  slot in the row's table, whether it is the pair's first or last
  step — is built on the device inside the same jitted program from
  the positions alone, and rides scalar prefetch with the block table
  under a ``PrefetchScalarGridSpec``; the BlockSpec *index maps* read
  it, so the pipeline DMAs exactly the queries and the pool block each
  step attends — paged attention as an index-mapping problem, no
  gather materialization. A block is one pool row with ALL its heads
  (``[block_size, heads * head_dim]``); the kernel loops the heads,
  reading each as a static lane slice of the block.
  Why a list and not a grid over ``(rows, query tiles, table width)``
  with the dead slots skipped, which this was until PR 34: a skipped
  step moved no bytes and computed nothing but was still TAKEN, at
  0.09–0.11 us against 0.8 us for a live one (PERF.md §6, PR 34: a
  probe on the chip), and a serving step's tables are mostly dead: 8%
  of 16 x 32 slots walked in the GPT-2 large chat cell, 13% of 32 x 80
  in the SDAR cell, so half to three fifths of a call went on steps
  that did nothing (97 -> 42 us and 532 -> 268 us a call at the cells'
  depths). A table that is full takes the steps it took before.
- ``impl="blockwise"`` — the same recurrence in pure ``lax`` for
  the CPU backend (tier-1): ONE ``fori_loop`` with a *traced*
  bound (the batch's deepest live block count) whose body visits one
  block per row as a whole-batch gather + matmul; rows already past
  their own depth are frozen by the mask (their update is an exact
  no-op). Never materializes the logical view; per-step transient
  work is O(B × max live blocks), not O(B × table width).
- ``impl="gather"`` — PR 8's formulation, verbatim (moved here from
  models/decoder.py so both paths live in one module). The reference
  oracle the fused paths are pinned against, and the contrast curve
  ``bench.py serving_decode.multi_turn`` publishes.

Numerics: the gather path takes one softmax over the full logical row;
the fused paths take the online (rescaled-accumulator) recurrence over
the same visible set. Identical math, different float accumulation
order — last-ulp differences, which is why the serving parity pins are
TOKEN-level at temperature=0 (tests/test_paged_kv.py, same contract as
the fused-prefill branch in models/decoder.py).

Masking contract (identical across impls): query ``i`` of row ``b``
sits at logical position ``pos[b, i]`` and attends every key position
``<= pos[b, i]``. Callers write the step's K/V through the table
BEFORE attending (models/decoder.py), so the current token sees
itself. Layout: ``q [B, S_q, N, D]``, pools
``[P, block_size, N * D]`` (``N`` and ``D`` are taken from ``q``),
``block_table [B, MB]`` int32, ``pos [B, S_q]`` int32; returns
``[B, S_q, N, D]``. ``pos`` is what a query may SEE, not where it sits:
a caller whose mask is not causal passes another position (block
diffusion, models/sdar_moe.py: the last position of the query's block).
A pool may hold FEWER heads than ``q`` (grouped-query attention): its
width over ``D`` is the number of K/V heads, query head ``h`` reads K/V
head ``h // group``, and the group is folded into query rows before any
formulation runs (:func:`paged_attention`).

A WINDOW (``window=w``; models/mellum_moe.py's sliding layers): query
``i`` sees key ``j`` iff ``0 <= pos_i - j < w``. The row's table is the
same logical one (entry ``p // block_size`` for position ``p``), the
blocks behind the window being whatever the host parked there (scratch,
once it has given them back). No formulation walks them: before any of
them runs the row's table and positions are shifted so that the first
block its queries can still see is block 0 (:func:`_window_view`), the
work list and the loops count from there, and the one thing a
formulation adds is the mask's lower edge. ``window=None`` traces the
program it always traced.

Pool layout (PR 30): the pools are FLAT, heads and head_dim in one
minor axis, because of what the chip does with anything else. The TPU
tiles an array's two minor dimensions ``(8, 128)``; a ``[.., N, 64]``
minor pair pads 64 lanes to 128 (and 20 heads to 24 sublanes), so the
runtime keeps such a buffer in another, compact layout than the
row-major one a scatter and a ``tpu_custom_call`` operand want, and
every program that touched a 4-D pool copied the WHOLE pool in and
out again around its one-row write (two copies per pool per call: 83%
of the device's busy time at GPT-2 large, ledger PR 29). A
``[block_size, N * D]`` minor pair tiles exactly for any head width,
so the stored layout is the row-major one: the write is in place in
the donated buffer and the kernel reads its result (pinned by
tests/test_chip_compile.py). A formulation that wants heads apart
reshapes a gathered BLOCK (a block-sized transient), never the pool.

INT8 KV (PR 15): with ``k_scale``/``v_scale`` supplied, the pools hold
``int8`` codes and the scales (``[P, block_size, heads]`` float32 —
one per head per token row of each block, stored block-aligned beside
the pool) dequantize them INSIDE each formulation: the gather path
dequantizes the materialized view, the blockwise loop one block at a
time right after its load, and the Pallas kernel unpacks a block's
codes in VMEM and lays each head's scales on its scores and its
probabilities (the same products in another order) — so the
HBM traffic a decode step pays is the int8 bytes, not the float ones
(per-step KV bandwidth halves vs bf16, quarters vs f32; the exact
follow-up PR 11 named). Quantization itself happens at WRITE time in
models/decoder.py via :func:`quantize_kv`. A per-(block, head) single
scale cannot work for an incremental decode cache — a scale-raising
write would require requantizing every code already in the block —
which is why the scales are per token row within each block.
"""

import functools

import jax
import jax.numpy as jnp

from tensorflowonspark_tpu.ops.flash_attention import on_tpu


def quantize_kv(x):
    """``[..., D]`` float K/V -> ``(codes int8 [..., D], scales
    float32 [...])`` — symmetric per-head (last-axis) absmax
    quantization to 127 levels. An all-zero vector quantizes to zero
    codes under scale 1.0 (never a 0/0). EXACT round-trip contract
    (pinned in tests): ``quantize_kv(dequantize_kv(*quantize_kv(x)))``
    reproduces the codes and scales bitwise — the absmax element maps
    to ±127 exactly, so requantizing the dequantized grid is a fixed
    point. paging.BlockPool.quantize is the numpy mirror of this
    formulation (one contract, two runtimes)."""
    s = jnp.max(jnp.abs(x), axis=-1).astype(jnp.float32) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def dequantize_kv(q, s):
    """Inverse of :func:`quantize_kv`: ``codes * scales`` in float32."""
    return q.astype(jnp.float32) * s[..., None].astype(jnp.float32)


def _nblocks(pos, block_size, table_width):
    """Blocks a row actually occupies: enough to cover its highest
    visible position, clamped to the table (bucket-padded prefill rows
    can carry ``pos`` past the logical capacity; the gather view ends
    at the table too, so the clamp preserves parity)."""
    return jnp.minimum((jnp.max(pos, axis=-1) + block_size)
                       // block_size, table_width)


def _window_view(block_table, pos, block_size, window):
    """``(table', pos')`` for a windowed call: each row's table rolled
    left so that entry 0 is the first block ANY of its queries still
    sees, and its positions counted from that block's first token. What
    lies past the row's last block after the roll is never visited
    (``_nblocks`` counts from the shifted positions)."""
    mb = block_table.shape[1]
    first = jnp.maximum(jnp.min(pos, axis=-1) - window + 1, 0) // block_size
    slots = jnp.minimum(first[:, None] + jnp.arange(mb)[None, :], mb - 1)
    return (jnp.take_along_axis(block_table, slots, axis=1),
            pos - (first * block_size)[:, None])


def _gather(q, k_pool, v_pool, block_table, pos, scale, k_scale=None,
            v_scale=None, window=None):
    """PR 8's XLA formulation, verbatim: materialize the logical
    ``[B, L, N, D]`` view through the table, one softmax over it
    (int8 pools dequantize into the materialized view — the reference
    the fused in-kernel dequant is pinned against)."""
    b, s, n, d = q.shape
    bs_blk = k_pool.shape[1]
    mb = block_table.shape[1]
    L = mb * bs_blk
    ck = k_pool[block_table].reshape(b, mb, bs_blk, n, d)
    cv = v_pool[block_table].reshape(b, mb, bs_blk, n, d)
    if k_scale is not None:
        ck = dequantize_kv(ck, k_scale[block_table])
        cv = dequantize_kv(cv, v_scale[block_table])
    ck = ck.reshape(b, L, n, d)
    cv = cv.reshape(b, L, n, d)
    logits = jnp.einsum("bqnd,bknd->bnqk", q, ck,
                        preferred_element_type=jnp.float32)
    logits = logits * scale
    visible = (jnp.arange(L)[None, None, :]
               <= pos[:, :, None])                   # [B, s, L]
    if window is not None:
        visible &= jnp.arange(L)[None, None, :] > pos[:, :, None] - window
    logits = jnp.where(visible[:, None, :, :], logits,
                       jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
    ctx = jnp.einsum("bnqk,bknd->bqnd", probs, cv)
    # int8 path dequantized to f32; hand back the query's dtype so the
    # output contract matches the float pools'
    return ctx if k_scale is None else ctx.astype(q.dtype)


#: table width at or below which the blockwise loop uses a STATIC
#: trip count (visit every table slot, masked): XLA compiles a
#: known-trip-count loop markedly faster than a dynamic-bound while,
#: and at <= 8 blocks the masked extra iterations cost about what the
#: bound bookkeeping would. Wider tables — where per-step work
#: tracking LIVE blocks instead of table width is the whole point —
#: take the traced bound. Trace-time dispatch: outputs are identical
#: either way (a masked iteration is an exact no-op).
_STATIC_TRIP_MAX_BLOCKS = 8


def _blockwise(q, k_pool, v_pool, block_table, pos, scale,
               k_scale=None, v_scale=None, window=None):
    """Online-softmax over each row's live blocks, pure ``lax``: the
    CPU tier-1 formulation of the fused kernel (and the fallback for
    any non-TPU backend). ONE ``fori_loop`` — iteration ``j`` gathers
    block ``j`` of every row at once ([B, bs, N * D] viewed as
    [B, bs, N, D], a live-block-sized transient) and folds it into the
    recurrence; rows whose own depth is < j mask to -inf, which makes their
    update an EXACT no-op (p = 0, correction = 1). The trip count is
    the batch's deepest live block count (traced), so mixed-depth
    batches cost the deepest row, never the table width — except on
    narrow tables (see :data:`_STATIC_TRIP_MAX_BLOCKS`), where a
    static count compiles faster and costs the same."""
    b, s, n, d = q.shape
    bs_blk = k_pool.shape[1]
    mb = block_table.shape[1]
    nblk = _nblocks(pos, bs_blk, mb)             # [B]

    m0 = jnp.full((b, s, n), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, s, n), jnp.float32)
    a0 = jnp.zeros((b, s, n, d), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        # clamp keeps the gather in-bounds for frozen rows; the
        # (j < nblk) mask below is what actually freezes them
        jj = jnp.minimum(j, nblk - 1)            # [B]
        bid = jnp.take_along_axis(block_table, jj[:, None],
                                  axis=1)[:, 0]  # [B]
        kb = k_pool[bid].reshape(b, bs_blk, n, d)
        vb = v_pool[bid].reshape(b, bs_blk, n, d)
        if k_scale is not None:
            # int8 fast path: the gather above moved the int8 bytes;
            # dequant happens here, on the one-block transient
            kb = dequantize_kv(kb, k_scale[bid])
            vb = dequantize_kv(vb, v_scale[bid])
        sc = jnp.einsum("bqnd,btnd->bqnt", q, kb,
                        preferred_element_type=jnp.float32)
        sc = sc * scale                          # [B, s, N, bs]
        kpos = jj[:, None] * bs_blk + jnp.arange(bs_blk)[None, :]
        vis = (kpos[:, None, :] <= pos[:, :, None]) \
            & (j < nblk)[:, None, None]          # [B, s, bs]
        if window is not None:
            vis &= kpos[:, None, :] > pos[:, :, None] - window
        sc = jnp.where(vis[:, :, None, :], sc, -jnp.inf)
        m_blk = jnp.max(sc, axis=-1)             # [B, s, N]
        m_new = jnp.maximum(m, m_blk)
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(jnp.isneginf(sc), 0.0,
                      jnp.exp(sc - safe_m[..., None]))
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqnt,btnd->bqnd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    trips = mb if mb <= _STATIC_TRIP_MAX_BLOCKS else jnp.max(nblk)
    m, l, acc = jax.lax.fori_loop(0, trips, body, (m0, l0, a0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / l_safe[..., None]).astype(q.dtype)


def _work_list(nblk, width):
    """The kernel's grid, step by step: ``nblk [P]`` (blocks each (row,
    query tile) pair can see, at least 1) -> ``(steps, pair, code)``.
    The pairs in order, each for its ``nblk`` steps: step ``s`` works
    for pair ``pair[s]`` on slot ``code[s] >> 2`` of its row's table,
    bit 1 of ``code[s]`` set on a pair's first step and bit 0 on its
    last. ``steps = sum(nblk)`` (traced) is the grid's bound; the lists
    have the static length ``P * width + 1``, and the entries past the
    live ones repeat the last live step. They are never taken, but the
    pipeline looks ONE entry ahead of the step it runs (on the chip a
    full table's last step read past a list of ``P * width`` and halted
    the core), so there is always one, and it is in bounds. A
    comparison of every step with every pair's end, no gather and no
    loop: 5,120 x 64 at the widest call of today's cells, and the same
    for every layer of a program, so XLA computes it once."""
    ends = jnp.cumsum(nblk, dtype=jnp.int32)
    s = jnp.minimum(
        jnp.arange(nblk.shape[0] * width + 1, dtype=jnp.int32), ends[-1] - 1)
    before = s[:, None] >= ends[None, :]    # [T, P]: pairs done by step s
    pair = jnp.sum(before, axis=1, dtype=jnp.int32)
    slot = s - jnp.sum(jnp.where(before, nblk[None, :], 0), axis=1,
                       dtype=jnp.int32)
    last = jnp.any(s[:, None] + 1 == ends[None, :], axis=1)
    return ends[-1], pair, slot * 4 + (slot == 0) * 2 + last


def _paged_kernel(*refs, scale, block_size, num_heads, quantized,
                  window=None):
    """One step of the work list (:func:`_work_list`): fold ONE live
    pool block — every head of it — into the online-softmax
    accumulators of its (row, q tile) pair; zero them on the pair's
    first step, emit on its last. The index maps already routed the
    pair's queries and the RIGHT pool block here. A block arrives flat,
    ``[rows, N * D]``, so the per-head recurrence is a static loop over
    ``num_heads`` reading each head's ``[rows, D]`` lanes out of the
    token-major block. ``quantized`` adds per-head scale refs riding
    the SAME index map as K/V; the codes are unpacked in VMEM after
    the (int8-sized) copy — the bandwidth the fast path saves is
    exactly the bytes the DMA no longer moves."""
    from jax.experimental import pallas as pl

    if quantized:
        (table_ref, pair_ref, code_ref, q_ref, pos_ref, k_ref, v_ref,
         ks_ref, vs_ref, o_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (table_ref, pair_ref, code_ref, q_ref, pos_ref, k_ref, v_ref,
         o_ref, acc_ref, m_ref, l_ref) = refs
    code = code_ref[pl.program_id(0)]
    j = code >> 2
    block_q, head_dim = q_ref.shape[1], q_ref.shape[3]

    @pl.when((code & 2) != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)

    kpos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_size), 1)
    vis = kpos <= pos_ref[0]                                # [bq, bs]
    if window is not None:
        vis &= kpos > pos_ref[0] - window
    if quantized:
        # a head's scales as a ROW [1, bs], laid on its scores and
        # its probabilities: q.(c*s) = (q.c)*s, p@(c*s) = (p*s)@c.
        # Scaling K and V themselves by a [bs, 1] column cut out of
        # the lanes of the scale block cost more than the attention
        ks_t, vs_t = ks_ref[0].T, vs_ref[0].T               # [N, bs]
    for h in range(num_heads):
        q = q_ref[0, :, h, :].astype(jnp.float32)           # [bq, D]
        lanes = slice(h * head_dim, (h + 1) * head_dim)
        kb = k_ref[0, :, lanes].astype(jnp.float32)         # [bs, D]
        vb = v_ref[0, :, lanes].astype(jnp.float32)
        sc = jax.lax.dot_general(
            q, kb, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if quantized:
            sc = sc * ks_t[h:h + 1]
        sc = jnp.where(vis, sc, -jnp.inf)
        m = m_ref[h]                                        # [bq, 1]
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(jnp.isneginf(sc), 0.0, jnp.exp(sc - safe_m))
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        m_ref[h] = m_new
        l_ref[h] = l_ref[h] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
            p * vs_t[h:h + 1] if quantized else p, vb,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((code & 1) != 0)
    def _emit():
        for h in range(num_heads):
            l = l_ref[h]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_ref[h] / l_safe).astype(o_ref.dtype)


#: query rows per grid step of the TPU kernel: prefill buckets are
#: tiled to this (bounding VMEM whatever the bucket), decode's single
#: row is padded up to one 8-row sublane tile
_BLOCK_Q = 128


def _pallas(q, k_pool, v_pool, block_table, pos, scale, interpret,
            k_scale=None, v_scale=None, window=None):
    """The TPU kernel over a work list: a ONE-axis grid whose bound is
    the number of live (row, q tile, block) triples, a traced scalar,
    with the lists and the block table as scalar prefetch for the
    index maps (module docstring: a grid over every table slot spent
    most of its time on steps that computed nothing). int8 pools
    bring their ``[P, bs, N]`` scales along on the K/V index map; the
    kernel dequantizes in VMEM. Query rows are padded to a whole
    number of ``block_q`` tiles (pad rows sit at position 0 and are
    sliced off the result); each tile visits only the blocks ITS
    deepest query can see."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, n, d = q.shape
    bs_blk = k_pool.shape[1]
    mb = block_table.shape[1]
    block_q = min(_BLOCK_Q, -(-s_q // 8) * 8)
    pad = -s_q % block_q
    nq = (s_q + pad) // block_q
    # a (row, q tile) pair is one row of q, pos, the table and the output
    pos = jnp.pad(pos.astype(jnp.int32), ((0, 0), (0, pad))) \
        .reshape(b * nq, block_q)
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) \
        .reshape(b * nq, block_q, n, d)
    table = jnp.repeat(block_table.astype(jnp.int32), nq, axis=0)
    steps, pair, code = _work_list(_nblocks(pos, bs_blk, mb), mb)
    quantized = k_scale is not None

    def pair_index(s, table_ref, pair_ref, code_ref):
        return (pair_ref[s], 0, 0, 0)

    def pool_index(s, table_ref, pair_ref, code_ref):
        return (table_ref[pair_ref[s], code_ref[s] >> 2], 0, 0)

    pool_spec = pl.BlockSpec((1, bs_blk, n * d), pool_index)
    in_specs = [
        pl.BlockSpec((1, block_q, n, d), pair_index),
        pl.BlockSpec((1, block_q, 1),
                     lambda s, t, pr, c: (pr[s], 0, 0)),
        pool_spec,
        pool_spec,
    ]
    inputs = [table, pair, code, q, pos[..., None], k_pool, v_pool]
    if quantized:
        # the scales ride the exact pool-block routing K/V use
        in_specs += [pl.BlockSpec((1, bs_blk, n), pool_index)] * 2
        inputs += [k_scale.astype(jnp.float32),
                   v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(steps,),
        in_specs=in_specs,
        # head-major output: each head's [block_q, D] result stores
        # dense (Mosaic has no packed-dtype store into one head's
        # sublane of a token-major tile); transposed back below
        out_specs=pl.BlockSpec((1, n, block_q, d), pair_index),
        scratch_shapes=[
            pltpu.VMEM((n, block_q, d), jnp.float32),   # acc
            pltpu.VMEM((n, block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((n, block_q, 1), jnp.float32),   # denominator
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale,
                               block_size=bs_blk, num_heads=n,
                               quantized=quantized, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b * nq, n, block_q, d), q.dtype),
        # the accumulators carry from a step to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(*inputs)
    return out.reshape(b, nq, n, block_q, d).transpose(0, 1, 3, 2, 4) \
        .reshape(b, nq * block_q, n, d)[:, :s_q]


def paged_attention(q, k_pool, v_pool, block_table, pos, scale=None,
                    impl=None, interpret=None, force_pallas=False,
                    k_scale=None, v_scale=None, window=None):
    """Attend ``q`` against paged K/V through ``block_table``.

    ``pos [B, S_q]`` is each query's logical position (it sees key
    positions ``<= pos``; the caller wrote this call's K/V through the
    table already). ``impl``: None/"auto" picks the Pallas kernel on
    TPU and the blockwise ``lax`` formulation on CPU
    (:func:`ops.flash_attention.on_tpu` decides, for both modules);
    "gather" is PR 8's materialize-the-view reference oracle;
    "blockwise"/"pallas" force a specific fused formulation
    (``interpret``/``force_pallas`` route the kernel through the
    Pallas interpreter for CPU tests). The pools are flat,
    ``[P, block_size, kv_heads * D]``, with ``D`` read off ``q`` and
    ``kv_heads`` off the pool's width (module docstring, "Pool layout";
    fewer than ``q``'s heads is grouped-query attention).
    ``k_scale``/``v_scale``
    (``[P, block_size, heads]`` float32, both or neither) mark the
    pools as int8 codes and dequantize them inside the chosen
    formulation — see the module docstring's int8-KV section.
    ``window`` bounds what a query sees from below (module docstring,
    "A WINDOW"): key positions ``> pos - window`` only."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    pos = jnp.asarray(pos, jnp.int32)
    block_table = jnp.asarray(block_table, jnp.int32)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    b, s_q, n, d = q.shape
    kv_heads, rest = divmod(k_pool.shape[-1], d)
    if k_pool.ndim != 3 or v_pool.shape != k_pool.shape or rest \
            or not kv_heads or n % kv_heads:
        raise ValueError(
            "pools must be [P, block_size, kv_heads * head_dim] = "
            "[P, {}, {}] for q {} (or narrower by a whole group of query "
            "heads), got {} and {}".format(
                k_pool.shape[1], n * d, q.shape, k_pool.shape,
                v_pool.shape))
    group = n // kv_heads
    if group > 1:
        # Fewer K/V heads than query heads: query head h reads K/V head
        # h // group. The group's queries of one position become
        # ``group`` query ROWS of their K/V head at that position, and
        # every formulation below runs as it does for equal heads
        # (group 1 takes none of this: the same program as before).
        if k_scale is not None:
            raise ValueError("int8 pools need as many K/V heads as "
                             "query heads")
        q = q.reshape(b, s_q, kv_heads, group, d).transpose(0, 1, 3, 2, 4) \
            .reshape(b, s_q * group, kv_heads, d)
        out = paged_attention(
            q, k_pool, v_pool, block_table, jnp.repeat(pos, group, axis=1),
            scale=scale, impl=impl, interpret=interpret,
            force_pallas=force_pallas, window=window)
        return out.reshape(b, s_q, group, kv_heads, d) \
            .transpose(0, 1, 3, 2, 4).reshape(b, s_q, n, d)
    if impl in (None, "auto"):
        impl = "pallas" if (force_pallas or on_tpu()) else "blockwise"
    if impl == "gather":
        return _gather(q, k_pool, v_pool, block_table, pos, scale,
                       k_scale=k_scale, v_scale=v_scale, window=window)
    if window is not None:
        block_table, pos = _window_view(block_table, pos, k_pool.shape[1],
                                        int(window))
    if impl == "blockwise":
        return _blockwise(q, k_pool, v_pool, block_table, pos, scale,
                          k_scale=k_scale, v_scale=v_scale, window=window)
    if impl == "pallas":
        if interpret is None:
            interpret = not on_tpu()
        return _pallas(q, k_pool, v_pool, block_table, pos, scale,
                       interpret, k_scale=k_scale, v_scale=v_scale,
                       window=window)
    raise ValueError(
        "unknown paged-attention impl {!r}; expected one of "
        "None/'auto', 'pallas', 'blockwise', 'gather'".format(impl))
