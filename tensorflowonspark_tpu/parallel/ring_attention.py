"""Ring attention: sequence/context parallelism over a mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §5
"Long-context"): sequences sharded over a ``seq`` mesh axis, with KV
blocks rotating around the ring (``jax.lax.ppermute`` — XLA lowers it to
ICI neighbor exchanges) while each device accumulates attention for its
resident Q shard using the online-softmax (flash) recurrence. Peak memory
is O(S/P) per device and the KV transfer overlaps the block matmuls, so
context length scales linearly with the ring size.

Layout contract: q/k/v are [batch, seq, heads, head_dim] global arrays,
sharded PartitionSpec(None, seq_axis, None, None). Causal masking uses
global positions, so it is exact regardless of ring placement.
"""

import functools

import jax
import jax.numpy as jnp


def reference_attention(q, k, v, causal=False, scale=None):
    """Plain full-sequence attention (the correctness oracle)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        logits = jnp.where(mask[None, None], logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknd->bqnd", probs, v)


def _block_update(q, k, v, m, l, o, q_offset, kv_offset, causal, scale):
    """One online-softmax accumulation step against a KV block."""
    s = jnp.einsum("bqnd,bknd->bnqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(s_q)
        k_pos = kv_offset + jnp.arange(s_k)
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_block = jnp.max(s, axis=-1)                       # [b, n, q]
    m_new = jnp.maximum(m, m_block)
    # fully-masked rows (causal, early q vs late kv): keep them inert
    safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = (o * corr[..., None] +
             jnp.einsum("bnqk,bknd->bnqd", p.astype(v.dtype), v)
             .astype(jnp.float32))
    return m_new, l_new, o_new


def _merge_partials(out_a, lse_a, out_b, lse_b):
    """Combine two attention partials over disjoint KV sets.

    out: [b, s, n, d]; lse: [b, n, s]. Exact: each partial is a
    normalized softmax-attention over its KV subset with row logsumexp
    lse; reweighting by exp(lse_i - lse_merged) reconstructs the full
    softmax. Fully-masked partials (lse == -inf, out == 0) merge as
    identity; -inf/-inf rows stay inert (no NaNs).
    """
    lse = jnp.logaddexp(lse_a, lse_b)
    safe = jnp.where(jnp.isneginf(lse), 0.0, lse)

    def w(l_i):
        return jnp.where(jnp.isneginf(l_i), 0.0, jnp.exp(l_i - safe))

    w_a = jnp.einsum("bns->bsn", w(lse_a))[..., None]
    w_b = jnp.einsum("bns->bsn", w(lse_b))[..., None]
    return out_a * w_a + out_b * w_b, lse


def zigzag_order(axis_size):
    """Half-block placement for the load-balanced causal layout.

    Returns the global half-block index held at each position of the
    zigzag layout: shard ``r`` holds half-blocks ``(r, 2P-1-r)`` — one
    early, one mirrored late — so under causal masking every shard has
    the same amount of live attention work at EVERY ring step, instead
    of early shards idling while late shards bound each lockstep step.
    """
    order = []
    for r in range(axis_size):
        order += [r, 2 * axis_size - 1 - r]
    return order


def to_zigzag(x, axis_size, axis=1):
    """Permute a [.., S, ..] global array into the zigzag layout (so a
    contiguous ``seq``-sharding gives each shard its early+late pair).
    S must divide by 2*axis_size. Inverse: :func:`from_zigzag`."""
    s = x.shape[axis]
    hb = 2 * axis_size
    if s % hb:
        raise ValueError(
            "sequence {} not divisible by 2*axis_size={}".format(s, hb))
    parts = jnp.split(x, hb, axis=axis)
    return jnp.concatenate([parts[i] for i in zigzag_order(axis_size)],
                           axis=axis)


def from_zigzag(x, axis_size, axis=1):
    """Inverse of :func:`to_zigzag`."""
    hb = 2 * axis_size
    order = zigzag_order(axis_size)
    inverse = [0] * hb
    for pos, blk in enumerate(order):
        inverse[blk] = pos
    parts = jnp.split(x, hb, axis=axis)
    return jnp.concatenate([parts[i] for i in inverse], axis=axis)


def ring_flash_attention(q, k, v, mesh, seq_axis="seq", causal=False,
                         scale=None, block_q=None, block_k=None,
                         interpret=None, layout="contiguous"):
    """Ring attention with the fused flash kernel as the block engine.

    Same contract and ppermute schedule as :func:`ring_attention`, but
    each per-step block update runs the Pallas flash kernel
    (ops/flash_attention.py) instead of materializing the
    [s_local, s_local] score matrix in XLA — peak memory O(S/P) per
    device in the *local* dimension too, and the MXU-tiled kernel does
    the FLOPs. Fully differentiable (the kernel's (out, lse) vjp).

    Causal masking uses the ring's alignment: all blocks are the same
    size and offsets are multiples of s_local, so every (q_shard,
    kv_block) pair is exactly one of fully-visible (kv strictly past),
    diagonal (standard local causal), or fully-masked (kv strictly
    future) — selected with ``lax.switch`` on the rotating source rank,
    no global-position support needed in the kernel.

    ``layout="zigzag"`` (causal only): inputs/outputs are in the
    :func:`to_zigzag` permutation — each shard holds an early half-block
    and its mirrored late half-block, so every shard does the SAME
    amount of live work each ring step. The contiguous layout's causal
    wall time is bounded by the busiest shard (a full block per step,
    ~2x the average work); zigzag makes each step cost ~one half-block
    pair everywhere, recovering the factor-2.
    """
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.ops.flash_attention import (
        DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention_lse)

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    block_q = block_q or DEFAULT_BLOCK_Q
    block_k = block_k or DEFAULT_BLOCK_K
    axis_size = mesh.shape[seq_axis]
    spec = P(None, seq_axis, None, None)
    if layout not in ("contiguous", "zigzag"):
        raise ValueError("layout must be 'contiguous' or 'zigzag'")
    if layout == "zigzag" and not causal:
        raise ValueError(
            "zigzag layout only helps (and is only implemented for) "
            "causal attention — non-causal work is already balanced")

    def _flash(qb, kb, vb, diag):
        return flash_attention_lse(qb, kb, vb, causal=diag, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def _ring(q_blk, k_blk, v_blk):
        rank = jax.lax.axis_index(seq_axis)
        b, s_local, n, d = q_blk.shape

        def flash_full(args):
            qb, kb, vb = args
            return _flash(qb, kb, vb, False)

        def flash_diag(args):
            qb, kb, vb = args
            return _flash(qb, kb, vb, True)

        def masked(args):
            qb, _, _ = args
            return (jnp.zeros_like(qb),
                    jnp.full((b, n, qb.shape[1]), -jnp.inf, jnp.float32))

        branches = (masked, flash_diag, flash_full)
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

        def step(t, carry):
            out, lse, k_cur, v_cur = carry
            src_rank = (rank - t) % axis_size
            if causal:
                # 0: kv strictly future (masked), 1: diagonal, 2: past
                idx = jnp.int32(1) + jnp.sign(rank - src_rank).astype(
                    jnp.int32)
                out_t, lse_t = jax.lax.switch(
                    idx, branches, (q_blk, k_cur, v_cur))
            else:
                out_t, lse_t = flash_full((q_blk, k_cur, v_cur))
            out, lse = _merge_partials(out, lse, out_t.astype(jnp.float32),
                                       lse_t)
            k_nxt = jax.lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, seq_axis, perm)
            return out, lse, k_nxt, v_nxt

        def step_zigzag(t, carry):
            # local halves: a = early block (id rank), b = mirrored late
            # block (id 2P-1-rank); received kv halves carry ids
            # (src_rank, 2P-1-src_rank). The qa/kb pair is masked by
            # construction (kb is always later), and qb/ka is always
            # fully visible — so each step costs ~one half-pair of live
            # work on EVERY shard, the whole point of the layout. The
            # accumulators stay SPLIT through the loop carry; one
            # concatenate happens after fori_loop.
            out_a, out_b, lse_a, lse_b, k_cur, v_cur = carry
            src_rank = (rank - t) % axis_size
            h = s_local // 2
            qa, qb = q_blk[:, :h], q_blk[:, h:]
            ka, kb = k_cur[:, :h], k_cur[:, h:]
            va, vb = v_cur[:, :h], v_cur[:, h:]

            # qa vs ka: ids (rank, src) — past/diag/future by sign
            idx_a = jnp.int32(1) + jnp.sign(rank - src_rank).astype(
                jnp.int32)
            o, s_ = jax.lax.switch(idx_a, branches, (qa, ka, va))
            out_a, lse_a = _merge_partials(out_a, lse_a,
                                           o.astype(jnp.float32), s_)
            # qb vs ka: qb id >= P > ka id — always fully visible
            o, s_ = flash_full((qb, ka, va))
            out_b, lse_b = _merge_partials(out_b, lse_b,
                                           o.astype(jnp.float32), s_)
            # qb vs kb: ids (2P-1-rank, 2P-1-src) — order flips
            idx_b = jnp.int32(1) + jnp.sign(src_rank - rank).astype(
                jnp.int32)
            o, s_ = jax.lax.switch(idx_b, branches, (qb, kb, vb))
            out_b, lse_b = _merge_partials(out_b, lse_b,
                                           o.astype(jnp.float32), s_)
            # qa vs kb: kb is strictly later than qa for every rank pair

            k_nxt = jax.lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, seq_axis, perm)
            return out_a, out_b, lse_a, lse_b, k_nxt, v_nxt

        if layout == "zigzag":
            h = s_local // 2
            oh = jnp.zeros((b, h, n, d), jnp.float32)
            lh = jnp.full((b, n, h), -jnp.inf, jnp.float32)
            out_a, out_b, lse_a, lse_b, _, _ = jax.lax.fori_loop(
                0, axis_size, step_zigzag, (oh, oh, lh, lh, k_blk, v_blk))
            out = jnp.concatenate([out_a, out_b], axis=1)
        else:
            out0 = jnp.zeros((b, s_local, n, d), jnp.float32)
            lse0 = jnp.full((b, n, s_local), -jnp.inf, jnp.float32)
            out, lse, _, _ = jax.lax.fori_loop(
                0, axis_size, step, (out0, lse0, k_blk, v_blk))
        return out.astype(q_blk.dtype)

    if layout == "zigzag":
        s_local = q.shape[1] // axis_size
        if s_local % 2:
            raise ValueError(
                "zigzag needs an even per-shard length, got {}".format(
                    s_local))
        half = s_local // 2
        if half % block_q or half % block_k:
            # the flash kernel sees HALF-length sequences under zigzag;
            # fail here instead of a confusing kernel assert downstream
            raise ValueError(
                "zigzag half-block length {} must be divisible by "
                "block_q={} and block_k={}".format(half, block_q, block_k))
    return _ring(q, k, v)


def ring_attention(q, k, v, mesh, seq_axis="seq", causal=False, scale=None):
    """Sequence-parallel attention over ``mesh[seq_axis]``.

    Returns an array shaped/sharded like ``q``. Works under jit; the
    per-step ``ppermute`` rotations are emitted as XLA collective-permutes
    riding ICI neighbor links.
    """
    from jax.sharding import PartitionSpec as P

    scale = scale if scale is not None else q.shape[-1] ** -0.5
    axis_size = mesh.shape[seq_axis]
    spec = P(None, seq_axis, None, None)

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)
    def _ring(q_blk, k_blk, v_blk):
        rank = jax.lax.axis_index(seq_axis)
        s_local = q_blk.shape[1]
        b, _, n, d = q_blk.shape
        m = jnp.full((b, n, s_local), -jnp.inf, jnp.float32)
        l = jnp.zeros((b, n, s_local), jnp.float32)
        o = jnp.zeros((b, n, s_local, d), jnp.float32)
        q_offset = rank * s_local

        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

        def step(t, carry):
            m, l, o, k_cur, v_cur = carry
            src_rank = (rank - t) % axis_size
            kv_offset = src_rank * s_local
            m, l, o = _block_update(q_blk, k_cur, v_cur, m, l, o,
                                    q_offset, kv_offset, causal, scale)
            # rotate KV to the next rank (skippable on the last step, but
            # a static rotate keeps the loop body uniform for XLA)
            k_nxt = jax.lax.ppermute(k_cur, seq_axis, perm)
            v_nxt = jax.lax.ppermute(v_cur, seq_axis, perm)
            return m, l, o, k_nxt, v_nxt

        m, l, o, _, _ = jax.lax.fori_loop(
            0, axis_size, step, (m, l, o, k_blk, v_blk))
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        out = (o / l[..., None]).astype(q_blk.dtype)
        return jnp.einsum("bnqd->bqnd", out)

    return _ring(q, k, v)
