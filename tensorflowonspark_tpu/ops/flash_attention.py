"""Fused flash attention (Pallas TPU kernels) with XLA fallback.

Blocked online-softmax attention: the grid walks (batch*head, Q tile,
KV tile) with the KV axis innermost, one K/V tile in VMEM per step and
the running max/denominator/accumulator in VMEM scratch, keeping the
[S, S] score matrix out of HBM entirely and VMEM use independent of
sequence length — the standard flash recurrence (128-wide tiles, f32
accumulators/stats).

Both directions are fused:

- forward: online-softmax kernel, also emitting the per-row logsumexp
  (LSE) needed by the backward.
- backward: two kernels in the FlashAttention-2 factorization —
  ``dq`` (KV tiles innermost) and ``dk/dv`` (Q tiles innermost) —
  recomputing P tiles from the saved LSE with f32
  accumulators, so training memory stays O(S) per (batch, head) instead
  of the O(S²) score matrix the rematerialized-XLA vjp used to build.
  ``delta = rowsum(dO ⊙ O)`` is precomputed in XLA (one fused
  elementwise+reduce).

Masking: causal (in-kernel position compare) and/or a per-key padding
mask (``key_mask`` [B, S_k] bool — BERT-style), carried through both
directions as an additive 0/-inf bias row.

Rectangular attention is supported (``S_q != S_k`` — cross attention);
causal requires equal lengths.

The CPU backend takes the XLA reference for both directions (and the
Pallas interpreter validates the kernels on CPU in tests); see
:func:`on_tpu`.

Layout: [batch, seq, heads, head_dim], same contract as
``parallel.ring_attention`` (whose per-shard block update this kernel
replaces in ``ring_flash_attention``).
"""

import functools

import jax
import jax.numpy as jnp

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def on_tpu():
    """The backend decision of every ``impl=None`` / default path in
    ``ops``: True on TPU (compiled Pallas kernels), False on CPU (XLA
    reference / blockwise ``lax``, which is what tier-1 runs). Any
    other backend is an accelerator these kernels were not written
    for: an error, never a quiet fall-through to the reference."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            "ops kernels support the 'tpu' backend (and the XLA "
            "reference on 'cpu'); jax.default_backend() is {!r}. Pass "
            "an explicit impl/interpret to choose a formulation."
            .format(backend))
    return backend == "tpu"


def _reference(q, k, v, causal, scale, bias=None):
    from tensorflowonspark_tpu.parallel.ring_attention import (
        reference_attention)

    if bias is None:
        return reference_attention(q, k, v, causal=causal, scale=scale)
    out, _ = _reference_lse(q, k, v, causal, scale, bias)
    return out


def _reference_lse(q, k, v, causal, scale, bias=None):
    """XLA (out, lse [b, n, s_q]) pair — same contract as the kernels.

    ``bias``: optional [B, S_k] additive f32 row (0 / -inf key mask).
    """
    logits = jnp.einsum("bqnd,bknd->bnqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias[:, None, None, :]
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((s_q, s_k), bool))
        logits = jnp.where(mask[None, None], logits, -jnp.inf)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)   # [b, n, q]
    safe = jnp.where(jnp.isneginf(lse), 0.0, lse)
    p = jnp.where(jnp.isneginf(logits), 0.0,
                  jnp.exp(logits - safe[..., None]))
    out = jnp.einsum("bnqk,bknd->bqnd", p.astype(v.dtype), v)
    return out.astype(q.dtype), lse


def _causal_mask(s, q_offset, k_offset, block_q, block_k):
    q_pos = q_offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = k_offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(q_pos >= k_pos, s, -jnp.inf)


def _scores(q, k_blk, bias_ref, causal, q_offset, k_offset):
    """Masked f32 score tile [BQ, BK] of pre-scaled ``q`` against one
    K tile; ``bias_ref`` block is the [1, 1, BK] additive key row."""
    s = jax.lax.dot_general(
        q, k_blk.astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias_ref is not None:
        s = s + bias_ref[0]
    if causal:
        s = _causal_mask(s, q_offset, k_offset, *s.shape)
    return s


def _when_visible(causal, q_offset, block_q, k_offset):
    """Decorator running a tile update unless causality masks the WHOLE
    tile (its first key sits past the tile's last query). A fully
    masked tile's update is an exact no-op (p = 0, correction = 1), so
    skipping it changes no bit of the result."""
    from jax.experimental import pallas as pl

    if not causal:
        return lambda fn: fn()
    return pl.when(k_offset <= q_offset + block_q - 1)


def _fwd_kernel(*refs, scale, causal, block_q, block_k, has_bias):
    """One (batch*head, q-block, kv-block) program: fold this KV tile
    into the online-softmax accumulators held in VMEM scratch; emit the
    normalized output and the row logsumexp on the last KV tile."""
    from jax.experimental import pallas as pl

    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, o_ref, lse_ref,
         m_ref, l_ref, acc_ref), bias_ref = refs, None
    kv_i = pl.program_id(2)
    q_offset = pl.program_id(1) * block_q
    k_offset = kv_i * block_k

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @_when_visible(causal, q_offset, block_q, k_offset)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale          # [BQ, D]
        v_blk = v_ref[0]                                  # [BK, D]
        s = _scores(q, k_ref[0], bias_ref, causal, q_offset, k_offset)
        m = m_ref[...]                                    # [BQ, 1]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - safe_m))
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kv_i == pl.num_programs(2) - 1)
    def _emit():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # LSE = m + log(l): the only softmax statistic the backward needs
        lse_ref[0] = jnp.where(l == 0.0, -jnp.inf,
                               m_ref[...] + jnp.log(l_safe))


def _recompute_p(q, k_blk, bias_ref, lse, causal, q_offset, k_offset):
    """P tile [BQ, BK] rebuilt from the saved row logsumexp [BQ, 1]."""
    s = _scores(q, k_blk, bias_ref, causal, q_offset, k_offset)
    lse_safe = jnp.where(jnp.isfinite(lse), lse, 0.0)
    return jnp.where(jnp.isneginf(s), 0.0, jnp.exp(s - lse_safe))


def _dq_kernel(*refs, scale, causal, block_q, block_k, has_bias):
    """dQ for one (batch*head, q-block), accumulated over the KV-tile
    grid axis in VMEM scratch; P is recomputed from the saved LSE."""
    from jax.experimental import pallas as pl

    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
         dq_ref, acc_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
         acc_ref), bias_ref = refs, None
    kv_i = pl.program_id(2)
    q_offset = pl.program_id(1) * block_q
    k_offset = kv_i * block_k

    @pl.when(kv_i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @_when_visible(causal, q_offset, block_q, k_offset)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale           # [BQ, D]
        k_blk = k_ref[0].astype(jnp.float32)               # [BK, D]
        p = _recompute_p(q, k_blk, bias_ref, lse_ref[0], causal,
                         q_offset, k_offset)               # [BQ, BK]
        dp = jax.lax.dot_general(
            do_ref[0].astype(jnp.float32), v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, BK]
        ds = p * (dp - delta_ref[0])
        acc_ref[...] += jax.lax.dot_general(
            ds, k_blk,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, D]

    @pl.when(kv_i == pl.num_programs(2) - 1)
    def _emit():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, block_q, block_k, has_bias):
    """dK/dV for one (batch*head, kv-block), accumulated over the
    Q-tile grid axis in VMEM scratch; P is recomputed from the LSE."""
    from jax.experimental import pallas as pl

    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_acc, dv_acc) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
         dv_ref, dk_acc, dv_acc), bias_ref = refs, None
    qi = pl.program_id(2)
    q_offset = qi * block_q
    k_offset = pl.program_id(1) * block_k

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @_when_visible(causal, q_offset, block_q, k_offset)
    def _update():
        q = q_ref[0].astype(jnp.float32) * scale           # [BQ, D]
        do = do_ref[0].astype(jnp.float32)                 # [BQ, D]
        p = _recompute_p(q, k_ref[0], bias_ref, lse_ref[0], causal,
                         q_offset, k_offset)               # [BQ, BK]
        dv_acc[...] += jax.lax.dot_general(
            p, do,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, D]
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32),
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BQ, BK]
        ds = p * (dp - delta_ref[0])
        dk_acc[...] += jax.lax.dot_general(
            ds, q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)            # [BK, D] (has scale)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _emit():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fold(x, b, s, n, d):
    """[B, S, N, D] -> [B*N, S, D]: each program owns one (batch, head)."""
    return jnp.reshape(jnp.transpose(x, (0, 2, 1, 3)), (b * n, s, d))


def _unfold(x, b, s, n, d):
    return jnp.transpose(jnp.reshape(x, (b, n, s, d)), (0, 2, 1, 3))


# Layouts the TPU lowering accepts (the last two dims of every block
# must be multiples of (8, 128) or span the array): per-query row
# statistics (lse, delta) travel as COLUMNS ``[B*N, S, 1]`` with
# ``(1, block, 1)`` blocks, which is also the orientation the kernels
# broadcast them in; the per-key bias travels as a ROW ``[B, 1, S_k]``
# with ``(1, 1, block)`` blocks. The bias is per-BATCH and the grids
# run over bh = b*N + n, so its index maps use bh // N (closing over
# the static head count) instead of materializing an N-fold repeat.
# K/V (and, in dK/dV, Q/dO) stream one tile per grid step along the
# innermost "arbitrary" axis, so VMEM use does not grow with sequence
# length.


def _check_blocks(s_q, s_k, block_q, block_k):
    block_q = min(block_q, s_q)
    block_k = min(block_k, s_k)
    assert s_q % block_q == 0 and s_k % block_k == 0, (
        "seq lens ({}, {}) must be divisible by block sizes ({}, {})"
        .format(s_q, s_k, block_q, block_k))
    return block_q, block_k


def grid_params():
    """Compiler parameters of the three flash kernels' grids: two
    independent axes, then the axis the VMEM accumulators carry over."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _flash_fwd(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    """Returns (out [B,Sq,N,D], lse [B*N, Sq]). Sq may differ from the
    KV length (cross attention); causal requires Sq == Sk.
    ``bias``: optional [B, S_k] additive f32 row (key mask)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, n, d = q.shape
    s_k = k.shape[1]
    assert not causal or s_q == s_k, "causal needs equal q/kv lengths"
    block_q, block_k = _check_blocks(s_q, s_k, block_q, block_k)

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    inputs = [_fold(q, b, s_q, n, d), _fold(k, b, s_k, n, d),
              _fold(v, b, s_k, n, d)]
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_k), lambda bh, i, j, n=n: (bh // n, 0, j)))
        inputs.append(bias.astype(jnp.float32)[:, None, :])
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, has_bias=bias is not None),
        grid=(b * n, s_q // block_q, s_k // block_k),
        in_specs=in_specs,
        out_specs=[
            q_spec,
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, s_q, d), q.dtype),
            jax.ShapeDtypeStruct((b * n, s_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denominator
            pltpu.VMEM((block_q, d), jnp.float32),   # acc
        ],
        compiler_params=grid_params(),
        interpret=interpret,
        name="flash_attention_fwd",
    )(*inputs)
    return _unfold(out, b, s_q, n, d), lse[..., 0]


def _flash_bwd(q, k, v, bias, out, lse, g, causal, scale, block_q,
               block_k, interpret, g_lse=None):
    """Fused dq/dk/dv. All tensors [B,S,N,D] except lse [B*N,S].

    ``g_lse`` ([B*N, S] or None): cotangent of the lse output for the
    (out, lse) variant — enters as ds += p * g_lse, folded into delta.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s_q, n, d = q.shape
    s_k = k.shape[1]
    block_q, block_k = _check_blocks(s_q, s_k, block_q, block_k)

    qf = _fold(q, b, s_q, n, d)
    kf = _fold(k, b, s_k, n, d)
    vf = _fold(v, b, s_k, n, d)
    of = _fold(out, b, s_q, n, d)
    gf = _fold(g, b, s_q, n, d)
    has_bias = bias is not None
    # delta = rowsum(dO ⊙ O): one fused XLA elementwise+reduce, f32
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)                            # [B*N, Sq]
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)
    kernel_kw = dict(scale=scale, causal=causal, block_q=block_q,
                     block_k=block_k, has_bias=has_bias)

    def specs(q_index, k_index):
        """(in_specs, inputs) shared by both kernels; the two differ
        only in which grid axis walks Q tiles and which KV tiles."""
        q_spec = pl.BlockSpec((1, block_q, d),
                              lambda bh, x, y: (bh,) + q_index(x, y))
        kv_spec = pl.BlockSpec((1, block_k, d),
                               lambda bh, x, y: (bh,) + k_index(x, y))
        col_spec = pl.BlockSpec((1, block_q, 1),
                                lambda bh, x, y: (bh,) + q_index(x, y))
        in_specs = [q_spec, kv_spec, kv_spec]
        inputs = [qf, kf, vf]
        if has_bias:
            in_specs.append(pl.BlockSpec(
                (1, 1, block_k),
                lambda bh, x, y, n=n: (bh // n, 0, k_index(x, y)[0])))
            inputs.append(bias.astype(jnp.float32)[:, None, :])
        in_specs += [q_spec, col_spec, col_spec]
        inputs += [gf, lse[..., None], delta[..., None]]
        return in_specs, inputs, q_spec, kv_spec

    # dQ: grid (bh, q-tile, kv-tile) — KV innermost
    in_specs, inputs, q_spec, _ = specs(lambda i, j: (i, 0),
                                        lambda i, j: (j, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kernel_kw),
        grid=(b * n, s_q // block_q, s_k // block_k),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b * n, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=grid_params(),
        interpret=interpret,
        name="flash_attention_dq",
    )(*inputs)

    # dK/dV: grid (bh, kv-tile, q-tile) — Q innermost
    in_specs, inputs, _, kv_spec = specs(lambda j, i: (i, 0),
                                         lambda j, i: (j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kernel_kw),
        grid=(b * n, s_k // block_k, s_q // block_q),
        in_specs=in_specs,
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct((b * n, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((b * n, s_k, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=grid_params(),
        interpret=interpret,
        name="flash_attention_dkv",
    )(*inputs)

    return (_unfold(dq, b, s_q, n, d), _unfold(dk, b, s_k, n, d),
            _unfold(dv, b, s_k, n, d))


def _flash(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    """Output-only attention: _flash_pair with the lse discarded.

    Differentiation flows through _flash_pair's custom_vjp; the unused
    lse output contributes a zero cotangent (folded into delta at no
    meaningful cost), so no second custom_vjp is needed.
    """
    out, _ = _flash_pair(q, k, v, bias, causal, scale, block_q, block_k,
                         interpret)
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_pair(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    """(out, lse) variant — the composable building block.

    Callers that merge attention partials (ring attention) need the
    per-row logsumexp alongside the normalized output, and need
    gradients to flow through BOTH: ``d lse / d s = p``, which folds
    into the existing backward kernels as ``delta_eff = delta - g_lse``
    (ds = p * (dp - delta + g_lse)) — no extra kernel.
    """
    return _flash_fwd(q, k, v, bias, causal, scale, block_q, block_k,
                      interpret)


def _flash_pair_vjp_fwd(q, k, v, bias, causal, scale, block_q, block_k,
                        interpret):
    out, lse = _flash_fwd(q, k, v, bias, causal, scale, block_q, block_k,
                          interpret)
    return (out, lse), (q, k, v, bias, out, lse)


def _flash_pair_vjp_bwd(causal, scale, block_q, block_k, interpret,
                        residuals, gs):
    q, k, v, bias, out, lse = residuals
    g, g_lse = gs
    dq, dk, dv = _flash_bwd(q, k, v, bias, out, lse, g, causal, scale,
                            block_q, block_k, interpret, g_lse=g_lse)
    return dq, dk, dv, None


_flash_pair.defvjp(_flash_pair_vjp_fwd, _flash_pair_vjp_bwd)


def _mask_to_bias(key_mask):
    """[B, S_k] bool -> [B, S_k] f32 additive row (True = attend)."""
    if key_mask is None:
        return None
    return jnp.where(key_mask, 0.0, -jnp.inf).astype(jnp.float32)


def flash_attention_lse(q, k, v, causal=False, scale=None, key_mask=None,
                        block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                        force_pallas=False, interpret=None):
    """Fused attention returning ``(out [B,S,N,D], lse [B,N,S])``.

    The building block for partial-attention composition (ring
    attention's per-step block update): two partials (out_a, lse_a),
    (out_b, lse_b) over disjoint KV merge exactly as

        lse = logaddexp(lse_a, lse_b)
        out = out_a * exp(lse_a - lse) + out_b * exp(lse_b - lse)

    Differentiable in q/k/v including through the lse output. Rows that
    attend to nothing (fully-masked) have lse == -inf and out == 0.

    ``key_mask``: optional [B, S_k] bool, True = key is attendable (the
    BERT-style padding mask).

    Backend policy matches :func:`flash_attention`: Pallas kernels on
    TPU; the XLA reference pair elsewhere (``interpret=True`` /
    ``force_pallas`` route through the Pallas interpreter for tests).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    bias = _mask_to_bias(key_mask)
    tpu = on_tpu()
    if not (tpu or force_pallas or interpret):
        return _reference_lse(q, k, v, causal, scale, bias)
    if interpret is None:
        interpret = not tpu
    b, s, n, d = q.shape
    out, lse = _flash_pair(q, k, v, bias, causal, scale, block_q, block_k,
                           interpret)
    return out, jnp.reshape(lse, (b, n, s))


def flash_attention(q, k, v, causal=False, scale=None, key_mask=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    force_pallas=False, interpret=None):
    """Fused attention. [B, S, N, D] in, [B, S, N, D] out.

    ``key_mask``: optional [B, S_k] bool, True = key is attendable.
    On TPU backends runs the Pallas kernels (both directions); elsewhere
    falls back to the XLA reference (``interpret=True`` forces the
    kernels through the Pallas interpreter — used by tests to validate
    kernel logic on CPU).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    bias = _mask_to_bias(key_mask)
    tpu = on_tpu()
    if interpret is None:
        interpret = not tpu
    if not (tpu or force_pallas):
        return _reference(q, k, v, causal, scale, bias)
    return _flash(q, k, v, bias, causal, scale, block_q, block_k,
                  interpret)
