"""Decoder-only causal LM with KV-cache decode support.

The inference-side sibling of the sequence-parallel training LM
(examples/longcontext/long_dist.py). Full-sequence (training) passes
run the fused flash attention kernel (ops/flash_attention.py — Pallas
on TPU, O(S) attention memory; XLA reference elsewhere); ``decode``
mode maintains an explicit KV cache ("cache" variable collection) so
autoregressive generation (generation.py) costs O(S) per new token
instead of re-running the O(S^2) prefix. The attention parameter tree
matches flax's ``MultiHeadDotProductAttention`` layout, so the
DECODER_TP_RULES catalog and checkpoints are layout-stable.

The reference framework has no generation story at all (its inference
is batch scoring — SURVEY.md §3.3); this is a don't-stop-at-parity
addition shaped for TPU: static shapes everywhere (cache pre-allocated
at ``max_len``), decode steps under ``lax.scan``.

The decode cache is SLOT-STRUCTURED for serving (PR 2): the
``cache_index``/``pos_idx`` cursors are per-row ``[B]`` vectors, so
each batch row can sit at its own sequence depth — the property
serving.DecodeEngine's continuous batching rests on. An s>1 call on an
initialized cache is a fused prefill continuing from each row's cursor
(one program for the whole prompt instead of an s-step scan),
formulated per query row exactly like s single-token steps — equal to
float noise in general and bitwise-equal on the engine's pinned
serving configs.

PAGED KV (PR 8): with ``kv_block_size > 0`` the decode cache stores
K/V in a shared BLOCK POOL ``[kv_blocks, kv_block_size, N * D]``
instead of per-row contiguous ``[B, max_len, N, D]`` regions, plus a
per-row ``block_table`` mapping logical block index -> pool row. The
pool is FLAT (heads and head_dim in one axis) for every model: that
minor pair tiles the chip's (8, 128) exactly, so the write below
updates the donated pool in place, where a ``[.., N, 64]`` pool was
copied whole into another layout and back around every call
(ops/paged_attention.py, "Pool layout"). Writes scatter through the
table (position ``p`` lands in pool row ``table[b, p // bs]`` at
offset ``p % bs``, all heads of the token as one row); attention then
consumes the pool and the block table DIRECTLY
(``ops.paged_attention.paged_attention`` with its default
formulation: a Pallas kernel on TPU whose K/V index maps read the
table, so per-step traffic scales with LIVE tokens, and a blockwise
``fori_loop`` online-softmax formulation elsewhere). No transient
``[B, L, N, D]`` materialization.

The paged and the contiguous paths compute the same visible set under
the same scale; they differ only in float accumulation order (the
online recurrence vs one softmax over the logical row), so the serving
parity pin paged == solo is TOKEN-level at temperature=0
(tests/test_paged_kv.py). Block allocation, sharing, and reclamation
are HOST decisions (paging.BlockPool via the engine); the module just
writes and attends where the table says.

With ``kv_block_size == 0`` the cache is contiguous, ``[B, max_len, N,
D]`` per row: what ``generation.generate`` / ``generate_jit`` run on,
and so the solo oracle of every bitwise pin. serving.DecodeEngine is
paged only.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp


class PagedKV(object):
    """One attention module's paged K/V cache: the ONE place the pool,
    the block table and the write cursor are declared, written and
    attended through, shared by every decoder family (``DecoderLM``
    here, ``models/sdar_moe.py``, ``models/mellum_moe.py``).

    Built inside a module's ``__call__`` it declares, in ``mod``'s
    ``cache`` collection, the flat pools ``cached_key``/``cached_value``
    ``[kv_blocks, block_size, kv_heads * head_dim]`` (int8 codes plus
    ``key_scale``/``value_scale`` ``[.., kv_heads]`` when
    ``quantized``), the per-row ``block_table [B, ceil(s / block_size)]``
    (sized at CREATION from the dummy pass's length — init_cache's
    total_len; entry 0, the scratch block, everywhere until the host
    allocator assigns real blocks) and the per-row ``cache_index [B]``.
    ``initialized`` is False on that creation pass (shapes only).

    ``window`` makes it the cache of a WINDOW layer: a query sees the
    last ``window`` positions only, so the host keeps only the blocks
    that reach back that far and parks scratch in the table entries
    behind them. Such a layer is another KIND of cache with a pool of
    its own size, and its table is declared under another name,
    ``window_table``: the leaf's name is what tells the host's feed
    which kind's table goes where (generation.TABLE_LEAVES).
    """

    def __init__(self, mod, b, s, kv_heads, head_dim, dtype, block_size,
                 blocks, quantized=False, window=None):
        if blocks < 2:
            raise ValueError(
                "paged decode needs kv_blocks >= 2 (row 0 is the scratch "
                "block), got {}".format(blocks))
        self.initialized = mod.has_variable("cache", "cached_key")
        self.block_size, self.quantized = block_size, quantized
        self.window = window
        shape = (blocks, block_size, kv_heads * head_dim)
        store = jnp.int8 if quantized else dtype
        self.key = mod.variable("cache", "cached_key", jnp.zeros, shape,
                                store)
        self.value = mod.variable("cache", "cached_value", jnp.zeros,
                                  shape, store)
        if quantized:
            # per-head scales, one per token row of each block, stored
            # block-aligned so attention's index maps route them with
            # the codes (ones: dequant of the zero codes stays exactly
            # zero)
            self.key_scale = mod.variable(
                "cache", "key_scale", jnp.ones, shape[:2] + (kv_heads,),
                jnp.float32)
            self.value_scale = mod.variable(
                "cache", "value_scale", jnp.ones, shape[:2] + (kv_heads,),
                jnp.float32)
        self.table = mod.variable(
            "cache", "block_table" if window is None else "window_table",
            lambda: jnp.zeros((b, -(-s // block_size)), jnp.int32))
        # Per-ROW write cursor [B], not a scalar: each batch row is an
        # independent sequence (a serving "slot") at its own depth.
        self.index = mod.variable(
            "cache", "cache_index", lambda: jnp.zeros((b,), jnp.int32))

    def positions(self, s):
        """Logical positions ``[B, s]`` this call's tokens sit at."""
        return self.index.value[:, None] + jnp.arange(s)[None, :]

    def attend(self, q, k, v, pos, visible=None):
        """Write ``k``/``v`` ``[B, s, kv_heads, D]`` at ``pos`` through
        the block table (:meth:`write`), and attend ``q [B, s, heads,
        D]`` through the table: query ``i`` of row ``b`` sees every key
        position ``<= visible[b, i]`` (``pos`` itself when None:
        causal) and, with a window, ``> visible[b, i] - window``,
        streaming the row's LIVE blocks through an online softmax."""
        pa, pk, pv, ksc, vsc = self.write(k, v, pos)
        return pa.paged_attention(
            q, pk, pv, self.table.value, pos if visible is None else visible,
            scale=q.shape[-1] ** -0.5, impl=None,
            k_scale=ksc, v_scale=vsc, window=self.window)

    def write(self, k, v, pos):
        """Write ``k``/``v`` ``[B, s, kv_heads, D]`` at ``pos`` through
        the block table and advance the cursor by ``s``. A position
        whose table entry is scratch (pad rows past the sequence, a
        window layer's positions that no later query will see) lands in
        scratch. Alone, it is the cache's part of a call that attends
        its own K and V (a prefill from position 0)."""
        import importlib

        pa = importlib.import_module(
            "tensorflowonspark_tpu.ops.paged_attention")
        b, s = pos.shape
        bs_blk = self.block_size
        table = self.table.value                   # [B, MB]
        mb = table.shape[1]
        blk_idx = pos // bs_blk
        # pad positions past the logical capacity route to the scratch
        # block (pool row 0): bucket-padded prefill tails can overshoot
        # L, and a clamped write would otherwise land on a VISIBLE
        # offset of whatever block sits in the last table entry
        blk = jnp.take_along_axis(
            table, jnp.minimum(blk_idx, mb - 1), axis=1)
        blk = jnp.where(blk_idx < mb, blk, 0)
        off = pos % bs_blk
        if self.quantized:
            # int8 fast path (PR 15): quantize at write time (per head,
            # per token row), scatter codes AND scales through the same
            # table routing; attention dequantizes in-formulation so
            # the per-step HBM traffic is the int8 bytes
            (k, sk), (v, sv) = pa.quantize_kv(k), pa.quantize_kv(v)
            ksc = self.key_scale.value.at[blk, off].set(sk)
            vsc = self.value_scale.value.at[blk, off].set(sv)
            self.key_scale.value = ksc
            self.value_scale.value = vsc
        else:
            ksc = vsc = None
        # a token's heads are one row of the flat pool
        pk = self.key.value.at[blk, off].set(k.reshape(b, s, -1))
        pv = self.value.value.at[blk, off].set(v.reshape(b, s, -1))
        self.key.value = pk
        self.value.value = pv
        self.index.value = self.index.value + s
        return pa, pk, pv, ksc, vsc


class CausalSelfAttention(nn.Module):
    """Causal attention: fused flash kernel for training, explicit KV
    cache for decode.

    Parameter structure deliberately matches flax's
    ``MultiHeadDotProductAttention`` (query/key/value DenseGeneral with
    [H, N, D] kernels, out with [N, D, H]) so TP rule catalogs
    (DECODER_TP_RULES) and existing checkpoints keep working — only the
    attention COMPUTATION differs: full-sequence passes run
    ``ops.flash_attention`` (Pallas on TPU, O(S) memory; XLA reference
    elsewhere) instead of materializing the [S, S] score matrix, and
    decode-mode single-token steps attend against this module's own
    cache variables (cached_key/cached_value/cache_index).
    """

    num_heads: int
    decode: bool = False
    #: paged KV (PR 8): block size in tokens; 0 = contiguous per-row
    #: cache (what generation.generate runs on: the solo oracle of the
    #: bitwise pins)
    kv_block_size: int = 0
    #: pool rows when paged (INCLUDING the scratch block row 0 that
    #: absorbs pad-position writes — see paging.py)
    kv_blocks: int = 0
    #: KV pool storage (PR 15; paged only): "" stores K/V at the
    #: compute dtype; "int8" stores symmetric per-head absmax codes
    #: with float32 scales per token row of each block ("key_scale" /
    #: "value_scale" cache vars, [P, block_size, N]) — writes quantize
    #: (ops.paged_attention.quantize_kv), attention dequantizes
    #: in-formulation, halving per-step KV bandwidth vs bf16 and
    #: doubling+ pool capacity at a fixed byte budget
    kv_dtype: str = ""

    @nn.compact
    def __call__(self, x):
        import importlib

        fa = importlib.import_module(
            "tensorflowonspark_tpu.ops.flash_attention")

        b, s, h = x.shape
        if h % self.num_heads:
            raise ValueError(
                "hidden size {} not divisible by num_heads {}".format(
                    h, self.num_heads))
        head_dim = h // self.num_heads
        dg = functools.partial(nn.DenseGeneral,
                               features=(self.num_heads, head_dim), axis=-1)
        q = dg(name="query")(x)
        k = dg(name="key")(x)
        v = dg(name="value")(x)

        if self.decode:
            paged = self.kv_block_size > 0
            is_initialized = self.has_variable("cache", "cached_key")
            if paged:
                if self.kv_dtype not in ("", "int8"):
                    raise ValueError(
                        "kv_dtype must be '' (compute dtype) or "
                        "'int8', got {!r}".format(self.kv_dtype))
                pool = PagedKV(self, b, s, self.num_heads, head_dim,
                               k.dtype, self.kv_block_size, self.kv_blocks,
                               quantized=self.kv_dtype == "int8")
            else:
                cached_key = self.variable(
                    "cache", "cached_key", jnp.zeros, k.shape, k.dtype)
                cached_value = self.variable(
                    "cache", "cached_value", jnp.zeros, v.shape, v.dtype)
                # Per-ROW write cursor [B], not a scalar: each batch row
                # is an independent sequence (a serving "slot"), so row b
                # writes its token at its own position and attends its
                # own prefix. Whole-batch generation is the degenerate
                # case where every row carries the same index — bitwise-
                # identical to the old scalar formulation (the
                # mask/scatter broadcasts agree elementwise).
                cache_index = self.variable(
                    "cache", "cache_index",
                    lambda: jnp.zeros((b,), jnp.int32))
            if is_initialized and paged:
                # PAGED step/prefill, any s: write K/V for logical
                # positions [idx, idx+s) through the block table, then
                # attend through the table (PagedKV.attend). s==1 is a
                # decode step; s>1 a fused (possibly mid-sequence,
                # prefix-cached) prefill.
                ctx = pool.attend(q, k, v, pool.positions(s))
            elif is_initialized and s == 1:
                # one token per step against the cache prefix
                idx = cache_index.value
                max_len = cached_key.value.shape[1]
                rows = jnp.arange(b)
                ck = cached_key.value.at[rows, idx].set(k[:, 0])
                cv = cached_value.value.at[rows, idx].set(v[:, 0])
                cached_key.value = ck
                cached_value.value = cv
                cache_index.value = idx + 1
                scale = head_dim ** -0.5
                logits = jnp.einsum("bqnd,bknd->bnqk", q, ck,
                                    preferred_element_type=jnp.float32)
                logits = logits * scale
                visible = jnp.arange(max_len)[None, :] <= idx[:, None]
                logits = jnp.where(visible[:, None, None, :], logits,
                                   jnp.finfo(jnp.float32).min)
                probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
                ctx = jnp.einsum("bnqk,bknd->bqnd", probs, cv)
            elif is_initialized:
                # FUSED PREFILL: an s-token call on an initialized cache
                # writes K/V rows [idx, idx+s) at each row's own cursor
                # and attends causally — one program instead of an
                # s-step scan. Formulated exactly like s single-token
                # steps (each query row contracts over the FULL cache
                # length under an arange <= pos mask): mathematically
                # identical per row, and bitwise-equal on the serving
                # engine's pinned configs (tests/test_decode_engine.py);
                # across arbitrary chunk shapes XLA's accumulation
                # order may differ in the last float bit. A fresh cache
                # (idx 0) is plain prompt prefill (generation.generate);
                # an advanced cache gets correct CHUNKED continuation
                # rather than the silent restart-at-zero a position-0
                # assumption would produce.
                idx = cache_index.value
                max_len = cached_key.value.shape[1]
                rows = jnp.arange(b)[:, None]
                pos = idx[:, None] + jnp.arange(s)[None, :]  # [B, s]
                ck = cached_key.value.at[rows, pos].set(k)
                cv = cached_value.value.at[rows, pos].set(v)
                cached_key.value = ck
                cached_value.value = cv
                cache_index.value = idx + s
                scale = head_dim ** -0.5
                logits = jnp.einsum("bqnd,bknd->bnqk", q, ck,
                                    preferred_element_type=jnp.float32)
                logits = logits * scale
                visible = (jnp.arange(max_len)[None, None, :]
                           <= pos[:, :, None])  # [B, s, max_len]
                logits = jnp.where(visible[:, None, :, :], logits,
                                   jnp.finfo(jnp.float32).min)
                probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
                ctx = jnp.einsum("bnqk,bknd->bqnd", probs, cv)
            else:
                # cache creation pass (full-length dummy): shapes only
                ctx = v
        elif s % fa.DEFAULT_BLOCK_Q == 0:
            ctx = fa.flash_attention(q, k, v, causal=True)
        else:
            # the Pallas kernel needs seq % block == 0 on TPU; short or
            # oddly-shaped sequences take the exact XLA reference
            ctx = fa._reference(q, k, v, True, head_dim ** -0.5)
        return nn.DenseGeneral(h, axis=(-2, -1), name="out")(ctx)


class DecoderBlock(nn.Module):
    num_heads: int
    decode: bool = False
    kv_block_size: int = 0
    kv_blocks: int = 0
    kv_dtype: str = ""

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm(name="ln1")(x)
        y = CausalSelfAttention(self.num_heads, decode=self.decode,
                                kv_block_size=self.kv_block_size,
                                kv_blocks=self.kv_blocks,
                                kv_dtype=self.kv_dtype,
                                name="attn")(y)
        x = x + y
        y = nn.LayerNorm(name="ln2")(x)
        h = x.shape[-1]
        y = nn.Dense(4 * h, name="mlp_in")(y)
        y = nn.gelu(y)
        y = nn.Dense(h, name="mlp_out")(y)
        return x + y


class DecoderLM(nn.Module):
    """Tiny GPT-style LM: learned positions, pre-LN blocks, tied-free head.

    ``decode=True`` instances carry the KV cache: init it by running a
    full-length dummy input with ``init`` (flax materializes the cache at
    that length), then feed one token at a time — or a whole prompt at
    once (fused prefill from position 0) on a fresh cache.
    """

    vocab: int
    hidden: int = 64
    num_heads: int = 4
    num_layers: int = 2
    max_len: int = 512
    decode: bool = False
    #: paged KV (PR 8; decode=True only): block size in tokens (0 =
    #: contiguous per-row cache) and pool rows including the scratch
    #: row. serving.DecodeEngine clones its model with these set; see
    #: CausalSelfAttention and docs/serving.md.
    kv_block_size: int = 0
    kv_blocks: int = 0
    #: KV pool storage (PR 15): "" = compute dtype, "int8" = quantized
    #: codes + per-head scales (see CausalSelfAttention.kv_dtype);
    #: ignored unless kv_block_size > 0. The engine's ``kv_dtype``
    #: knob clones the model with this set.
    kv_dtype: str = ""

    @nn.compact
    def __call__(self, tokens):
        b, s = tokens.shape
        x = nn.Embed(self.vocab, self.hidden, name="tok_embed")(tokens)
        pos_embed = self.param(
            "pos_embed", nn.initializers.normal(0.02),
            (self.max_len, self.hidden))
        if self.decode:
            # the LM tracks its own position alongside the attention KV
            # caches (the flax lm1b pattern): 0 during cache init (the
            # full-length dummy pass), then advancing by s per call.
            # Like the attention cache_index, the position cursor is
            # per-ROW [B] so each slot decodes at its own depth.
            initializing = not self.has_variable("cache", "pos_idx")
            pos_idx = self.variable("cache", "pos_idx",
                                    lambda: jnp.zeros((b,), jnp.int32))
            if initializing:
                # full-length dummy pass: positions 0..s-1, all rows
                x = x + pos_embed[:s][None]
            elif s == 1:
                # mode="clip" for the same reason as the fused-prefill
                # branch below: a speculative draft's propose scan
                # (PR 15) advances row cursors one past another up to
                # k-1 positions BEYOND a nearly-full row's capacity —
                # the writes route to the scratch block, but the
                # default fill mode would hand those rows NaN
                # embeddings whose K/V poisons attention through the
                # 0 x NaN contraction (the exact PR 11 bug class).
                # In-range rows are untouched (bitwise-identical).
                x = x + jnp.take(pos_embed, pos_idx.value,
                                 axis=0, mode="clip")[:, None, :]
                pos_idx.value = pos_idx.value + s
            else:
                # fused prefill: positions continue from each row's own
                # cursor (see CausalSelfAttention's prefill branch).
                # mode="clip": bucket-pad rows can sit PAST max_len
                # (paged prefill whose tail bucket overshoots the
                # logical capacity), and jnp.take's default fill mode
                # would hand them NaN embeddings — NaN K/V that, even
                # written to the scratch block and fully masked, still
                # poisons attention (0 * NaN = NaN in the probs @ V
                # contraction). Clipped pad rows get a wrong-but-
                # FINITE embedding; their K/V is invisible by the
                # cursor discipline, which only zero-weights finite
                # values.
                pos = pos_idx.value[:, None] + jnp.arange(s)[None, :]
                x = x + jnp.take(pos_embed, pos, axis=0, mode="clip")
                pos_idx.value = pos_idx.value + s
        else:
            x = x + pos_embed[:s][None]
        # causality lives inside CausalSelfAttention (flash kernel /
        # cache visibility) — no mask threading
        for i in range(self.num_layers):
            x = DecoderBlock(self.num_heads, decode=self.decode,
                             kv_block_size=self.kv_block_size,
                             kv_blocks=self.kv_blocks,
                             kv_dtype=self.kv_dtype,
                             name="block_%d" % i)(x)
        x = nn.LayerNorm(name="ln_f")(x)
        return nn.Dense(self.vocab, name="head")(x)
