"""Autoregressive generation over a KV cache — TPU-idiomatic decode.

The reference's inference is batch scoring only (SURVEY.md §3.3); this
is the don't-stop-at-parity decode loop for the decoder LM family
(models/decoder.py): the whole generation — prompt prefill AND sampling
— runs as two ``lax.scan``s inside ONE jit with static shapes, so XLA
compiles a single program per (batch, prompt_len, max_new) signature
and each new token costs O(1) attention against the pre-allocated
cache instead of re-running the O(S²) prefix.

    model = DecoderLM(vocab=V, ..., decode=True, max_len=TOTAL)
    out = generate(model, params, prompt, max_new_tokens=64)

``temperature=0`` is greedy; otherwise softmax sampling with the given
PRNG key. ``generate`` feeds one token per step (the flax decode-cache
contract), which makes its prefill a scan — simple and fully compiled.

For SERVING, this module also provides the slot-structured primitives
over the paged block pool (``paged_prefill_into_slot`` — fused
multi-token, shape-bucketed — and ``paged_decode_step`` over per-slot
cursors and block tables) that serving.DecodeEngine schedules
continuously; see docs/serving.md. Both paths produce identical greedy
outputs per sequence.
"""

import functools

import jax
import jax.numpy as jnp


def init_cache(model, batch, total_len):
    """Fresh KV cache for ``batch`` sequences of up to ``total_len``.

    Shape-only: ``jax.eval_shape`` over ``model.init`` yields the cache
    pytree structure without executing the full-length dummy forward
    (the cache starts as zeros anyway; params come from training, not
    from here).
    """
    dummy = jnp.zeros((batch, total_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dummy))
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        shapes["cache"])


def check_sampling_config(temperature, top_k, top_p, rng):
    """Raise ValueError on sampling configs that would serve silently
    wrong tokens (top_k=0 / top_p=0 mask EVERY logit to -inf and emit
    token 0 forever; temperature>0 without a key replays one stream).
    Shared by ``generate`` and ``serving.DecodeEngine`` so both paths
    fail loudly on the same inputs."""
    if temperature and rng is None:
        raise ValueError("temperature sampling needs a PRNG key")
    if top_k is not None and int(top_k) < 1:
        raise ValueError("top_k must be >= 1, got {}".format(top_k))
    if top_p is not None and not 0.0 < float(top_p) <= 1.0:
        raise ValueError("top_p must be in (0, 1], got {}".format(top_p))


def filter_logits(logits, top_k=None, top_p=None, temperature=0.0):
    """Apply top-k then nucleus filtering to ``[B, V]`` logits.

    Both filters mask by INDEX, not by value threshold: a value cutoff
    keeps every token tied with the boundary logit, which degenerates to
    a no-op on tied/uniform logits. ``top_p >= 1.0`` is an exact no-op
    by construction — the cumsum formulation would drop tail tokens once
    float32 saturates at 1.0. The nucleus keeps the smallest sorted
    prefix whose mass reaches p (the head token always survives).
    """
    rows = jnp.arange(logits.shape[0])[:, None]
    if top_k is not None:
        _, idx_k = jax.lax.top_k(logits, int(top_k))
        keep = jnp.zeros(logits.shape, bool).at[rows, idx_k].set(True)
        logits = jnp.where(keep, logits, -jnp.inf)
    if top_p is not None and top_p < 1.0:
        idx = jnp.argsort(logits, axis=-1)[:, ::-1]
        sorted_logits = jnp.take_along_axis(logits, idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits / (temperature or 1.0),
                               axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = cum - probs < top_p  # mass BEFORE this token
        keep = jnp.zeros(logits.shape, bool).at[rows, idx].set(keep_sorted)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def generate(model, params, prompt, max_new_tokens, temperature=0.0,
             rng=None, top_k=None, top_p=None, eos_token=None,
             pad_token=0):
    """[B, S] prompt -> [B, S + max_new_tokens] generated tokens.

    ``model`` must be a decode-mode instance (``decode=True``) whose
    ``max_len >= S + max_new_tokens``. Prompts must be REAL tokens of
    uniform length — there is no padding mask in the decode cache, so a
    padded ragged batch would silently attend its pad positions; bucket
    ragged prompts by length instead. Deterministic (greedy) when
    ``temperature == 0``; otherwise ``rng`` is required. ``top_k``
    restricts sampling to the k highest logits; ``top_p`` to the
    smallest nucleus whose probability mass reaches p (composable:
    top_k filters first). ``eos_token`` freezes a
    sequence once emitted — output positions after it become
    ``pad_token`` — with STATIC shapes (every sequence still runs
    ``max_new_tokens`` steps; finished ones just stop changing, the
    TPU-correct formulation of early stop).
    """
    if getattr(model, "kv_block_size", 0):
        # the solo path has no block allocator: a paged model's default
        # table maps every row to the scratch block, which would serve
        # garbage silently. Paged decode is the serving engine's job
        # (serving.DecodeEngine manages tables via paging.BlockPool);
        # solo generation wants the contiguous-cache twin of the model.
        raise ValueError(
            "generate() needs a contiguous-cache model "
            "(kv_block_size=0); paged KV decode runs through "
            "serving.DecodeEngine")
    prompt = jnp.asarray(prompt, jnp.int32)
    b, s = prompt.shape
    if int(max_new_tokens) < 0:
        raise ValueError(
            "max_new_tokens must be >= 0, got {}".format(max_new_tokens))
    total = s + int(max_new_tokens)
    if model.max_len < total:
        raise ValueError(
            "model.max_len={} < prompt {} + max_new_tokens {}".format(
                model.max_len, s, max_new_tokens))
    check_sampling_config(temperature, top_k, top_p, rng)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if int(max_new_tokens) == 0:
        # nothing to decode; returning the prompt keeps the output
        # contract ([B, S + N]) instead of crashing in split(rng, 0).
        # Placed AFTER the argument checks so N=0 rejects the same
        # invalid top_k/top_p/max_len calls every nonzero N does.
        return prompt
    cache = init_cache(model, b, model.max_len)

    def one_token(cache, token):
        """token [B, 1] -> (new cache, logits [B, V])."""
        logits, updated = model.apply(
            {"params": params, "cache": cache}, token, mutable=["cache"])
        return updated["cache"], logits[:, -1, :]

    def prefill_step(carry, tok_col):
        cache, _ = carry
        cache, logits = one_token(cache, tok_col[:, None])
        return (cache, logits), None

    (cache, logits), _ = jax.lax.scan(
        prefill_step, (cache, jnp.zeros((b, model.vocab), jnp.float32)),
        prompt.T)

    def pick_frozen(logits, key, done):
        """``_pick_tokens`` (the ONE sampling implementation, shared
        with the slot path so they cannot diverge), but finished
        sequences emit pad and stay finished."""
        token = _pick_tokens(logits, key, temperature, top_k, top_p)
        if eos_token is None:
            return token, done
        token = jnp.where(done, jnp.int32(pad_token), token)
        return token, done | (token == eos_token)

    done0 = jnp.zeros((b,), bool)

    def decode_step(carry, key):
        cache, logits, done = carry
        token, done = pick_frozen(logits, key, done)
        cache, next_logits = one_token(cache, token[:, None])
        return (cache, next_logits, done), token

    # the LAST token needs no cache-advancing forward: scan N-1 steps,
    # then pick once from the carried logits (N forwards would waste one)
    keys = jax.random.split(rng, max_new_tokens)
    if max_new_tokens > 1:
        (cache, logits, done0), body_tokens = jax.lax.scan(
            decode_step, (cache, logits, done0), keys[:-1])
    else:
        body_tokens = jnp.zeros((0, b), jnp.int32)
    last, _ = pick_frozen(logits, keys[-1], done0)
    new_tokens = jnp.concatenate([body_tokens, last[None]], axis=0)
    return jnp.concatenate([prompt, new_tokens.T], axis=1)


# -- slot-structured primitives (continuous-batching decode) -----------
#
# The whole-generation ``generate``/``generate_jit`` above compiles one
# program per (batch, prompt_len, max_new) signature and runs each batch
# to completion — fine for offline jobs, the wrong shape for serving
# mixed-length traffic. The primitives below decompose generation so a
# scheduler (serving.DecodeEngine) can run ITERATION-LEVEL batching over
# a slot-structured KV cache: ``init_cache(model, slots, total_len)``
# gives one cache with S independent slots (rows), each with its own
# write cursor (models/decoder.py keeps ``cache_index``/``pos_idx``
# per-ROW for exactly this), a prefill runs one request's prompt
# (padded to a shape bucket) into one slot, and a decode step runs one
# fixed-shape step over all S slots at their own cursors.


def _pick_tokens(logits, key, temperature, top_k, top_p):
    """[B, V] logits -> [B] sampled/argmax tokens — the single sampling
    implementation behind BOTH the solo path (``generate``'s
    pick_frozen) and the slot path, so they stay bitwise-identical at
    every temperature."""
    logits = filter_logits(logits, top_k=top_k, top_p=top_p,
                           temperature=temperature)
    if temperature:
        return jax.random.categorical(
            key, logits / temperature, axis=-1).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


#: flax cache leaves that are per-row WRITE CURSORS, not K/V storage
_CURSOR_LEAVES = ("cache_index", "pos_idx")


def _leaf_name(path):
    entry = path[-1]
    return getattr(entry, "key", None) or getattr(entry, "name", str(entry))


def _fed_tokens(picked, feed):
    """Each row's input token of a token step: the host's where it gave
    one (``feed[:, 0] >= 0``), else the device's own pick of the step
    before (the first ``S`` of its answer: :func:`with_routed`). Inside
    the step program: no dispatch of its own."""
    if picked.shape[0] != feed.shape[0]:
        picked = picked[:feed.shape[0]]
    return jnp.where(feed[:, 0] >= 0, feed[:, 0], picked)


def answer_len(model, tokens, positions):
    """Length of what a token program of ``model`` answers for a call
    over ``positions`` positions that picks ``tokens`` tokens (a step:
    one a slot of each; a prefill: one token, a bucket of positions):
    the tokens, and behind them ``experts_per_tok`` expert ids a
    position for each of the model's ``routed_layers`` (none for a model
    without experts: the tokens alone)."""
    layers = int(getattr(model, "routed_layers", 0))
    return tokens + layers * positions * (
        int(model.experts_per_tok) if layers else 0)


def with_routed(picked, updated):
    """What a token program answers: the tokens ``picked`` and, in the
    SAME int32 array (one copy to the host, a turn late with the
    tokens), the experts the router sent each position of the call to,
    ``[layers, positions, experts_per_tok]`` raveled, where the model
    sows them (:func:`_expert_ids`). A model that sows nothing answers
    the tokens alone: the program it always was."""
    sown = updated.get("intermediates", {})
    if not jax.tree.leaves(sown):
        return picked
    return jnp.concatenate([picked.reshape(-1),
                            _expert_ids(sown).astype(jnp.int32).ravel()])


def split_routed(answer, tokens, positions, top_k):
    """``(tokens [tokens], expert_ids [layers, positions, top_k] or
    None)`` of a token program's answer (host side, numpy; views): the
    inverse of :func:`with_routed`."""
    import numpy as np

    answer = np.asarray(answer).reshape(-1)
    if answer.size == tokens:
        return answer, None
    return answer[:tokens], answer[tokens:].reshape(-1, positions, top_k)


def pack_step_feed(given, idx, tables):
    """The host's part of a token step's input, ONE int32 array handed
    to the call as numpy (the call transfers it itself): column 0 the
    token ``given [S]`` a row starts from, -1 where the row feeds back
    the device's own pick; column 1 the cursors ``idx [S]``; after them
    the block ``tables [S, MB]``."""
    return pack_block_feed(given[:, None], idx, tables)


# -- paged-KV slot primitives (PR 8) -----------------------------------
#
# For models built with ``kv_block_size > 0`` (models/decoder.py): K/V
# lives in a shared block pool and each slot reaches its sequence
# through a block-table row. Because the POOL is batch-independent
# (only tables and cursors are per-row), prefill is a batch-1 apply
# with the slot's table row and a start cursor, which writes the
# tail's K/V straight into the slot's blocks — also exactly how a
# PREFIX-CACHED admission prefills only the un-shared tail of its
# prompt (start = shared prefix length, a block multiple; the fused
# mid-sequence continuation branch reads the shared prefix K/V through
# the table).
#
# WHAT CROSSES THE JIT BOUNDARY (PR 37). A call of a jitted program
# costs the host by the buffers it hands over and, more, by those it
# takes back, whatever they hold, and a token engine's gap is that call
# where the device's step is shorter (GPT-2 large on a v5e, PERF.md
# PR 37: 6.1 ms a call with 730 buffers in and 146 out, 73 of them small
# arrays allocated anew each step, over a device step of 4.5 ms; 2.2 ms
# with the pools alone crossing). So the cache crosses as its POOL
# leaves only (:func:`init_pools`: K and V a layer, an int8 pool's
# scales beside them), donated and answered in place. The cursor and
# table leaves of the flax ``cache`` collection never cross: the host
# is the authority on both, so each program builds them inside its
# trace from what it is fed (:func:`_set_paged_leaves`,
# :func:`_slot_view`; which leaves a model's cache has is asked of the
# model, :func:`_cache_shapes`) and answers none of them. A step
# allocates its tokens' array and nothing else.
#
# The PARAMETERS cross as the tree they are, a buffer a leaf. Stacking
# the layers' like leaves into one array each (sliced again inside the
# trace) takes the same call to 0.8 ms and was measured and left out:
# the compiler stages a whole parameter through the chip's fast memory
# beside the compute and reads a slice of a stack in place, so the
# device's step went 4.48 -> 6.23 ms with the matrices stacked and
# 4.48 -> 4.54 with the biases and norms alone (4-5 us a slice read),
# and the device's step is the gap once the call is under it.
#
# A model with a recurrent state (PR 38) adds its STATE leaves to what
# crosses (:data:`_STATE_LEAVES`: a row a slot and layer), donated and
# answered in place like the pools; which row a call's batch row is
# comes from a slot leaf built inside the trace, as the cursors are.
#
# The jitted wrappers donate the pools, so the scheduler's steady-state
# loop updates them in place instead of copying them. Every engine
# program is the jit of a NAMED function: a trace's host spans then
# read ``PjitFunction(paged_prefill)`` and the device's ``XLA Modules``
# line ``jit_paged_prefill``, where a lambda leaves every program
# ``<lambda>``.


#: flax cache leaves that are per-row BLOCK TABLES, one name per kind
#: of cache (models/decoder.PagedKV: a window layer's is its own). The
#: host hands the kinds' tables over side by side in ONE array, in this
#: order (paging.CacheKinds.tables), each the same width.
TABLE_LEAVES = ("block_table", "window_table")

#: flax cache leaves that are per-BLOCK pool storage: what of a paged
#: cache lives on the device between calls, and its shippable content
#: (everything else is per-slot host-owned state: cursors and block
#: tables cross no boundary)
_POOL_LEAVES = ("cached_key", "cached_value", "key_scale", "value_scale")

#: flax cache leaves that are per-SLOT recurrent state (PR 38,
#: models/falcon_h1.py: a Mamba-2 mixer's SSM state and its
#: convolution's last inputs, a row a slot): they live on the device
#: between calls and cross the boundary donated, beside the pools, but
#: are addressed by slot and not through a table, and ship nowhere
_STATE_LEAVES = ("ssm_state", "conv_state")

#: flax cache leaves that say which state row each batch row of a call
#: is (built inside the trace like a cursor: a step's rows are the
#: slots, a prefill's one row the slot it fills)
_SLOT_LEAVES = ("state_slot",)


@functools.lru_cache(maxsize=32)
def _cache_shapes(model):
    """The ``cache`` collection of a paged ``model`` as shapes, by a
    shape-only ``init`` over one block of positions: which leaves there
    are, where, and of what dtype. The pools' shapes are the model's
    own; a cursor's or a table's follow the call and mean nothing
    here."""
    dummy = jnp.zeros((1, model.kv_block_size), jnp.int32)
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), dummy))["cache"]


def init_pools(model):
    """Fresh pools of a paged ``model``: its cache collection with the
    pool leaves only, zeros. What an engine keeps on the device and
    every paged program takes, donated, and answers."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        _pools_of(_cache_shapes(model)))


def _pools_of(cache):
    """``cache`` (a nested mapping) with its pool leaves only."""
    out = {}
    for name, sub in cache.items():
        if hasattr(sub, "items"):
            sub = _pools_of(sub)
            if sub:
                out[name] = sub
        elif name in _POOL_LEAVES or name in _STATE_LEAVES:
            out[name] = sub
    return out


def _fed_cache(model, cache, cursor, tables, slot=None):
    """The flax ``cache`` collection of ``model`` for one call: the pool
    and state leaves of ``cache`` (whatever else it holds is not read),
    every cursor leaf ``cursor``, every table leaf its kind's columns of
    ``tables [B, kinds x width]`` and every slot leaf ``slot`` for each
    row (None: row ``b`` is slot ``b``), each cast to the leaf's dtype.
    A model without slot leaves traces nothing of ``slot``."""
    shapes = _cache_shapes(model)
    names = {_leaf_name(path) for path, _
             in jax.tree_util.tree_leaves_with_path(shapes)}
    kinds = [name for name in TABLE_LEAVES if name in names]
    width = tables.shape[-1] // len(kinds)
    by_kind = {name: tables[..., n * width:(n + 1) * width]
               for n, name in enumerate(kinds)}

    def build(shapes, cache):
        out = {}
        for name, sub in shapes.items():
            if hasattr(sub, "items"):
                out[name] = build(sub, cache[name])
            elif name in _CURSOR_LEAVES:
                out[name] = cursor.astype(sub.dtype)
            elif name in _SLOT_LEAVES:
                rows = cursor.shape[0]
                out[name] = (jnp.arange(rows) if slot is None else
                             jnp.full((rows,), slot)).astype(sub.dtype)
            elif name in by_kind:
                out[name] = by_kind[name].astype(sub.dtype)
            else:
                out[name] = cache[name]
        return out

    return build(shapes, cache)


def _set_paged_leaves(model, cache, idx, tables):
    """The cache collection of a step over every slot: the pools of
    ``cache``, the cursor leaves ``idx [S]`` and the block-table leaves
    ``tables [S, kinds x width]`` (each kind's its own columns,
    :data:`TABLE_LEAVES`): the host scheduler is the authority on both
    position AND block mapping, every call, so neither is kept on the
    device.

    A freed slot must NOT keep advancing its cursor while it idles, and
    a re-admitted slot restarts at its new length. Feeding the cursors
    to each step makes the model's own increments advisory, so an
    inactive slot (cursor 0, every table entry the scratch block) just
    re-writes one scratch position in place.

    This same discipline is what makes MID-FLIGHT EVICTION (PR 4:
    cancel / deadline, serving.DecodeEngine._evict_expired) free: an
    evicted request's slot is simply marked free on the host — no
    device-side cleanup exists or is needed, because a freed slot's
    stale K/V is unreachable once no table row names its blocks, and
    neighbors never see it. Eviction therefore cannot perturb
    concurrent sequences, which is why cancelled-neighbor outputs stay
    bitwise-identical (tests/test_serving_lifecycle.py pins this).

    A step's rows ARE the slots, so a state leaf's row of batch row
    ``b`` is ``b`` (the slot leaves); a row at cursor 0 is idle and its
    state is neither read nor written (ops/ssm_step.py)."""
    return _fed_cache(model, cache, jnp.asarray(idx, jnp.int32),
                      jnp.asarray(tables, jnp.int32))


def _slot_view(model, cache, table_row, start, slot=0):
    """The batch-1 view of ONE slot on the shared pool: cursor leaves at
    ``start``, block-table leaves the slot's row, slot leaves ``slot``,
    pool and state leaves as they are (they are batch-independent)."""
    return _fed_cache(
        model, cache, jnp.full((1,), jnp.asarray(start, jnp.int32)),
        jnp.asarray(table_row, jnp.int32)[None, :], slot)


def paged_prefill_into_slot(model, params, cache, table_row, tokens,
                            tail_len, start, temperature=0.0, top_k=None,
                            top_p=None, rng=None, slot=0):
    """Prefill a prompt TAIL into the pool blocks ``table_row`` maps.

    ``tokens [bucket]`` is the un-shared tail of the prompt padded to
    its shape bucket; ``tail_len`` its real length; ``start`` the
    logical position the tail begins at (0 cold, the shared-prefix
    length — always a block multiple — on a prefix-cache hit).
    ``table_row [MB]`` is the slot's full block table: shared prefix
    blocks first (read-only here: the cursor starts past them), then
    the private blocks the tail writes, then scratch (0) padding that
    absorbs bucket-pad writes.

    Runs as ONE batch-1 apply against the SHARED pool:
    the pool leaves are batch-independent, so the slot's writes land in
    place and no other slot's blocks are touched. Returns
    ``(cache', first_token)`` with the first generated token picked
    from the logits at the last real tail position (so a warm
    ``max_new_tokens=1`` request costs one tiny-bucket forward). A
    model that ``takes_last`` runs its head on that one position and
    not on the bucket; one that sows its routed experts answers them
    behind the token (:func:`with_routed`). ``slot`` is the state row a
    model with a recurrent state writes whole (a prefill from position
    0; models/falcon_h1.py); a model without one reads nothing of it."""
    tail_len = jnp.asarray(tail_len, jnp.int32)
    variables = {"params": params,
                 "cache": _slot_view(model, cache, table_row, start, slot)}
    if getattr(model, "takes_last", False):
        cap, upd = model.apply(variables, tokens[None, :],
                               last=(tail_len - 1)[None],
                               mutable=["cache", "intermediates"])
        cap = cap[:, 0]
    else:
        logits, upd = model.apply(variables, tokens[None, :],
                                  mutable=["cache"])
        cap = jax.lax.dynamic_index_in_dim(
            logits, tail_len - 1, axis=1, keepdims=False)
    first = _pick_tokens(cap, rng, temperature, top_k, top_p)[0]
    return _pools_of(upd["cache"]), with_routed(first, upd)


def paged_decode_step(model, params, cache, tokens, idx, tables,
                      temperature=0.0, top_k=None, top_p=None, rng=None):
    """One fixed-shape decode step over every slot.

    ``tokens [S]`` is each slot's previously emitted token, ``idx [S]``
    each slot's write cursor and ``tables [S, MB]`` each slot's
    block-table row (the scheduler's host-side copies — see
    :func:`_set_paged_leaves`). Every slot computes (static shapes);
    the scheduler simply ignores emissions from slots it knows are free.
    Returns ``(cache', next_tokens [S])``, with the routed experts of
    every slot's position behind the tokens where the model sows them
    (:func:`with_routed`)."""
    mutable = ["cache", "intermediates"] \
        if getattr(model, "routed_layers", 0) else ["cache"]
    logits, upd = model.apply(
        {"params": params,
         "cache": _set_paged_leaves(model, cache, idx, tables)},
        tokens[:, None], mutable=mutable)
    picked = _pick_tokens(logits[:, -1, :], rng, temperature, top_k, top_p)
    return _pools_of(upd["cache"]), with_routed(picked, upd)


# the jitted program of paged_step_fns carries the name
# ``paged_decode_step`` too (it is what a trace shows), which hides
# this one inside that function: the alias is how its wrapper gets here
_paged_decode_step = paged_decode_step


@functools.lru_cache(maxsize=32)
def paged_step_fns(model, temperature=0.0, top_k=None, top_p=None):
    """(jitted paged prefill, jitted paged decode) for one paged model
    + sampling config, cache-donating, reused across engines. Each
    takes the cache as its pools (:func:`init_pools`; of a whole cache
    collection the pool leaves are read) and answers the pools.

    Compile-count contract (asserted in tests): ONE decode program per
    (slots, total_len) engine config, one prefill program per TAIL
    bucket (``start``/``tail_len`` are traced scalars, so a warm prefix
    and a cold prompt of equal tail bucket share a program).
    ``fn._cache_size()`` exposes the live program count —
    serving.DecodeEngine surfaces both via ``compile_stats()``.

    The decode fn is ``step(params, cache, picked [S], feed, key) ->
    (cache', picked' [S])``: ``picked`` is the step before's own answer,
    still on the device, and ``feed`` the host's part
    (:func:`pack_step_feed`: tokens, cursors, then the block tables in
    the one array), so a step can be dispatched before the one before
    it has been read. A model with ``routed_layers`` answers the routed
    experts behind the tokens (:func:`with_routed`, as long as
    :func:`answer_len` says), and the next step reads the first ``S``.

    The prefill is told the SLOT it fills (a traced scalar: one
    program for every slot), which a model with a recurrent state
    (paging.StateKind) writes whole and every other model ignores."""
    def paged_prefill(params, cache, table_row, tokens, tail_len, start,
                      key, slot):
        return paged_prefill_into_slot(
            model, params, cache, table_row, tokens, tail_len, start,
            temperature=temperature, top_k=top_k, top_p=top_p, rng=key,
            slot=slot)

    def paged_decode_step(params, cache, picked, feed, key):
        return _paged_decode_step(
            model, params, cache, _fed_tokens(picked, feed), feed[:, 1],
            feed[:, 2:], temperature=temperature, top_k=top_k, top_p=top_p,
            rng=key)

    return (jax.jit(paged_prefill, donate_argnums=(1,)),
            jax.jit(paged_decode_step, donate_argnums=(1,)))


# -- block-stepping primitives (diffusion over blocks) -------------------
#
# A model that generates by diffusion over blocks (models/sdar_moe.py:
# its ``block_len`` field says so) does not yield one token per row per
# step. A step runs the ``block_len`` positions of every slot's current
# block against the paged cache - their K/V computed from the block's
# current, partly masked state and written at the slot's cursor - and
# answers, per position, the most probable token and its probability.
# The HOST decides what that means (:func:`unmask`): it keeps the
# cursor where it is while masks are left (so the next pass overwrites
# the block's K/V) and moves it on by ``block_len`` after the pass that
# ran the final tokens (the commit). Every slot is in its own phase, so
# there is ONE step program of shape ``[slots, block_len]``; a prefill
# writes the prompt's whole blocks and samples nothing.


def _expert_ids(intermediates):
    """``[layers, positions, experts_per_tok]``: the experts the router
    sent each position of this call to, as the model's expert layers
    sowed them. Padding and idle slots' positions are routed like any
    other: the caller, who knows which are live, does the counting."""
    return jnp.stack(jax.tree.leaves(intermediates))


def paged_block_prefill(model, params, cache, table_row, tokens, start):
    """Write the K/V of whole prompt blocks ``tokens [bucket]`` (padded;
    pad rows land past the slot's cursor or in scratch) from logical
    position ``start`` into the blocks ``table_row`` maps. No head, no
    token: returns ``(cache', expert_ids [layers, bucket, k])``."""
    _, upd = model.apply(
        {"params": params,
         "cache": _slot_view(model, cache, table_row, start)},
        tokens[None, :], head=False, mutable=["cache", "intermediates"])
    return _pools_of(upd["cache"]), _expert_ids(upd["intermediates"])


def paged_block_step(model, params, cache, tokens, idx, tables):
    """One pass over every slot's current block: ``tokens [S, B]`` (MASK
    where a position is still masked) at cursors ``idx [S]``. Returns
    ``(cache', (best [S, B] int32, confidence [S, B] float32,
    expert_ids [layers, S * B, k] int32))``: the most probable token of
    each position and its probability."""
    logits, upd = model.apply(
        {"params": params,
         "cache": _set_paged_leaves(model, cache, idx, tables)},
        tokens, mutable=["cache", "intermediates"])
    top = jnp.max(logits, axis=-1)
    conf = jnp.exp(top - jax.nn.logsumexp(logits, axis=-1))
    return _pools_of(upd["cache"]), (
        jnp.argmax(logits, axis=-1).astype(jnp.int32), conf,
        _expert_ids(upd["intermediates"]))


_paged_block_prefill, _paged_block_step = paged_block_prefill, \
    paged_block_step


@functools.lru_cache(maxsize=32)
def paged_block_fns(model):
    """(jitted block prefill, jitted block step) for one block-stepping
    model, cache-donating - the sibling of :func:`paged_step_fns`, same
    compile-count contract (one step program, one prefill per bucket),
    the same naming of programs and the same form of the cache.

    The step runs between every two host decisions with the device
    waiting on both sides, and every array that crosses is a round
    trip of its own. So it takes ONE array (:func:`pack_block_feed`)
    and answers ONE (:func:`unpack_block_step`): ``step(params, cache,
    feed [S, B + 1 + MB] int32) -> (cache', answers int32)``."""
    def paged_block_prefill(params, cache, table_row, tokens, start):
        return _paged_block_prefill(model, params, cache, table_row,
                                    tokens, start)

    def paged_block_step(params, cache, feed):
        b = model.block_len
        cache, (best, conf, routed) = _paged_block_step(
            model, params, cache, feed[:, :b], feed[:, b], feed[:, b + 1:])
        # the confidences bit for bit among the int32
        return cache, jnp.concatenate([
            best.ravel(),
            jax.lax.bitcast_convert_type(conf, jnp.int32).ravel(),
            routed.astype(jnp.int32).ravel()])

    return (jax.jit(paged_block_prefill, donate_argnums=(1,)),
            jax.jit(paged_block_step, donate_argnums=(1,)))


def pack_block_feed(tokens, idx, tables):
    """The jitted block step's one argument (host side, numpy): each
    slot's block ``tokens [S, B]``, its cursor ``idx [S]`` and its
    table row ``tables [S, MB]`` side by side, int32."""
    import numpy as np

    return np.concatenate([tokens, idx[:, None], tables],
                          axis=1, dtype=np.int32)


def unpack_block_step(packed, slots, block_len, top_k):
    """``(best [S, B] int32, confidence [S, B] float32, expert_ids
    [layers, S * B, top_k] int32)`` out of the one array the jitted
    block step of :func:`paged_block_fns` answers (host side, numpy;
    views, nothing is copied)."""
    import numpy as np

    packed = np.asarray(packed)
    n = slots * block_len
    return (packed[:n].reshape(slots, block_len),
            packed[n:2 * n].view(np.float32).reshape(slots, block_len),
            packed[2 * n:].reshape(-1, n, top_k))


def unmask(conf, masked, quota, threshold):
    """Which masked positions of a block a denoising pass unmasks (host
    side, numpy): every one whose confidence exceeds ``threshold``, or,
    if those are fewer than ``quota``, the ``quota`` most confident
    (``low_confidence_dynamic``; a threshold of 1 or more is the static
    schedule of ``quota`` a pass). Ties go to the earlier position.
    Boolean, ``masked``'s shape: one block ``[B]``, or the blocks of
    many rows ``[..., B]`` at once, each by itself."""
    import numpy as np

    conf = np.where(masked, conf, -np.inf)
    high = conf > threshold
    # rank 0 = the most confident; unmasked positions (-inf) come last
    rank = np.argsort(np.argsort(-conf, axis=-1, kind="stable"),
                      axis=-1, kind="stable")
    return np.where(high.sum(axis=-1, keepdims=True) >= quota, high,
                    (rank < quota) & masked)


# -- KV block-row shipping primitives (PR 17) ---------------------------
#
# The device half of prefill/decode disaggregation: a prefill worker
# exports the pool rows its blocks occupy (host-side gather — the bytes
# that go on the wire are the POOL'S OWN storage, int8 codes + float32
# scales on a quantized pool, so shipping needs no dequant round-trip
# and splice parity is bitwise by construction), and a decode worker
# scatters them into ITS pool at freshly allocated block ids. Leaves
# are keyed by their full tree path, not discovery order, so a
# structural mismatch (different layer count, missing scales) fails
# loudly instead of splicing K into V.

def pool_leaves(cache):
    """``[(path, leaf)]`` of a paged cache's pool storage, in tree
    order: the one place that says which leaves those are (block
    shipping, the engine's byte counts and its ``kv_dtype``)."""
    return [(path, leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(cache)
            if _leaf_name(path) in _POOL_LEAVES]


def state_leaves(cache):
    """``[(path, leaf)]`` of a cache's per-slot state rows
    (:data:`_STATE_LEAVES`), in tree order."""
    return [(path, leaf) for path, leaf
            in jax.tree_util.tree_leaves_with_path(cache)
            if _leaf_name(path) in _STATE_LEAVES]


def pool_leaves_by_table(model, cache):
    """``{table leaf name: [pool leaves]}``: each kind's pools, a kind
    being named by the table leaf that ``model`` declares beside them
    in the same attention module (:data:`TABLE_LEAVES`; the pools
    themselves carry no table); a state kind's rows under the slot leaf
    its mixer declares beside them (:data:`_SLOT_LEAVES`)."""
    table_of = {_path_key(path[:-1]): _leaf_name(path) for path, _
                in jax.tree_util.tree_leaves_with_path(_cache_shapes(model))
                if _leaf_name(path) in TABLE_LEAVES + _SLOT_LEAVES}
    out = {}
    for path, leaf in pool_leaves(cache) + state_leaves(cache):
        out.setdefault(table_of[_path_key(path[:-1])], []).append(leaf)
    return out


def _path_key(path):
    """Stable string key of one cache-leaf path (e.g.
    ``block_0/attn/cached_key``) — the wire name a shipped row set is
    keyed under, identical across processes for one model config."""
    return "/".join(
        str(getattr(e, "key", None) or getattr(e, "name", None) or e)
        for e in path)


def gather_block_rows(cache, block_ids):
    """Host-side gather of pool rows ``block_ids`` from every pool leaf.

    Returns ``[(path_key, rows)]`` in tree order, ``rows`` a numpy array
    of shape ``[len(block_ids), *leaf.shape[1:]]`` in the LEAF'S dtype —
    int8 codes stay int8, scales stay float32. One device->host copy
    per leaf; the caller (the engine's scheduler thread) must hold the
    blocks referenced so the pool cannot recycle them mid-gather."""
    import numpy as np

    ids = np.asarray(list(block_ids), np.int32)
    return [(_path_key(path), np.asarray(leaf)[ids])
            for path, leaf in pool_leaves(cache)]


def scatter_block_rows(cache, block_ids, rows):
    """Inverse of :func:`gather_block_rows`: cache' with each shipped
    row set written at ``block_ids`` into its path-matched pool leaf.

    ``rows`` is ``{path_key: array}`` (or the gather's pair list).
    Raises ValueError on a leaf the shipment lacks or a dtype mismatch
    (an fp32 shipment cannot splice into an int8 pool — requantizing
    here would break the bitwise-parity contract; ship pools must
    match dtypes end to end)."""
    rows = dict(rows)
    ids = jnp.asarray(list(block_ids), jnp.int32)

    def repl(path, leaf):
        if _leaf_name(path) not in _POOL_LEAVES:
            return leaf
        key = _path_key(path)
        if key not in rows:
            raise ValueError(
                "shipment lacks pool leaf {!r} (incompatible model "
                "config between ship endpoints)".format(key))
        arr = rows[key]
        if str(arr.dtype) != str(leaf.dtype):
            raise ValueError(
                "shipped rows for {!r} are {} but the pool stores {} — "
                "ship endpoints must share kv_dtype".format(
                    key, arr.dtype, leaf.dtype))
        return leaf.at[ids].set(jnp.asarray(arr))

    return jax.tree_util.tree_map_with_path(repl, cache)


# -- speculative decoding primitives (PR 15) ----------------------------
#
# Draft-model speculation over the SAME paged pool discipline: a
# reduced-depth clone of the target (same vocab/embedding/head, the
# first ``num_layers_draft`` blocks, weight-tied — see
# :func:`draft_params`) proposes k tokens with k cheap single-token
# steps fused into ONE scanned program (``paged_propose_tokens``); the
# target then scores all k proposals in ONE fused multi-token apply
# (``paged_verify_step`` — the s>1 branch of models/decoder.py, i.e.
# the multi-token prefill machinery pointed at decode). Token-matching
# acceptance makes the emitted stream exactly the target's: at
# temperature=0 the verify picks ARE the plain engine's argmax chain,
# so greedy speculative output is bitwise-identical to the plain
# engine (pinned in tests/test_speculative.py); at temperature>0 every
# emitted token is still a true target-model sample (the draft token
# is only kept when it EQUALS the target's own pick at that position),
# but the PRNG stream advances differently per accepted run length, so
# sampled outputs are exact in distribution, not bitwise-reproducible
# against the plain engine — serving.DecodeEngine documents this
# honestly.
#
# The draft maintains its OWN cache pytree but shares the engine's
# HOST state — block tables and cursors — so one BlockPool governs
# both: every target write has a mirrored draft write at the same
# (block, offset), which is what keeps prefix-cache hits valid for the
# draft pool too.


def draft_params(params, num_layers_draft):
    """Weight-tied draft parameters: the target's embeddings, first
    ``num_layers_draft`` blocks, final norm, and head — the exact
    subtree a ``model.clone(num_layers=num_layers_draft)`` consumes.
    No copies: the returned dict aliases the target's arrays (tying is
    the point — no separate draft training pipeline exists, and the
    truncated-depth model is the honest zero-extra-weights draft).
    Raises KeyError-shaped ValueError on param trees that are not
    DecoderLM-family (no ``block_0``/``tok_embed`` naming)."""
    keep = {"tok_embed", "pos_embed", "ln_f", "head"}
    keep.update("block_%d" % i for i in range(int(num_layers_draft)))
    tied = {name: params[name] for name in keep if name in params}
    missing = keep - set(tied)
    if missing:
        raise ValueError(
            "params lack the DecoderLM-family entries {} needed for a "
            "weight-tied draft".format(sorted(missing)))
    return tied


def paged_propose_tokens(model, params, cache, last, idx, tables, k,
                         temperature=0.0, top_k=None, top_p=None,
                         rng=None):
    """k chained draft decode steps as ONE program: feed ``last [S]``,
    pick, feed the pick, ... — ``lax.scan`` over k single-token paged
    steps, each writing its K/V through the shared block tables at the
    advancing cursors. Returns ``(cache', drafts [S, k])`` where
    ``drafts[:, j]`` is the draft's pick after consuming the j-th fed
    token (so the fed sequence is ``[last, d_1, ..., d_{k-1}]`` and
    the proposals are ``d_1..d_k``)."""
    import jax

    # the cursors advance inside the scan, so it carries the whole
    # collection; the pools alone leave the program
    cache = _set_paged_leaves(model, cache, idx, tables)
    if rng is None:
        rng = jax.random.PRNGKey(0)
    keys = jax.random.split(rng, k)

    def body(carry, key):
        cache, tok = carry
        logits, upd = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            mutable=["cache"])
        picked = _pick_tokens(logits[:, -1, :], key, temperature,
                              top_k, top_p)
        return (upd["cache"], picked), picked

    (cache, _), drafts = jax.lax.scan(body, (cache, last), keys)
    return _pools_of(cache), drafts.T  # [k, S] -> [S, k]


def paged_verify_step(model, params, cache, tokens, idx, tables,
                      temperature=0.0, top_k=None, top_p=None,
                      rng=None):
    """Score a whole proposal window in ONE target apply: ``tokens
    [S, k]`` is ``[last, d_1, ..., d_{k-1}]`` per slot; the s=k fused
    branch writes all k K/V rows through the tables and yields logits
    at every position. Returns ``(cache', picks [S, k])`` — the
    target's own next-token choice after each fed token. Acceptance is
    the caller's (host-side) token match: ``d_{j+1}`` stands iff it
    equals ``picks[:, j]``, and ``picks[:, a]`` is the correction
    token when the match chain breaks at ``a``."""
    logits, upd = model.apply(
        {"params": params,
         "cache": _set_paged_leaves(model, cache, idx, tables)},
        tokens, mutable=["cache"])
    s, k, v = logits.shape
    picked = _pick_tokens(logits.reshape(s * k, v), rng, temperature,
                          top_k, top_p)
    return _pools_of(upd["cache"]), picked.reshape(s, k)


def paged_spec_round(model, draft_model, params, draft_params, cache,
                     draft_cache, last, idx, tables, k,
                     temperature=0.0, top_k=None, top_p=None,
                     rng=None):
    """One whole speculative round — propose THEN verify — as a single
    traceable computation: composed from :func:`paged_propose_tokens`
    and :func:`paged_verify_step` (no duplicated logic), with the
    draft's fed window wired straight into the verify feed ON DEVICE.
    Under one jit this is ONE dispatch and ONE host sync per round
    instead of two of each — on a CPU CI box the dispatch+sync is a
    real fraction of a round, and on TPU it halves launch overhead.
    Returns ``(cache', draft_cache', drafts [S, k], targets
    [S, k])``."""
    import jax

    if rng is None:
        rng = jax.random.PRNGKey(0)
    rng_d, rng_v = jax.random.split(rng)
    draft_cache, drafts = paged_propose_tokens(
        draft_model, draft_params, draft_cache, last, idx, tables, k,
        temperature=temperature, top_k=top_k, top_p=top_p, rng=rng_d)
    feed = jnp.concatenate([last[:, None], drafts[:, :k - 1]], axis=1)
    cache, targets = paged_verify_step(
        model, params, cache, feed, idx, tables,
        temperature=temperature, top_k=top_k, top_p=top_p, rng=rng_v)
    return cache, draft_cache, drafts, targets


@functools.lru_cache(maxsize=32)
def speculative_step_fns(model, draft_model, k, temperature=0.0,
                         top_k=None, top_p=None):
    """The jitted FUSED round fn for one (target, draft, k, sampling)
    tuple, cache-donating (both models' pools), reused across engines —
    the speculative sibling of :func:`paged_step_fns`. Compile-count
    contract: ONE round program per engine config (k is static; the
    fn is fixed-shape over all S slots). Call signature:
    ``fn(params, draft_params, cache, draft_cache, last, idx, tables,
    key) -> (cache', draft_cache', drafts, targets)``."""
    import jax

    def spec_round(params, draft_params, cache, draft_cache, last, idx,
                   tables, key):
        return paged_spec_round(
            model, draft_model, params, draft_params, cache, draft_cache,
            last, idx, tables, int(k), temperature=temperature,
            top_k=top_k, top_p=top_p, rng=key)

    return jax.jit(spec_round, donate_argnums=(2, 3))


def default_buckets(total_len, lo=8):
    """Power-of-two prompt buckets up to ``total_len``: the compile-count
    bound for prefill is ``len(default_buckets(...))`` programs."""
    buckets, b = [], max(2, int(lo))
    while b < total_len:
        buckets.append(b)
        b *= 2
    buckets.append(int(total_len))
    return tuple(buckets)


def bucket_for(length, buckets):
    """Smallest bucket >= length (raises if the prompt outgrows them)."""
    for b in buckets:
        if length <= b:
            return b
    raise ValueError(
        "prompt length {} exceeds the largest bucket {}".format(
            length, buckets[-1]))


@functools.lru_cache(maxsize=64)
def _jitted_generate(model, max_new_tokens, temperature, top_k, top_p,
                     eos_token, pad_token):
    # flax Modules are frozen dataclasses (hashable), so the option
    # tuple keys a REUSED jitted fn — a fresh jax.jit per call would
    # recompile every time
    def generate_fixed(params, tokens, key):
        return generate(
            model, params, tokens, max_new_tokens, temperature, key,
            top_k=top_k, top_p=top_p, eos_token=eos_token,
            pad_token=pad_token)

    return jax.jit(generate_fixed)


def generate_jit(model, params, prompt, max_new_tokens, temperature=0.0,
                 rng=None, top_k=None, top_p=None, eos_token=None,
                 pad_token=0):
    """jit-compiled :func:`generate`: one compile per option tuple x
    input-shape signature, cached across calls."""
    # normalize to hashable python scalars: array-typed eos_token (a
    # natural way to pass it) would crash lru_cache, and 5.0 vs 5 would
    # key two compiles of the identical program
    fn = _jitted_generate(model, int(max_new_tokens), float(temperature),
                          None if top_k is None else int(top_k),
                          None if top_p is None else float(top_p),
                          None if eos_token is None else int(eos_token),
                          int(pad_token))
    return fn(params, prompt,
              rng if rng is not None else jax.random.PRNGKey(0))
