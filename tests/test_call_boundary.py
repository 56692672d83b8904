"""What crosses the jit boundary of the decode engine's programs (PR 37).

A call costs the host by the buffers it hands over and takes back, so
the engine hands its programs the cache as POOLS only: the cursor and
table leaves of the flax collection are built inside each trace from
the feed and answered by no program. These pin the counts (read off the
lowered step program, not assumed), what the engine keeps on the
device, and that a whole cache collection handed to a program is read
for its pools alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import generation, serving, tracing
from tensorflowonspark_tpu.models import mellum_moe, sdar_moe
from tensorflowonspark_tpu.models.decoder import DecoderLM

V, H, NH, MAXLEN = 17, 32, 4, 48
MOE = dict(vocab=97, hidden=32, num_heads=4, num_kv_heads=2, head_dim=8,
           num_experts=4, experts_per_tok=2, moe_hidden=16, max_len=64,
           dtype=jnp.float32)


def _decoder(layers=2):
    return DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=layers,
                     max_len=MAXLEN, decode=True)


def _sdar(layers=2):
    return sdar_moe.SdarMoeLM(num_layers=layers, mask_token_id=96, **MOE)


def _mellum(layers=4):
    return mellum_moe.MellumMoeLM(num_layers=layers, sliding_window=8,
                                  prefill_chunk=8, **MOE)


def _params(model, seed=7):
    whole = model.clone(decode=False, kv_block_size=0, kv_blocks=0)
    return whole.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, 16), jnp.int32))["params"]


@pytest.fixture(scope="module")
def lm():
    return _decoder(), _params(_decoder())


FAMILIES = {"DecoderLM": lambda: _decoder(), "SdarMoeLM": lambda: _sdar(),
            "MellumMoeLM": lambda: _mellum()}


#: a ``DecoderLM``'s parameter leaves: 16 a layer, 6 outside the layers
def PARAM_BUFFERS(layers):
    return 16 * layers + 6


def _call_buffers(layers, **kw):
    dec = _decoder(layers)
    with serving.DecodeEngine(dec, _params(dec), slots=2, **kw) as eng:
        pools = len(jax.tree.leaves(eng._cache))
        return eng.compile_stats()["decode_call_buffers"], pools


def _paged(family):
    fields = dict(decode=True, kv_block_size=8, kv_blocks=9)
    if family == "MellumMoeLM":
        fields["kv_window_blocks"] = 5
    return FAMILIES[family]().clone(**fields)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_init_pools_are_the_pool_leaves_of_the_cache_collection(family):
    model = _paged(family)
    pools = generation.init_pools(model)
    whole = generation.init_cache(model, 2, 32)
    assert jax.tree.structure(pools) \
        == jax.tree.structure(generation._pools_of(whole))
    want = generation.pool_leaves(whole)
    got = generation.pool_leaves(pools)
    assert len(got) == len(want) == len(jax.tree.leaves(pools)) > 0
    for (pa, a), (pb, b) in zip(got, want):
        assert generation._path_key(pa) == generation._path_key(pb)
        assert a.shape == b.shape and a.dtype == b.dtype
        assert not np.asarray(a).any()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_fed_collection_is_the_same_from_pools_as_from_the_whole(family):
    """Every cursor and table leaf the model declares is built from the
    feed, whichever form the cache came in, each kind's table from its
    own columns."""
    model = _paged(family)
    kinds = 1 + len(getattr(model, "cache_kinds", None) or {})
    idx = jnp.asarray([3, 9])
    tables = jnp.arange(2 * 4 * kinds).reshape(2, 4 * kinds)
    from_pools = generation._set_paged_leaves(
        model, generation.init_pools(model), idx, tables)
    from_whole = generation._set_paged_leaves(
        model, generation.init_cache(model, 2, 32), idx, tables)
    assert jax.tree.structure(from_pools) == jax.tree.structure(from_whole) \
        == jax.tree.structure(generation.init_cache(model, 2, 32))
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(from_pools),
            jax.tree.leaves(from_whole)):
        name = generation._leaf_name(path)
        assert np.array_equal(np.asarray(a), np.asarray(b)), path
        if name in generation._CURSOR_LEAVES:
            assert np.asarray(a).tolist() == [3, 9]
        elif name in generation.TABLE_LEAVES:
            at = generation.TABLE_LEAVES.index(name) if kinds > 1 else 0
            assert np.array_equal(np.asarray(a),
                                  np.asarray(tables[:, 4 * at:4 * at + 4]))
    one = generation._slot_view(model, generation.init_pools(model),
                                tables[1], 7)
    assert jax.tree.structure(one) == jax.tree.structure(from_pools)
    cursors = [np.asarray(leaf).tolist() for path, leaf
               in jax.tree_util.tree_leaves_with_path(one)
               if generation._leaf_name(path) in generation._CURSOR_LEAVES]
    assert cursors and all(c == [7] for c in cursors)


@pytest.mark.parametrize("layers", [2, 6])
def test_step_call_takes_and_answers_two_pools_a_layer(layers):
    """Read off the lowered step program: 2 pools a layer in, the same
    out with the tokens, and beside them the parameters, picked, feed
    and key: no cursor, no table (4 buffers a layer in and 4 out, and
    one, before PR 37)."""
    total, pools = _call_buffers(layers)
    assert pools == 2 * layers
    assert total == PARAM_BUFFERS(layers) + pools + 3 + pools + 1


def test_int8_pools_keep_their_scales_and_nothing_else():
    total, pools = _call_buffers(2, kv_dtype="int8")
    assert pools == 4 * 2                  # codes and scales, K and V
    assert total == PARAM_BUFFERS(2) + 3 + 2 * pools + 1


def test_engine_cache_is_pools_only(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        names = {generation._leaf_name(path) for path, _ in
                 jax.tree_util.tree_leaves_with_path(eng._cache)}
        assert names == {"cached_key", "cached_value"}
        eng.submit([1, 2, 3], 6).result(300)
        after = {generation._leaf_name(path) for path, _ in
                 jax.tree_util.tree_leaves_with_path(eng._cache)}
        assert after == names
        by_table = generation.pool_leaves_by_table(eng._model, eng._cache)
        assert {k: len(v) for k, v in by_table.items()} \
            == {"block_table": 2 * dec.num_layers}


def test_a_whole_cache_collection_is_read_for_its_pools(lm):
    """The programs take the pools; handed the whole collection (a
    caller that made it with ``init_cache``) they read its pool leaves
    and answer the pools, the same tokens."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8) as eng:
        model, bps = eng._model, eng._blocks_per_slot
    prefill, step = generation.paged_step_fns(model)
    toks = np.zeros(8, np.int32)
    toks[:3] = [5, 3, 9]
    tables = np.zeros((2, bps), np.int32)
    tables[0] = np.arange(1, bps + 1)
    args = (jnp.asarray(tables[0]), jnp.asarray(toks), jnp.int32(3),
            jnp.int32(0), jax.random.PRNGKey(0), jnp.int32(0))
    feed = generation.pack_step_feed(
        np.array([-1, -1], np.int32), np.array([3, 0], np.int32), tables)
    got = []
    for cache in (generation.init_pools(model),
                  generation.init_cache(model, 2, MAXLEN)):
        cache, first = prefill(params, cache, *args)
        assert jax.tree.structure(cache) \
            == jax.tree.structure(generation.init_pools(model))
        cache, picked = step(params, cache, jnp.full(2, first), feed,
                             jax.random.PRNGKey(0))
        got.append((int(first), np.asarray(picked).tolist()))
    assert got[0] == got[1]


def test_respawn_keeps_the_parameters_it_was_given(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        want = eng.submit([5, 3, 9, 1, 2], 12).result(300)
        assert eng.params is params
        eng.stop()
        fresh = eng.respawn()
        try:
            assert fresh.params is params
            assert fresh.submit([5, 3, 9, 1, 2], 12).result(300) == want
        finally:
            fresh.stop()


def test_call_buffers_gauge_is_exported_once_a_request_went_through(lm):
    dec, params = lm
    assert "tfos_serving_decode_call_buffers" in tracing.METRIC_FAMILIES
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        assert "decode_call_buffers" not in \
            eng.counters.snapshot()["gauges"]
        eng.submit([1, 2, 3], 4).result(300)
        want = eng.compile_stats()["decode_call_buffers"]
        assert eng.counters.snapshot()["gauges"]["decode_call_buffers"] \
            == want == PARAM_BUFFERS(2) + 3 + 4 * dec.num_layers + 1
        assert "tfos_serving_decode_call_buffers {}".format(want) \
            in eng.metrics.render()
        # reading it compiled nothing
        assert eng.compile_stats()["decode_programs"] == 1


def test_speculative_round_takes_both_models_pools(lm):
    dec = _decoder(layers=4)
    params = _params(dec)
    with serving.DecodeEngine(dec, params, slots=2, speculate_k=3,
                              draft_layers=2) as eng:
        got = eng.submit([5, 3, 9], 9).result(300)
        stats = eng.compile_stats()
        # the target's and the draft's parameters, 8 + 4 pools in
        # and out, last, cursors, tables, key; drafts and targets out
        assert stats["decode_call_buffers"] == PARAM_BUFFERS(4) \
            + PARAM_BUFFERS(2) + 2 * 12 + 4 + 2
        assert stats["spec_round_programs"] == 1
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        assert eng.submit([5, 3, 9], 9).result(300) == got


def test_block_step_call_takes_pools_blocks_and_one_feed():
    """The parameters, 2 pools a layer, the 4 leaves of the slots'
    blocks (generation.init_block_state) and the feed in; the pools,
    the blocks and the answer out: 2 x 3 + 4 + 1 + 2 x 3 + 4 + 1 = 22
    beside the parameters."""
    model = _sdar(layers=3)
    params = _params(model)
    with serving.DecodeEngine(model.clone(decode=True), params, slots=2,
                              total_len=32, kv_block_size=8) as eng:
        leaves = len(jax.tree.leaves(params))
        assert len(jax.tree.leaves(eng._blk_state)) == 4
        assert eng.compile_stats()["decode_call_buffers"] \
            == leaves + 2 * 3 + 4 + 1 + 2 * 3 + 4 + 1 == leaves + 22


def test_two_kinds_of_cache_cross_as_their_pools():
    model = _mellum(layers=4)
    params = _params(model)
    with serving.DecodeEngine(model.clone(decode=True), params, slots=2,
                              total_len=32, kv_block_size=8) as eng:
        by_table = generation.pool_leaves_by_table(eng._model, eng._cache)
        assert {k: len(v) for k, v in by_table.items()} \
            == {"block_table": 2, "window_table": 6}
        got = eng.submit([5, 3, 9], 6).result(300)
        assert len(got) == 9
        # the answer carries the routed experts behind the tokens
        assert eng.compile_stats()["decode_call_buffers"] \
            == len(jax.tree.leaves(params)) + 8 + 3 + 8 + 1
