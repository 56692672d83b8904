"""Mellum-MoE (models/mellum_moe.py) against its plain reference
(benchmarks/reference/mellum_moe.py), at small sizes on seeded random
weights: the forward pass with both kinds of layer, YaRN, the two kinds
of paged cache under prefill and decode (by hand through the cache
manager, and through ``serving.DecodeEngine``), the windowed paged
kernel, the block accounting and what the engine refuses.

Tolerances. The reference is float32 at ``highest`` precision on the
bfloat16-rounded weights. A float32 instance of the model differs from
it only by the order of float32 sums: logits of size 3 (the weights
here are eight times the seed's, so that a wrong mask moves a logit by
tenths) agree to ``F32_TOL``. A bfloat16 instance rounds every
activation to 8 bits of mantissa, a few roundings in a row:
``BF16_TOL`` is a twentieth of the logits' own spread.
"""

import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum_moe as ref
from tensorflowonspark_tpu import generation, paging, serving
from tensorflowonspark_tpu.models import mellum_moe

pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")

F32_TOL = 5e-5
BF16_TOL = 0.15
WINDOW, BLOCK = 8, 4

#: two whole periods; a window of 8 under sequences of 40 and more; the
#: YaRN ramp placed where tiny positions reach it
TINY = dict(vocab=97, hidden=32, num_heads=4, num_kv_heads=2, head_dim=8,
            num_layers=8, num_experts=8, experts_per_tok=2, moe_hidden=16,
            rope_theta=5e5, rms_eps=1e-6, max_len=64,
            layer_types=["sliding", "sliding", "sliding", "full"],
            sliding_window=WINDOW, yarn_factor=16.0,
            yarn_original_max_len=16, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782)


def _weights(seed=0, model=TINY, dtype=jnp.float32):
    params = ref.init_params(jax.random.PRNGKey(seed), model)
    params = jax.tree.map(
        lambda a: (a.astype(jnp.float32) * (8 if a.ndim > 1 else 1))
        .astype(jnp.bfloat16), params)
    return params, jax.tree.map(lambda a: a.astype(dtype), params)


def _model(dtype=jnp.float32, **kw):
    fields = dict(TINY, prefill_chunk=8, **kw)
    fields["layer_types"] = tuple(fields["layer_types"])
    return mellum_moe.MellumMoeLM(dtype=dtype, **fields)


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    """The reference attends ``Q_BLOCK`` queries at once and wants longer
    sequences to be a multiple of it."""
    monkeypatch.setattr(ref, "Q_BLOCK", 8)


def _ref_logits(params, tokens, model=TINY, rows=None):
    padded = list(tokens) + [0] * (-len(tokens) % 8)
    rows = list(range(len(tokens))) if rows is None else rows
    return np.asarray(ref.logits(params, padded, model, rows))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_forward_is_the_reference_forward(dtype, tol):
    """Two periods of sliding, sliding, sliding, full over a sequence
    five windows long: both masks, both RoPEs, QK-norm, grouped heads
    and the top-k experts, every logit."""
    params, cast = _weights(dtype=dtype)
    tokens = np.random.RandomState(1).randint(0, 97, size=43)
    got = _model(dtype).apply({"params": cast}, jnp.asarray(tokens)[None])[0]
    want = _ref_logits(params, tokens.tolist())
    assert np.abs(want).max() > 1.0
    assert np.abs(np.asarray(got) - want).max() < tol


def test_a_sliding_layer_sees_its_window_and_a_full_layer_everything():
    """In a model of sliding layers alone a token nine positions back
    moves nothing and one seven back does; one full layer among them
    and the far token shows."""
    for kinds, far_moves in ((("sliding",), False),
                             (("sliding", "full"), True)):
        model = _model(layer_types=kinds, num_layers=2)
        _, cast = _weights(model=dict(TINY, num_layers=2))
        tokens = np.random.RandomState(2).randint(0, 97, size=(1, 24))
        base = np.asarray(model.apply({"params": cast}, jnp.asarray(tokens)))

        def moved(at):
            other = tokens.copy()
            other[0, at] = (other[0, at] + 1) % 97
            out = np.asarray(model.apply({"params": cast},
                                         jnp.asarray(other)))
            return np.abs(out[0, 23] - base[0, 23]).max()

        # two layers of window 8 reach back 14 positions at most
        assert (moved(23 - 15) > 1e-4) == far_moves
        assert moved(23 - 7) > 1e-4


def test_a_window_wider_than_the_sequence_is_the_all_full_model():
    """With YaRN switched to the plain frequencies (factor 1, cos and
    sin times 1) the two kinds differ by the window alone: a window no
    sequence reaches gives the all-full model's logits, whole and
    through the two caches."""
    plain = dict(yarn_factor=1.0, yarn_attention_factor=1.0)
    _, cast = _weights()
    tokens = np.random.RandomState(3).randint(0, 97, size=(1, 29))
    wide = _model(sliding_window=1000, **plain)
    full = _model(layer_types=("full",), **plain)
    a = wide.apply({"params": cast}, jnp.asarray(tokens))
    b = full.apply({"params": cast}, jnp.asarray(tokens))
    assert np.abs(np.asarray(a) - np.asarray(b)).max() < F32_TOL
    narrow = _model(**plain).apply({"params": cast}, jnp.asarray(tokens))
    assert np.abs(np.asarray(narrow) - np.asarray(b)).max() > 1e-3
    prompt = tokens[0, :11].tolist()
    outs = []
    for model in (wide, full):
        with serving.DecodeEngine(
                model.clone(decode=True), cast, slots=2, total_len=48,
                buckets=(16, 32), kv_block_size=BLOCK) as eng:
            outs.append(eng.submit(prompt, 14).result(300))
    assert outs[0] == outs[1]


def test_yarn_frequencies_and_factor_against_numbers_worked_by_hand():
    """The published ``rope_scaling``: theta 500,000, factor 16 over an
    original 8,192, beta_fast 32, beta_slow 1 at 128 lanes. The
    correction dimensions are 128 ln(8192 / (32 * 2 pi)) / (2 ln 5e5) =
    18.08, rounded down to 18, and 128 ln(8192 / (2 pi)) / (2 ln 5e5) =
    34.98, rounded up to 35: dimensions up to 18 turn as plain RoPE,
    from 35 on sixteen times slower, and between them by the ramp
    (i - 18) / 17. The factor on cos and sin is 0.1 ln 16 + 1."""
    inv = mellum_moe.yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    plain = 5e5 ** (-np.arange(64) * 2 / 128.0)
    assert inv.shape == (64,)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-12)
    ramp = (26 - 18) / 17.0
    assert inv[26] == pytest.approx(
        plain[26] * (1 - ramp) + plain[26] / 16 * ramp, rel=1e-12)
    # 500000 ** (-52 / 128) = 0.004839..., so dimension 26 turns at
    # 0.004839 * (1 - 0.4706 * 15 / 16)
    assert inv[26] == pytest.approx(0.0048394 * (1 - 0.470588 * 0.9375),
                                    rel=1e-4)
    assert np.all(np.diff(inv) < 0)
    assert 0.1 * math.log(16) + 1 == pytest.approx(
        mellum_moe.MellumMoeLM(vocab=8).yarn_attention_factor, rel=1e-15)
    np.testing.assert_allclose(
        inv, ref.yarn_inv_freq(dict(
            head_dim=128, rope_theta=5e5, yarn_factor=16.0,
            yarn_original_max_len=8192, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0)), rtol=1e-12)
    # cos and sin carry the factor: a turned vector is 1.277 times as long
    x = jnp.ones((1, 3, 1, 128), jnp.float32)
    pos = jnp.asarray([[0, 5, 900]])
    turned = mellum_moe.rope(x, pos, 5e5, inv_freq=tuple(inv),
                             factor=1.2772588722239782)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(turned), axis=-1),
        1.2772588722239782 * math.sqrt(128), rtol=1e-5)


# -- the two caches, by hand through the manager ------------------------


def _by_hand(model, cast, prompt, steps, total=48, bucket=None):
    """Prefill ``prompt`` and decode ``steps`` tokens of the reference's
    own choice through the paged caches, the host's part done by
    ``paging.CacheKinds`` as the engine does it (admit what the decode
    will still see, grow, give back). Returns ``(logits of every call
    [1 + steps, vocab], tokens fed, blocks the window kind held at each
    step)``."""
    width = total // BLOCK
    kv = paging.CacheKinds(1, width, BLOCK, width, model.cache_kinds)
    dec = model.clone(decode=True, kv_block_size=BLOCK, kv_blocks=width + 1,
                      kv_window_blocks=kv.kinds[1].pool.num_blocks + 1)
    cache = generation.init_cache(dec, 1, total)
    n = len(prompt)
    for kind in kv:
        kind.admit(0, n)
    bucket = bucket or -(-n // 8) * 8
    toks = np.zeros(bucket, np.int32)
    toks[:n] = prompt
    logits, upd = dec.apply(
        {"params": cast,
         "cache": generation._slot_view(dec, cache, kv.tables[0], 0)},
        jnp.asarray(toks)[None], last=jnp.asarray([n - 1]),
        mutable=["cache", "intermediates"])
    cache = upd["cache"]
    out, fed, held = [np.asarray(logits[0, 0])], [], []
    for i in range(steps):
        cursor = n + i
        token = int(np.argmax(out[-1]))
        for kind in kv:
            kind.trim(0, cursor)
            kind.grow(0, cursor // BLOCK)
        held.append(len(kv.kinds[1].blocks[0]))
        stepped = generation._set_paged_leaves(
            dec, cache, jnp.asarray([cursor]), jnp.asarray(kv.tables))
        logits, upd = dec.apply({"params": cast, "cache": stepped},
                                jnp.asarray([[token]]),
                                mutable=["cache", "intermediates"])
        cache = upd["cache"]
        out.append(np.asarray(logits[0, 0]))
        fed.append(token)
    for kind in kv:
        kind.release(0)
        assert kind.in_use() == 0 and not kind.tables.any()
    return np.stack(out), fed, held


@pytest.mark.parametrize("prompt_len,steps,bucket", [
    (5, 9, None),      # shorter than the window, grows past it
    (21, 6, 32),       # longer than the window: hazard (a), padded bucket
    (7, 10, None),     # crosses a block's edge (8) and the window's mid-decode
    (40, 5, None),     # five windows long, ends the table's last blocks
], ids=["short", "longer_than_window", "block_edge", "five_windows"])
def test_prefill_then_decode_through_both_caches_is_the_full_forward(
        prompt_len, steps, bucket):
    """Logits of the prefill's last position and of every decode step
    against the reference's full forward over the finished sequence."""
    params, cast = _weights(seed=prompt_len)
    prompt = np.random.RandomState(prompt_len).randint(
        0, 97, size=prompt_len).tolist()
    got, fed, held = _by_hand(_model(), cast, prompt, steps, bucket=bucket)
    seq = prompt + fed
    want = _ref_logits(params, seq,
                       rows=list(range(prompt_len - 1, len(seq))))
    assert np.abs(got - want).max() < F32_TOL
    # a window of 8 in blocks of 4: never more than ceil(7 / 4) + 1
    assert max(held) <= -(-(WINDOW - 1) // BLOCK) + 1 == 3


def test_a_prefill_writes_only_what_the_decode_will_see():
    """A prompt of 21 in a window layer's pool of 3 blocks of 4 (one
    slot's window): the prefill attended its own K and V and wrote
    blocks 3 to 5 (positions 12 to 20, of which 14 on are seen), the
    rest went to scratch."""
    model = _model()
    kv = paging.CacheKinds(1, 12, BLOCK, 12, model.cache_kinds)
    window = kv.kinds[1]
    assert window.pool.num_blocks == 3 \
        == paging.WindowKind.most_a_slot(WINDOW, BLOCK)
    assert window.need(21) == 3 and kv.full.need(21) == 6
    window.admit(0, 21)
    assert window.first[0] == 3 and len(window.blocks[0]) == 3
    assert np.flatnonzero(window.tables[0]).tolist() == [3, 4, 5]
    # the step at cursor 21 writes block 5 and sees from 14 on; at 24
    # it needs block 6 and block 3 (12..15) is still seen (17 on: no),
    # so that one goes back first and the pool has the next
    assert window.trim(0, 24) == 1 and window.first[0] == 4
    assert window.grow(0, 24 // BLOCK) == 1 and window.in_use() == 3


# -- through the engine ---------------------------------------------------


def _greedy(model, cast, prompt, n):
    whole = jax.jit(lambda p, t: model.apply({"params": p}, t))
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(whole(cast, jnp.asarray(seq)[None])[0, -1])))
    return seq


def _engine(model, cast, **kw):
    kw = dict(dict(slots=3, total_len=48, buckets=(8, 16, 32),
                   kv_block_size=BLOCK), **kw)
    return serving.DecodeEngine(model.clone(decode=True), cast, **kw)


def _counts(eng):
    return eng.counters.snapshot()["counts"]


def test_engine_serves_each_request_as_the_model_alone_would():
    """Three requests at once, shorter than the window, longer than it
    and crossing block edges mid-decode, token by token with one step
    in flight: each equals greedy generation with no cache, and every
    served token is the reference's best."""
    params, cast = _weights(seed=7)
    model = _model()
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(0, 97, size=p).tolist(), n)
            for p, n in ((5, 9), (21, 14), (30, 12))]
    with _engine(model, cast) as eng:
        handles = [eng.submit(p, n) for p, n in reqs]
        got = [h.result(300) for h in handles]
        counts = _counts(eng)
        assert eng.compile_stats()["decode_programs"] == 1
    for (prompt, n), out in zip(reqs, got):
        assert out == _greedy(model, cast, prompt, n)
        want = _ref_logits(params, out,
                           rows=list(range(len(prompt) - 1, len(out) - 1)))
        served = np.asarray(out[len(prompt):])
        gaps = want.max(axis=-1) - want[np.arange(len(served)), served]
        assert gaps.max() < F32_TOL
    assert counts["steps_dispatched_ahead"] > 0
    assert counts["kv_window_blocks_given_back"] > 0


def test_block_accounting_of_both_kinds():
    """While a request decodes, the window kind never holds more than
    ``ceil((window - 1) / block) + 1`` blocks a slot (the published
    model: ``ceil(1023 / block) + 1``) and the full kind grows by a
    block every ``block`` tokens; both pools are empty at the end."""
    _, cast = _weights(seed=9)
    model = _model()
    prompt = np.random.RandomState(9).randint(0, 97, size=19).tolist()
    most = -(-(WINDOW - 1) // BLOCK) + 1
    with _engine(model, cast, slots=2) as eng:
        window = eng._kv.kinds[1]
        assert window.pool.num_blocks == 2 * most
        handle = eng.submit(prompt, 24)
        seen_w, seen_f = [], []
        for _ in handle.stream(timeout=300):
            seen_w.append(max(len(b) for b in window.blocks))
            seen_f.append(max(len(b) for b in eng._kv.full.blocks))
        assert max(seen_w) <= most
        assert max(seen_f) == -(-(19 + 24 - 1) // BLOCK)
        for kind in eng._kv:
            assert kind.in_use() == 0 and not kind.tables.any()
        counts = _counts(eng)
        stats = eng.load_stats()
    # 19 + 24 tokens leave blocks 0..(42 - 7) // 4 - 1 behind
    assert counts["kv_window_blocks_given_back"] == (42 - 7) // 4 - 3
    assert 0 < counts["kv_window_block_steps"] \
        <= most * counts["decode_steps"]
    assert counts["kv_window_block_steps"] < counts["kv_block_steps"]
    assert stats["kv_blocks_free"] == stats["kv_blocks_total"]
    assert eng.kv_bytes_per_token() == {
        "full": 2 * 2 * 2 * 8 * 4, "window": 6 * 2 * 2 * 8 * 4}


def test_the_new_counters_count():
    """Grid steps and table slots by kind, and the router's counters
    from TOKEN steps and token prefills."""
    _, cast = _weights(seed=11)
    model = _model()
    prompt = np.random.RandomState(11).randint(0, 97, size=13).tolist()
    with _engine(model, cast, slots=2) as eng:
        eng.submit(prompt, 10).result(300)
        counts = _counts(eng)
        width = 48 // BLOCK
        layers, k = TINY["num_layers"], TINY["experts_per_tok"]
        # 9 decode steps of one live row, and a prefill of 13 positions
        assert counts["decode_steps"] == 9
        assert counts["expert_calls"] == layers * (9 + 1)
        assert counts["expert_rows"] == layers * k * (9 + 13)
        assert counts["expert_rows_mean"] == pytest.approx(
            counts["expert_rows"] / TINY["num_experts"])
        assert counts["expert_rows_max"] * TINY["num_experts"] \
            >= counts["expert_rows"]
        assert counts["attn_table_slots"] == 9 * 2 * width \
            == counts["attn_window_table_slots"]
        # the step at cursor c walks blocks first_seen(c) .. c // 4 of
        # the live row's window table, the idle row its one
        walked = sum(c // BLOCK - max(c - WINDOW + 1, 0) // BLOCK + 1
                     for c in range(13, 22))
        assert counts["attn_window_grid_steps"] == walked + 9
        assert counts["attn_grid_steps"] == sum(
            c // BLOCK + 1 for c in range(13, 22)) + 9
        samples = eng.timers.counts()
        assert samples["trim_blocks"] >= 9


def test_precompile_leaves_the_first_calls_nothing_to_compile():
    """Every bucket's prefill and the step compile side by side ahead
    of the first request; serving then compiles nothing and answers as
    ever. An engine with requests in it is refused, and so is one whose
    programs take other arguments."""
    _, cast = _weights(seed=17)
    # a model no other test of this process serves: its programs are new
    model = _model(rms_eps=2e-6)
    compiles = []

    def listener(event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(seconds)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        with _engine(model, cast) as eng:
            before = len(compiles)
            assert eng.precompile() == 3 + 1
            assert len(compiles) == before + 4
            prompts = [list(range(1, n + 1)) for n in (5, 12, 30)]
            got = [eng.submit(p, 6).result(300) for p in prompts]
            assert len(compiles) == before + 4
            assert eng.compile_stats()["prefill_programs"] == 3
            assert eng.compile_stats()["decode_programs"] == 1
            with eng._cv:
                held = eng.submit(prompts[0], 4)
                with pytest.raises(RuntimeError, match="idle"):
                    eng.precompile()
            held.result(300)
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert got[0] == _greedy(model, cast, prompts[0], 6)
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    dec = DecoderLM(vocab=32, hidden=16, num_heads=2, num_layers=2,
                    max_len=32, decode=True)
    params = dec.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 32), jnp.int32))["params"]
    with serving.DecodeEngine(dec, params, slots=1, speculate_k=2,
                              draft_layers=1) as eng:
        with pytest.raises(ValueError, match="speculate_k"):
            eng.precompile()


def test_preempted_request_re_enters_and_resumes_to_the_same_tokens():
    """Two requests over pools that hold one of them whole: the younger
    is preempted when the older grows, re-enters by a prefill of prompt
    plus what it had emitted, and both answer as if alone."""
    _, cast = _weights(seed=13)
    model = _model()
    rng = np.random.RandomState(13)
    a = rng.randint(0, 97, size=14).tolist()
    b = rng.randint(0, 97, size=9).tolist()
    with _engine(model, cast, slots=2, kv_blocks=11,
                 buckets=(8, 16, 32, 48)) as eng:
        with eng._cv:
            ha, hb = eng.submit(a, 26), eng.submit(b, 20)
        got_a, got_b = ha.result(300), hb.result(300)
        assert _counts(eng)["preemptions"] >= 1
        for kind in eng._kv:
            assert kind.in_use() == 0
    assert got_a == _greedy(model, cast, a, 26)
    assert got_b == _greedy(model, cast, b, 20)


def test_a_block_left_as_the_next_begins_preempts_nobody():
    """A window of 9 in blocks of 4 (window = 1 mod block): at every
    fourth cursor a slot's window leaves a block in the turn in which
    its next one begins. The window pool holds ``ceil((window - 1) /
    block) + 1`` = 3 blocks a slot and no more, so the block goes back
    BEFORE the turn's growth: with every slot past the window the house
    is full, nobody is preempted, and each answers as if alone."""
    _, cast = _weights(seed=15)
    model = _model(sliding_window=9)
    rng = np.random.RandomState(15)
    reqs = [(rng.randint(0, 97, size=p).tolist(), n)
            for p, n in ((12, 21), (15, 18), (10, 23))]
    with _engine(model, cast) as eng:
        window = eng._kv.kinds[1]
        assert window.pool.num_blocks == 3 * 3
        with eng._cv:
            handles = [eng.submit(p, n) for p, n in reqs]
        most = 0
        for _ in handles[2].stream(timeout=300):
            most = max(most, window.in_use())
        got = [h.result(300) for h in handles]
        counts = _counts(eng)
        assert window.in_use() == 0
        alone = [eng.submit(p, n).result(300) for p, n in reqs]
    assert most == 9    # all three slots held their whole window at once
    assert counts.get("preemptions", 0) == 0
    assert counts["kv_window_blocks_given_back"] >= 3 * 4
    assert got == alone


@pytest.mark.parametrize("kw,why", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(speculate_k=2), "speculate_k"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tier="decode"), "tier='decode'"),
    (dict(tier="prefill"), "tier='prefill'"),
])
def test_engine_refuses_what_it_does_not_do_for_two_kinds(kw, why):
    _, cast = _weights()
    with pytest.raises(ValueError) as e:
        _engine(_model(), cast, **kw)
    assert why in str(e.value) and "full, window" in str(e.value)


def test_engine_with_two_kinds_ships_no_kv_and_shares_no_prefix():
    _, cast = _weights()
    with _engine(_model(), cast) as eng:
        assert eng.prefix_cache is False
        with pytest.raises(ValueError, match="2 kinds of cache"):
            eng.export_prefix([1, 2, 3, 4, 5])
        prompt = list(range(1, 13))
        assert eng.submit(prompt, 3).result(300) \
            == eng.submit(prompt, 3).result(300)
        assert _counts(eng).get("prefix_hit_blocks", 0) == 0


def test_a_model_of_one_kind_has_one_kind_of_cache():
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    dec = DecoderLM(vocab=32, hidden=16, num_heads=2, num_layers=1,
                    max_len=32, decode=True)
    params = dec.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 32), jnp.int32))["params"]
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        assert eng.prefix_cache is True
        assert [k.name for k in eng._kv] == ["full"]
        assert eng._tables.shape == (1, 32 // 16)


# -- the kernel and the manager alone ------------------------------------


@pytest.mark.parametrize("impl", ["blockwise", "pallas"])
@pytest.mark.parametrize("s_q,group", [(1, 1), (1, 4), (5, 2)])
def test_paged_attention_with_a_window_is_the_gather_oracle(impl, s_q,
                                                            group):
    """Rows at different depths, the blocks behind each row's window
    parked on scratch as the host does: the walk from the first block
    still seen equals one softmax over the window."""
    rng = np.random.RandomState(s_q * 10 + group)
    b, kv, d, bs, mb, window = 3, 2, 8, 4, 9, 10
    pool = 1 + b * mb
    q = jnp.asarray(rng.randn(b, s_q, kv * group, d), jnp.float32)
    k_pool = jnp.asarray(rng.randn(pool, bs, kv * d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(pool, bs, kv * d), jnp.float32)
    last = np.array([6, 17, 33])
    pos = last[:, None] - (s_q - 1) + np.arange(s_q)[None, :]
    table = 1 + np.arange(b * mb).reshape(b, mb)
    dead = np.maximum(pos.min(axis=1) - window + 1, 0) // bs
    parked = np.where(np.arange(mb)[None, :] < dead[:, None], 0, table)
    want = pa.paged_attention(q, k_pool, v_pool, table, pos, impl="gather",
                              window=window)
    got = pa.paged_attention(q, k_pool, v_pool, parked, pos, impl=impl,
                             interpret=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    # and the window is seen: without it the answer is another
    full = pa.paged_attention(q, k_pool, v_pool, table, pos, impl="gather")
    assert np.abs(np.asarray(full) - np.asarray(want))[1:].max() > 1e-3


def test_window_view_rolls_the_table_to_the_first_block_seen():
    table = jnp.asarray([[0, 0, 0, 7, 8, 9, 0, 0],
                         [4, 5, 0, 0, 0, 0, 0, 0]])
    pos = jnp.asarray([[21], [6]])
    rolled, shifted = pa._window_view(table, pos, 4, 8)
    # row 0 sees 14..21: from block 3; row 1 sees 0..6: from block 0
    assert np.asarray(rolled)[0, :3].tolist() == [7, 8, 9]
    assert np.asarray(rolled)[1, :2].tolist() == [4, 5]
    assert np.asarray(shifted).tolist() == [[21 - 12], [6]]


def test_cache_kinds_need_grow_trim_and_bytes():
    kv = paging.CacheKinds(2, 8, 4, 16, {"window": 8})
    full, window = kv.kinds
    assert kv.tables.shape == (2, 16) and kv.others() == [window]
    # every slot's window, and no more: ceil(7 / 4) + 1 blocks each
    assert window.pool.num_blocks == 2 * 3
    assert [full.need(n) for n in (1, 4, 5, 30)] == [1, 1, 2, 8]
    # cursor n writes n and sees n - 7 .. n
    assert [window.need(n) for n in (1, 7, 8, 9, 30)] == [1, 2, 2, 3, 3]
    assert [int(window.first_seen(c)) for c in (0, 7, 8, 11, 30)] \
        == [0, 0, 0, 1, 5]
    for kind in kv:
        kind.admit(1, 10)
    # ten tokens: blocks 0..2 of both kinds (the step at 10 sees from 3)
    assert kv.tables[1].tolist() == [
        *full.blocks[1], 0, 0, 0, 0, 0, *window.blocks[1], 0, 0, 0, 0, 0]
    assert window.lacks(1, 3) == 1 and window.grow(1, 3) == 1
    # the step at 15 sees from 8: blocks 0 and 1 go back
    assert window.trim(1, 15) == 2 and window.first[1] == 2
    assert kv.tables[1, 8:].tolist() == [0, 0, *window.blocks[1], 0, 0, 0, 0]
    assert full.trim(1, 15) == 0 and window.in_use() == 2
    small = paging.WindowKind(paging.BlockPool(2, 4),
                              np.zeros((1, 8), np.int32), 8)
    with pytest.raises(paging.PoolExhausted):
        small.admit(0, 30)
    assert small.in_use() == 0 and not small.blocks[0]
    with pytest.raises(ValueError, match="unknown cache kind"):
        paging.CacheKinds(1, 8, 4, 8, {"latent": 1})
    leaves = {"block_table": [np.zeros((17, 4, 16), np.float32)] * 2,
              "window_table": [np.zeros((7, 4, 16), jnp.bfloat16)] * 6}
    kv.set_block_bytes(leaves)
    assert kv.bytes_per_token() == {"full": 2 * 16 * 4,
                                    "window": 6 * 16 * 2}


def test_tables_of_two_kinds_ride_one_feed():
    """The step's feed carries both kinds' tables side by side and each
    table leaf takes its own columns; a cache of one kind takes the
    tables whole."""
    model = _model(num_layers=4).clone(
        decode=True, kv_block_size=BLOCK, kv_blocks=9, kv_window_blocks=5)
    cache = generation.init_cache(model, 2, 16)
    tables = jnp.arange(2 * 8).reshape(2, 8)
    fed = generation._set_paged_leaves(model, cache, jnp.asarray([3, 9]),
                                       tables)
    attn = fed["layer_0"]["attn"], fed["layer_3"]["attn"]
    assert np.asarray(attn[0]["window_table"]).tolist() \
        == np.asarray(tables[:, 4:]).tolist()
    assert np.asarray(attn[1]["block_table"]).tolist() \
        == np.asarray(tables[:, :4]).tolist()
    assert attn[0]["cached_key"].shape == (5, BLOCK, 16)
    assert attn[1]["cached_key"].shape == (9, BLOCK, 16)
    by_table = generation.pool_leaves_by_table(model, cache)
    assert sorted((k, len(v)) for k, v in by_table.items()) \
        == [("block_table", 2), ("window_table", 6)]
    assert generation.answer_len(model, 2, 2) == 2 + 4 * 2 * 2
