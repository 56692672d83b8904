"""SDAR-MoE (models/sdar_moe.py) against its plain reference
(benchmarks/reference/sdar_moe.py), at small sizes on seeded random
weights: the forward pass, the paged cache under denoising and commit
passes, the engine stepping by blocks, the grouped-query paged kernel
and the grouped matrix product of the expert layer.

Tolerances. The reference is float32 at ``highest`` precision on the
bfloat16-rounded weights. A float32 instance of the model differs from
it only by the order of float32 sums: logits of size 0.5 agree to
``F32_TOL`` (a few ulp of the sums over 64 to 128 terms). A bfloat16
instance rounds every activation to 8 bits of mantissa, so its logits
carry ``2**-8`` of relative noise per product, a few of them in a row:
``BF16_TOL`` is twenty times what one rounding of a logit of 0.5 gives,
and a tenth of the logits' own spread, so a wrong mask, a missing
QK-norm or a wrong expert would still show (they move logits by 0.1
and more).
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_moe as ref
from tensorflowonspark_tpu import generation, serving
from tensorflowonspark_tpu.models import sdar_moe
from tensorflowonspark_tpu.ops import expert_gmm

pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")

F32_TOL = 2e-5
BF16_TOL = 0.04

TINY = dict(vocab=97, hidden=64, num_heads=4, num_kv_heads=2, head_dim=16,
            num_layers=2, num_experts=8, experts_per_tok=2, moe_hidden=32,
            rope_theta=1e6, rms_eps=1e-6, max_len=64, block_len=4,
            denoise_steps=4, confidence_threshold=0.9, mask_token_id=96)


def _weights(seed=0, model=TINY, dtype=jnp.float32):
    params = ref.init_params(jax.random.PRNGKey(seed), model)
    return params, jax.tree.map(lambda a: a.astype(dtype), params)


def _model(dtype=jnp.float32, **kw):
    return sdar_moe.SdarMoeLM(**dict(TINY, **kw), dtype=dtype)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
def test_forward_is_the_reference_forward(dtype, tol):
    """Block-causal mask, grouped K/V heads, QK-norm, rotate-half RoPE
    and the top-k experts, all at once: every logit of a sequence whose
    length is no multiple of the block."""
    params, cast = _weights(dtype=dtype)
    tokens = np.random.RandomState(1).randint(0, 96, size=(1, 23))
    got = _model(dtype).apply({"params": cast}, jnp.asarray(tokens))[0]
    want = ref.logits(params, tokens[0].tolist(), TINY)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < tol


def test_mask_is_causal_between_blocks_and_full_inside():
    """A token changed in a later block moves no logit of an earlier
    block; one changed inside a block moves the logits of the positions
    BEFORE it in the same block (which a causal mask would not)."""
    _, cast = _weights()
    tokens = np.random.RandomState(2).randint(0, 96, size=(1, 12))
    base = np.asarray(_model().apply({"params": cast}, jnp.asarray(tokens)))
    later = tokens.copy()
    later[0, 9] = (later[0, 9] + 1) % 96
    moved = np.asarray(_model().apply({"params": cast}, jnp.asarray(later)))
    assert np.array_equal(moved[0, :8], base[0, :8])
    assert np.abs(moved[0, 8] - base[0, 8]).max() > 1e-4


def _paged(model, slots, total_len, kv_block_size=8):
    """A paged instance, its fresh cache, and a block table that gives
    slot ``s`` its own run of pool rows (row 0 is scratch)."""
    per_slot = total_len // kv_block_size
    paged = model.clone(decode=True, kv_block_size=kv_block_size,
                        kv_blocks=slots * per_slot + 1)
    cache = generation.init_cache(paged, slots, total_len)
    tables = 1 + np.arange(slots * per_slot, dtype=np.int32) \
        .reshape(slots, per_slot)
    return paged, cache, tables


def _pass_logits(paged, params, cache, tokens, idx, tables):
    cache = generation._set_paged_leaves(paged, cache, idx, tables)
    logits, upd = paged.apply({"params": params, "cache": cache},
                              jnp.asarray(tokens), mutable=["cache"])
    return upd["cache"], np.asarray(logits)


@pytest.mark.parametrize("prompt_len,max_new", [(8, 8), (11, 7), (3, 10)])
def test_passes_through_the_paged_cache_match_the_reference(prompt_len,
                                                            max_new):
    """Prefill of the prompt's whole blocks, then every denoising pass
    and the commit of every block through the paged cache: the logits
    of each pass equal the reference's full forward on the same state
    (a prompt with a remainder; a request whose end is inside a block;
    a prompt shorter than a block, which prefills nothing)."""
    params, cast = _weights(3)
    model = _model()
    rng = np.random.RandomState(prompt_len)
    prompt = rng.randint(0, 96, size=prompt_len).tolist()
    seq, passes = sdar_moe.generate(model, cast, prompt, max_new)
    served = seq[prompt_len:]
    paged, cache, tables = _paged(model, slots=2, total_len=32)
    prefill, step = generation.paged_block_fns(paged)
    b, slot = TINY["block_len"], 1
    fill = prompt_len - prompt_len % b
    if fill:
        bucket = np.zeros(16, np.int32)
        bucket[:fill] = prompt[:fill]
        cache, _ = prefill(cast, cache, jnp.asarray(tables[slot]),
                           jnp.asarray(bucket), jnp.int32(0))
    idx = np.zeros(2, np.int32)
    for block in range(fill // b, -(-len(seq) // b)):
        idx[slot] = block * b
        states = ref.block_states(prompt, served, passes, TINY, block)
        # the commit runs the final tokens (and MASK past the end)
        final = [seq[i] if i < len(seq) else TINY["mask_token_id"]
                 for i in range(block * b, block * b + b)]
        for tokens in [s[0] for s in states] + [seq[:block * b] + final]:
            feed = np.zeros((2, b), np.int32)
            feed[slot] = tokens[-b:]
            cache, got = _pass_logits(paged, cast, cache, feed, idx, tables)
            want = ref.logits(params, tokens, TINY,
                              rows=range(block * b, block * b + b))
            assert np.abs(got[slot] - np.asarray(want)).max() < F32_TOL
    # and the jitted step answers the argmax and its probability
    feed = np.zeros((2, b), np.int32)
    cache, answers = step(
        cast, cache, generation.pack_block_feed(feed, idx, tables))
    best, conf, ids = generation.unpack_block_step(
        answers, 2, b, TINY["experts_per_tok"])
    _, logits = _pass_logits(paged, cast, cache, feed, idx, tables)
    assert np.array_equal(np.asarray(best), logits.argmax(-1))
    np.testing.assert_allclose(
        np.asarray(conf), jax.nn.softmax(logits, -1).max(-1), rtol=1e-5)
    # the experts each of the 2 x b positions was routed to, a layer
    ids = np.asarray(ids)
    assert ids.shape == (TINY["num_layers"], 2 * b,
                         TINY["experts_per_tok"])
    assert ids.min() >= 0 and ids.max() < TINY["num_experts"]


def _engine(model, params, **kw):
    opts = dict(slots=3, total_len=64, buckets=[8, 16, 32],
                kv_block_size=8, kv_blocks=24)
    opts.update(kw)
    return serving.DecodeEngine(model.clone(decode=True), params, **opts)


REQUESTS = [(9, 11), (12, 8), (3, 5), (17, 13), (6, 4), (8, 1)]


def _requests(seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, 96, size=p).tolist(), n) for p, n in REQUESTS]


@pytest.mark.parametrize("steps", [1, 2, 4])
def test_engine_with_rows_in_different_phases_is_each_request_alone(steps):
    """Six requests over three slots: rows sit in different passes of
    different blocks, some in their commit, while others are admitted.
    Each request's tokens AND the pass at which each was unmasked equal
    the cacheless generation of that request alone, for 1, 2 and 4
    denoising steps (4, 2, 1 positions a pass); a delivery is a block."""
    _, cast = _weights(4)
    model = _model(denoise_steps=steps)
    reqs = _requests()
    alone = [sdar_moe.generate(model, cast, p, n) for p, n in reqs]
    with _engine(model, cast) as eng:
        handles = [eng.submit(p, n) for p, n in reqs]
        streamed = [list(h.stream(timeout=120)) for h in handles]
        counts = eng.counters.snapshot()["counts"]
    b = TINY["block_len"]
    for h, (seq, passes), (prompt, n), toks in zip(handles, alone, reqs,
                                                   streamed):
        assert h.result() == seq
        assert toks == seq[len(prompt):]
        assert h.unmask_passes == passes
        assert max(passes) <= steps - 1
        first = min(n, b - len(prompt) % b)
        rest = n - first
        want = [first] + [b] * (rest // b) + ([rest % b] if rest % b else [])
        assert h.deliveries == want
    assert counts["tokens_unmasked"] == sum(n for _, n in REQUESTS)
    assert counts["commit_row_passes"] \
        == sum(len(h.deliveries) for h in handles)
    assert counts["row_passes"] >= counts["tokens_unmasked"] * steps // b \
        + counts["commit_row_passes"]
    assert counts["expert_calls"] == TINY["num_layers"] * (
        counts["decode_steps"] + counts["prefills"] - 1)  # one prompt < B
    # the experts' load counts a request's own positions and no other:
    # the prompt's whole blocks once, then each block's positions (not
    # those past the request's end) at every pass and its commit; never
    # an idle slot's rows or a prefill's padding
    rows = 0
    for h, (prompt, _) in zip(handles, reqs):
        rows += len(prompt) // b * b
        given, at = len(prompt) % b, 0
        for n in h.deliveries:
            passes = max(h.unmask_passes[at:at + n]) + 2
            rows += (given + n) * passes
            given, at = 0, at + n
    assert counts["expert_rows"] == \
        TINY["num_layers"] * TINY["experts_per_tok"] * rows
    assert counts["expert_rows_mean"] == pytest.approx(
        counts["expert_rows"] / TINY["num_experts"])
    assert counts["expert_rows_max"] * TINY["num_experts"] \
        >= counts["expert_rows"]


def test_attn_grid_steps_of_a_block_step_see_to_the_blocks_end():
    """A block step's row sees to the last position of the block it
    denoises, so its share of the paged kernel's grid is the KV blocks
    up to there at every pass and at the commit; the two idle slots
    take one step each. 9 prompt tokens leave the cursor at 8 (two
    whole blocks of 4 prefilled), then blocks at 8, 12, 16: KV blocks
    of 8 tokens, so 2, 2 and 3 of them."""
    _, cast = _weights(4)
    model = _model()
    prompt = np.random.RandomState(3).randint(0, 96, size=9).tolist()
    with _engine(model, cast) as eng:
        handle = eng.submit(prompt, 11)
        handle.result(120)
        counts = eng.counters.snapshot()["counts"]
        slots, width = eng._tables.shape
    assert handle.deliveries == [3, 4, 4]
    steps, at, want = 0, 0, 0
    for cursor, n in zip((8, 12, 16), handle.deliveries):
        passes = max(handle.unmask_passes[at:at + n]) + 2
        steps, at = steps + passes, at + n
        want += passes * ((cursor + TINY["block_len"] - 1) // 8 + 1)
    assert counts["decode_steps"] == steps
    assert counts["attn_grid_steps"] == want + steps * (slots - 1)
    assert counts["attn_table_slots"] == steps * slots * width


def test_preempted_request_re_enters_and_equals_the_request_alone():
    """A pool too small for three long requests: the youngest is
    preempted under exhaustion and re-enters through the common paged
    path (prompt plus the blocks delivered so far prefilled, the block
    in progress denoised again); tokens and passes still equal each
    request alone, and no delivered token is delivered twice."""
    _, cast = _weights(4)
    model = _model()
    rng = np.random.RandomState(0)
    reqs = [(rng.randint(0, 96, size=p).tolist(), n)
            for p, n in [(9, 22), (12, 19), (5, 26)]]
    alone = [sdar_moe.generate(model, cast, p, n) for p, n in reqs]
    with _engine(model, cast, kv_blocks=8) as eng:
        handles = [eng.submit(p, n) for p, n in reqs]
        got = [h.result(timeout=120) for h in handles]
        counts = eng.counters.snapshot()["counts"]
    assert counts["preemptions"] >= 1
    assert got == [seq for seq, _ in alone]
    assert [h.unmask_passes for h in handles] == [p for _, p in alone]
    assert [sum(h.deliveries) for h in handles] == [n for _, n in reqs]


def test_planted_confident_logits_unmask_several_positions_in_one_pass():
    """With the head scaled up the best token's probability passes the
    threshold 0.9 at most positions, so the dynamic rule unmasks more
    than the quota of one a pass: blocks finish in fewer passes, and
    the engine still equals the request alone."""
    _, cast = _weights(5)
    cast = dict(cast, head=cast["head"] * 60.0)
    model = _model()
    reqs = _requests(1)
    alone = [sdar_moe.generate(model, cast, p, n) for p, n in reqs]
    with _engine(model, cast) as eng:
        handles = [eng.submit(p, n) for p, n in reqs]
        got = [h.result(timeout=120) for h in handles]
        counts = eng.counters.snapshot()["counts"]
    assert got == [seq for seq, _ in alone]
    assert [h.unmask_passes for h in handles] == [p for _, p in alone]
    several = sum(1 for _, passes in alone
                  if len(passes) > len(set(passes)) + 1)
    assert several >= 3
    assert counts["tokens_unmasked"] / counts["row_passes"] > 0.85


def test_unmask_rule_quota_threshold_and_ties():
    masked = np.array([True, True, False, True])
    conf = np.array([0.2, 0.95, 0.99, 0.92])
    # two above the threshold, both go, whatever the quota of one
    assert generation.unmask(conf, masked, 1, 0.9).tolist() == \
        [False, True, False, True]
    # none above: the quota's most confident among the MASKED
    assert generation.unmask(conf, masked, 1, 0.96).tolist() == \
        [False, True, False, False]
    assert generation.unmask(conf, masked, 2, 1.0).tolist() == \
        [False, True, False, True]
    # fewer masked than the quota: all of them; a tie: the earlier
    assert generation.unmask(conf, masked, 4, 1.0).tolist() == \
        masked.tolist()
    assert generation.unmask(np.full(4, 0.5), masked, 1, 0.9).tolist() == \
        [True, False, False, False]
    # many rows at once (the engine's turn between passes): each row
    # by itself, a row with no mask left (its commit) unmasks nothing
    rows_conf = np.stack([conf, np.full(4, 0.5), conf, conf])
    rows_masked = np.stack([masked, masked, np.zeros(4, bool), ~masked])
    for quota, tau in [(1, 0.9), (1, 0.96), (2, 1.0), (4, 1.0)]:
        got = generation.unmask(rows_conf, rows_masked, quota, tau)
        assert got.tolist() == [
            generation.unmask(c, m, quota, tau).tolist()
            for c, m in zip(rows_conf, rows_masked)]
        assert not got[2].any()
    # the reference states the same rule on log-confidences
    for quota, tau in [(1, 0.9), (1, 0.96), (2, 1.0), (4, 1.0)]:
        assert ref.unmask_rule(np.log(conf), masked, quota, tau).tolist() \
            == generation.unmask(conf, masked, quota, tau).tolist()


@pytest.mark.parametrize("kw,why", [
    (dict(temperature=0.7, rng=jax.random.PRNGKey(0)), "temperature"),
    (dict(top_k=4), "top_k"),
    (dict(eos_token=5), "eos_token"),
    (dict(speculate_k=2), "speculate_k"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tier="prefill"), "tier"),
    (dict(total_len=62, buckets=[8, 16]), "must divide"),
])
def test_block_stepping_engine_refuses_what_it_does_not_do(kw, why):
    _, cast = _weights()
    with pytest.raises(ValueError, match=why) as err:
        _engine(_model(), cast, **kw)
    assert "diffusion over blocks" in str(err.value)


def test_block_stepping_engine_ships_no_kv_and_shares_no_prefix():
    _, cast = _weights()
    with _engine(_model(), cast) as eng:
        assert eng.prefix_cache is False
        with pytest.raises(ValueError, match="neither ships nor adopts"):
            eng.export_prefix(list(range(16)))
        eng.submit(list(range(1, 20)), 6).result(timeout=120)
        eng.submit(list(range(1, 20)), 6).result(timeout=120)
        counts = eng.counters.snapshot()["counts"]
    assert counts.get("prefix_hit_blocks", 0) == 0


def _gqa_case(seed, b=2, s_q=4, n=32, kv=4, d=128, bs=16, mb=4,
              dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    pool_rows = b * mb + 1
    q = jnp.asarray(rng.randn(b, s_q, n, d), dtype)
    kp = jnp.asarray(rng.randn(pool_rows, bs, kv * d), dtype)
    vp = jnp.asarray(rng.randn(pool_rows, bs, kv * d), dtype)
    table = 1 + rng.permutation(b * mb).reshape(b, mb).astype(np.int32)
    start = np.array([20, 36])[:b]
    pos = start[:, None] + np.arange(s_q)[None, :]
    return q, kp, vp, jnp.asarray(table), jnp.asarray(pos)


@pytest.mark.parametrize("impl", ["blockwise", "pallas"])
def test_paged_attention_with_grouped_heads_and_block_visibility(impl):
    """Group 8 at head size 128, each query seeing its block's last
    position: the fused formulations (the kernel in interpret mode)
    equal the gather oracle, and the oracle equals attention written
    out head by head with K/V head ``n // 8``. Float32 sums in another
    order: 1e-5 of values of size 1."""
    q, kp, vp, table, pos = _gqa_case(7)
    visible = sdar_moe.block_end(pos, 4)
    want = pa.paged_attention(q, kp, vp, table, visible, impl="gather")
    got = pa.paged_attention(q, kp, vp, table, visible, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    b, s_q, n, d = q.shape
    kv = kp.shape[-1] // d
    k = np.asarray(kp)[np.asarray(table)].reshape(b, -1, kv, d)
    v = np.asarray(vp)[np.asarray(table)].reshape(b, -1, kv, d)
    for row, i, head in [(0, 0, 0), (1, 3, 31), (0, 2, 9)]:
        sc = k[row, :, head // 8] @ np.asarray(q)[row, i, head] * d ** -0.5
        sc = np.where(np.arange(len(sc)) <= int(visible[row, i]), sc, -np.inf)
        p = np.exp(sc - sc.max())
        np.testing.assert_allclose(
            np.asarray(want)[row, i, head],
            (p / p.sum()) @ v[row, :, head // 8], atol=1e-5, rtol=1e-5)


def test_paged_attention_group_one_takes_the_old_path():
    """As many K/V heads as query heads: nothing is folded, the program
    is the formulation's own, as GPT-2 runs it."""
    q, kp, vp, table, pos = _gqa_case(8, n=4, kv=4, d=16, bs=8)
    through = jax.make_jaxpr(lambda *a: pa.paged_attention(
        *a, impl="blockwise"))(q, kp, vp, table, pos)
    direct = jax.make_jaxpr(lambda *a: pa._blockwise(*a, 16 ** -0.5))(
        q, kp, vp, table, pos)
    assert str(through) == str(direct)


def _routing(t, num_experts, top_k, empty, crowded, seed=0):
    """Expert ids ``[t, top_k]`` in which expert ``empty`` gets no
    position and ``crowded`` most of them."""
    rng = np.random.RandomState(seed)
    others = [e for e in range(num_experts) if e not in (empty, crowded)]
    ids = np.stack([rng.choice(others, size=top_k - 1, replace=False)
                    for _ in range(t)])
    first = np.where(rng.rand(t) < 0.8, crowded, rng.choice(others, size=t))
    for r in range(t):  # no expert twice for one position
        while first[r] in ids[r]:
            first[r] = rng.choice(others)
    return jnp.asarray(np.concatenate([first[:, None], ids], 1), jnp.int32)


def _loop_over_experts(lhs, rhs, sizes):
    """The plainest statement of the grouped product: every held expert
    over every row, kept where the row is in the expert's group."""
    ends = np.cumsum(sizes)
    row = np.arange(lhs.shape[0])
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    for e in range(rhs.shape[0]):
        mine = (row >= ends[e] - sizes[e]) & (row < ends[e])
        out[mine] = np.asarray(lhs)[mine] @ np.asarray(rhs[e])
    return out


@pytest.mark.parametrize("first,held", [(0, 8), (2, 4)])
def test_expert_gmm_is_ragged_dot_is_the_loop_under_uneven_routing(first,
                                                                   held):
    """One expert with no position and one with most of them, all held
    or a share of them: the kernel (interpret mode), ``ragged_dot`` and
    the loop over experts give the same rows, and each equals the row
    times its own expert's matrix. Float32 throughout: 1e-5."""
    t, k, e, h, f = 24, 3, 8, 32, 48
    experts = _routing(t, e, k, empty=3, crowded=5)
    tm = expert_gmm.row_tile(t * k, held)
    layout = expert_gmm.plan(experts, e, first, held, tm)
    counts = np.asarray(layout["counts"])
    assert counts[3] == 0 and counts[5] > t // 2 and counts.sum() == t * k
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(t, h), jnp.float32)
    w = jnp.asarray(rng.randn(held, h, f), jnp.float32)
    lhs = x[layout["row_token"]]
    outs = {impl: np.asarray(expert_gmm.expert_gmm(lhs, w, layout, tm,
                                                   impl=impl))
            for impl in ("pallas", "ragged")}
    np.testing.assert_allclose(outs["pallas"], outs["ragged"], atol=1e-5)
    np.testing.assert_allclose(
        _loop_over_experts(lhs, w, np.asarray(layout["sizes"])),
        outs["ragged"], atol=1e-5)
    dest = np.asarray(layout["dest"])
    rows = lhs.shape[0]
    for r in range(t):
        for j in range(k):
            local = int(experts[r, j]) - first
            if 0 <= local < held:
                np.testing.assert_allclose(
                    outs["pallas"][dest[r, j]],
                    np.asarray(x[r] @ w[local]), atol=1e-4)
            else:
                assert dest[r, j] == rows  # not held here: no row
    # every row of a tile that holds nothing is zero
    live = int(layout["live"])
    assert not outs["pallas"][live * tm:].any()


@pytest.mark.parametrize("holders", [1, 2, 4, 8])
def test_the_shares_of_all_holders_add_up_to_the_uncut_layer(holders):
    """128 experts, 8 a position, split over 2, 4 and 8 holders (and
    held whole): each holder routes over all 128 and computes its own
    experts' part; the parts sum to what the uncut reference gives for
    the whole layer (its output less its input after attention, which
    every holder computes alike and is counted once). Float32 model:
    the sums differ in order only."""
    model = dict(TINY, num_experts=128, experts_per_tok=8, num_layers=1)
    params, cast = _weights(6, model)
    layer = cast["layer_0"]
    x = jnp.asarray(np.random.RandomState(2).randn(19, 64), jnp.float32)
    whole = np.asarray(ref.layer_output(params, x, model))
    zero = jax.tree.map(jnp.zeros_like, params)
    zero["layer_0"] = dict(params["layer_0"], moe=dict(
        params["layer_0"]["moe"],
        down=jnp.zeros_like(params["layer_0"]["moe"]["down"])))
    after_attention = np.asarray(ref.layer_output(zero, x, model))
    h = sdar_moe.rmsnorm(jnp.asarray(after_attention),
                         layer["ln_post"]["scale"], 1e-6)
    per = 128 // holders
    total = np.zeros_like(whole)
    for i in range(holders):
        mine = slice(i * per, (i + 1) * per)
        moe = layer["moe"]
        y, experts = expert_gmm.expert_layer(
            h, moe["router"], moe["gate"][mine], moe["up"][mine],
            moe["down"][mine], 8, first=i * per)
        # every holder routes over all 128, whatever it holds
        assert experts.shape == (19, 8)
        assert int(experts.min()) < 16 and int(experts.max()) >= 112
        total += np.asarray(y)
        # and the reference, given the same share, gives the same part
        share = dict(model, first_expert=i * per, expert_count=per)
        part = np.asarray(ref.layer_output(params, x, share)) \
            - after_attention
        np.testing.assert_allclose(np.asarray(y), part, atol=F32_TOL)
    np.testing.assert_allclose(total, whole - after_attention,
                               atol=F32_TOL)
