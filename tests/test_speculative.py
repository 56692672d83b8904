"""Speculative decoding + int8 paged-KV invariants (PR 15).

The speculation contract is that the draft/verify round is INVISIBLE
at temperature=0: token-matching acceptance emits exactly the target's
argmax chain, so a speculative engine's greedy output must be
bitwise-identical to the plain paged engine's and to solo
``generate()`` — across mixed lengths, prefix-cached admissions, and
preemption-continuation, at any acceptance rate (random weights give a
low one, exercising the rejection/correction path; zero-residual-tail
weights give acceptance 1.0, exercising the full-accept path). Plus
the accounting contracts (``spec_rounds <= spec_proposed <= k *
spec_rounds`` — each slot-round tallies only its emittable window —
and ``spec_accepted <= spec_proposed``, live acceptance rate from one
formula), the schema (``speculate_k`` / ``spec_acceptance_rate`` /
``kv_dtype`` through load_stats / healthz / metrics, zero schema when
off), and the int8 half: exact scale round-trip on the BlockPool
mirror, top-1 token agreement >= 99% teacher-forced through the REAL
paged write/read path, byte accounting, and the chaos-marked churn
legs (cancel / evict / drain with speculation mid-round) that ride
``make chaos``.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spec_weights import zero_residual_tail
from tensorflowonspark_tpu import chaos, generation, paging, serving
from tensorflowonspark_tpu.models.decoder import DecoderLM

V, H, NH, L, MAXLEN = 17, 32, 4, 2, 64


@pytest.fixture(scope="module")
def lm():
    train = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=L,
                      max_len=MAXLEN, decode=False)
    dec = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=L,
                    max_len=MAXLEN, decode=True)
    params = train.init(jax.random.PRNGKey(7),
                        jnp.zeros((2, MAXLEN), jnp.int32))["params"]
    return dec, params


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.disarm()


def _solo(dec, params, prompt, max_new):
    out = generation.generate_jit(
        dec, params, jnp.asarray([prompt], jnp.int32), max_new)
    return np.asarray(out)[0].tolist()


def _counts(eng):
    return eng.counters.snapshot()["counts"]


# -- the speculative bitwise pin ----------------------------------------


@pytest.mark.parametrize("k", [2, 4])
def test_speculative_bitwise_mixed_lengths(lm, k):
    """THE acceptance pin: mixed-length requests through a speculative
    engine emit exactly the tokens the plain paged engine and solo
    ``generate`` do at temperature=0 — at the natural (low) acceptance
    of random weights, so the rejection/correction path is what's
    being pinned."""
    dec, params = lm
    rng = np.random.RandomState(0)
    reqs = []
    for _ in range(6):
        p = rng.randint(0, V, size=rng.randint(3, 20)).tolist()
        reqs.append((p, int(rng.randint(1, 10))))
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=2,
                              speculate_k=k) as eng:
        assert eng._spec_k == k and eng.draft_layers == 1
        got = [h.result(300) for h in
               [eng.submit(p, mn) for p, mn in reqs]]
        counts = _counts(eng)
    assert got == want
    assert counts.get("spec_rounds", 0) > 0


def test_speculative_prefix_cached_bitwise(lm):
    """Warm-prefix admissions under speculation: the draft pool
    mirrors the target pool block for block, so a table-pointing warm
    admission must still produce bitwise-solo output — and provably
    hit the cache."""
    dec, params = lm
    rng = np.random.RandomState(3)
    sys_prompt = rng.randint(0, V, size=40).tolist()
    reqs = [(sys_prompt + rng.randint(0, V, size=4).tolist(), 8)
            for _ in range(3)]
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=16,
                              speculate_k=3) as eng:
        got = [eng.submit(p, mn).result(300) for p, mn in reqs]
        counts = _counts(eng)
    assert got == want
    assert counts.get("prefix_hit_blocks", 0) == 4


def test_speculative_preemption_continuation_bitwise(lm):
    """Pool exhaustion with the k-token lookahead: growth covers the
    round's whole write window, preemption picks the youngest, and
    the continuation resumes the stream bitwise."""
    dec, params = lm
    rng = np.random.RandomState(5)
    p1 = rng.randint(0, V, size=9).tolist()
    p2 = rng.randint(0, V, size=9).tolist()
    want = [_solo(dec, params, p1, 20), _solo(dec, params, p2, 20)]
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=5, prefix_cache=False,
                              speculate_k=3) as eng:
        h1 = eng.submit(p1, 20)
        h2 = eng.submit(p2, 20)
        got = [h1.result(300), h2.result(300)]
        counts = _counts(eng)
        pool = eng._pool
    assert counts.get("preemptions", 0) >= 1
    assert got == want
    assert pool.live_refs() == {} and pool.allocatable() == 5


def test_speculative_eos_matches_plain(lm):
    """EOS inside a round's emitted window must stop the request at
    the same token the plain engine stops at (later window tokens are
    dropped, never delivered)."""
    dec, params = lm
    rng = np.random.RandomState(8)
    reqs = [(rng.randint(0, V, size=7).tolist(), 14) for _ in range(3)]
    outs = {}
    for label, kw in (("plain", {}), ("spec", {"speculate_k": 4})):
        with serving.DecodeEngine(dec, params, slots=2, eos_token=3,
                                  **kw) as eng:
            outs[label] = [h.result(300) for h in
                           [eng.submit(p, mn) for p, mn in reqs]]
    assert outs["spec"] == outs["plain"]


def test_full_acceptance_on_zero_residual_tail(lm):
    """Zero-residual-tail weights make the weight-tied draft agree
    with the target at every position: acceptance must be exactly
    1.0, every round emits k tokens, and the output is still
    bitwise-solo (the full-accept path's pin; the bench leg's
    draft-friendly device justified here)."""
    dec, params = lm
    params = zero_residual_tail(params, 1, L)
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, V, size=6).tolist(), 12) for _ in range(2)]
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=2,
                              speculate_k=3) as eng:
        got = [h.result(300) for h in
               [eng.submit(p, mn) for p, mn in reqs]]
        load = eng.load_stats()
        counts = _counts(eng)
    assert got == want
    assert load["spec_acceptance_rate"] == 1.0
    assert counts["spec_accepted"] == counts["spec_proposed"]


# -- accounting + schema ------------------------------------------------


def test_spec_counter_arithmetic_and_live_rate(lm):
    """The pinned counter algebra: rounds <= proposed <= k x rounds
    (each slot-round tallies only its EMITTABLE window min(k,
    remaining) — a request near its length cap must not inflate the
    published acceptance rate with positions it could never emit),
    accepted <= proposed, and the BEAT-riding acceptance rate is
    exactly accepted/proposed. Full-acceptance weights make every
    window deterministic: after the prefill's token a request has 8
    left, emitted as windows of 3, 3 and a CLAMPED 2."""
    dec, params = lm
    params = zero_residual_tail(params, 1, L)
    k = 3
    with serving.DecodeEngine(dec, params, slots=2,
                              speculate_k=k) as eng:
        for _ in range(2):
            eng.submit(list(range(1, 8)), 9).result(300)
        counts = _counts(eng)
        load = eng.load_stats()
    proposed = counts["spec_proposed"]
    accepted = counts["spec_accepted"]
    rounds = counts["spec_rounds"]
    assert rounds > 0
    assert rounds <= proposed <= k * rounds
    # max_new=9 with k=3: the last window of a request that decodes
    # to its cap is CLAMPED below k, so the strict inequality is
    # actually exercised here, not vacuously true
    assert (rounds, proposed) == (2 * 3, 2 * (3 + 3 + 2))
    assert proposed < k * rounds
    assert 0 <= accepted <= proposed
    assert load["spec_acceptance_rate"] == round(accepted / proposed, 4)
    # tokens actually emitted never exceed what rounds could emit
    assert counts["decode_tokens"] <= rounds * k


def test_draft_params_weight_tying():
    """The draft's params ARE the target's arrays (aliases, not
    copies), and non-DecoderLM trees fail loudly."""
    train = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=L,
                      max_len=MAXLEN, decode=False)
    params = train.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, MAXLEN), jnp.int32))["params"]
    tied = generation.draft_params(params, 1)
    assert set(tied) == {"tok_embed", "pos_embed", "ln_f", "head",
                         "block_0"}
    assert tied["block_0"] is params["block_0"]  # tied, not copied
    with pytest.raises(ValueError, match="block_1"):
        generation.draft_params({"tok_embed": 0}, 2)


def test_spec_validation(lm):
    dec, params = lm
    with pytest.raises(ValueError, match="speculate_k"):
        serving.DecodeEngine(dec, params, slots=1, speculate_k=1)
    with pytest.raises(ValueError, match="draft_layers"):
        serving.DecodeEngine(dec, params, slots=1, draft_layers=1)
    with pytest.raises(ValueError, match="draft_layers"):
        serving.DecodeEngine(dec, params, slots=1, speculate_k=2,
                             draft_layers=L + 1)


def test_kv_dtype_validation(lm):
    dec, params = lm
    with pytest.raises(ValueError, match="kv_dtype"):
        serving.DecodeEngine(dec, params, slots=1, kv_dtype="int4")
    # fp32 aliases are the off switch, not an error
    with serving.DecodeEngine(dec, params, slots=1,
                              kv_dtype="fp32") as eng:
        assert eng.kv_dtype == "float32"


def test_schema_through_load_stats_healthz_metrics(lm):
    """The pinned operator schema: speculate_k / spec_acceptance_rate
    / kv_dtype through load_stats, /healthz, and the /metrics info
    gauge — zero schema (0 / 0.0 / compute dtype) on engines with
    both features off, so consumers need no presence checks."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        load = eng.load_stats()
        assert load["speculate_k"] == 0
        assert load["spec_acceptance_rate"] == 0.0
        assert load["kv_dtype"] == "float32"
    with serving.DecodeEngine(dec, params, slots=2, speculate_k=2,
                              kv_dtype="int8") as eng:
        eng.submit([1, 2, 3], 4).result(300)
        load = eng.load_stats()
        assert load["speculate_k"] == 2
        assert load["spec_acceptance_rate"] >= 0.0
        assert load["kv_dtype"] == "int8"
        server = serving.ModelServer(None, engine=eng, name="m")
        code, body = server.healthz()
        assert code == 200
        assert body["speculate_k"] == 2
        assert body["kv_dtype"] == "int8"
        assert "spec_acceptance_rate" in body
        text = server.metrics_text()
        assert 'tfos_serving_kv_dtype{dtype="int8"} 1' in text
        server.engine = None  # the engine is this test's to stop


def test_respawn_preserves_spec_and_kv_dtype(lm):
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1, speculate_k=2,
                               draft_layers=1, kv_dtype="int8")
    try:
        eng.stop()
        fresh = eng.respawn()
        try:
            assert fresh._spec_k == 2
            assert fresh.draft_layers == 1
            assert fresh.kv_dtype == "int8"
            assert fresh.load_stats()["speculate_k"] == 2
        finally:
            fresh.stop()
    finally:
        eng.stop()


def test_estimate_admission_scales_with_acceptance(lm):
    """The speculation-adjusted estimate: after serving, a
    speculative engine's tokens-per-round EWMA > 1 must price
    per-token service BELOW the raw round EWMA (the plain formula
    would overcharge every token at the heavier round cost)."""
    dec, params = lm
    params = zero_residual_tail(params, 1, L)  # acceptance 1.0
    with serving.DecodeEngine(dec, params, slots=2,
                              speculate_k=4) as eng:
        eng.submit(list(range(1, 6)), 12).result(300)
        with eng._cv:
            est = eng._estimate_locked(10)
        assert eng._tokens_round_ewma > 1.0
        # service priced at round/tpr, not at the raw round EWMA
        raw = (eng._prefill_ewma or 0.0) + 10 * eng._step_ewma
        assert est["service_s"] < raw


def test_fleet_view_carries_spec_and_kv_dtype(lm):
    """The heterogeneous-rollout pin: a speculative int8 replica's BEAT payload surfaces speculate_k /
    spec_acceptance_rate / kv_dtype through the router's
    replica_views and its /healthz per-replica body."""
    from tensorflowonspark_tpu import fleet

    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=1, name="lm",
                            engine_kw={"slots": 2, "speculate_k": 2,
                                       "kv_dtype": "int8"},
                            beat_interval=0.05) as f:
        deadline = time.monotonic() + 10
        views = []
        while time.monotonic() < deadline:
            views = f.router.replica_views()
            if views and views[0]["kv_dtype"] == "int8":
                break
            time.sleep(0.05)
        assert views and views[0]["speculate_k"] == 2
        assert views[0]["kv_dtype"] == "int8"
        assert views[0]["spec_acceptance_rate"] == 0.0
        code, body = f.router.healthz()
        assert code == 200
        rep = body["replicas"]["replica-0"]
        assert rep["speculate_k"] == 2
        assert rep["kv_dtype"] == "int8"
        assert "spec_acceptance_rate" in rep


# -- int8 agreement + bytes ---------------------------------------------


def test_int8_top1_agreement_teacher_forced(lm):
    """The int8 accuracy pin: teacher-forced top-1 agreement >= 99%
    between the float paged path and the int8 paged path, through the
    REAL write (quantize+scatter) and read (in-formulation dequant)
    code — full sequences written through the block tables, argmax
    compared at every position."""
    dec, params = lm
    bs = 8
    blocks_per_row = MAXLEN // bs
    rng = np.random.RandomState(2)
    seqs = [_solo(dec, params,
                  rng.randint(0, V, size=10).tolist(), MAXLEN - 10)
            for _ in range(6)]
    match = total = 0
    for kv_dtype in ("", "int8"):
        model = dec.clone(kv_block_size=bs,
                          kv_blocks=blocks_per_row + 1,
                          kv_dtype=kv_dtype)
        cache = generation.init_cache(model, 1, MAXLEN)
        table = jnp.arange(1, blocks_per_row + 1,
                           dtype=jnp.int32)[None, :]
        argmaxes = []
        for seq in seqs:
            c = generation._set_paged_leaves(
                model, cache, jnp.zeros((1,), jnp.int32), table)
            logits, _ = model.apply(
                {"params": params, "cache": c},
                jnp.asarray([seq], jnp.int32), mutable=["cache"])
            argmaxes.append(np.asarray(jnp.argmax(logits, -1))[0])
        if kv_dtype == "":
            ref = argmaxes
        else:
            for a, b in zip(ref, argmaxes):
                match += int((a == b).sum())
                total += a.size
    assert total >= 300
    assert match / total >= 0.99, \
        "top-1 agreement {} below 0.99".format(match / total)


def test_int8_engine_serves_and_costs_fewer_bytes(lm):
    """End-to-end int8 engine: correct request shapes, leak-free
    teardown, and the byte accounting — the int8 pool (codes +
    scales) at equal blocks costs under half the float pool, matching
    BlockPool.block_bytes to the byte."""
    dec, params = lm
    rng = np.random.RandomState(4)
    reqs = [(rng.randint(0, V, size=6).tolist(), 8) for _ in range(3)]
    sizes = {}
    for kv_dtype in (None, "int8"):
        with serving.DecodeEngine(dec, params, slots=2,
                                  kv_block_size=8, kv_blocks=10,
                                  kv_dtype=kv_dtype) as eng:
            got = [h.result(300) for h in
                   [eng.submit(p, mn) for p, mn in reqs]]
            assert [len(g) for g in got] == [14, 14, 14]
            sizes[kv_dtype or "fp32"] = eng.kv_cache_bytes()
            assert eng._pool.live_refs() == {}
    assert sizes["int8"] * 2 < sizes["fp32"]
    # the analytic accounting matches the measured pool: 11 resident
    # rows (10 + scratch) x block_bytes per layer x L layers
    pool = paging.BlockPool(10, 8, kv_dtype="int8")
    head_dim = H // NH
    assert sizes["int8"] == 11 * pool.block_bytes(NH, head_dim, L)
    fp_pool = paging.BlockPool(10, 8)
    assert sizes["fp32"] == 11 * fp_pool.block_bytes(NH, head_dim, L)


def test_block_pool_kv_dtype_validation_and_stats():
    with pytest.raises(ValueError, match="kv_dtype"):
        paging.BlockPool(4, 8, kv_dtype="int4")
    pool = paging.BlockPool(4, 8, kv_dtype="int8")
    assert pool.stats()["kv_dtype"] == "int8"
    # int8 + scales cost less than half of f32 at head_dim 16
    assert pool.block_bytes(4, 16) * 2 < \
        paging.BlockPool(4, 8).block_bytes(4, 16)


# -- churn legs (make chaos) --------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow
def test_spec_leak_churn_cancel_evict_drain(lm):
    """The PR 8 churn contract with speculation mid-round: cancel /
    injected disconnect / deadline eviction / drain all land at round
    boundaries while the engine is emitting multi-token windows —
    every block returns (draft pool shares the ids, so a leak in
    either bookkeeping shows), and the surviving request's output is
    still bitwise-solo."""
    dec, params = lm
    rng = np.random.RandomState(9)
    eng = serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                               kv_blocks=12, speculate_k=3)
    try:
        pool = eng._pool
        for _ in range(3):
            prompt = rng.randint(0, V, size=18).tolist()
            victim = eng.submit(prompt, 30)
            deadline = time.monotonic() + 60
            while not victim.generated:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            victim.cancel()
            with pytest.raises(serving.Cancelled):
                victim.result(120)
            chaos.arm("disconnect_client_at_token=2")
            gone = eng.submit(prompt, 30)
            with pytest.raises(serving.Cancelled):
                gone.result(120)
            eng._step_ewma = eng._prefill_ewma = None
            slow = eng.submit(prompt, 40, deadline_s=0.005)
            with pytest.raises(serving.DeadlineExceeded):
                slow.result(120)
            ok = eng.submit(prompt, 3)
            assert ok.result(120) == _solo(dec, params, prompt, 3)
            assert chaos.poll_until(
                lambda: pool.live_refs() == {}, timeout=30), \
                pool.live_refs()
            assert pool.allocatable() == 12
        last = eng.submit(rng.randint(0, V, size=10).tolist(), 6)
        assert eng.drain(timeout=120) is True
        assert last.result(5)
        assert pool.live_refs() == {}
        assert pool.allocatable() == 12
    finally:
        eng.stop()


@pytest.mark.chaos
@pytest.mark.slow
def test_spec_int8_churn_leak_free(lm):
    """Same churn with BOTH fast paths on (speculation + int8 pools):
    completions keep their shapes (int8 is lossy, so no bitwise
    assert — the agreement pin is teacher-forced above) and every
    block returns through every exit path."""
    dec, params = lm
    rng = np.random.RandomState(10)
    eng = serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                               kv_blocks=12, speculate_k=3,
                               kv_dtype="int8")
    try:
        pool = eng._pool
        for _ in range(2):
            prompt = rng.randint(0, V, size=18).tolist()
            victim = eng.submit(prompt, 30)
            deadline = time.monotonic() + 60
            while not victim.generated:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            victim.cancel()
            with pytest.raises(serving.Cancelled):
                victim.result(120)
            eng._step_ewma = eng._prefill_ewma = None
            slow = eng.submit(prompt, 40, deadline_s=0.005)
            with pytest.raises(serving.DeadlineExceeded):
                slow.result(120)
            ok = eng.submit(prompt, 4)
            assert len(ok.result(120)) == len(prompt) + 4
            assert chaos.poll_until(
                lambda: pool.live_refs() == {}, timeout=30), \
                pool.live_refs()
            assert pool.allocatable() == 12
        assert eng.drain(timeout=120) is True
    finally:
        eng.stop()
