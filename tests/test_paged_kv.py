"""Paged KV cache + prefix reuse invariants (PR 8).

The tentpole's whole contract is that paging is INVISIBLE to every
request: block tables, lazy growth, prefix sharing, LRU eviction, and
even mid-flight preemption may only change WHERE K/V bytes live, never
what tokens come out. Pinned here as the bitwise equality of the
paged engine and solo ``generate`` (the contiguous cache) at
temperature=0, warm-prefix == cold-prefix twins, and bitwise
continuation across a preemption. Plus the accounting contracts:
admission honesty under block pressure (shed, don't 504), and the
leak-proofing churn loop (cancel / disconnect / deadline-evict / drain
returns every block — refcounts zero, free list full).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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


# -- BlockPool (host allocator) unit tests ------------------------------


def test_pool_alloc_release_refcounts():
    pool = paging.BlockPool(4, 8)
    ids = pool.alloc(3)
    assert len(ids) == 3 and len(set(ids)) == 3
    assert 0 not in ids  # scratch is never handed out
    assert pool.allocatable() == 1
    assert all(pool.ref_count(b) == 1 for b in ids)
    pool.acquire(ids[:1])  # a sharer
    assert pool.ref_count(ids[0]) == 2
    pool.release(ids)
    assert pool.ref_count(ids[0]) == 1 and pool.allocatable() == 3
    pool.release(ids[:1])
    assert pool.allocatable() == 4 and pool.live_refs() == {}
    with pytest.raises(ValueError, match="unreferenced"):
        pool.release(ids[:1])


def test_pool_exhaustion_is_atomic():
    pool = paging.BlockPool(3, 8)
    pool.alloc(2)
    with pytest.raises(paging.PoolExhausted):
        pool.alloc(2)
    # nothing was allocated by the failed call
    assert pool.allocatable() == 1


def test_pool_prefix_chain_and_lru():
    pool = paging.BlockPool(4, 4)
    prompt = list(range(10))  # blocks at 4 and 8; tail 2
    ids = pool.alloc(pool.blocks_for(len(prompt)))  # 3 blocks
    pool.register(prompt, 4, ids[0])
    pool.register(prompt, 8, ids[1])
    # full-block sharing only, capped to leave >= 1 tail token
    assert pool.match_prefix(prompt) == ids[:2]
    assert pool.match_prefix(prompt[:8] + [99]) == ids[:2]
    assert pool.match_prefix(prompt[:4] + [99] * 6) == ids[:1]
    assert pool.match_prefix(prompt[:8]) == ids[:1]  # block 2 is tail
    assert pool.match_prefix([99] * 10) == []
    # release: registered blocks park in the LRU (still hittable),
    # unregistered go straight to the free list
    pool.release(ids)
    assert pool.stats()["cached"] == 2
    assert pool.allocatable() == 4
    assert pool.match_prefix(prompt) == ids[:2]
    # allocation pressure evicts the LEAST recently released first and
    # unregisters it; a later match stops at the broken chain
    taken = pool.alloc(3)  # free list has 2 -> evicts one cached block
    assert pool.stats()["evictions"] == 1
    assert pool.match_prefix(prompt) in ([], ids[:1])
    pool.release(taken)
    dropped = pool.drop_cache()
    assert pool.stats()["cached"] == 0
    assert dropped >= 1


def test_plan_admission_matches_plan_plus_capacity():
    pool = paging.BlockPool(num_blocks=8, block_size=4)
    tokens = list(range(1, 10))  # 9 tokens -> 2 shareable full blocks
    ids = pool.alloc(2)
    pool.register(tokens, 4, ids[0])
    pool.register(tokens, 8, ids[1])
    pool.release(ids)  # parked in the LRU, still registered
    shared, need, lru_res, allocatable, epoch = \
        pool.plan_admission(tokens)
    assert (shared, need, lru_res) == pool.plan(tokens)
    assert allocatable == pool.allocatable() == 8
    assert epoch == pool.epoch()
    assert shared == ids and need == 1 and lru_res == 2


def test_plan_admission_atomic_snapshot_under_churn():
    """Racecheck regression pin (PR 14): the admission estimate used
    to read ``plan()`` and ``allocatable()`` in two separate pool-lock
    acquisitions from HTTP handler threads while the scheduler thread
    acquired/released blocks between them. The torn read counts a
    chain as BOTH lru-resident (capacity it will consume) AND already
    acquired (capacity already gone) — double-charging the deficit
    (spurious shed) or masking it (admit into a certain 504).
    ``plan_admission`` reads everything under one lock hold; the
    invariant below distinguishes a consistent snapshot from a torn
    one and must hold on every read under churn."""
    pool = paging.BlockPool(num_blocks=8, block_size=4)
    tokens = list(range(1, 10))
    ids = pool.alloc(2)
    pool.register(tokens, 4, ids[0])
    pool.register(tokens, 8, ids[1])
    pool.release(ids)
    chain_len, total = 2, 8
    stop = threading.Event()
    barrier = threading.Barrier(2)
    bad = []

    def churn():
        barrier.wait()
        while not stop.is_set():
            pool.acquire(ids)   # chain live: lru 0, allocatable 6
            pool.release(ids)   # chain parked: lru 2, allocatable 8

    def audit():
        barrier.wait()
        for _ in range(4000):
            shared, need, lru_res, allocatable, _ = \
                pool.plan_admission(tokens)
            assert shared == ids, "registry churned unexpectedly"
            # in ONE snapshot the chain is parked (in lru_res AND in
            # allocatable) or live (in neither): lru_res + the blocks
            # missing from capacity can never exceed the chain length.
            # A torn read (lru_res from the parked state, allocatable
            # from the live state) yields 2 + 2 > 2.
            if lru_res + (total - allocatable) > chain_len:
                bad.append((lru_res, allocatable))
        stop.set()

    ts = [threading.Thread(target=churn, daemon=True,
                           name="tfos-test-pool-churn"),
          threading.Thread(target=audit, daemon=True,
                           name="tfos-test-pool-audit")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    stop.set()
    assert not bad, \
        "torn plan/capacity read(s) under churn: {}".format(bad[:5])


def test_pool_register_first_writer_wins():
    pool = paging.BlockPool(4, 4)
    prompt = list(range(6))
    a, b = pool.alloc(2)
    pool.register(prompt, 4, a)
    pool.register(prompt, 4, b)  # duplicate chain: no-op
    assert pool.match_prefix(prompt) == [a]
    pool.release([a, b])
    # b was never registered -> free list; a -> LRU
    assert pool.stats()["cached"] == 1


# -- the bitwise pin ----------------------------------------------------


def test_paged_engine_is_bitwise_solo(lm):
    """THE acceptance pin: mixed-length requests through the paged
    engine and solo ``generate`` (the contiguous cache) emit exactly
    the same tokens at temperature=0."""
    dec, params = lm
    rng = np.random.RandomState(0)
    reqs = []
    for _ in range(6):
        p = rng.randint(0, V, size=rng.randint(3, 20)).tolist()
        reqs.append((p, int(rng.randint(1, 10))))
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        assert eng.kv_block_size == 16  # the default engine's pool
        paged = [h.result(300) for h in
                 [eng.submit(p, mn) for p, mn in reqs]]
    assert paged == want


def test_warm_prefix_bitwise_and_hit_counters(lm):
    """A warm-prefix admission (block-table pointing at shared blocks,
    tail-only prefill) must be bitwise-identical to its cold twin —
    and provably WARM (hit counters, fewer prefilled tokens)."""
    dec, params = lm
    rng = np.random.RandomState(3)
    sys_prompt = rng.randint(0, V, size=40).tolist()  # 2 full 16-blocks
    reqs = [(sys_prompt + rng.randint(0, V, size=4).tolist(), 8)
            for _ in range(3)]
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=2,
                              kv_block_size=16) as eng:
        # serial: the first request is cold and registers the prefix,
        # the rest hit its blocks
        got = [eng.submit(p, mn).result(300) for p, mn in reqs]
        counts = _counts(eng)
        stats = eng.load_stats()
    assert got == want
    assert counts.get("prefix_hit_blocks", 0) == 4  # 2 blocks x 2 warm
    assert counts.get("prefix_miss_blocks", 0) == 2  # the cold twin
    assert stats["prefix_hit_rate"] > 0.5
    # all blocks returned; the shared prefix is retained as cache
    assert stats["kv_blocks_free"] == stats["kv_blocks_total"]


def test_identical_prompt_full_hit_still_generates(lm):
    """A FULLY cached prompt still leaves >= 1 tail token for the
    prefill forward (the logits its first token samples from), and its
    output replays bitwise."""
    dec, params = lm
    prompt = list(range(16)) * 2  # 32 tokens = 2 exact blocks of 16
    want = _solo(dec, params, prompt, 6)
    with serving.DecodeEngine(dec, params, slots=2,
                              kv_block_size=16) as eng:
        assert eng.submit(prompt, 6).result(300) == want
        assert eng.submit(prompt, 6).result(300) == want
        # sharing is capped at (len-1)//bs = 1 block: the second block
        # holds the last prompt token, which the tail must recompute
        assert _counts(eng).get("prefix_hit_blocks", 0) == 1


def test_live_shared_prefix_admits_concurrently(lm):
    """Sharing a LIVE prefix block costs no pool capacity: with the
    pool nearly exhausted by request A (32-token shared prefix + tail,
    3 of 4 blocks live), a same-prefix request B must still admit
    CONCURRENTLY — its plan needs only its 1 tail block, not
    tail + prefix. (Regression: the admission gate once counted live
    shared blocks against allocatable and serialized exactly this
    workload.) Both ride the same decode steps, so B's 4 tokens finish
    strictly before A's 12 — impossible if B had waited for A."""
    dec, params = lm
    sys_prompt = list(range(1, 17)) + list(range(16, 0, -1))  # 2 blocks
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=16,
                              kv_blocks=4) as eng:
        a = eng.submit(sys_prompt + [3], 12)
        deadline = time.monotonic() + 60
        while not a.generated:  # A's prefix is registered and LIVE
            assert time.monotonic() < deadline
            time.sleep(0.002)
        b = eng.submit(sys_prompt + [5], 4)
        got_b = b.result(120)
        assert not a._done.is_set(), \
            "B should finish mid-A (concurrent admission)"
        got_a = a.result(120)
        assert _counts(eng).get("prefix_hit_blocks", 0) == 2
        assert _counts(eng).get("preemptions", 0) == 0
    assert got_a == _solo(dec, params, sys_prompt + [3], 12)
    assert got_b == _solo(dec, params, sys_prompt + [5], 4)


def test_preemption_continuation_bitwise(lm):
    """Pool exhaustion preempts the youngest admission (blocks freed,
    requeued at front); its continuation re-prefill must resume the
    stream bitwise-identically."""
    dec, params = lm
    rng = np.random.RandomState(5)
    p1 = rng.randint(0, V, size=9).tolist()
    p2 = rng.randint(0, V, size=9).tolist()
    want = [_solo(dec, params, p1, 20), _solo(dec, params, p2, 20)]
    # each request grows to ceil(29/8)=4 blocks; two need 8 > 5
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=5, prefix_cache=False) as eng:
        h1 = eng.submit(p1, 20)
        h2 = eng.submit(p2, 20)
        got = [h1.result(300), h2.result(300)]
        counts = _counts(eng)
        pool = eng._pool
    assert counts.get("preemptions", 0) >= 1
    assert got == want
    assert pool.live_refs() == {} and pool.allocatable() == 5


def test_paged_pool_is_smaller_than_full_length_slots(lm):
    """The memory story: 6 sequences whose worst case is 18 blocks all
    serve correctly through an 8-block pool, which is smaller than the
    6 full-length rows a contiguous cache (``generate``'s, at batch 6)
    holds for them."""
    dec, params = lm
    rng = np.random.RandomState(6)
    reqs = [(rng.randint(0, V, size=9).tolist(), 15) for _ in range(6)]
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=6, kv_block_size=8,
                              kv_blocks=8, prefix_cache=False) as eng:
        paged_bytes = eng.kv_cache_bytes()
        got = [h.result(600) for h in
               [eng.submit(p, mn) for p, mn in reqs]]
    assert got == want
    contig_bytes = sum(
        leaf.size * leaf.dtype.itemsize for path, leaf in
        jax.tree_util.tree_leaves_with_path(
            generation.init_cache(dec, 6, MAXLEN))
        if generation._leaf_name(path) in ("cached_key", "cached_value"))
    assert contig_bytes == 2 * L * 6 * MAXLEN * H * 4
    # 9 blocks of 8 tokens resident (incl. scratch) vs 6 x 64 rows
    assert paged_bytes < contig_bytes / 4


def test_block_pressure_prices_admission_and_sheds(lm):
    """Admission honesty under block pressure: a request whose prefill
    blocks are unobtainable gets its queue wait floored at the earliest
    possible release, so a deadline feasible by slot math alone sheds
    (503 + Retry-After) instead of queueing into a 504."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=8, kv_block_size=16,
                              kv_blocks=4, prefix_cache=False) as eng:
        # warm the EWMAs (cold engines never shed)
        eng.submit([1, 2, 3], 2).result(300)
        # blocker takes all 4 blocks at admission and decodes a while
        blocker = eng.submit((list(range(1, 14)) * 4)[:50], 14)
        deadline = time.monotonic() + 60
        while _counts(eng).get("prefills", 0) < 2:
            assert time.monotonic() < deadline, "blocker never admitted"
            time.sleep(0.005)
        probe = [4, 5, 6, 7]
        plain = eng.estimate_admission(4)
        priced = eng.estimate_admission(4, prompt=probe)
        # the block floor is visible in the estimate itself
        assert priced["queue_wait_s"] > plain["queue_wait_s"]
        # a deadline the slot math would admit but the block math
        # cannot meet -> Shed at the door
        infeasible = (plain["queue_wait_s"] + plain["service_s"]
                      + priced["queue_wait_s"] + priced["service_s"]) / 2
        with pytest.raises(serving.Shed):
            eng.submit(probe, 4, deadline_s=infeasible)
        assert _counts(eng).get("shed", 0) == 1
        assert isinstance(serving.Shed("x"), serving.Retriable)
        blocker.result(600)


def test_validate_rejects_request_larger_than_pool(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=16,
                              kv_blocks=2) as eng:
        with pytest.raises(ValueError, match="KV blocks"):
            eng.submit(list(range(1, 9)), 30)  # 38 tokens = 3 blocks
        # a fitting request still serves
        assert len(eng.submit([1, 2], 4).result(300)) == 6


def test_kv_block_size_zero_is_refused_with_and_without_kv_blocks(lm):
    dec, params = lm
    for kw in (dict(), dict(kv_blocks=4)):
        with pytest.raises(ValueError, match="paged block pool only"):
            serving.DecodeEngine(dec, params, slots=1, kv_block_size=0,
                                 **kw)


class _NoPagedFields(object):
    """A decode model of a family that never got the paged fields."""

    max_len = MAXLEN


@pytest.mark.parametrize("how", ["kv_block_size=0", "model"])
def test_engine_without_a_block_pool_is_refused_with_one_message(lm, how):
    """There is no contiguous engine mode, asked for by the option or
    by a model that cannot be re-speced for a pool: both are refused,
    before a thread starts, with the message that names the path that
    does run on the contiguous cache."""
    dec, params = lm
    args = (dec, dict(kv_block_size=0)) if how == "kv_block_size=0" \
        else (_NoPagedFields(), dict())
    before = threading.active_count()
    with pytest.raises(ValueError) as err:
        serving.DecodeEngine(args[0], params, slots=1, **args[1])
    text = str(err.value)
    assert "paged block pool only" in text
    assert "generation.generate is the contiguous-cache path" in text
    assert type(args[0]).__name__ in text
    assert threading.active_count() == before


def test_attn_impl_is_no_option_and_no_reported_field(lm):
    """One formulation serves every engine, so there is nothing to
    select and nothing to report: pinned so that neither the option nor
    a field that can read one value drifts back."""
    dec, params = lm
    with pytest.raises(TypeError, match="attn_impl"):
        serving.DecodeEngine(dec, params, slots=1, attn_impl="fused")
    with pytest.raises(TypeError, match="attn_impl"):
        dec.clone(attn_impl="fused")
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        assert not hasattr(eng, "attn_impl")
        assert "attn_impl" not in eng._spawn_args
        assert "attn_impl" not in eng.load_stats()
        server = serving.ModelServer(None, engine=eng, name="m")
        code, body = server.healthz()
        assert code == 200 and "attn_impl" not in body
        assert body["generated_prefix_hit_blocks"] == 0
        assert "attn_impl" not in server.metrics_text()
        server.engine = None  # the engine is this test's to stop


def test_solo_generate_rejects_paged_model(lm):
    dec, params = lm
    paged = dec.clone(kv_block_size=16, kv_blocks=9)
    with pytest.raises(ValueError, match="contiguous"):
        generation.generate(paged, params, jnp.asarray([[1, 2]]), 4)


def test_healthz_and_load_stats_carry_block_pool(lm):
    """The pinned operator schema: /healthz and the BEAT-riding
    load_stats both carry kv_blocks_free / kv_blocks_total /
    prefix_hit_rate."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        eng.submit([1, 2, 3], 2).result(300)
        server = serving.ModelServer(None, engine=eng, name="m")
        code, body = server.healthz()
        assert code == 200
        assert body["kv_blocks_total"] == eng.kv_blocks > 0
        assert body["kv_blocks_free"] == eng.kv_blocks
        assert body["prefix_hit_rate"] == 0.0
        stats = eng.load_stats()
        assert stats["kv_blocks_total"] == eng.kv_blocks
        gauges = eng.counters.snapshot()["gauges"]
        assert gauges["kv_blocks_total"] == eng.kv_blocks
        assert gauges["kv_blocks_free"] == eng.kv_blocks
        server.engine = None  # the engine is this test's to stop


# -- fused paged-attention kernel (PR 11) -------------------------------


def test_fused_equals_solo_under_pressure(lm):
    """THE PR 11 parity pin: a workload of mixed lengths, a shared
    prefix (prefix-cached admissions), and a pool small enough to force
    preemption-continuation, through the engine (attention straight off
    the block table) emits exactly the tokens solo ``generate`` does at
    temperature=0. The two differ only in float accumulation order, so
    the token streams must be identical (the fused formulation against
    the gather one is tests/test_paged_attention.py's, at the op)."""
    dec, params = lm
    rng = np.random.RandomState(21)
    shared = rng.randint(0, V, size=16).tolist()  # 2 full 8-blocks
    reqs = [(shared + rng.randint(0, V, size=3).tolist(), 13),
            (rng.randint(0, V, size=9).tolist(), 16),
            (shared + rng.randint(0, V, size=5).tolist(), 11),
            (rng.randint(0, V, size=5).tolist(), 10)]
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    # 5 blocks cannot hold two grown sequences: preemption fires
    # (the same engine config as the preemption-continuation test,
    # so this one reuses its compiled programs)
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=5) as eng:
        got = [h.result(300) for h in
               [eng.submit(p, mn) for p, mn in reqs]]
        counts = _counts(eng)
    assert counts.get("prefix_hit_blocks", 0) >= 2
    assert counts.get("preemptions", 0) >= 1
    assert got == want


def test_scratch_isolation_through_fused_path(lm):
    """Bucket-padded prefill pad writes can never corrupt a visible
    offset through the fused path: a warm-prefix admission whose tail
    bucket OVERSHOOTS the logical capacity (start 16 + bucket 64 > L
    64 routes 16 pad writes to the scratch block) runs while a
    neighbor decodes — both outputs must stay bitwise-solo."""
    dec, params = lm
    rng = np.random.RandomState(22)
    pre = rng.randint(0, V, size=16).tolist()
    warm_p = pre + rng.randint(0, V, size=33).tolist()  # 49 tokens
    other_p = rng.randint(0, V, size=7).tolist()
    want_warm = _solo(dec, params, warm_p, 6)
    want_other = _solo(dec, params, other_p, 22)
    with serving.DecodeEngine(dec, params, slots=2,
                              kv_block_size=8) as eng:
        # register the 2-block prefix (17 tokens -> blocks at 8, 16)
        eng.submit(pre + [1], 2).result(300)
        other = eng.submit(other_p, 22)
        deadline = time.monotonic() + 60
        while not other.generated:  # neighbor is mid-decode
            assert time.monotonic() < deadline
            time.sleep(0.002)
        warm = eng.submit(warm_p, 6)
        assert warm.result(300) == want_warm
        assert other.result(300) == want_other
        # the admission really was warm (tail-only prefill)
        assert _counts(eng).get("prefix_hit_blocks", 0) >= 2


def test_generated_prefix_multi_turn_bitwise_and_counters(lm):
    """Generated-prefix registration (PR 11): a follow-up turn whose
    prompt is the prior turn's prompt + reply admits against the
    RESIDENT history — bitwise-identical to solo, with the decode-
    filled block provably registered and hit. Full blocks only: 23
    written tokens of turn 1 register exactly 2 blocks (one prompt-
    origin, one generated)."""
    dec, params = lm
    rng = np.random.RandomState(23)
    p1 = rng.randint(0, V, size=11).tolist()
    with serving.DecodeEngine(dec, params, slots=2,
                              kv_block_size=8) as eng:
        t1 = eng.submit(p1, 13).result(300)  # 24 tokens, 23 written
        stats = eng._pool.stats()
        # blocks at 8 (prompt) and 16 (contains generated content);
        # the partial tail block (16..23) must NOT be registered
        assert stats["generated_registered"] == 1
        p2 = t1 + [3]
        want = _solo(dec, params, p2, 5)
        assert eng.submit(p2, 5).result(300) == want
        counts = _counts(eng)
        assert counts.get("generated_prefix_hit_blocks", 0) == 1
        assert counts.get("prefix_hit_blocks", 0) == 2
        load = eng.load_stats()
        assert load["generated_prefix_hit_blocks"] == 1
        assert load["generated_prefix_registered"] >= 1
        # LRU interaction: the registered history is retention (cache),
        # not leak — flushing it fills the literal free list
        assert eng._pool.live_refs() == {}
        eng._pool.drop_cache()
        stats = eng._pool.stats()
        assert stats["cached"] == 0 and stats["free"] == stats["total"]


def test_generated_prefix_registered_with_a_step_in_flight(lm):
    """A block that decode fills is registered while the request goes
    on, with a step in flight: by the tokens the host holds, one short
    of the cursor. A follow-up whose prompt covers that block, sent
    while the first turn still decodes, admits against it and reads
    the right K/V: its tokens are the solo rollout's, and so are the
    first turn's."""
    dec, params = lm
    rng = np.random.RandomState(29)
    p1 = rng.randint(0, V, size=11).tolist()
    t1 = _solo(dec, params, p1, 40)
    p2 = t1[:26] + [3]  # blocks [0,8) [8,16) [16,24): two hold generated
    want = _solo(dec, params, p2, 5)
    with serving.DecodeEngine(dec, params, slots=2,
                              kv_block_size=8) as eng:
        # every step boundary of the first turn is a few milliseconds
        # here; hold one open so the follow-up provably arrives mid-turn
        h1 = eng.submit(p1, 40)
        assert chaos.poll_until(lambda: len(h1.generated) >= 16,
                                timeout=60, interval=0.001)
        chaos.arm("stall_decode_for=0.3")
        h2 = eng.submit(p2, 5)
        assert h2.result(300) == want
        assert h1.result(300) == t1
        counts = _counts(eng)
        # h1's two generated blocks were registered mid-flight (it had
        # a row in the step in flight each time) and h2 hit them
        assert counts["generated_prefix_hit_blocks"] == 2
        assert counts["prefix_hit_blocks"] == 3
        assert counts["steps_dispatched_ahead"] > 30
        assert counts["tokens_dropped_in_flight"] == 0
        assert eng._pool.live_refs() == {}


def test_finish_by_length_with_its_last_token_in_flight_grows_no_block(lm):
    """A request whose last token is in flight owes no step: it gets no
    row, and no block is allocated for a write that never comes. 8
    prompt tokens and 9 new ones: the 8 steps write positions 8..15,
    and the cursor then stands at 16, in a third block nobody writes.
    One allocation at admission, one at the first step, no third."""
    dec, params = lm
    prompt = np.random.RandomState(31).randint(0, V, size=8).tolist()
    want = _solo(dec, params, prompt, 9)
    with serving.DecodeEngine(dec, params, slots=1, kv_block_size=8,
                              kv_blocks=3, prefix_cache=False) as eng:
        assert eng.submit(prompt, 9).result(300) == want
        counts = _counts(eng)
        allocs = eng.timers.counts()["block_alloc"]
        assert eng._pool.allocatable() == 3
    assert allocs == 2
    assert counts["decode_steps"] == 8
    assert counts["steps_dispatched_ahead"] == 7
    assert counts["kv_block_steps"] == 2 * 8


def test_attn_grid_steps_count_live_blocks_and_idle_rows(lm):
    """What the paged kernel's grid takes, counted where the step's
    feed is built: 8 prompt tokens and 9 new ones on one of two slots
    are 8 steps at cursors 8..15, each seeing 2 blocks of 8, beside an
    idle slot's one step; a grid over every table slot would take
    ``slots x table width`` a step."""
    dec, params = lm
    prompt = np.random.RandomState(37).randint(0, V, size=8).tolist()
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              prefix_cache=False) as eng:
        assert _counts(eng)["attn_grid_steps"] == 0
        eng.submit(prompt, 9).result(300)
        counts = _counts(eng)
        width = eng._tables.shape[1]
    assert counts["decode_steps"] == 8
    assert counts["attn_grid_steps"] == 8 * (2 + 1)
    assert counts["attn_table_slots"] == 8 * 2 * width
    assert width == MAXLEN // 8


def test_generated_registration_gated_by_prefix_cache(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              prefix_cache=False) as eng:
        eng.submit(list(range(1, 12)), 13).result(300)
        assert eng._pool.stats()["generated_registered"] == 0
        assert eng.load_stats()["generated_prefix_registered"] == 0


@pytest.mark.chaos
@pytest.mark.slow
def test_leak_churn_cancel_disconnect_evict_drain(lm):
    """The leak-proofing pin: a churn loop of cancel / injected client
    disconnect / deadline eviction / drain returns EVERY block — live
    refcounts empty, the allocatable set back to full, and after
    flushing the (deliberate) prefix-cache retention the literal free
    list is full too. No orphaned shared blocks."""
    dec, params = lm
    rng = np.random.RandomState(9)
    eng = serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                               kv_blocks=12)
    try:
        pool = eng._pool
        for round_ in range(3):
            prompt = rng.randint(0, V, size=18).tolist()  # shares blocks
            # 1) explicit cancel mid-decode
            victim = eng.submit(prompt, 30)
            deadline = time.monotonic() + 60
            while not victim.generated:
                assert time.monotonic() < deadline
                time.sleep(0.002)
            victim.cancel()
            with pytest.raises(serving.Cancelled):
                victim.result(120)
            # 2) injected client disconnect (chaos plane)
            chaos.arm("disconnect_client_at_token=2")
            gone = eng.submit(prompt, 30)
            with pytest.raises(serving.Cancelled):
                gone.result(120)
            # 3) deadline eviction mid-decode (blank the rate evidence
            # so the tight deadline ADMITS — the established idiom from
            # test_serving_lifecycle — and expires at a step boundary)
            eng._step_ewma = eng._prefill_ewma = None
            slow = eng.submit(prompt, 40, deadline_s=0.005)
            with pytest.raises(serving.DeadlineExceeded):
                slow.result(120)
            # plus a request that finishes normally
            ok = eng.submit(prompt, 3)
            assert ok.result(120) == _solo(dec, params, prompt, 3)
            assert chaos.poll_until(
                lambda: pool.live_refs() == {}, timeout=30), \
                pool.live_refs()
            assert pool.allocatable() == 12
        # 4) drain with work in flight: zero loss, zero leak
        last = eng.submit(rng.randint(0, V, size=10).tolist(), 6)
        assert eng.drain(timeout=120) is True
        assert last.result(5)
        assert pool.live_refs() == {}
        assert pool.allocatable() == 12
        # retention was CACHE, not leak: flushing it fills the literal
        # free list
        pool.drop_cache()
        stats = pool.stats()
        assert stats["cached"] == 0 and stats["free"] == 12
    finally:
        eng.stop()


# -- prefix-chain digest export (PR 16) ---------------------------------


def test_prefix_digest_deterministic_and_hit_ranked():
    """The digest is a pure function of registry state: same chains +
    same tallies -> identical output, every full-block boundary is its
    own matchable entry, and observed heat reorders the top."""
    pool = paging.BlockPool(8, 4)
    prompt = list(range(12))
    ids = pool.alloc(3)
    pool.register(prompt, 4, ids[0])
    pool.register(prompt, 8, ids[1])
    pool.register(prompt, 12, ids[2])
    d1 = pool.prefix_digest()
    assert d1 == pool.prefix_digest()  # deterministic
    assert d1["block_size"] == 4 and d1["truncated"] is False
    # one entry per registered boundary, hash = chain_digest of the
    # chain's token prefix (what the router recomputes from a prompt)
    assert sorted(e[1] for e in d1["top"]) == [1, 2, 3]
    by_depth = {depth: h for h, depth in d1["top"]}
    for depth in (1, 2, 3):
        assert by_depth[depth] == paging.chain_digest(prompt, 4 * depth)
    # equal heat: deeper chains lead
    assert [e[1] for e in d1["top"]] == [3, 2, 1]
    # a DIFFERENT hot chain outranks the deep cold one once hit
    other = [90 + i for i in range(4)]
    oid = pool.alloc(1)
    pool.register(other, 4, oid[0])
    for _ in range(3):
        assert pool.match_prefix(other + [7]) == oid
    top = pool.prefix_digest()["top"]
    assert top[0] == [paging.chain_digest(other, 4), 1]


def test_prefix_digest_top_k_truncation_honest():
    """A 1000-chain registry publishes exactly top-K entries with the
    ``truncated`` flag raised — the bound is enforced AND admitted."""
    pool = paging.BlockPool(1001, 2)
    for i in range(1000):
        bid = pool.alloc(1)
        pool.register([i, 0], 2, bid[0])
    d = pool.prefix_digest()
    assert len(d["top"]) == paging.PREFIX_DIGEST_TOP_K
    assert d["truncated"] is True
    small = pool.prefix_digest(top_k=5)
    assert len(small["top"]) == 5 and small["truncated"] is True


def test_prefix_digest_includes_generated_chains(lm):
    """A decode-boundary registration (PR 11 generated-origin chain)
    appears in the digest exactly like a prompt chain: the turn-2
    prompt's chain hash is publishable the moment decode crosses the
    block boundary."""
    dec, params = lm
    rng = np.random.RandomState(29)
    p1 = rng.randint(0, V, size=11).tolist()
    with serving.DecodeEngine(dec, params, slots=2,
                              kv_block_size=8) as eng:
        t1 = eng.submit(p1, 13).result(300)  # 24 tokens, 23 written
        assert eng._pool.stats()["generated_registered"] == 1
        stats = eng.load_stats()
        assert stats["prefix_digest_block_size"] == 8
        hashes = {e[0] for e in stats["prefix_digest"]}
        # the depth-2 chain ends inside GENERATED content (block 8..16
        # was filled by decode) yet its hash is derived the same way
        assert paging.chain_digest(t1, 16) in hashes
        assert paging.chain_digest(t1, 8) in hashes
        assert stats["digest_truncated"] is False
        gauges = eng.counters.snapshot()["gauges"]
        assert gauges["prefix_digest_chains"] == len(
            stats["prefix_digest"])
