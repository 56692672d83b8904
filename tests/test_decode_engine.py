"""Continuous-batching decode engine invariants (serving.DecodeEngine).

The engine's whole contract is that slot-structured continuous batching
is INVISIBLE to each request: at temperature=0 a request's output must
be bitwise-identical to a solo ``generation.generate`` call, regardless
of what the other slots are doing, how often its slot was previously
occupied, or which shape bucket its prompt padded into. Plus the perf
contract that motivates the design: compile count stays O(buckets),
not O(request signatures).
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import chaos, generation, serving
from tensorflowonspark_tpu.models.decoder import DecoderLM

V, H, NH, L, MAXLEN = 17, 32, 4, 2, 48


@pytest.fixture(scope="module")
def lm():
    train = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=L,
                      max_len=MAXLEN, decode=False)
    dec = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=L,
                    max_len=MAXLEN, decode=True)
    params = train.init(jax.random.PRNGKey(7),
                        jnp.zeros((2, MAXLEN), jnp.int32))["params"]
    return dec, params


def _solo(dec, params, prompt, max_new, **kw):
    out = generation.generate_jit(
        dec, params, jnp.asarray([prompt], jnp.int32), max_new, **kw)
    return np.asarray(out)[0].tolist()


def _mixed_requests(rng, n, lo_p=3, hi_p=12, lo_n=1, hi_n=10):
    reqs = []
    for _ in range(n):
        p = rng.randint(0, V, size=rng.randint(lo_p, hi_p)).tolist()
        mn = int(rng.randint(lo_n, hi_n))
        reqs.append((p, min(mn, MAXLEN - len(p))))
    return reqs


def test_temp0_bitwise_identical_to_solo_generate(lm):
    """The acceptance pin: mixed-length requests through a shared
    2-slot engine emit EXACTLY the tokens each would get alone."""
    dec, params = lm
    reqs = _mixed_requests(np.random.RandomState(0), 6)
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        handles = [eng.submit(p, mn) for p, mn in reqs]
        got = [h.result(300) for h in handles]
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, (i, g, w)


def test_no_cross_slot_logit_leakage(lm):
    """A request's tokens must not change with slot COMPANY: run one
    request alone (its neighbor slot idle/masked), then crowded among
    five concurrent others — identical output both times, so neither
    idle slots nor foreign active sequences perturb its logits."""
    dec, params = lm
    rng = np.random.RandomState(1)
    probe = (rng.randint(0, V, size=7).tolist(), 9)
    others = _mixed_requests(rng, 5)
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        alone = eng.submit(*probe).result(300)
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        hs = [eng.submit(p, mn) for p, mn in others[:2]]
        hp = eng.submit(*probe)
        hs += [eng.submit(p, mn) for p, mn in others[2:]]
        crowded = hp.result(300)
        for h in hs:
            h.result(300)
    assert alone == crowded


def test_slot_reuse_after_eos_has_no_cache_bleed(lm):
    """A 1-slot engine forces every request through the SAME slot, each
    admission overwriting the previous occupant's cache rows; with an
    eos that fires mid-sequence the slot frees early and the next
    request must still match its solo rollout bitwise."""
    dec, params = lm
    rng = np.random.RandomState(2)
    # choose as eos a token the greedy rollout actually emits, so the
    # early-exit path (slot freed before max_new) really executes
    first = rng.randint(0, V, size=5).tolist()
    base = _solo(dec, params, first, 10)
    eos = base[len(first) + 1]
    reqs = [(first, 10)] + _mixed_requests(rng, 4)
    want = []
    for p, mn in reqs:
        solo = _solo(dec, params, p, mn, eos_token=eos)
        gen = solo[len(p):]
        if eos in gen:  # engine semantics: truncate at (and keep) eos
            gen = gen[:gen.index(eos) + 1]
        want.append(p + gen)
    with serving.DecodeEngine(dec, params, slots=1, eos_token=eos) as eng:
        got = [eng.submit(p, mn).result(300) for p, mn in reqs]
    assert got == want
    # the eos path genuinely fired early on the seeded first request
    assert got[0][-1] == eos and len(got[0]) < len(first) + 10


def test_compile_count_bounded_by_buckets(lm):
    """The perf contract: a workload of many DISTINCT (prompt_len,
    max_new) signatures compiles one decode program per engine config
    plus at most one prefill program per touched bucket — while the
    old whole-generation path would compile once per signature."""
    # a dedicated model config so generation.paged_step_fns' lru cache
    # entry (and its program counts) belongs to this test alone
    train = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=1,
                      max_len=64, decode=False)
    dec = DecoderLM(vocab=V, hidden=H, num_heads=NH, num_layers=1,
                    max_len=64, decode=True)
    params = train.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 64), jnp.int32))["params"]
    rng = np.random.RandomState(3)
    reqs = [(rng.randint(0, V, size=n).tolist(), int(rng.randint(1, 9)))
            for n in (2, 3, 5, 7, 9, 12, 17, 21, 29, 33)]
    signatures = {(len(p), mn) for p, mn in reqs}
    assert len(signatures) == len(reqs)  # genuinely mixed workload
    with serving.DecodeEngine(dec, params, slots=4) as eng:
        buckets = eng.buckets
        touched = {generation.bucket_for(len(p), buckets)
                   for p, mn in reqs}
        for h in [eng.submit(p, mn) for p, mn in reqs]:
            h.result(300)
        stats = eng.compile_stats()
    assert stats["decode_programs"] == 1, stats
    assert stats["prefill_programs"] == len(touched), (stats, touched)
    assert stats["prefill_programs"] <= len(buckets)


def test_max_new_one_and_zero_paths(lm):
    """max_new=1 completes at prefill (no decode step); max_new=0 never
    touches the device and returns the prompt."""
    dec, params = lm
    prompt = [1, 2, 3, 4]
    want = _solo(dec, params, prompt, 1)
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        h1 = eng.submit(prompt, 1)
        h0 = eng.submit(prompt, 0)
        assert h1.result(300) == want
        assert h0.result(300) == prompt
        snap = eng.counters.snapshot()["counts"]
    assert snap.get("decode_steps", 0) == 0, snap
    assert snap["prefills"] == 1, snap


def test_submit_validation(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1,
                              total_len=32) as eng:
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit([], 4)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit([1, 2], -1)
        with pytest.raises(ValueError, match="bucket"):
            eng.submit([1] * 33, 1)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit([1, 99999], 1)
        with pytest.raises(ValueError, match="vocab"):
            eng.submit([-5], 1)
        with pytest.raises(ValueError, match="total_len"):
            eng.submit([1] * 30, 8)
    with pytest.raises(RuntimeError, match="stopped"):
        eng.submit([1], 1)
    # the degenerate max_new=0 path must hit the same liveness checks:
    # a dead engine answering a probe with success reads as healthy
    with pytest.raises(RuntimeError, match="stopped"):
        eng.submit([1], 0)


def test_engine_rejects_bad_sampling_config(lm):
    """The engine shares generate()'s sampling checks: a config that
    would serve silently wrong tokens must refuse at construction."""
    dec, params = lm
    with pytest.raises(ValueError, match="top_k"):
        serving.DecodeEngine(dec, params, slots=1, top_k=0)
    with pytest.raises(ValueError, match="top_p"):
        serving.DecodeEngine(dec, params, slots=1, top_p=0.0)
    with pytest.raises(ValueError, match="PRNG"):
        serving.DecodeEngine(dec, params, slots=1, temperature=0.8)


def test_queue_full_backpressure(lm):
    """submit() past max_queue raises QueueFull with nothing queued —
    and a multi-request body is all-or-nothing."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1, max_queue=2) as eng:
        blocker = eng.submit([1, 2], 40)  # holds the single slot
        deadline = time.monotonic() + 60
        while eng.counters.snapshot()["counts"].get("prefills", 0) < 1:
            assert time.monotonic() < deadline, "blocker never admitted"
            time.sleep(0.01)
        eng.submit([1], 4)
        eng.submit([2], 4)  # queue now at max_queue=2
        with pytest.raises(serving.QueueFull, match="max_queue"):
            eng.submit([3], 4)
        # atomic body admission: 2 queued + 2 more > max_queue, so the
        # WHOLE body refuses and queue_depth is unchanged
        depth_before = eng.counters.snapshot()["gauges"]["queue_depth"]
        with pytest.raises(serving.QueueFull):
            eng._submit_many([([4], 4), ([5], 4)])
        depth = eng.counters.snapshot()["gauges"]["queue_depth"]
        assert depth == depth_before
        blocker.result(300)  # drain so stop() isn't racing live decode


def test_streaming_and_counters(lm):
    """stream() yields tokens incrementally; the tracing.Counters
    export (queue depth / slot occupancy / tokens-per-step) reflects
    the run."""
    dec, params = lm
    prompt = [3, 1, 4, 1]
    want = _solo(dec, params, prompt, 8)
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        h = eng.submit(prompt, 8)
        streamed = list(h.stream(timeout=300))
        snap = eng.counters.snapshot()
        tps = eng.counters.rate("decode_tokens", "decode_steps")
    assert prompt + streamed == want
    assert h.latency is not None and h.latency >= 0
    assert snap["counts"]["tokens"] == 8
    # the prefill-emitted first token is counted in "tokens" but NOT in
    # "decode_tokens", so occupancy stays bounded by the slot count
    assert snap["counts"]["decode_tokens"] == 7
    assert snap["counts"]["requests_completed"] == 1
    assert snap["gauges"]["queue_depth"] == 0
    assert 0 < tps <= eng.slots


def test_engine_failure_fails_clients_not_hangs(lm):
    """A scheduler-loop death must surface to every waiting client as
    an error, and later submits must refuse loudly."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=2)
    try:
        # poison the loop: a params pytree of the wrong structure makes
        # the prefill call raise inside the scheduler thread
        eng.params = {"nope": jnp.zeros(())}
        h = eng.submit([1, 2, 3], 4)
        with pytest.raises(RuntimeError, match="failed"):
            h.result(120)
        with pytest.raises(RuntimeError):
            eng.submit([1, 2, 3], 4)
    finally:
        eng.stop()


# -- one step in flight ---------------------------------------------------
#
# A token engine dispatches step n+1 before it reads step n (the next
# input is the device's own output), so what the host knows lags the
# cursor by one step. Everything a client can see must be as if it did
# not.


def _counts(eng):
    return eng.counters.snapshot()["counts"]


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.disarm()


def test_streams_of_unequal_lengths_are_solo_token_by_token(lm):
    """Concurrent requests of unequal lengths, each consumed from
    ``stream()`` by a thread of its own while steps are in flight: every
    stream is the solo rollout's tokens one by one, whole and in order,
    requests that end while their neighbours go on among them."""
    dec, params = lm
    reqs = _mixed_requests(np.random.RandomState(11), 9, hi_p=14, lo_n=1,
                           hi_n=30)
    want = [_solo(dec, params, p, mn)[len(p):] for p, mn in reqs]
    got = [[] for _ in reqs]

    def consume(i, handle):
        for tok in handle.stream(timeout=300):
            got[i].append(tok)

    with serving.DecodeEngine(dec, params, slots=3, kv_block_size=8) as eng:
        threads = [threading.Thread(target=consume,
                                    args=(i, eng.submit(p, mn)))
                   for i, (p, mn) in enumerate(reqs)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
            assert not t.is_alive()
        counts = _counts(eng)
    for i, (g, w) in enumerate(zip(got, want)):
        assert g == w, (i, g, w)
    # the steps did run ahead, and by length alone nothing is dropped
    assert counts["steps_dispatched_ahead"] > 0.5 * counts["decode_steps"]
    assert counts["tokens_dropped_in_flight"] == 0
    assert counts["decode_tokens"] == sum(mn - 1 for _, mn in reqs)


def test_eos_with_a_step_in_flight_ends_there_and_frees_the_slot(lm):
    """A request that ends on ``eos_token`` has a row in the step
    dispatched before its EOS was read: it ends AT the EOS, the row is
    dropped and counted, and the request admitted into the freed slot
    on the next turn (one slot: the same one) gets its own tokens."""
    dec, params = lm
    rng = np.random.RandomState(2)
    first = rng.randint(0, V, size=5).tolist()
    base = _solo(dec, params, first, 12)
    eos = base[len(first) + 2]  # the second token a decode step emits
    reqs = [(first, 12)] + _mixed_requests(rng, 3, lo_n=4)
    want = []
    for p, mn in reqs:
        gen = _solo(dec, params, p, mn, eos_token=eos)[len(p):]
        if eos in gen:
            gen = gen[:gen.index(eos) + 1]
        want.append(p + gen)
    with serving.DecodeEngine(dec, params, slots=1, eos_token=eos,
                              kv_block_size=8) as eng:
        handles = [eng.submit(p, mn) for p, mn in reqs]  # queued behind
        got = [h.result(300) for h in handles]
        counts = _counts(eng)
    assert got == want
    assert got[0][-1] == eos and len(got[0]) < len(first) + 12
    # a request that ended early on a STEP's token had one row in
    # flight behind it (an EOS from the prefill itself has none)
    early = sum(len(p) + 1 < len(g) < len(p) + mn
                for g, (p, mn) in zip(got, reqs))
    assert early >= 1
    assert counts["tokens_dropped_in_flight"] == early
    assert counts["requests_completed"] == len(reqs)


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_eviction_with_a_step_in_flight_drops_its_row(lm, how):
    """A request cancelled, or past its deadline, while a step holds a
    row of it: the row's token is nobody's (counted), the victim gets
    its error, and the neighbour's stream is its solo rollout."""
    dec, params = lm
    probe_prompt, probe_new = [3, 1, 4, 1], 14
    want = _solo(dec, params, probe_prompt, probe_new)
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8) as eng:
        # programs compiled before any clock matters, and no shedding
        # on the evidence of a compile
        eng.submit([1, 2], 3).result(300)
        eng._step_ewma = eng._prefill_ewma = None
        # the pair's first step boundary is held open: the step
        # dispatched when it ends carries the victim's row, and the
        # eviction (it comes before the dispatch in a turn) meets it a
        # turn later; a deadline runs out half way through the stall
        stall, deadline = (2.0, 1.0) if how == "deadline" else (1.0, None)
        chaos.arm("stall_decode_for={}".format(stall))
        with eng._cv:  # both queued before the scheduler plans a turn
            victim = eng.submit([2, 7, 1], 40, deadline_s=deadline)
            probe = eng.submit(probe_prompt, probe_new)
        assert chaos.poll_until(
            lambda: _counts(eng).get("prefills", 0) >= 3, timeout=60)
        if how == "cancel":
            victim.cancel()
        assert probe.result(120) == want
        with pytest.raises(serving.DeadlineExceeded if how == "deadline"
                           else serving.Cancelled):
            victim.result(10)
        counts = _counts(eng)
    assert counts["deadline_exceeded" if how == "deadline"
                  else "cancelled"] == 1
    assert counts["tokens_dropped_in_flight"] == 1
    assert len(victim.generated) == 1  # the prefill's; the step's dropped


@pytest.mark.parametrize("how", ["drain", "stop"])
def test_drain_and_stop_with_a_step_in_flight_strand_no_client(lm, how):
    """``drain()`` with steps in flight finishes every admitted request
    whole. ``stop()`` first lands the step in flight (its tokens are
    delivered, a last token would complete its request) and then fails
    what is outstanding at once: no client waits out a timeout."""
    dec, params = lm
    reqs = _mixed_requests(np.random.RandomState(13), 5, lo_n=8, hi_n=30)
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    eng = serving.DecodeEngine(dec, params, slots=2, kv_block_size=8)
    try:
        if how == "stop":
            # the first step boundary held open: stop() arrives before
            # the first step, which is then dispatched and found in
            # flight by the loop's stopping check
            chaos.arm("stall_decode_for=1.0")
        with eng._cv:  # all queued before the scheduler plans a turn
            handles = [eng.submit(p, mn) for p, mn in reqs]
        if how == "drain":
            assert eng.drain(timeout=300) is True
            assert [h.result(1) for h in handles] == want
            return
        assert chaos.poll_until(
            lambda: _counts(eng).get("prefills", 0) >= 2, timeout=60)
        eng.stop()
        t0 = time.monotonic()
        for h in handles:
            with pytest.raises(RuntimeError, match="stopped"):
                h.result(5)
        assert time.monotonic() - t0 < 5
        # the two in their slots hold the prefill's token and the token
        # of the step that was in flight; the queued ones nothing
        assert [len(h.generated) for h in handles] == [2, 2, 0, 0, 0]
        for h, w in zip(handles[:2], want):
            assert h.prompt + h.generated == w[:len(h.prompt) + 2]
        assert _counts(eng)["decode_steps"] == 1
    finally:
        eng.stop()


def test_sampled_request_alone_draws_as_a_serial_loop_does(lm):
    """temperature > 0, one request alone: the engine splits its key
    once per prefill and once per dispatched step, in that order, and
    feeds the device's own draw back without reading it first; the
    tokens are those of a loop that reads every draw and hands it back
    from the host, on the same keys."""
    dec, params = lm
    prompt, max_new, slots = [5, 3, 9, 1, 2], 12, 2
    kw = dict(temperature=0.9, top_k=8)
    with serving.DecodeEngine(dec, params, slots=slots,
                              rng=jax.random.PRNGKey(5), **kw,
                              kv_block_size=8) as eng:
        got = eng.submit(prompt, max_new).result(300)
        model, bps = eng._model, eng._blocks_per_slot
        assert _counts(eng)["steps_dispatched_ahead"] == max_new - 2

    prefill, step = generation.paged_step_fns(model, 0.9, 8, None)
    cache = generation.init_cache(model, slots, MAXLEN)
    key = jax.random.PRNGKey(5)
    toks = np.zeros(8, np.int32)
    toks[:len(prompt)] = prompt
    key, sub = jax.random.split(key)
    tables = np.zeros((slots, bps), np.int32)
    # the blocks a fresh pool hands out first: 1, 2, ... in order
    tables[0] = np.arange(1, bps + 1)
    cache, first = prefill(params, cache, jnp.asarray(tables[0]),
                           jnp.asarray(toks), jnp.int32(len(prompt)),
                           jnp.int32(0), sub, jnp.int32(0))
    want, idx = [int(first)], np.zeros(slots, np.int32)
    idx[0] = len(prompt)
    for _ in range(max_new - 1):
        key, sub = jax.random.split(key)
        given = np.array([want[-1]] + [-1] * (slots - 1), np.int32)
        cache, picked = step(
            params, cache, jnp.zeros(slots, jnp.int32),
            generation.pack_step_feed(given, idx, tables), sub)
        want.append(int(np.asarray(picked)[0]))
        idx[0] += 1
    assert got == prompt + want
