"""Serving-plane request lifecycle (PR 4): deadlines, cancellation,
graceful drain, and engine auto-restart.

PR 2's engine decoded every admitted request to ``max_new_tokens`` no
matter what the client did, and PR 3's watch could only mark a dead
scheduler unhealthy. These tests pin the lifecycle contracts that close
those gaps:

- an infeasible deadline SHEDS at admission (``Shed`` -> 503 +
  Retry-After) and a feasible one admits — the boundary is the engine's
  own measured-rate estimate, never a cold guess;
- a cancelled or deadline-expired request frees its slot at the NEXT
  decode-step boundary (asserted via the slot-occupancy gauge), with
  concurrent temperature=0 requests bitwise-unchanged;
- abandoning ``stream()`` cancels (the streaming slot leak);
- ``drain()`` loses zero admitted requests and /healthz answers the
  pinned ``draining`` schema while it runs;
- ``Supervisor.watch(..., restart=RestartEngine())`` rebuilds a dead
  engine and re-arms the server (chaos scheduler-kill e2e is the
  ``chaos``-marked leg at the bottom).
"""

import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import chaos, generation, serving, supervisor
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


def _occupancy(eng):
    return eng.counters.snapshot()["gauges"].get("slot_occupancy")


def _post(url, payload, timeout=60):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# -- cancellation ----------------------------------------------------------

def test_cancel_frees_slot_at_step_boundary(lm):
    """The acceptance pin: a cancelled request's slot frees within one
    decode-step boundary (slot-occupancy gauge -> 0) instead of
    decoding to max_new_tokens, and result() raises Cancelled."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        # hold the first step boundary open: with warm jit caches the
        # whole rollout can finish before a 50ms poll tick, and
        # cancelling a COMPLETED request is (correctly) a no-op — the
        # stall pins "cancel lands mid-flight" deterministically
        chaos.arm("stall_decode_for=1.0")
        victim = eng.submit([1, 2, 3], 40)
        assert chaos.poll_until(
            lambda: _counts(eng).get("prefills", 0) >= 1, timeout=60)
        assert victim.cancel()
        # eviction lands at the next boundary: occupancy drops to 0
        # long before the 40-token rollout could have finished
        assert chaos.poll_until(lambda: _occupancy(eng) == 0, timeout=30)
        with pytest.raises(serving.Cancelled):
            victim.result(10)
        counts = _counts(eng)
        assert counts.get("cancelled") == 1
        assert len(victim.generated) < 40
        # cancel after completion is a no-op and reports it
        done = eng.submit([1, 2], 2)
        done.result(60)
        assert done.cancel() is False


def test_cancel_leaves_concurrent_outputs_bitwise_unchanged(lm):
    """Evicting one slot must not perturb its neighbors: a probe
    sharing the engine with a cancelled victim emits exactly its solo
    temperature=0 rollout."""
    dec, params = lm
    probe_prompt, probe_new = [3, 1, 4, 1], 12
    want = _solo(dec, params, probe_prompt, probe_new)
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        # same stall discipline as above: the cancel must provably land
        # while the victim is mid-flight next to the probe
        chaos.arm("stall_decode_for=1.0")
        victim = eng.submit([2, 7, 1], 40)
        probe = eng.submit(probe_prompt, probe_new)
        assert chaos.poll_until(
            lambda: _counts(eng).get("prefills", 0) >= 2, timeout=60)
        victim.cancel()
        assert probe.result(120) == want
        with pytest.raises(serving.Cancelled):
            victim.result(10)


def test_stream_abandonment_cancels_the_request(lm):
    """The streaming slot leak: a consumer that closes (or GCs) the
    stream generator mid-sequence must cancel the request — the slot
    frees instead of decoding to max_new_tokens for nobody."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        # stall discipline (see test_cancel_frees_slot...): the close
        # must provably land while the sequence is still decoding
        chaos.arm("stall_decode_for=1.0")
        handle = eng.submit([1, 2, 3], 40)
        stream = handle.stream(timeout=60)
        got = [next(stream) for _ in range(3)]
        assert len(got) == 3
        stream.close()  # consumer walks away
        assert chaos.poll_until(lambda: _occupancy(eng) == 0, timeout=30)
        assert _counts(eng).get("cancelled") == 1
        # slot is genuinely reusable: the next request completes
        assert eng.submit([5, 6], 3).result(120) == \
            _solo(dec, params, [5, 6], 3)
    # a FULLY consumed stream must NOT count as a cancellation
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        handle = eng.submit([1, 2, 3], 4)
        assert [1, 2, 3] + list(handle.stream(timeout=60)) == \
            _solo(dec, params, [1, 2, 3], 4)
        assert _counts(eng).get("cancelled", 0) == 0


def test_queued_cancel_never_reaches_a_prefill(lm):
    """Cancelling a still-queued request drops it from the queue —
    its prefill never runs."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        blocker = eng.submit([1, 2], 30)
        assert chaos.poll_until(
            lambda: _counts(eng).get("prefills", 0) >= 1, timeout=60)
        queued = eng.submit([3, 4], 30)
        assert queued.cancel()
        with pytest.raises(serving.Cancelled):
            queued.result(60)
        blocker.result(120)
        assert _counts(eng).get("prefills") == 1


# -- deadlines -------------------------------------------------------------

def test_inflight_deadline_evicts_at_step_boundary(lm):
    """A COLD engine (no rate evidence) admits any deadline; one that
    expires mid-flight evicts at the next step boundary with
    DeadlineExceeded and the deadline_exceeded counter. The deadline is
    far below the 40-token rollout's cost (even warm, prefill alone
    outlives 1ms), so expiry-before-completion is deterministic."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        handle = eng.submit([2, 3], 40, deadline_s=0.001)
        with pytest.raises(serving.DeadlineExceeded):
            handle.result(120)
        assert chaos.poll_until(lambda: _occupancy(eng) == 0, timeout=30)
        counts = _counts(eng)
        assert counts.get("deadline_exceeded") == 1
        assert len(handle.generated) < 40
        # DeadlineExceeded IS a Cancelled (one except catches both)
        assert issubclass(serving.DeadlineExceeded, serving.Cancelled)


def test_deadline_shed_vs_admit_boundary(lm):
    """The admission boundary, driven through the engine's own
    estimator: with warmed rate EWMAs and a loaded queue, a deadline
    below the estimate sheds (Shed, retry_after, shed counter, nothing
    queued) and a deadline above it admits."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        # warm the EWMAs with real traffic so estimates are evidence
        eng.submit([1, 2, 3], 6).result(120)
        assert eng._step_ewma is not None
        blocker = eng.submit([1, 2], 40)
        queued = eng.submit([3, 4], 40)
        est = eng.estimate_admission(40)
        need = est["queue_wait_s"] + est["service_s"]
        assert need > 0
        depth_before = eng.counters.snapshot()["gauges"]["queue_depth"]
        with pytest.raises(serving.Shed) as err:
            eng.submit([5, 6], 40, deadline_s=need / 100.0)
        assert err.value.retry_after >= 1.0
        counts = _counts(eng)
        assert counts.get("shed") == 1
        # shed is refusal-at-the-door: nothing of it was queued
        assert eng.counters.snapshot()["gauges"]["queue_depth"] == \
            depth_before
        # a generous deadline admits (boundary's other side)
        admitted = eng.submit([5, 6], 4, deadline_s=need * 100.0)
        blocker.result(300)
        queued.result(300)
        assert admitted.result(300) == _solo(dec, params, [5, 6], 4)

    # cold engine never sheds: no evidence, no refusal
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        est = eng.estimate_admission(40)
        assert est == {"queue_wait_s": 0.0, "service_s": 0.0}


def test_deadline_validation(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit([1, 2], 4, deadline_s=0)
        with pytest.raises(ValueError, match="deadline_s"):
            eng.submit([1, 2], 4, deadline_s=-1.5)


# -- graceful drain --------------------------------------------------------

def test_drain_loses_zero_admitted_requests(lm):
    """The drain pin: every request admitted before drain() completes
    normally (correct tokens), new submissions refuse with the
    retriable Draining, and the engine ends stopped."""
    dec, params = lm
    reqs = [([1 + i, 2, 3], 6 + i) for i in range(5)]
    want = [_solo(dec, params, p, mn) for p, mn in reqs]
    eng = serving.DecodeEngine(dec, params, slots=2)
    handles = [eng.submit(p, mn) for p, mn in reqs]
    drained = eng.drain(timeout=300)
    assert drained is True
    for handle, expect in zip(handles, want):
        assert handle.result(1) == expect  # already complete
    # a drained-then-stopped engine refuses with the RETRIABLE
    # Draining (503 "go to another replica"), never a plain 'stopped'
    # 500 — the race a client loses at the drain boundary must still
    # point it at a retry
    with pytest.raises(serving.Draining):
        eng.submit([1], 1)
    assert eng.healthy()["draining"] is True
    assert eng.healthy()["alive"] is False
    # Draining is retriable (503 + Retry-After on the HTTP surface)
    assert issubclass(serving.Draining, serving.Retriable)


def test_server_drain_healthz_schema_and_refusal(lm):
    """/healthz flips to the pinned 'draining' schema while admitted
    work finishes, POST refuses 503 with Retry-After, and after the
    drain every admitted handle has its full result — zero loss through
    the server path too."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1)
    ms = serving.ModelServer(None, name="lm", port=0, engine=eng)
    host, port = ms.start()
    base = "http://%s:%d" % (host, port)
    try:
        # stall discipline (see test_cancel_frees_slot...): a warm
        # engine runs both rollouts in less than an HTTP round trip,
        # and the drain must provably still be under way when asked
        chaos.arm("stall_decode_for=1.0")
        handles = [eng.submit([1, 2, 3], 30), eng.submit([4, 5], 30)]
        t = threading.Thread(target=ms.drain, kwargs={"timeout": 300})
        t.start()
        try:
            # pinned draining schema, live over HTTP mid-drain
            assert chaos.poll_until(lambda: ms._draining, timeout=30)
            with pytest.raises(urllib.error.HTTPError) as err:
                urllib.request.urlopen(base + "/healthz", timeout=30)
            assert err.value.code == 503
            body = json.loads(err.value.read())
            assert body["status"] == "draining"
            assert "reason" in body
            assert "counts" in body and "queue_depth" in body \
                and "slot_occupancy" in body and "engine" in body
            # new work refuses with the LB-friendly retry hint
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(base + "/v1/models/lm:generate",
                      {"prompt": [1, 2], "max_new_tokens": 2})
            assert err.value.code == 503
            assert err.value.headers["Retry-After"] is not None
        finally:
            t.join(timeout=300)
        assert handles[0].result(1) == _solo(dec, params, [1, 2, 3], 30)
        assert handles[1].result(1) == _solo(dec, params, [4, 5], 30)
    finally:
        ms.stop()


def test_healthz_ok_schema_includes_lifecycle_counts(lm):
    """The healthy-path schema now carries the lifecycle counters an
    operator alerts on (shed / cancelled / deadline_exceeded /
    engine_restarts appear once nonzero) plus the draining flag."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        ms = serving.ModelServer(None, name="lm", engine=eng)
        handle = eng.submit([1, 2], 20)
        handle.cancel()
        assert chaos.poll_until(
            lambda: _counts(eng).get("cancelled") == 1, timeout=30)
        code, body = ms.healthz()
        assert code == 200 and body["status"] == "ok"
        assert body["engine"]["draining"] is False
        assert body["counts"]["cancelled"] == 1


# -- HTTP lifecycle surface ------------------------------------------------

def test_http_deadline_rides_the_body(lm):
    """deadline_s in the :generate body: a cold engine admits it and
    the mid-flight expiry surfaces as 504; malformed deadlines are
    400s."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1)
    with serving.ModelServer(None, name="lm", port=0, engine=eng) as ms:
        url = "http://%s:%d/v1/models/lm:generate" % (ms._host, ms._port)
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url, {"prompt": [1, 2, 3], "max_new_tokens": 40,
                        "deadline_s": 0.001})
        assert err.value.code == 504
        assert "deadline" in json.loads(err.value.read())["error"]
        for bad in ("nope", 0, -3):
            with pytest.raises(urllib.error.HTTPError) as err:
                _post(url, {"prompt": [1, 2], "max_new_tokens": 2,
                            "deadline_s": bad})
            assert err.value.code == 400, bad
        # a feasible request still completes normally
        code, out = _post(url, {"prompt": [1, 2, 3], "max_new_tokens": 4,
                                "deadline_s": 300})
        assert code == 200
        assert out["tokens"] == _solo(dec, params, [1, 2, 3], 4)


def test_http_client_disconnect_cancels(lm):
    """An HTTP client that hangs up mid-generate cancels its engine
    work: the slot frees at the next boundary instead of decoding for
    a closed socket."""
    import http.client

    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1)
    with serving.ModelServer(None, name="lm", port=0, engine=eng) as ms:
        # hold the first step boundary open: with warm jit caches the
        # whole 40-token rollout can finish inside one 50ms disconnect
        # poll, and a completed request (correctly) never cancels —
        # the stall makes "client hangs up MID-decode" deterministic
        chaos.arm("stall_decode_for=1.0")
        conn = http.client.HTTPConnection(ms._host, ms._port, timeout=30)
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_new_tokens": 40}).encode()
        conn.request("POST", "/v1/models/lm:generate", body,
                     {"Content-Type": "application/json"})
        # wait until the request is genuinely admitted, then vanish
        assert chaos.poll_until(
            lambda: _counts(eng).get("prefills", 0) >= 1, timeout=60)
        conn.close()
        assert chaos.poll_until(
            lambda: _counts(eng).get("cancelled", 0) == 1, timeout=60)
        assert chaos.poll_until(lambda: _occupancy(eng) == 0, timeout=30)
        # the server survived: fresh requests complete
        code, out = _post(
            "http://%s:%d/v1/models/lm:generate" % (ms._host, ms._port),
            {"prompt": [5, 6], "max_new_tokens": 3})
        assert code == 200
        assert out["tokens"] == _solo(dec, params, [5, 6], 3)


# -- engine auto-restart ---------------------------------------------------

def test_restart_engine_policy_decides_bounded_backoff():
    pol = supervisor.RestartEngine(max_restarts=2, backoff=1.0,
                                   backoff_factor=2.0, max_backoff=1.5)
    d0 = pol.decide(0)
    d1 = pol.decide(1)
    d2 = pol.decide(2)
    assert d0.action == supervisor.Decision.RESTART and d0.delay == 1.0
    assert d1.action == supervisor.Decision.RESTART and d1.delay == 1.5
    assert d2.action == supervisor.Decision.FAIL
    assert "gave up" in d2.reason


def test_supervisor_restarts_dead_engine_and_rearms_server(lm):
    """The recovery pin (thread-death flavor): poison the scheduler so
    it dies, watch with RestartEngine -> outstanding handles fail
    RETRIABLE, the engine is rebuilt from its ORIGINAL construction
    config, the server re-arms (healthz 200), engine_restarts
    increments, and fresh requests complete bitwise-correct."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=2)
    ms = serving.ModelServer(None, name="lm", engine=eng)
    sup = supervisor.Supervisor(
        config=supervisor.SupervisorConfig(poll_interval=0.05))
    try:
        sup.watch(eng, server=ms,
                  restart=supervisor.RestartEngine(max_restarts=2,
                                                   backoff=0.05))
        # poison the live attribute: the loop's next device call dies.
        # respawn() must rebuild from the ORIGINAL params, not this.
        eng.params = {"nope": jnp.zeros(())}
        handle = eng.submit([1, 2, 3], 8)
        with pytest.raises(serving.Retriable):
            handle.result(120)
        assert chaos.poll_until(
            lambda: ms.engine is not eng and ms._unhealthy is None,
            timeout=60)
        fresh = ms.engine
        assert fresh.counters is eng.counters  # counts continue
        assert _counts(fresh).get("engine_restarts") == 1
        assert fresh.submit([1, 2, 3], 4).result(120) == \
            _solo(dec, params, [1, 2, 3], 4)
        assert ms.healthz()[0] == 200
    finally:
        sup.stop()
        ms.stop()


def test_watch_does_not_resurrect_a_deliberate_stop(lm):
    """stop()/drain() are operator intent: the restart policy must not
    fight them. A stopped engine stays stopped (server marked
    unhealthy, no respawn)."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1)
    ms = serving.ModelServer(None, name="lm", engine=eng)
    sup = supervisor.Supervisor(
        config=supervisor.SupervisorConfig(poll_interval=0.05))
    try:
        sup.watch(eng, server=ms,
                  restart=supervisor.RestartEngine(backoff=0.01))
        eng.stop()
        assert chaos.poll_until(lambda: ms._unhealthy is not None,
                                timeout=30)
        time.sleep(0.3)  # a respawn would have landed by now
        assert ms.engine is eng
        assert _counts(eng).get("engine_restarts", 0) == 0
    finally:
        sup.stop()
        ms.stop()


def test_restart_exhaustion_marks_server_unhealthy(lm):
    """A permanently broken engine exhausts the policy and the server
    lands 503 for good — honest terminal state, not a restart loop."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1)
    ms = serving.ModelServer(None, name="lm", engine=eng)
    sup = supervisor.Supervisor(
        config=supervisor.SupervisorConfig(poll_interval=0.05))
    try:
        # poison the STORED construction params too: a respawned engine
        # builds fine but dies on its first request — the repeatedly-
        # failing-replica shape
        bad = {"nope": jnp.zeros(())}
        eng.params = bad
        eng._spawn_args["params"] = bad
        sup.watch(eng, server=ms,
                  restart=supervisor.RestartEngine(max_restarts=1,
                                                   backoff=0.01))
        with pytest.raises(serving.Retriable):
            eng.submit([1, 2, 3], 8).result(120)
        # the one allowed restart lands and re-arms the server...
        assert chaos.poll_until(
            lambda: ms.engine is not eng and ms._unhealthy is None,
            timeout=60)
        # ...then the poisoned respawn dies on its first request and the
        # policy is exhausted: terminal 503, no restart loop
        with pytest.raises(serving.Retriable):
            ms.engine.submit([1, 2, 3], 8).result(120)
        assert chaos.poll_until(
            lambda: ms._unhealthy is not None
            and "gave up" in ms._unhealthy, timeout=60)
        assert ms.healthz()[0] == 503
        restarted = sup.events.events("engine_restarted")
        assert len(restarted) == 1  # the one allowed attempt
    finally:
        sup.stop()
        ms.stop()


# -- chaos e2e (serial `make chaos` leg; also `slow`, so tier-1 skips) -----

@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_scheduler_kill_autorestart_e2e(lm):
    """The acceptance chaos pin, end to end over HTTP: SIGKILL-equivalent
    the decode scheduler mid-workload (chaos kill_scheduler_at_step) ->
    outstanding handles fail retriable -> the supervisor auto-restarts
    the engine -> engine_restarts increments -> fresh requests complete
    with temperature=0 outputs bitwise-identical to solo generate."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=2)
    ms = serving.ModelServer(None, name="lm", port=0, engine=eng)
    ms.start()
    sup = supervisor.Supervisor(
        config=supervisor.SupervisorConfig(poll_interval=0.05))
    url = "http://%s:%d/v1/models/lm:generate" % (ms._host, ms._port)
    try:
        sup.watch(eng, server=ms,
                  restart=supervisor.RestartEngine(max_restarts=2,
                                                   backoff=0.05))
        chaos.arm("kill_scheduler_at_step=3")
        handles = [eng.submit([1 + i, 2, 3], 20) for i in range(4)]
        failures = []
        for handle in handles:
            with pytest.raises(serving.Retriable):
                handle.result(120)
            failures.append(True)
        assert len(failures) == 4  # every outstanding handle failed fast
        chaos.disarm()  # the fresh engine must not re-fire the kill
        assert chaos.poll_until(
            lambda: ms.engine is not eng and ms._unhealthy is None,
            timeout=60)
        assert _counts(ms.engine).get("engine_restarts") == 1
        # fresh traffic over the SAME HTTP surface completes correctly
        code, out = _post(url, {"prompt": [1, 2, 3], "max_new_tokens": 5})
        assert code == 200
        assert out["tokens"] == _solo(dec, params, [1, 2, 3], 5)
        # healthz recovered and reports the restart
        code, body = ms.healthz()
        assert code == 200
        assert body["counts"]["engine_restarts"] == 1
    finally:
        sup.stop()
        ms.stop()


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_stall_decode_expires_inflight_deadlines(lm):
    """stall_decode_for: a stalled-but-alive scheduler (the slow-replica
    signature) expires in-flight deadlines; the engine stays healthy and
    undeadlined neighbors still complete bitwise-correct."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        # let the engine warm so the stall hits mid-decode, not prefill
        eng.submit([9, 8], 2).result(120)
        chaos.arm("stall_decode_for=0.4")
        # reset the admission evidence: on a cold run the warm-up's one
        # decode sample IS the compile (~seconds), and the estimator
        # would shed this request at the door — admission shedding has
        # its own test; this one pins the IN-FLIGHT expiry path
        eng._step_ewma = eng._prefill_ewma = None
        deadlined = eng.submit([1, 2, 3], 30, deadline_s=0.2)
        survivor = eng.submit([4, 5], 6)
        with pytest.raises(serving.DeadlineExceeded):
            deadlined.result(120)
        assert survivor.result(120) == _solo(dec, params, [4, 5], 6)
        assert eng.healthy()["alive"] is True
        assert _counts(eng).get("deadline_exceeded") == 1


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_client_disconnect_at_token(lm):
    """disconnect_client_at_token: the injected mid-stream disconnect
    cancels the request at the next step boundary; slot-occupancy
    returns to 0 and a concurrent request is bitwise-unaffected."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        chaos.arm("disconnect_client_at_token=3")
        victim = eng.submit([1, 2, 3], 40)
        probe = eng.submit([4, 5], 8)
        with pytest.raises(serving.Cancelled):
            victim.result(120)
        assert 3 <= len(victim.generated) < 40
        assert probe.result(120) == _solo(dec, params, [4, 5], 8)
        assert chaos.poll_until(lambda: _occupancy(eng) == 0, timeout=30)
        assert _counts(eng).get("cancelled") == 1
