"""Serving fleet (PR 6): replica registry, metrics-driven router,
failover, half-open health, rolling drain.

Three layers, matching the module's design:

- PURE policy — ``fleet.route_order`` (least-loaded selection from
  gauge snapshots, stale-lease exclusion, deterministic tie-breaking)
  and the ``ReplicaHealth`` half-open state machine, table-driven with
  injected time, no sockets; plus the shared ``serving.retry_call``
  client retry policy (bounded backoff + full jitter, Retry-After
  floor, Retriable-only).
- SCHEMA pins — the stable ``replica_id`` identity on /healthz and
  /metrics (survives ``respawn()``), the reservation server's
  serving-role lease view (``serving_snapshot`` + the ``/stats``
  ``serving`` key), and the retriable-503 ``kind`` field the router
  classifies on.
- E2E — a 2-replica fleet over real HTTP (tier-1: routed requests are
  bitwise solo-identical, metrics expose per-replica labels), the
  3-replica rolling-drain weight-upgrade cycle under live traffic
  (slow), and the chaos leg: kill one replica's scheduler mid-stream,
  zero client-visible failures, supervised restart, MTTR recorded
  (chaos marker — collected by ``make chaos``).
"""

import json
import socket
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import (chaos, cluster, fleet, generation,
                                   paging, reservation, serving)
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


def _counts(eng):
    return eng.counters.snapshot()["counts"]


def _solo(dec, params, prompt, max_new):
    out = generation.generate_jit(
        dec, params, jnp.asarray([prompt], jnp.int32), max_new)
    return np.asarray(out)[0].tolist()


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# -- serving.retry_call (shared client retry policy) -----------------------

def test_retry_call_retries_only_retriable():
    calls = []

    def fn():
        calls.append(1)
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        serving.retry_call(fn, attempts=5, sleep=lambda s: None)
    assert len(calls) == 1, "non-Retriable must propagate on first raise"


def test_retry_call_bounded_attempts_and_backoff_growth():
    delays = []
    calls = []

    def fn():
        calls.append(1)
        raise serving.Retriable("transient")

    with pytest.raises(serving.Retriable):
        serving.retry_call(fn, attempts=4, base_delay=0.1, max_delay=10.0,
                           sleep=delays.append, rng=lambda: 1.0)
    assert len(calls) == 4
    # rng=1.0 makes jitter deterministic: retry_after (Retriable's
    # default 1.0) floors every delay, plus the full anti-stampede
    # jitter fraction of the floor
    floor = 1.0 * (1.0 + serving.RETRY_AFTER_JITTER)
    assert delays == [pytest.approx(floor)] * 3


def test_retry_call_retry_after_jitter_spreads_synchronized_clients():
    """Two clients told the same Retry-After by one recovering replica
    must NOT re-arrive at the same instant: the floor gains up to
    RETRY_AFTER_JITTER of itself, drawn per client."""

    def fn():
        raise serving.Shed("busy", retry_after=2.0)

    def delays_for(draw):
        delays = []
        with pytest.raises(serving.Shed):
            serving.retry_call(fn, attempts=2, base_delay=0.01,
                               max_delay=10.0, sleep=delays.append,
                               rng=lambda: draw)
        return delays

    lo, hi = delays_for(0.0), delays_for(1.0)
    assert lo == [pytest.approx(2.0)], "zero draw keeps the exact floor"
    assert hi == [pytest.approx(2.0 * (1 + serving.RETRY_AFTER_JITTER))]
    assert hi[0] > lo[0], "different draws must spread the stampede"


def test_retry_call_full_jitter_bounded_by_envelope():
    delays = []

    def fn():
        e = serving.Retriable("transient")
        e.retry_after = None  # no server hint: pure jittered backoff
        raise e

    with pytest.raises(serving.Retriable):
        serving.retry_call(fn, attempts=4, base_delay=0.2, max_delay=10.0,
                           sleep=delays.append, rng=lambda: 0.5)
    assert delays == [pytest.approx(0.1), pytest.approx(0.2),
                      pytest.approx(0.4)]


def test_retry_call_honors_retry_after_floor_capped():
    delays = []

    def fn():
        raise serving.Shed("busy", retry_after=3.0)

    with pytest.raises(serving.Shed):
        serving.retry_call(fn, attempts=3, base_delay=0.01, max_delay=2.0,
                           sleep=delays.append, rng=lambda: 0.0)
    # Retry-After floors the jittered delay but caps at max_delay
    assert delays == [2.0, 2.0]


def test_retry_call_zero_retry_after_fails_over_immediately():
    delays = []
    calls = []

    def fn():
        calls.append(1)
        if len(calls) < 3:
            raise fleet.ReplicaUnavailable("next replica",
                                           retry_after=0.0)
        return "ok"

    # rng pinned to its MAX: the no-sleep contract must hold because
    # retry_after==0 skips the sleep entirely, not because the jitter
    # happened to draw zero
    assert serving.retry_call(fn, attempts=4, sleep=delays.append,
                              rng=lambda: 1.0) == "ok"
    assert delays == [], "failover with retry_after=0 must not sleep"


def test_http_retriable_mapping():
    e = serving.http_retriable(503, "7")
    assert isinstance(e, serving.Retriable) and e.retry_after == 7.0
    assert serving.http_retriable(429).retry_after == 0.5
    assert serving.http_retriable(503, "garbage").retry_after == 1.0
    for status in (200, 400, 404, 499, 500, 504):
        assert serving.http_retriable(status) is None


# -- route_order (pure dispatch policy) ------------------------------------

def _view(rid, age=0.1, alive=True, draining=False, queue_depth=0,
          slot_occupancy=0, queue_wait_ewma_s=0.0, inflight=0,
          state=fleet.ReplicaHealth.UP):
    return {"replica_id": rid, "age": age, "alive": alive,
            "draining": draining, "queue_depth": queue_depth,
            "slot_occupancy": slot_occupancy,
            "queue_wait_ewma_s": queue_wait_ewma_s,
            "inflight": inflight, "state": state}


def test_route_order_least_loaded():
    views = [_view("a", queue_depth=3),
             _view("b", slot_occupancy=1),
             _view("c", queue_depth=1, slot_occupancy=1)]
    assert fleet.route_order(views) == ["b", "c", "a"]


def test_route_order_router_inflight_counts_as_load():
    # the router's own open requests cover the beat-staleness window:
    # a burst dispatched 10ms ago is load even if no gauge shows it yet
    views = [_view("a", inflight=2), _view("b")]
    assert fleet.route_order(views) == ["b", "a"]


def test_route_order_queue_wait_breaks_equal_backlog():
    views = [_view("a", queue_depth=1, queue_wait_ewma_s=0.5),
             _view("b", queue_depth=1, queue_wait_ewma_s=0.1)]
    assert fleet.route_order(views) == ["b", "a"]


def test_route_order_deterministic_tie_break_by_id():
    views = [_view("r2"), _view("r0"), _view("r1")]
    assert fleet.route_order(views) == ["r0", "r1", "r2"]
    assert fleet.route_order(list(reversed(views))) == ["r0", "r1", "r2"]


def test_route_order_excludes_stale_dead_draining_down():
    views = [
        _view("stale", age=5.0),          # lease older than stale_after
        _view("no-lease", age=None),      # never beat
        _view("dead", alive=False),       # engine scheduler dead
        _view("retiring", draining=True),  # excludes itself via beat
        _view("down", state=fleet.ReplicaHealth.DOWN),
        _view("ok", queue_depth=9),
    ]
    assert fleet.route_order(views, stale_after=2.0) == ["ok"]


def test_route_order_probe_ranks_after_every_healthy():
    views = [_view("probe", state=fleet.ReplicaHealth.PROBE),
             _view("busy", queue_depth=50)]
    # even a heavily loaded healthy replica outranks an unverified one
    assert fleet.route_order(views) == ["busy", "probe"]


def test_route_order_empty_when_nothing_routable():
    assert fleet.route_order([_view("a", age=99.0)]) == []
    assert fleet.route_order([]) == []


# -- prefix/session affinity (PR 16; pure policy) --------------------------


def _digest_view(rid, chains=(), block_size=16, slots=0, **kw):
    """A replica view carrying a beat digest: ``chains`` is a list of
    (tokens, depth_blocks) pairs hashed the way the pool publishes."""
    v = _view(rid, **kw)
    v["slots"] = slots
    v["prefix_digest_block_size"] = block_size
    v["prefix_digest"] = [
        [paging.chain_digest(tokens, depth * block_size), depth]
        for tokens, depth in chains]
    v["digest_truncated"] = False
    return v


def test_digest_match_deepest_resident_chain():
    prompt = list(range(50))
    view = _digest_view("a", chains=[(prompt, 1), (prompt, 2)])
    # the deepest RESIDENT chain wins, capped by the prompt's own
    # shareable depth ((len-1)//block — a tail token never shares)
    assert fleet.digest_match(view, prompt) == 2
    assert fleet.digest_match(view, prompt[:17]) == 1
    assert fleet.digest_match(view, prompt[:16]) == 0  # all tail
    assert fleet.digest_match(view, [9] * 50) == 0     # different chain
    # a view with no digest and malformed entries are cold
    assert fleet.digest_match(_view("b"), prompt) == 0
    broken = _digest_view("c", chains=[(prompt, 1)])
    broken["prefix_digest"] = [["x"], None, ["h", "deep"]]
    assert fleet.digest_match(broken, prompt) == 0


def test_digest_match_respects_each_views_block_size():
    """Depth is counted in each view's OWN block size: the same
    resident token span reads as depth 2 on an 8-block replica and
    depth 1 on a 16-block one, and a prompt too short to fill a
    view's chain misses it entirely."""
    prompt = list(range(33))
    v8 = _digest_view("a", chains=[(prompt, 2)], block_size=8)
    v16 = _digest_view("b", chains=[(prompt, 2)], block_size=16)
    assert fleet.digest_match(v8, prompt) == 2
    assert fleet.digest_match(v16, prompt) == 2
    # 17 tokens share 2 full 8-blocks -> the SAME 16-token span the
    # 8-block replica registered; the 16-block replica's resident
    # chain is 32 tokens deep, which this prompt cannot reach
    assert fleet.digest_match(v8, prompt[:17]) == 2
    assert fleet.digest_match(v16, prompt[:17]) == 0


def test_affinity_order_promotes_hint_then_deepest_digest():
    prompt = list(range(40))
    views = [_view("a"),
             _digest_view("b", chains=[(prompt, 2)], queue_depth=1),
             _digest_view("c", chains=[(prompt, 1)], queue_depth=2)]
    matches = {rid: fleet.digest_match(v, prompt)
               for rid, v in (("b", views[1]), ("c", views[2]))}
    # digest only: deeper resident chain leads, cold least-loaded next
    assert fleet.affinity_order(views, matches) == ["b", "c", "a"]
    # a session hint outranks even a deeper digest match elsewhere
    assert fleet.affinity_order(views, matches, session_hint="c") == \
        ["c", "b", "a"]
    # no affinity inputs -> exactly route_order
    assert fleet.affinity_order(views) == fleet.route_order(views)


def test_affinity_load_guard_demotes_overloaded_warm_replica():
    prompt = list(range(40))
    warm = _digest_view("warm", chains=[(prompt, 2)], queue_depth=3,
                        slot_occupancy=2)  # backlog 5 over coldest 0
    views = [_view("cold"), warm]
    matches = {"warm": 2}
    order, info = fleet.affinity_plan(views, matches)
    assert order == ["cold", "warm"]
    assert info["guarded"] == ["warm"] and info["promoted"] == []
    # inside the guard the warm replica still wins
    warm2 = _digest_view("warm", chains=[(prompt, 2)], queue_depth=2,
                         slot_occupancy=2)
    order, info = fleet.affinity_plan([_view("cold"), warm2], matches)
    assert order == ["warm", "cold"] and info["promoted"] == ["warm"]
    # slot saturation with a standing queue guards regardless of the
    # backlog delta (queue growth on a full replica is the hotspot)
    sat = _digest_view("warm", chains=[(prompt, 2)], slots=2,
                       slot_occupancy=2, queue_depth=1)
    order, info = fleet.affinity_plan(
        [_view("cold", queue_depth=2), sat], matches)
    assert info["guarded"] == ["warm"]
    assert order == fleet.route_order([_view("cold", queue_depth=2),
                                       sat])


def test_affinity_never_promotes_probe_and_fails_over_cold():
    prompt = list(range(40))
    probe = _digest_view("probe", chains=[(prompt, 3)],
                         state=fleet.ReplicaHealth.PROBE)
    views = [_view("cold", queue_depth=5), probe]
    # an unverified half-open replica keeps its last-resort rank,
    # however warm its digest claims it is
    assert fleet.affinity_order(views, {"probe": 3},
                                session_hint="probe") == \
        ["cold", "probe"]
    # a draining/dead/stale warm replica is not in the base order at
    # all: the request proceeds COLD and the plan says why
    gone = _digest_view("gone", chains=[(prompt, 3)], draining=True)
    order, info = fleet.affinity_plan([_view("cold"), gone],
                                      {"gone": 3}, session_hint="gone")
    assert order == ["cold"]
    assert info["hint_routable"] is False


def test_affinity_map_ttl_capacity_and_purge():
    clock = [100.0]
    m = fleet.AffinityMap(capacity=2, ttl_s=5.0, now=lambda: clock[0])
    m.note("s1", "replica-0")
    assert m.lookup("s1") == "replica-0"
    # TTL: an expired entry is evidence-free and self-evicts on read
    clock[0] += 5.1
    assert m.lookup("s1") is None and len(m) == 0
    # capacity is LRU over note recency
    m.note("a", "r0")
    m.note("b", "r1")
    m.note("a", "r0")  # renew: b is now the least recently noted
    m.note("c", "r2")
    assert m.lookup("b") is None
    assert m.lookup("a") == "r0" and m.lookup("c") == "r2"
    # evict reports whether an entry existed (once-per-incident guard)
    assert m.evict("a") is True
    assert m.evict("a") is False
    # purge_replica drops every session pinned to a retiring replica
    m2 = fleet.AffinityMap(capacity=8, ttl_s=5.0, now=lambda: clock[0])
    m2.note("x", "r9")
    m2.note("y", "r9")
    m2.note("z", "r2")
    assert m2.purge_replica("r9") == 2
    assert m2.lookup("x") is None and m2.lookup("z") == "r2"


# -- ReplicaHealth (half-open state machine, injected time) ----------------

def test_health_threshold_then_down_then_probe_then_recover():
    h = fleet.ReplicaHealth(fail_threshold=2, cooldown=10.0)
    assert h.state("r", now=0.0) == h.UP
    h.note_failure("r", now=0.0)
    assert h.state("r", now=0.0) == h.UP, "below threshold stays up"
    h.note_failure("r", now=1.0)
    assert h.state("r", now=1.0) == h.DOWN
    assert h.state("r", now=10.9) == h.DOWN
    # cooldown expired -> half-open
    assert h.state("r", now=11.1) == h.PROBE
    h.note_success("r")
    assert h.state("r", now=11.2) == h.UP


def test_health_probe_failure_redowns_with_escalated_cooldown():
    h = fleet.ReplicaHealth(fail_threshold=1, cooldown=10.0,
                            cooldown_factor=2.0)
    h.note_failure("r", now=0.0)           # down #1: until 10
    assert h.state("r", now=10.5) == h.PROBE
    h.note_failure("r", now=10.5)          # probe failed: down #2 = 20s
    assert h.state("r", now=30.0) == h.DOWN
    assert h.state("r", now=30.6) == h.PROBE


def test_health_success_resets_escalation():
    h = fleet.ReplicaHealth(fail_threshold=1, cooldown=10.0,
                            cooldown_factor=2.0, max_cooldown=100.0)
    h.note_failure("r", now=0.0)
    h.note_failure("r", now=10.5)          # escalated to 20s
    h.note_success("r")                    # verified healthy: full reset
    h.note_failure("r", now=50.0)          # next incident: base cooldown
    assert h.state("r", now=60.5) == h.PROBE


def test_health_cooldown_capped():
    h = fleet.ReplicaHealth(fail_threshold=1, cooldown=10.0,
                            cooldown_factor=10.0, max_cooldown=15.0)
    h.note_failure("r", now=0.0)
    h.note_failure("r", now=10.5)          # would be 100s; capped at 15
    assert h.state("r", now=10.5 + 15.1) == h.PROBE


def test_health_holds_are_owner_scoped():
    """Rolling drain and the supervisor hold a replica independently:
    one releasing must not readmit on the other's behalf (the drain's
    hold stands until ITS wire-verified /healthz)."""
    h = fleet.ReplicaHealth()
    h.quiesce("r", "draining", owner="rolling-drain")
    h.quiesce("r", "engine dead", owner="supervisor")
    h.readmit("r", owner="supervisor")
    assert h.state("r", now=0.0) == h.DOWN
    h.readmit("r", owner="rolling-drain")
    assert h.state("r", now=0.0) == h.UP
    # owner=None is the operator's force-clear
    h.quiesce("r", owner="a")
    h.quiesce("r", owner="b")
    h.readmit("r", owner=None)
    assert h.state("r", now=0.0) == h.UP


def test_health_quiesce_is_administrative_no_probe_path():
    h = fleet.ReplicaHealth(cooldown=0.001)
    h.quiesce("r", "rolling drain")
    assert h.state("r", now=0.0) == h.DOWN
    h.note_success("r")  # traffic outcomes must not override the hold
    assert h.state("r", now=1e9) == h.DOWN, "quiesce never half-opens"
    h.readmit("r")
    assert h.state("r", now=1e9) == h.UP


# -- racecheck regression pins (PR 14): the fleet's shared-state
# fixes, each pinned barrier-style like PR 10's two-thread
# compile-claim test ------------------------------------------------------

def test_new_rid_concurrent_unique():
    """``_next_idx += 1`` was an unlocked read-modify-write shared by
    the autoscaler thread and operator threads: two concurrent
    spawn_replica calls could mint the SAME replica id (two engines,
    one identity, one lease — split-brain by construction). Under the
    fleet lock every id is unique."""
    f = fleet.ServingFleet(None, None, replicas=1)
    n_threads, per_thread = 4, 400
    barrier = threading.Barrier(n_threads)
    out = [None] * n_threads

    def mint(i):
        barrier.wait()
        out[i] = [f._new_rid() for _ in range(per_thread)]

    threads = [threading.Thread(target=mint, args=(i,), daemon=True,
                                name="tfos-test-rid-%d" % i)
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    rids = [r for chunk in out for r in chunk]
    assert len(set(rids)) == n_threads * per_thread, \
        "duplicate replica ids minted under concurrency"


def test_replica_lookup_survives_concurrent_churn():
    """``_replica`` used to iterate ``self.replicas`` while spawn /
    retire mutated it from other threads — removing an earlier element
    shifts the list under the iterator and a PRESENT member can be
    skipped (lookup returns None for a replica the fleet tracks).
    Under the lock the anchor is always found."""
    f = fleet.ServingFleet(None, None, replicas=1)

    class _R(object):
        remote = False

        def __init__(self, rid):
            self.replica_id = rid

    churners = [_R("churn-%d" % i) for i in range(8)]
    for r in churners:
        f._track(r)
    anchor = _R("anchor")
    f._track(anchor)
    stop = threading.Event()
    barrier = threading.Barrier(2)
    misses = []

    def churn():
        barrier.wait()
        while not stop.is_set():
            for r in churners:
                f._untrack(r)
            for r in churners:
                f._track(r)

    def lookup():
        barrier.wait()
        for _ in range(3000):
            if f._replica("anchor") is None:
                misses.append(1)
        stop.set()

    ts = [threading.Thread(target=churn, daemon=True,
                           name="tfos-test-churn"),
          threading.Thread(target=lookup, daemon=True,
                           name="tfos-test-lookup")]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    stop.set()
    assert not misses, \
        "tracked anchor replica vanished from lookup {} time(s) " \
        "during churn".format(len(misses))


class _FenceServer(object):
    """Minimal ModelServer surface for a bare Replica agent."""

    replica_id = "replica-f"
    engine = None
    name = "model"

    def __init__(self):
        self.fence_reason = None

    def start(self):
        return ("127.0.0.1", 0)

    def fence(self, reason):
        self.fence_reason = reason

    def unfence(self):
        self.fence_reason = None

    def stop(self):
        pass


class _FenceOnceClient(object):
    """reservation.Client stand-in whose FIRST beat parks on a barrier
    (so the test can line a re_register up against the in-flight
    exchange) and then comes back FENCED; every later beat succeeds."""

    barrier = None
    fenced_once = False

    def __init__(self, addr, **kw):  # accepts connect_timeout etc.
        pass

    def lease(self, rid):
        return 1

    def beat(self, rid, payload, epoch=None):
        cls = _FenceOnceClient
        if not cls.fenced_once:
            cls.fenced_once = True
            cls.barrier.wait(timeout=10)
            time.sleep(0.2)  # hold the exchange open past re_register
            raise reservation.Fenced("stale epoch", epoch=2)

    def close(self):
        pass


def test_re_register_never_loses_to_inflight_fence(monkeypatch):
    """Racecheck regression pin: Replica.epoch/fenced were mutated by
    the beat thread AND re_register() with no lock. A re_register
    landing while a FENCED beat was in flight had its reset
    overwritten by the beat's latch — the replica ended permanently
    fenced with a dead beat loop, while re_register reported success.
    Serialized, the latch lands first and re_register then clears it
    and restarts the loop."""
    monkeypatch.setattr(fleet.reservation, "Client", _FenceOnceClient)
    _FenceOnceClient.barrier = threading.Barrier(2)
    _FenceOnceClient.fenced_once = False
    server = _FenceServer()
    replica = fleet.Replica(server, ("127.0.0.1", 1),
                            beat_interval=0.01)
    replica.start()
    try:
        # the first beat is now parked inside its exchange
        _FenceOnceClient.barrier.wait(timeout=10)
        replica.re_register()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and (
                replica.fenced or not replica._thread.is_alive()):
            time.sleep(0.02)
        assert replica.fenced is False, \
            "re_register's reset was overwritten by the in-flight " \
            "fence latch"
        assert server.fence_reason is None, \
            "server left fenced after a successful re_register"
        assert replica._thread.is_alive(), \
            "beat loop dead after re_register"
    finally:
        replica.stop()


def test_concurrent_executor_spawns_pick_distinct_executors(monkeypatch):
    """Review-fix pin: the executor pick (free_executor) and the
    dispatch/track are ONE atomic placement decision. Unserialized,
    two concurrent spawns both read the hosting ledger before either
    tracks its RemoteReplica and both pick the SAME free executor —
    the second bootstrap can never run there. Under the fleet lock
    the second pick sees the first's track and takes the other
    executor."""
    class _FakeResult(object):
        def first_error(self):
            return None

    class _FakeRDD(object):
        def foreachPartitionAsync(self, fn, **kw):
            return _FakeResult()

    class _FakeSC(object):
        def executors_alive(self):
            return ["e0", "e1"]

        def parallelize(self, seq, n):
            return _FakeRDD()

    f = fleet.ServingFleet(None, None, replicas=1,
                           placement="executors", sc=_FakeSC())
    f._started = True
    f._resv_addr = ("127.0.0.1", 0)
    monkeypatch.setattr(
        f, "_await_lease",
        lambda rid, timeout, min_epoch=None: {"addr": ["127.0.0.1", 1]})
    monkeypatch.setattr(fleet.FleetRouter, "_await_healthz",
                        staticmethod(lambda addr, timeout: True))
    barrier = threading.Barrier(2)
    got = [None, None]
    errors = []

    def spawn(i):
        barrier.wait()
        try:
            got[i] = f.spawn_replica(timeout=5)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    ts = [threading.Thread(target=spawn, args=(i,), daemon=True,
                           name="tfos-test-spawn-%d" % i)
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=30)
    assert not errors, errors
    eids = {r.executor_id for r in got if r is not None}
    assert eids == {"e0", "e1"}, \
        "concurrent spawns double-placed: {}".format(eids)


def _fake_executor_fleet(monkeypatch, executors):
    class _FakeResult(object):
        def first_error(self):
            return None

    class _FakeRDD(object):
        def foreachPartitionAsync(self, fn, **kw):
            return _FakeResult()

    class _FakeSC(object):
        def executors_alive(self):
            return list(executors)

        def parallelize(self, seq, n):
            return _FakeRDD()

    f = fleet.ServingFleet(None, None, replicas=1,
                           placement="executors", sc=_FakeSC())
    f._started = True
    f._resv_addr = ("127.0.0.1", 0)
    monkeypatch.setattr(
        f, "_await_lease",
        lambda rid, timeout, min_epoch=None: {"addr": ["127.0.0.1", 1]})
    monkeypatch.setattr(fleet.FleetRouter, "_await_healthz",
                        staticmethod(lambda addr, timeout: True))
    return f


def test_replacement_can_reuse_the_corpses_own_executor(monkeypatch):
    """Review-fix pin: the executor pick used to run while the corpse
    handle was still tracked, so the victim's own executor read as
    hosting and was excluded — on a single-executor fleet every
    replacement raised NoCapacity forever even after the executor
    revived. The corpse is untracked before the pick now."""
    f = _fake_executor_fleet(monkeypatch, ["e0"])
    corpse = fleet.RemoteReplica("replica-0", f.reservation,
                                 executor_id="e0")
    f._track(corpse)
    replacement = f.spawn_replica(replica_id="replica-0", timeout=5)
    assert replacement.executor_id == "e0"
    assert f._replica("replica-0") is replacement

    # and a replacement that finds NO capacity keeps the dead
    # identity TRACKED (the PR-13 contract: REPLACE must re-fire)
    f2 = _fake_executor_fleet(monkeypatch, [])
    corpse2 = fleet.RemoteReplica("replica-9", f2.reservation,
                                  executor_id="gone")
    f2._track(corpse2)
    with pytest.raises(fleet.NoCapacity):
        f2.spawn_replica(replica_id="replica-9", timeout=5)
    assert f2._replica("replica-9") is corpse2, \
        "NoCapacity untracked the corpse — the autoscaler would " \
        "forget the dead identity"


# -- replica identity schema (satellite) -----------------------------------

def test_replica_id_stable_across_respawn(lm):
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1,
                               replica_id="replica-x")
    try:
        assert eng.replica_id == "replica-x"
        assert eng.load_stats()["replica_id"] == "replica-x"
        eng.stop()
        fresh = eng.respawn()
        try:
            assert fresh.replica_id == "replica-x", \
                "replica identity must survive respawn()"
        finally:
            fresh.stop()
    finally:
        eng.stop()


def test_default_replica_ids_are_distinct(lm):
    dec, params = lm
    a = serving.DecodeEngine(dec, params, slots=1)
    b = serving.DecodeEngine(dec, params, slots=1)
    try:
        assert a.replica_id and b.replica_id
        assert a.replica_id != b.replica_id
    finally:
        a.stop()
        b.stop()


def test_healthz_and_metrics_carry_replica_id(lm):
    """Pinned schema: /healthz body has ``replica_id``; /metrics has the
    ``tfos_serving_replica_info{replica_id=...} 1`` join gauge."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1,
                               replica_id="replica-7")
    server = serving.ModelServer(None, engine=eng, name="m", port=0)
    host, port = server.start()
    try:
        _, body = _get("http://%s:%d/healthz" % (host, port))
        assert json.loads(body)["replica_id"] == "replica-7"
        _, text = _get("http://%s:%d/metrics" % (host, port))
        assert '# TYPE tfos_serving_replica_info gauge' in text
        assert 'tfos_serving_replica_info{replica_id="replica-7"} 1' \
            in text
        assert text.endswith("# EOF\n")
    finally:
        server.stop()


def test_engine_failed_503_carries_kind(lm):
    """Pinned schema: a retriable 503's body names WHICH transient
    condition (``kind``) — the router penalizes EngineFailed but not
    Shed/Draining, and it can only tell them apart through this."""
    dec, params = lm
    eng = serving.DecodeEngine(dec, params, slots=1)
    server = serving.ModelServer(None, engine=eng, name="m", port=0)
    host, port = server.start()
    try:
        eng._broken = RuntimeError("boom")  # engine failed, server up
        with pytest.raises(urllib.error.HTTPError) as err:
            _post("http://%s:%d/v1/models/m:generate" % (host, port),
                  {"prompt": [1, 2], "max_new_tokens": 2})
        assert err.value.code == 503
        body = json.loads(err.value.read())
        assert body["kind"] == "EngineFailed"
        assert err.value.headers.get("Retry-After") is not None
    finally:
        eng._broken = None
        server.stop()


# -- reservation serving-role lease view (satellite) -----------------------

def test_reservation_serving_snapshot_and_stats_view():
    server = reservation.Server(0)
    addr = server.start(host="127.0.0.1")
    client = reservation.Client(addr)
    try:
        # a trainer-style lease must NOT appear in the serving view
        client.beat(0, {"state": "running", "train_step": 3})
        client.beat("replica-0", {
            "role": "serving", "replica_id": "replica-0",
            "addr": ["127.0.0.1", 1234], "model": "lm",
            "serving": {"queue_depth": 2, "slot_occupancy": 1,
                        "queue_wait_ewma_s": 0.05, "alive": True,
                        "draining": False}})
        snap = server.serving_snapshot()
        assert set(snap) == {"replica-0"}
        view = snap["replica-0"]
        assert view["addr"] == ["127.0.0.1", 1234]
        assert view["model"] == "lm"
        assert view["serving"]["queue_depth"] == 2
        assert view["age"] < 5.0
        # /stats exposes the same view under the "serving" key
        assert server.stats_addr is not None
        _, body = _get("http://%s:%d/stats" % tuple(server.stats_addr))
        stats = json.loads(body)
        assert set(stats["serving"]) == {"replica-0"}
        assert stats["serving"]["replica-0"]["serving"][
            "slot_occupancy"] == 1
        assert "metrics" not in stats["serving"]["replica-0"]
    finally:
        client.close()
        server.stop()


# -- fleet e2e (tier-1: small, fast) ---------------------------------------

def test_two_replica_fleet_routes_and_matches_solo(lm):
    """The core fleet contract over real HTTP: concurrent requests
    through the router all succeed, every output is bitwise-identical
    to a solo generate, the router's /healthz sees both replicas, and
    /metrics exposes per-replica labeled serving series plus the
    fleet families."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=2, name="lm",
                            engine_kw={"slots": 2}) as f:
        url = f.url("/v1/models/lm:generate")
        prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 2], [3, 3, 3]]
        results = [None] * len(prompts)

        def client(i):
            status, body = _post(url, {"prompt": prompts[i],
                                       "max_new_tokens": 6})
            results[i] = (status, body["tokens"])

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i, prompt in enumerate(prompts):
            status, tokens = results[i]
            assert status == 200
            assert tokens == _solo(dec, params, prompt, 6)
        status, body = _get(f.url("/healthz"))
        health = json.loads(body)
        assert status == 200 and health["routable"] == 2
        assert set(health["replicas"]) == {"replica-0", "replica-1"}
        _, text = _get(f.url("/metrics"))
        assert text.endswith("# EOF\n")
        assert "tfos_fleet_requests_total" in text
        assert 'tfos_fleet_replica_up{replica="replica-0"} 1' in text
        assert 'tfos_fleet_replica_up{replica="replica-1"} 1' in text
        # per-replica labeled engine series from the beat snapshots
        assert 'replica="replica-0"' in text \
            and "tfos_serving_decode_steps_total" in text
        counts = f.router.counters.snapshot()["counts"]
        assert counts.get("requests") == len(prompts)
        assert counts.get("failovers", 0) == 0


def test_router_404_and_healthz_unavailable_when_no_replicas():
    resv = reservation.Server(0)
    resv.start(host="127.0.0.1")
    router = fleet.FleetRouter(resv, name="lm")
    try:
        host, port = router.start()
        # healthz: 503 with routable == 0 (no leases at all)
        with pytest.raises(urllib.error.HTTPError) as err:
            _get("http://%s:%d/healthz" % (host, port))
        assert err.value.code == 503
        assert json.loads(err.value.read())["routable"] == 0
        # unknown route -> 404
        with pytest.raises(urllib.error.HTTPError) as err:
            _get("http://%s:%d/nope" % (host, port))
        assert err.value.code == 404
        # a generate with nothing routable -> retriable 503 with
        # Retry-After after the bounded failover budget
        with pytest.raises(urllib.error.HTTPError) as err:
            _post("http://%s:%d/v1/models/lm:generate" % (host, port),
                  {"prompt": [1], "max_new_tokens": 1})
        assert err.value.code == 503
        assert err.value.headers.get("Retry-After") is not None
        assert json.loads(err.value.read())["kind"] == \
            "NoReplicaAvailable"
    finally:
        router.stop()
        resv.stop()


def test_draining_replica_excluded_by_its_own_beat(lm):
    """A replica whose engine is draining advertises it on its next
    beat and the router stops routing to it — no health penalty, no
    failover storm, just exclusion."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=2, name="lm",
                            engine_kw={"slots": 2},
                            beat_interval=0.05) as f:
        victim = f.replicas[0].engine
        victim.drain()  # drains idle engine; draining+stopped flags set
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            views = f.router.replica_views()
            order = fleet.route_order(views, f.router.stale_after)
            if order == ["replica-1"]:
                break
            time.sleep(0.05)
        assert fleet.route_order(
            f.router.replica_views(), f.router.stale_after) == \
            ["replica-1"]
        # traffic still flows, all of it to the survivor
        status, body = _post(f.url("/v1/models/lm:generate"),
                             {"prompt": [1, 2], "max_new_tokens": 3})
        assert status == 200
        assert body["tokens"] == _solo(dec, params, [1, 2], 3)


def test_client_disconnect_propagates_through_router(lm):
    """The PR-4 disconnect contract survives the extra hop: when the
    router's OWN client hangs up mid-request, the router tears down
    its upstream connection, the replica's socket-EOF cancel fires,
    and the slot frees instead of decoding to max_new for nobody."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=1, name="lm",
                            engine_kw={"slots": 1},
                            beat_interval=0.05) as f:
        engine = f.replicas[0].engine
        # warm the programs, then hold the next request's first step
        # boundary open so the disconnect provably lands mid-flight
        _post(f.url("/v1/models/lm:generate"),
              {"prompt": [1, 2], "max_new_tokens": 2})
        chaos.arm("stall_decode_for=1.5")
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_new_tokens": 40}).encode()
        host, port = f.router_addr
        sock = socket.create_connection((host, port), timeout=30)
        sock.sendall(
            b"POST /v1/models/lm:generate HTTP/1.1\r\n"
            b"Host: x\r\nContent-Type: application/json\r\n"
            b"Content-Length: " + str(len(body)).encode() +
            b"\r\n\r\n" + body)
        # wait until the request is admitted upstream, then vanish
        assert chaos.poll_until(
            lambda: _counts(engine).get("prefills", 0) >= 2, timeout=60)
        sock.close()
        # the victim's slot frees at the next step boundary: cancelled
        # counter ticks and occupancy returns to 0 long before a
        # 40-token rollout could finish
        assert chaos.poll_until(
            lambda: _counts(engine).get("cancelled", 0) >= 1, timeout=30)
        assert chaos.poll_until(
            lambda: engine.counters.snapshot()["gauges"]
            .get("slot_occupancy") == 0, timeout=30)
        assert chaos.poll_until(
            lambda: f.router.counters.snapshot()["counts"]
            .get("client_disconnects", 0) >= 1, timeout=10)
        chaos.disarm()
        # the replica is NOT penalized: the next request routes fine
        status, rbody = _post(f.url("/v1/models/lm:generate"),
                              {"prompt": [1, 2], "max_new_tokens": 3})
        assert status == 200
        assert rbody["tokens"] == _solo(dec, params, [1, 2], 3)


# -- rolling drain (weight-upgrade cycle, live traffic) --------------------

@pytest.mark.slow
def test_rolling_drain_zero_lost_requests_under_traffic(lm):
    """The acceptance pin: ``rolling_drain()`` across 3 replicas
    completes a weight-upgrade cycle — every replica's engine replaced
    (fresh object, same identity), zero lost requests among continuous
    client traffic, zero drain loss. The upgrade callable swaps in a
    second params object, standing in for new weights."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=3, name="lm",
                            engine_kw={"slots": 2},
                            beat_interval=0.05) as f:
        url = f.url("/v1/models/lm:generate")
        old_engines = {r.replica_id: r.engine for r in f.replicas}
        stop = threading.Event()
        failures, successes = [], []

        def traffic():
            i = 0
            while not stop.is_set():
                i += 1
                try:
                    status, body = _post(
                        url, {"prompt": [1 + i % 5, 2],
                              "max_new_tokens": 4})
                    assert status == 200
                    successes.append(body["tokens"])
                except Exception as e:  # noqa: BLE001 - the assertion
                    failures.append(repr(e))
                time.sleep(0.01)

        threads = [threading.Thread(target=traffic) for _ in range(3)]
        for t in threads:
            t.start()
        try:
            time.sleep(0.3)  # traffic flowing before the cycle starts

            def upgrade(old):
                return serving.DecodeEngine(
                    dec, params, slots=2, replica_id=old.replica_id)

            report = f.rolling_drain(upgrade=upgrade,
                                     healthz_timeout=30.0)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
        assert report["completed"] and report["zero_loss"], report
        assert [r["replica_id"] for r in report["replicas"]] == \
            ["replica-0", "replica-1", "replica-2"]
        assert all(r["drained_clean"] and r["recovered"]
                   for r in report["replicas"]), report
        # every engine object was replaced; identity survived
        for replica in f.replicas:
            assert replica.engine is not old_engines[replica.replica_id]
            assert replica.engine.replica_id == replica.replica_id
        assert not failures, failures
        assert successes, "traffic must have flowed during the cycle"
        # outputs stayed solo-correct through the swaps
        want = {tuple(_solo(dec, params, [1 + i, 2], 4))
                for i in range(5)}
        assert {tuple(t) for t in successes} <= want


# -- chaos: kill one replica mid-stream (collected by `make chaos`) --------

@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_kill_one_replica_zero_client_visible_failures(lm):
    """The fleet acceptance e2e: 3 replicas behind the router, chaos
    kills ONE replica's decode scheduler mid-stream
    (``kill_scheduler_at_step`` scoped by ``only=<replica_id>``).
    Every in-flight and subsequent client request completes with the
    bitwise solo output (failures stay INTERNAL: retriable 503s the
    router fails over); the supervisor quiesces the replica first,
    restarts its engine, readmits it; MTTR is recorded from the event
    log."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=3, name="lm",
                            engine_kw={"slots": 2},
                            beat_interval=0.05) as f:
        f.supervise()
        url = f.url("/v1/models/lm:generate")
        # warm the shared decode programs so the kill lands mid-decode,
        # not mid-compile
        _post(url, {"prompt": [1, 2, 3], "max_new_tokens": 2})
        chaos.arm("kill_scheduler_at_step=3,only=replica-1")
        results, errors = [], []

        def client(i):
            try:
                status, body = _post(
                    url, {"prompt": [1 + i % 5, 2, 3],
                          "max_new_tokens": 16}, timeout=180)
                results.append((i, status, body["tokens"]))
            except Exception as e:  # noqa: BLE001 - the assertion
                errors.append(repr(e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, \
            "client-visible failures during replica kill: %s" % errors
        assert len(results) == 12
        for i, status, tokens in results:
            assert status == 200
            assert tokens == _solo(dec, params, [1 + i % 5, 2, 3], 16)
        # the kill actually happened and was failed over internally
        assert chaos.poll_until(
            lambda: any(e["name"] == "engine_restarted"
                        for e in f.supervisor.events.events()),
            timeout=60), "supervised restart never completed"
        events = f.supervisor.events.events()
        dead = [e for e in events if e["name"] == "engine_dead"]
        restarted = [e for e in events if e["name"] == "engine_restarted"]
        assert dead and restarted
        assert dead[0].get("replica") == "replica-1"
        mttr = restarted[0]["t"] - dead[0]["t"]
        assert 0 <= mttr < 60, mttr
        # restart counted on the shared counters (series continuity)
        assert f.replicas[1].engine.counters.snapshot()["counts"] \
            .get("engine_restarts") == 1
        # the revived replica serves again (readmitted): wait until the
        # router would route to it, then push one more request through
        assert chaos.poll_until(
            lambda: "replica-1" in fleet.route_order(
                f.router.replica_views(), f.router.stale_after),
            timeout=30), "killed replica never readmitted"
        status, body = _post(url, {"prompt": [9, 2, 3],
                                   "max_new_tokens": 4})
        assert status == 200
        assert body["tokens"] == _solo(dec, params, [9, 2, 3], 4)


def test_fleet_stop_then_start_reforms(lm):
    """stop() fully resets fleet state: a second start() re-forms with
    fresh replicas and a fresh reservation server instead of routing,
    draining, or watching over stopped corpses."""
    dec, params = lm
    f = fleet.ServingFleet(dec, params, replicas=1, name="lm",
                           engine_kw={"slots": 1})
    f.start()
    f.stop()
    assert f.replicas == [] and f.router is None
    f.start()
    try:
        assert len(f.replicas) == 1
        status, body = _post(f.url("/v1/models/lm:generate"),
                             {"prompt": [5, 1], "max_new_tokens": 3})
        assert status == 200
        assert body["tokens"] == _solo(dec, params, [5, 1], 3)
    finally:
        f.stop()


def test_cluster_serving_fleet_helper(lm):
    """cluster.serving_fleet: one call forms, starts, and (optionally)
    supervises an in-process fleet."""
    dec, params = lm
    f = cluster.serving_fleet(dec, params, replicas=2, name="lm",
                              engine_kw={"slots": 1}, supervise=True)
    try:
        assert f.supervisor is not None
        assert len(f.supervisor._watched) == 2
        status, body = _post(f.url("/v1/models/lm:generate"),
                             {"prompt": [2, 4], "max_new_tokens": 3})
        assert status == 200
        assert body["tokens"] == _solo(dec, params, [2, 4], 3)
    finally:
        f.stop()


# -- trace-context propagation (PR 10): X-TFOS-Trace + /debug/trace --------

def _get_with_headers(url, timeout=30):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode(), dict(r.headers)


def _stitched_sources(doc):
    """{label: set of tids with any event} from a stitched document."""
    labels = {e["pid"]: e["args"]["name"] for e in doc["traceEvents"]
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    out = {label: set() for label in labels.values()}
    for e in doc["traceEvents"]:
        if e.get("ph") in ("X", "i"):
            out[labels[e["pid"]]].add(e["tid"])
    return out


def test_router_mints_trace_and_debug_trace_stitches_replica(lm):
    """One routed request: the router mints an X-TFOS-Trace id, the
    replica engine ADOPTS it, and GET /debug/trace on the router
    returns ONE stitched Perfetto document where the router's dispatch
    span and the replica's engine spans share that id — with the ring
    saturation total in the X-TFOS-Trace-Dropped header."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=1, name="lm",
                            engine_kw={"slots": 1},
                            beat_interval=0.05) as f:
        # ServingFleet gives each replica its OWN ring (one ring per
        # process in real deployments) — pinned here: the stitch labels
        # spans by source, which a shared global ring would make vacuous
        assert f.replicas[0].engine.flight \
            is not fleet.tracing.flight_recorder()
        status, body = _post(f.url("/v1/models/lm:generate"),
                             {"prompt": [3, 1, 4], "max_new_tokens": 3})
        assert status == 200
        assert body["tokens"] == _solo(dec, params, [3, 1, 4], 3)
        status, text, headers = _get_with_headers(f.url("/debug/trace"))
        assert status == 200
        assert "X-TFOS-Trace-Dropped" in headers
        assert int(headers["X-TFOS-Trace-Dropped"]) >= 0
        doc = json.loads(text)
        assert doc.get("dropped", {}).keys() == {"router", "replica-0"}
        dispatches = [e for e in doc["traceEvents"]
                      if e.get("name") == "dispatch"
                      and e.get("ph") == "X"]
        assert len(dispatches) == 1
        trace_id = dispatches[0]["tid"]
        assert dispatches[0]["args"]["status"] == 200
        sources = _stitched_sources(doc)
        # the minted id joins the router's row to the replica's spans
        assert trace_id in sources["router"]
        assert trace_id in sources["replica-0"], sources
        # the replica actually emitted engine lifecycle spans under it
        replica_spans = {e["name"] for e in doc["traceEvents"]
                         if e.get("ph") == "X"
                         and e["tid"] == trace_id
                         and e.get("name") != "dispatch"
                         and e.get("name") != "upstream"}
        assert {"prefill", "decode"} <= replica_spans, replica_spans


@pytest.mark.slow
@pytest.mark.chaos
def test_failover_request_yields_one_stitched_cross_replica_trace(
        lm, tmp_path):
    """Acceptance (PR 10): a fleet request that fails over MID-STREAM
    produces one stitched trace containing spans from BOTH replicas —
    the dying replica's partial lifecycle and the survivor's complete
    one share the single router-minted trace id."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=2, name="lm",
                            engine_kw={"slots": 2},
                            beat_interval=0.05) as f:
        assert f.replicas[0].engine.flight \
            is not f.replicas[1].engine.flight, \
            "fleet replicas must own distinct span rings"
        url = f.url("/v1/models/lm:generate")
        # UNSCOPED kill + fuse: the decode-step site only fires on an
        # engine with ACTIVE slots, so the victim is deterministically
        # whichever replica serves the request — and the single-shot
        # fuse guarantees the survivor completes the failover
        chaos.arm("kill_scheduler_at_step=5,fuse={}".format(
            tmp_path / "kill_fuse"))
        status, body = _post(url, {"prompt": [2, 3, 4],
                                   "max_new_tokens": 16}, timeout=180)
        # the client saw ONE clean answer (the failover is internal)
        assert status == 200
        assert body["tokens"] == _solo(dec, params, [2, 3, 4], 16)
        status, text, headers = _get_with_headers(f.url("/debug/trace"))
        assert status == 200
        doc = json.loads(text)
        # the failed-over dispatch: >1 upstream attempt on one trace id
        dispatches = [e for e in doc["traceEvents"]
                      if e.get("name") == "dispatch"
                      and e.get("ph") == "X"
                      and e["args"].get("attempts", 1) > 1]
        assert dispatches, "no failed-over dispatch recorded"
        trace_id = dispatches[0]["tid"]
        sources = _stitched_sources(doc)
        assert trace_id in sources["replica-0"], sources
        assert trace_id in sources["replica-1"], sources
        # one upstream span per attempt, both on the request's row
        upstreams = [e for e in doc["traceEvents"]
                     if e.get("name") == "upstream"
                     and e["tid"] == trace_id]
        assert len(upstreams) == 2
        assert {u["args"]["replica"] for u in upstreams} == \
            {"replica-0", "replica-1"}


# -- prefix/session affinity (PR 16; e2e + chaos) --------------------------


def test_session_affinity_sticky_routing_and_schema(lm):
    """A conversation carrying a ``session`` id sticks to the replica
    that served its first turn (the dispatch-history side of the
    affinity map — no digest needed), turn-2 stays bitwise-solo, and
    the new observability schema renders: affinity counters on the
    router, digest gauges per replica."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=2, name="lm",
                            engine_kw={"slots": 2},
                            beat_interval=0.05) as f:
        url = f.url("/v1/models/lm:generate")
        p1 = list(range(1, 14))
        status, body = _post(url, {"prompt": p1, "max_new_tokens": 8,
                                   "session": "conv-1"})
        assert status == 200
        t1 = body["tokens"]
        rid = f.router.affinity.lookup("conv-1")
        assert rid in ("replica-0", "replica-1")
        # turn 2: continuation of turn 1 under the same session id
        p2 = t1 + [3]
        want = _solo(dec, params, p2, 6)
        for _ in range(3):
            status, body = _post(url, {"prompt": p2,
                                       "max_new_tokens": 6,
                                       "session": "conv-1"})
            assert status == 200 and body["tokens"] == want
            assert f.router.affinity.lookup("conv-1") == rid
        counts = f.router.counters.snapshot()["counts"]
        assert counts.get("affinity_hits", 0) >= 3
        # a sessionless request neither reads nor grows the map
        status, _ = _post(url, {"prompt": [5, 6], "max_new_tokens": 2})
        assert status == 200 and len(f.router.affinity) == 1
        status, body = _get(f.url("/healthz"))
        health = json.loads(body)
        assert health["affinity_entries"] == 1
        assert all("prefix_digest_chains" in v
                   for v in health["replicas"].values())
        _, text = _get(f.url("/metrics"))
        assert "tfos_fleet_affinity_entries 1" in text
        assert "tfos_serving_prefix_digest_chains" in text
        # session type errors are the replica's 400, not a router crash
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url, {"prompt": [1, 2], "max_new_tokens": 2,
                        "session": 7})
        assert err.value.code == 400


@pytest.mark.slow
@pytest.mark.chaos
def test_affinity_kill_warm_replica_fails_over_cold(lm):
    """The PR 16 failover contract, end to end: a conversation's warm
    replica is killed mid-session; the next turn completes 200 served
    COLD with bitwise solo-identical tokens at temp=0, zero duplicate
    completions, and the affinity map entry for the dead replica is
    evicted (counted as ``affinity_breaks{failover_cold}``) before
    the session rebinds to its new home."""
    dec, params = lm
    with fleet.ServingFleet(dec, params, replicas=3, name="lm",
                            engine_kw={"slots": 2},
                            beat_interval=0.05) as f:
        f.supervise()
        url = f.url("/v1/models/lm:generate")
        # warm the shared decode programs (sessionless: no map entry)
        _post(url, {"prompt": [1, 2, 3], "max_new_tokens": 2})
        p1 = list(range(1, 14))
        status, body = _post(url, {"prompt": p1, "max_new_tokens": 8,
                                   "session": "conv"})
        assert status == 200
        t1 = body["tokens"]
        warm_rid = f.router.affinity.lookup("conv")
        assert warm_rid is not None
        # kill the WARM replica's scheduler on its next decode steps
        chaos.arm("kill_scheduler_at_step=3,only={}".format(warm_rid))
        p2 = t1 + [3]
        status, body = _post(url, {"prompt": p2, "max_new_tokens": 16,
                                   "session": "conv"}, timeout=180)
        assert status == 200
        assert body["tokens"] == _solo(dec, params, p2, 16)
        # served COLD: the session moved off the dead replica, through
        # an explicit eviction (failover_cold), then rebound
        new_rid = f.router.affinity.lookup("conv")
        assert new_rid is not None and new_rid != warm_rid
        with f.router._obs_lock:
            breaks = dict(f.router._affinity_breaks)
        assert breaks.get("failover_cold", 0) >= 1
        # zero duplicate completions: every client request completed
        # exactly once across the whole fleet (the dead replica's
        # aborted attempt never produced a second completion)
        total = sum(r.engine.counters.snapshot()["counts"]
                    .get("requests_completed", 0) for r in f.replicas)
        assert total == 3
        # the killed replica recovers under supervision and can be
        # routed again — affinity healing is just future dispatches
        assert chaos.poll_until(
            lambda: warm_rid in fleet.route_order(
                f.router.replica_views(), f.router.stale_after),
            timeout=60), "killed replica never readmitted"
