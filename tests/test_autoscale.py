"""Cross-executor serving + SLO-driven autoscaler (PR 13).

Three layers, the repo's usual shape:

- PURE policy — ``autoscale.decide`` table tests with injected time
  and hand-built views (breach -> up, cooldown suppresses flap,
  min/max clamps, evidence-gated cold start, stale-history gating,
  dead-lease replacement), plus the ``replica_view`` extraction from
  a beat-shaped snapshot entry (TTFT p99 off the wire histogram).
- CONTROLLER units over a real in-process fleet — decision/evidence
  event trail, gauges and counters on the router's /metrics, and the
  closed loop: a burst scales 1 -> 2, sustained idle retires back to
  1 with the lease deregistered (tier-1 fast).
- E2E (slow / chaos) — executor-hosted placement: replica pids differ
  from the driver's, routed tokens are bitwise solo-identical, a load
  burst grows the fleet onto a free executor with zero client-visible
  failures, scale-down under live traffic loses nothing
  (rolling_drain-grade), and the chaos leg SIGKILLs a replica's whole
  executor (``kill_serving_executor_at_request``) — failover +
  fenced autoscaler replacement, zero client-visible failures,
  supervisor attribution (collected by ``make chaos``).
"""

import json
import os
import tempfile
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import (autoscale, chaos, cluster, fleet,
                                   generation, serving, tracing)
from tensorflowonspark_tpu.autoscale import (AutoscalePolicy,
                                             ScaleDecision, decide)
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


def _post(url, payload, timeout=180):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _scaled_to(f, n):
    """True once the fleet holds n replicas AND each has a live
    lease — spawn_replica tracks the handle before the bootstrap
    completes, so the handle count alone reads a half-born fleet."""
    return len(f.replicas) == n \
        and len(f.reservation.serving_snapshot()) == n


def _post_with_retry(url, payload, attempts=30, timeout=120):
    """The shared client retry policy: transient 429/503 (shedding,
    draining, no-replica windows) retry with backoff; anything else
    propagates — what 'zero client-visible failures' means."""
    def attempt():
        try:
            return _post(url, payload, timeout=timeout)[1]
        except urllib.error.HTTPError as e:
            retriable = serving.http_retriable(
                e.code, e.headers.get("Retry-After"))
            if retriable is not None:
                raise retriable
            raise
    return serving.retry_call(attempt, attempts=attempts,
                              base_delay=0.2, max_delay=2.0)


# -- pure policy tables ----------------------------------------------------

def _view(rid="r0", age=0.1, alive=True, draining=False, queue_depth=0,
          occ=0, slots=4, qwait=0.0, completed=10, ttft=None,
          executor=None):
    return {"replica_id": rid, "age": age, "alive": alive,
            "draining": draining, "queue_depth": queue_depth,
            "slot_occupancy": occ, "slots": slots,
            "queue_wait_ewma_s": qwait, "kv_blocks_free": None,
            "kv_blocks_total": None, "completed": completed,
            "ttft_p99_s": ttft, "executor": executor}


def _policy(**kw):
    base = dict(min_replicas=1, max_replicas=3, queue_wait_slo_s=0.5,
                occupancy_high=0.85, occupancy_low=0.25,
                up_cooldown_s=2.0, down_cooldown_s=10.0,
                dead_after_s=3.0)
    base.update(kw)
    return AutoscalePolicy(**base)


def test_decide_breach_scales_up():
    d = decide(_policy(), [_view(queue_depth=3, qwait=1.0, occ=4)],
               {}, now=100.0)
    assert d.action == ScaleDecision.UP
    assert "queue_wait_ewma" in d.reason
    assert d.evidence["queue_depth"] == 3


def test_decide_up_cooldown_suppresses_flap():
    views = [_view(queue_depth=3, qwait=1.0)]
    d = decide(_policy(), views, {"last_up": 99.0}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "cooldown" in d.reason
    d = decide(_policy(), views, {"last_up": 97.0}, now=100.0)
    assert d.action == ScaleDecision.UP


def test_decide_up_clamped_at_max():
    views = [_view(rid="r%d" % i, queue_depth=2, qwait=1.0)
             for i in range(3)]
    d = decide(_policy(max_replicas=3), views, {}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "max_replicas" in d.reason


def test_decide_cold_start_holds_without_evidence():
    """A fleet that has served nothing and holds no work must not
    scale in EITHER direction — there is no evidence to scale on."""
    views = [_view(rid="r0", completed=0), _view(rid="r1", completed=0)]
    d = decide(_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "cold" in d.reason


def test_decide_idle_scales_down_least_loaded():
    views = [_view(rid="r0", qwait=0.2), _view(rid="r1", qwait=0.0)]
    d = decide(_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.DOWN
    assert d.replica_id == "r1", "victim must be the least loaded"


def test_decide_down_retires_coldest_cache_first():
    """PR 16: sustained-idle retirement prefers the replica whose
    prefix digest shows the LEAST resident cache — scale-in must not
    destroy the fleet's hottest conversations. Load (the old key)
    only breaks warmth ties."""
    warm = _view(rid="r0")
    warm["prefix_warmth"] = 7  # summed digest depths
    cold = _view(rid="r1", qwait=0.3)  # more loaded, but cache-cold
    cold["prefix_warmth"] = 0
    d = decide(_policy(), [warm, cold], {}, now=100.0)
    assert d.action == ScaleDecision.DOWN
    assert d.replica_id == "r1", "victim must be the coldest cache"
    assert "coldest" in d.reason


def test_decide_down_warmth_tie_breaks_by_generated_hits_then_load():
    """Equal digest warmth: a replica actively serving multi-turn
    reuse (generated-prefix hits) is retired LAST; with both warmth
    signals tied, the least-loaded replica goes (the original key)."""
    a = _view(rid="r0", qwait=0.1)
    a["prefix_warmth"] = 3
    a["generated_prefix_hit_blocks"] = 5
    b = _view(rid="r1", qwait=0.2)
    b["prefix_warmth"] = 3
    b["generated_prefix_hit_blocks"] = 0
    d = decide(_policy(), [a, b], {}, now=100.0)
    assert d.replica_id == "r1"
    b["generated_prefix_hit_blocks"] = 5
    d = decide(_policy(), [a, b], {}, now=100.0)
    assert d.replica_id == "r0", "all-warmth tie falls back to load"


def test_decide_down_clamped_at_min():
    d = decide(_policy(), [_view()], {}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "min" in d.reason


def test_decide_down_cooldown_counts_scales_in_both_directions():
    """Hysteresis: a recent scale-UP also delays the next scale-down —
    a burst's trailing edge must not flap the fleet."""
    views = [_view(rid="r0"), _view(rid="r1")]
    d = decide(_policy(), views, {"last_up": 95.0}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "down-cooldown" in d.reason
    d = decide(_policy(), views, {"last_up": 85.0}, now=100.0)
    assert d.action == ScaleDecision.DOWN


def test_decide_idle_with_zero_completions_holds():
    # occ>0 so the cold gate doesn't catch it first: slots hold work
    # but NOTHING has ever completed — still not scale-down evidence
    views = [_view(rid="r0", completed=0, occ=1, slots=8),
             _view(rid="r1", completed=0, slots=8)]
    d = decide(_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "zero completions" in d.reason


def test_decide_stale_breach_without_standing_queue_is_history():
    """The queue-wait EWMA holds its last burst's value while idle; a
    'breach' no current request experiences must not pin the fleet
    wide (it would also block every scale-down forever)."""
    views = [_view(rid="r0", qwait=5.0), _view(rid="r1", qwait=5.0)]
    d = decide(_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.DOWN


def test_decide_ttft_breach_needs_standing_queue_too():
    pol = _policy(ttft_p99_slo_s=0.2)
    d = decide(pol, [_view(queue_depth=1, ttft=0.5)], {}, now=100.0)
    assert d.action == ScaleDecision.UP
    assert "ttft_p99" in d.reason
    d = decide(pol, [_view(queue_depth=0, ttft=0.5)], {}, now=100.0)
    assert d.action != ScaleDecision.UP


def test_decide_dead_lease_replaces_before_anything_else():
    views = [_view(rid="r0", age=10.0, queue_depth=3, qwait=1.0),
             _view(rid="r1", queue_depth=3, qwait=1.0)]
    d = decide(_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.REPLACE
    assert d.replica_id == "r0"
    assert "lease expired" in d.reason


def test_decide_engine_dead_under_live_lease_replaces():
    d = decide(_policy(), [_view(rid="r0", alive=False)], {}, now=100.0)
    assert d.action == ScaleDecision.REPLACE
    assert "engine dead" in d.reason


def test_decide_draining_replica_is_not_dead_and_not_live():
    # a draining replica is a deliberate retirement in progress:
    # never "replace" it, never count it live
    d = decide(_policy(), [_view(rid="r0", draining=True, age=10.0)],
               {}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "no live replicas" in d.reason


def test_decide_never_mutates_state():
    state = {"last_up": None, "last_down": None}
    decide(_policy(), [_view(queue_depth=3, qwait=1.0)], state, 100.0)
    assert state == {"last_up": None, "last_down": None}


# -- view extraction from the beat wire ------------------------------------

def test_replica_view_extracts_gauges_ttft_and_host():
    hist = tracing.Histogram()
    for v in [0.01] * 99 + [0.8]:
        hist.observe(v)
    info = {"age": 0.2, "addr": ["127.0.0.1", 1], "epoch": 2,
            "serving": {"alive": True, "draining": False,
                        "queue_depth": 4, "slot_occupancy": 2,
                        "slots": 8, "queue_wait_ewma_s": 0.125,
                        "prefix_digest": [["ab12", 2], ["cd34", 3],
                                          ["bad"], None],
                        "generated_prefix_hit_blocks": 4},
            "metrics": {"counters": {"tfos_serving": {
                "counts": {"requests_completed": 7}}},
                "hists": {"tfos_serving_ttft_seconds":
                          hist.snapshot()}},
            "host": {"executor": 3, "pid": 4242}}
    view = autoscale.replica_view("replica-9", info)
    assert view["replica_id"] == "replica-9"
    assert view["queue_depth"] == 4 and view["slots"] == 8
    assert view["completed"] == 7
    assert view["executor"] == 3
    assert view["ttft_p99_s"] == pytest.approx(hist.quantile(0.99))
    # digest warmth (PR 16): summed depths, malformed entries skipped
    assert view["prefix_warmth"] == 5
    assert view["generated_prefix_hit_blocks"] == 4


def test_replica_view_no_lease_reads_dead():
    view = autoscale.replica_view("replica-0", None)
    assert view["age"] is None and view["alive"] is False
    d = decide(_policy(), [view], {}, now=100.0)
    assert d.action == ScaleDecision.REPLACE


# -- controller over a real in-process fleet -------------------------------

def test_controller_records_decisions_and_metrics(lm):
    dec, params = lm
    f = fleet.ServingFleet(dec, params, replicas=1,
                           engine_kw={"slots": 2})
    f.start()
    try:
        ctl = autoscale.AutoscaleController(
            f, policy=_policy(), interval=60.0)  # no thread churn
        d = ctl.poll_once()
        assert d.action == ScaleDecision.HOLD
        assert "cold" in d.reason
        counts = ctl.counters.snapshot()
        assert counts["counts"]["decisions"] == 1
        assert counts["gauges"]["replicas_live"] == 1
        assert counts["gauges"]["replicas_target"] == 1
        events = ctl.events.events("autoscale_decision")
        assert len(events) == 1 and events[0]["action"] == "hold"
        assert events[0]["evidence"]["views"], "evidence must ride along"
        # repeated identical holds are not re-logged (state trail, not
        # a poll-rate heartbeat)
        ctl.poll_once()
        assert len(ctl.events.events("autoscale_decision")) == 1
        # autoscale families render on the ROUTER's /metrics
        text = f.router.metrics_text()
        assert "tfos_autoscale_decisions_total" in text
        assert "tfos_autoscale_replicas_live" in text
    finally:
        f.stop()


class _StubReservation(object):
    """Serving-snapshot stub: N idle-but-proven replicas (fresh
    leases, zero queues, completions > 0) — the exact evidence that
    makes ``decide`` return DOWN."""

    def __init__(self, rids):
        self.rids = list(rids)

    def serving_snapshot(self):
        return {rid: {
            "age": 0.1,
            "serving": {"alive": True, "draining": False,
                        "queue_depth": 0, "slot_occupancy": 0,
                        "queue_wait_ewma_s": 0.0, "slots": 4},
            "metrics": {"counters": {"tfos_serving": {
                "counts": {"requests_completed": 7}}}},
        } for rid in self.rids}


class _StubFleet(object):
    """Just enough ServingFleet surface for AutoscaleController:
    tracked replicas, a snapshot source, and a retire verb that
    records every invocation (the double-retire detector)."""

    placement = "driver"
    router = None

    class _R(object):
        def __init__(self, rid):
            self.replica_id = rid

    def __init__(self, rids):
        self.replicas = [self._R(r) for r in rids]
        self.reservation = _StubReservation(rids)
        self.retired = []
        self._mu = threading.Lock()

    def retire_replica(self, rid, drain_timeout=None):
        with self._mu:
            self.retired.append(rid)
        # hold the apply window open so an unserialized second poll
        # would evaluate the SAME pre-retire evidence
        time.sleep(0.05)
        with self._mu:
            self.replicas = [r for r in self.replicas
                             if r.replica_id != rid]
            self.reservation.rids.remove(rid)
        return True


def test_concurrent_poll_once_retires_exactly_once():
    """Racecheck regression pin (PR 14, barrier-style like PR 10's
    two-thread compile-claim test): the controller's decision state
    (`_state` stamps, suppression memos) is shared between its loop
    thread and public ``poll_once`` callers. Unserialized, two
    concurrent polls both read last_down=None over identical idle
    evidence, both decide DOWN, and both retire — a min_replicas=1
    fleet shrinks to zero on one verdict. The controller lock makes
    the second poll see the first's stamp and hold."""
    stub = _StubFleet(["replica-0", "replica-1"])
    ctl = autoscale.AutoscaleController(
        stub, policy=_policy(min_replicas=1, down_cooldown_s=30.0))
    barrier = threading.Barrier(2)
    errors = []

    def poll():
        barrier.wait()
        try:
            ctl.poll_once()
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append(e)

    threads = [threading.Thread(target=poll, daemon=True,
                                name="tfos-test-poll-%d" % i)
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not errors, errors
    assert len(stub.retired) == 1, \
        "one idle verdict must retire exactly one replica, got " \
        "{}".format(stub.retired)
    assert len(stub.replicas) == 1
    # and the second poll's verdict was the cooldown hold, recorded
    # on the decision trail
    actions = [e["action"] for e in
               ctl.events.events("autoscale_decision")]
    assert actions.count("down") == 1


def test_replace_dead_lease_driver_placement_re_registers():
    """Review-fix pin: a driver-placement replica whose LEASE died
    (beat loop fenced/wedged — the process is right here) used to be
    routed into fleet.replace_replica, which unconditionally raises
    for driver fleets: the controller wedged in a permanent REPLACE
    loop and could never scale again. The repair verb is
    re_register."""
    stub = _StubFleet(["replica-0"])
    # lease present but STALE (age past dead_after_s) -> REPLACE with
    # lease_dead, remote=False
    stub.reservation.serving_snapshot = lambda: {"replica-0": {
        "age": 99.0, "serving": {"alive": True, "draining": False,
                                 "queue_depth": 0, "slot_occupancy": 0,
                                 "queue_wait_ewma_s": 0.0, "slots": 4},
        "metrics": {}}}
    replica = stub.replicas[0]
    replica.remote = False
    calls = []
    replica.re_register = lambda: calls.append("re_register")
    stub._replica = lambda rid: replica \
        if rid == replica.replica_id else None
    ctl = autoscale.AutoscaleController(stub, policy=_policy())
    d = ctl.poll_once()
    assert d.action == ScaleDecision.REPLACE
    assert calls == ["re_register"], \
        "driver-placement dead lease must repair via re_register, " \
        "not the always-raising replace_replica"
    assert ctl.counters.snapshot()["counts"].get("replacements") == 1
    assert not ctl.events.events("autoscale_replace_failed")


class _LeaseStubReservation(object):
    def __init__(self):
        self.snapshot = {}

    def serving_snapshot(self):
        return dict(self.snapshot)

    def lease_epoch(self, rid):
        return (self.snapshot.get(rid) or {}).get("epoch")


class _HoldStubRouter(object):
    def __init__(self):
        self.holds = []

    def quiesce(self, rid, reason="", owner="operator"):
        self.holds.append(("quiesce", rid, owner))

    def readmit(self, rid, owner="operator"):
        self.holds.append(("readmit", rid, owner))


def test_watch_serving_releases_hold_on_lease_recovery():
    """Review-fix pin: a lease that went stale past the watch's
    stale_after and then RECOVERED (a beat stall, not a death) left
    the supervisor's owner-scoped quiesce in place forever — no
    replacement runs spawn_replica's force-clear, so a healthy
    replica stayed administratively down (a 1-replica fleet: 503s
    for good). Recovery must release the supervisor's own hold."""
    from tensorflowonspark_tpu import supervisor as supervisor_mod

    class _Remote(object):
        remote = True
        replica_id = "replica-0"
        executor_id = "e0"

    class _Fleet(object):
        def __init__(self):
            self.replicas = [_Remote()]
            self.reservation = _LeaseStubReservation()
            self.router = _HoldStubRouter()

    fleet_stub = _Fleet()
    sup = supervisor_mod.Supervisor()
    sup._serving_watch = {"fleet": fleet_stub, "stale_after": 1.0,
                          "reported": set()}
    # dead lease -> supervisor quiesces under its own owner
    fleet_stub.reservation.snapshot = {}
    sup._check_serving_leases()
    assert ("quiesce", "replica-0", "supervisor") \
        in fleet_stub.router.holds
    # lease recovers WITHOUT a replacement -> the hold must lift
    fleet_stub.reservation.snapshot = {"replica-0": {
        "age": 0.1, "epoch": 1,
        "serving": {"alive": True}}}
    sup._check_serving_leases()
    assert ("readmit", "replica-0", "supervisor") \
        in fleet_stub.router.holds, \
        "recovered lease left the supervisor hold in place"
    assert sup.events.events("serving_replica_recovered")


def test_controller_repairs_unwatched_inprocess_engine_death(lm):
    """An in-process replica whose engine scheduler dies while its
    beat keeps flowing (lease fresh, ``alive: false``) is repaired by
    the CONTROLLER when no supervisor watches it — deferring to a
    supervisor that does not exist would wedge the autoscaler on
    REPLACE forever."""
    dec, params = lm
    f = fleet.ServingFleet(dec, params, replicas=1,
                           engine_kw={"slots": 2})
    f.start()
    try:
        ctl = autoscale.AutoscaleController(
            f, policy=_policy(dead_after_s=5.0), interval=60.0)
        chaos.arm("kill_scheduler_at_step=1,only=replica-0")
        # the kill site is the decode-step boundary: drive one request
        # so the scheduler actually steps (and dies)
        handle = f.replicas[0].engine.submit([1, 2, 3], 8)
        with pytest.raises(Exception):
            handle.result(30)
        assert chaos.poll_until(
            lambda: not f.replicas[0].engine.healthy()["alive"],
            timeout=15.0), "scheduler kill must land"
        chaos.disarm()

        def _lease_says_dead():
            gauges = (f.reservation.serving_snapshot().get("replica-0")
                      or {}).get("serving") or {}
            return gauges.get("alive") is False

        # the controller reads the BEAT view, not the engine object:
        # wait for the death to ride a beat
        assert chaos.poll_until(_lease_says_dead, timeout=10.0)
        d = ctl.poll_once()
        assert d.action == ScaleDecision.REPLACE
        assert f.replicas[0].engine.healthy()["alive"], \
            "controller must respawn the unwatched engine in place"
        assert ctl.counters.snapshot()["counts"]["replacements"] == 1
        # the repaired replica actually serves
        assert f.replicas[0].engine.generate([1, 2, 3], 3) == _solo(
            dec, params, [1, 2, 3], 3)
    finally:
        f.stop()


def test_autoscale_closed_loop_inprocess(lm):
    """The loop, closed, driver placement (fast): a burst breaches the
    queue-wait SLO -> 1 scales to 2 with zero client-visible failures;
    sustained idle retires back to 1 through the zero-loss drain path,
    and the retired replica's lease is DEREGISTERED."""
    dec, params = lm
    pol = AutoscalePolicy(min_replicas=1, max_replicas=2,
                          queue_wait_slo_s=0.05, up_cooldown_s=0.3,
                          down_cooldown_s=1.0, occupancy_low=0.999,
                          dead_after_s=10.0)
    f = cluster.serving_fleet(dec, params, replicas=1,
                              engine_kw={"slots": 2})
    ctl = f.autoscale(policy=pol, interval=0.1)
    try:
        url = f.url("/v1/models/model:generate")
        prompts = [[(i % 5) + 1, 2, 3, 4] for i in range(12)]
        outs = [None] * len(prompts)
        errors = []

        def burst():
            def client(i):
                try:
                    _, outs[i] = _post(url, {"prompt": prompts[i],
                                             "max_new_tokens": 20})
                except Exception as e:  # noqa: BLE001 - asserted below
                    errors.append(repr(e))
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        # a short burst can fall between beat/poll samples on a fast
        # warm box; re-burst (bounded) until the breach is observed
        for _ in range(3):
            burst()
            if chaos.poll_until(lambda: _scaled_to(f, 2),
                                timeout=5.0):
                break
        assert errors == [], "scale-up must be client-invisible"
        assert _scaled_to(f, 2), \
            "burst must have scaled 1 -> 2 (events: {})".format(
                ctl.events.events("autoscale_decision"))
        # the new replica is tracked in fleet.replicas before the
        # controller tallies the counter — poll the tiny gap closed
        assert chaos.poll_until(
            lambda: ctl.counters.snapshot()["counts"]
            .get("scale_ups", 0) >= 1, timeout=5.0)
        # every response is bitwise solo-identical (spot-check a few)
        for i in (0, 5, 11):
            if outs[i] is not None:
                assert outs[i]["tokens"] == _solo(dec, params,
                                                  prompts[i], 20)
        # sustained idle -> retire back to min with the lease dropped
        assert chaos.poll_until(lambda: _scaled_to(f, 1),
                                timeout=30.0), \
            "idle fleet must retire to min_replicas"
        # same tracked-before-tallied gap as scale_ups above — poll it
        assert chaos.poll_until(
            lambda: ctl.counters.snapshot()["counts"]
            .get("scale_downs", 0) >= 1, timeout=5.0)
        down = ctl.events.events("autoscale_scaled_down")
        assert down and down[-1]["drained_clean"], \
            "retirement must be the zero-loss drain path"
        retired = down[-1]["replica"]
        assert retired not in f.reservation.serving_snapshot(), \
            "retired replica's lease must be deregistered"
        # post-retirement traffic still lands (the survivor serves)
        out = _post_with_retry(url, {"prompt": [1, 2, 3],
                                     "max_new_tokens": 4})
        assert out["tokens"] == _solo(dec, params, [1, 2, 3], 4)
    finally:
        f.stop()


def test_retire_replica_under_live_traffic_zero_loss(lm):
    """Scale-down's zero-loss pin (rolling_drain-grade): retiring a
    replica while clients hammer the router loses NOTHING — quiesce
    stops new dispatches, the drain finishes admitted work, and
    failover absorbs the rest."""
    dec, params = lm
    f = fleet.ServingFleet(dec, params, replicas=2,
                           engine_kw={"slots": 2})
    f.start()
    try:
        url = f.url("/v1/models/model:generate")
        stop = threading.Event()
        served = []
        errors = []

        def traffic(seed):
            i = 0
            while not stop.is_set():
                prompt = [(seed + i) % 5 + 1, 2, 3]
                try:
                    out = _post_with_retry(
                        url, {"prompt": prompt, "max_new_tokens": 6})
                    served.append((prompt, out["tokens"]))
                except Exception as e:  # noqa: BLE001 - asserted below
                    errors.append(repr(e))
                i += 1

        threads = [threading.Thread(target=traffic, args=(s,))
                   for s in range(3)]
        for t in threads:
            t.start()
        try:
            chaos.poll_until(lambda: len(served) >= 6, timeout=60.0)
            clean = f.retire_replica("replica-1")
            assert clean, "retirement drain must finish admitted work"
            chaos.poll_until(
                lambda: len(served) >= 12, timeout=60.0)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=60)
        assert errors == [], "zero client-visible failures"
        assert len(served) >= 12
        assert "replica-1" not in f.reservation.serving_snapshot()
        for prompt, tokens in served[:6] + served[-3:]:
            assert tokens == _solo(dec, params, prompt, 6)
    finally:
        f.stop()


# -- executor-hosted placement (slow / chaos) ------------------------------

def _context(num_executors, extra_env=None):
    from tensorflowonspark_tpu.engine.context import Context
    env = {"JAX_PLATFORMS": "cpu"}
    env.update(extra_env or {})
    return Context(num_executors, executor_env=env)


@pytest.mark.slow
def test_executor_hosted_fleet_pids_differ_and_bitwise(lm):
    """The executor-role serving bootstrap: replicas run in EXECUTOR
    processes (pids differ from the driver), requests flow 200 +
    bitwise-solo through the unchanged FleetRouter, the remote
    drain/respawn lifecycle RPCs round-trip, and stop() tears the
    executor-hosted nodes down instead of leaking them."""
    dec, params = lm
    sc = _context(2)
    try:
        f = cluster.serving_fleet(dec, params, replicas=2,
                                  placement="executors", sc=sc,
                                  engine_kw={"slots": 2},
                                  spawn_timeout=180)
        try:
            snap = f.reservation.serving_snapshot()
            assert set(snap) == {"replica-0", "replica-1"}
            pids = {rid: info["host"]["pid"]
                    for rid, info in snap.items()}
            assert all(pid != os.getpid() for pid in pids.values()), \
                "replicas must run outside the driver process"
            assert len(set(pids.values())) == 2, \
                "each replica must run in its own executor"
            hosts = {info["host"]["executor"]
                     for info in snap.values()}
            assert hosts == {0, 1}
            url = f.url("/v1/models/model:generate")
            for prompt, max_new in ([1, 2, 3, 4, 5], 8), ([2, 1], 6):
                status, out = _post(url, {"prompt": prompt,
                                          "max_new_tokens": max_new})
                assert status == 200
                assert out["tokens"] == _solo(dec, params, prompt,
                                              max_new)
            # remote lifecycle RPCs round-trip (the rolling_drain verbs)
            rep = f.replicas[0]
            assert rep.remote
            assert rep.drain_engine(timeout=60) is True
            assert rep.respawn_engine()["ok"] is True
            assert fleet.FleetRouter._await_healthz(rep.addr, 30.0)
            # packed fleet: no free executor -> loud NoCapacity
            with pytest.raises(fleet.NoCapacity):
                f.spawn_replica()
        finally:
            f.stop()
        assert f.reservation.serving_snapshot() == {}
        assert sorted(sc.executors_alive()) == [0, 1], \
            "teardown must not kill executors, only serving nodes"
    finally:
        sc.stop()


@pytest.mark.slow
def test_executor_hosted_burst_scales_one_to_two_zero_failures(lm):
    """The acceptance e2e: a load burst against a 1-replica
    executor-hosted fleet scales onto the free executor with zero
    client-visible failures, and the new replica's pid differs from
    both the driver's and the first replica's."""
    dec, params = lm
    sc = _context(2)
    try:
        pol = AutoscalePolicy(min_replicas=1, max_replicas=2,
                              queue_wait_slo_s=0.05, up_cooldown_s=0.5,
                              down_cooldown_s=2.0, occupancy_low=0.999,
                              dead_after_s=10.0)
        f = cluster.serving_fleet(dec, params, replicas=1,
                                  placement="executors", sc=sc,
                                  engine_kw={"slots": 2},
                                  spawn_timeout=180)
        ctl = f.autoscale(policy=pol, interval=0.1)
        try:
            url = f.url("/v1/models/model:generate")
            errors = []
            outs = [None] * 16

            def client(i):
                try:
                    outs[i] = _post_with_retry(
                        url, {"prompt": [(i % 5) + 1, 2, 3],
                              "max_new_tokens": 16})
                except Exception as e:  # noqa: BLE001 - asserted
                    errors.append(repr(e))

            for _ in range(3):
                threads = [threading.Thread(target=client, args=(i,))
                           for i in range(16)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                if chaos.poll_until(lambda: _scaled_to(f, 2),
                                    timeout=30.0):
                    break
            assert errors == []
            assert _scaled_to(f, 2), \
                "burst must scale 1 -> 2 ({})".format(
                    ctl.events.events("autoscale_decision"))
            snap = f.reservation.serving_snapshot()
            pids = {info["host"]["pid"] for info in snap.values()}
            assert os.getpid() not in pids and len(pids) == 2
            for i in (0, 7, 15):
                if outs[i] is not None:
                    assert outs[i]["tokens"] == _solo(
                        dec, params, [(i % 5) + 1, 2, 3], 16)
            # idle -> retires back to 1 with zero loss
            assert chaos.poll_until(lambda: _scaled_to(f, 1),
                                    timeout=60.0)
            down = ctl.events.events("autoscale_scaled_down")
            assert down and down[-1]["drained_clean"]
        finally:
            f.stop()
    finally:
        sc.stop()


@pytest.mark.slow
@pytest.mark.chaos
def test_kill_serving_executor_failover_and_replacement(lm):
    """Whole-executor SIGKILL on the serving plane: the chaos point
    fires at the scoped replica's 3rd request, the lease expires, the
    router down-marks, the supervisor attributes the loss, and the
    autoscaler spawns a FENCED replacement under the same identity on
    the free executor — zero client-visible failures end to end, no
    restart-budget burn."""
    dec, params = lm
    fuse = tempfile.mktemp(prefix="tfos-chaos-fuse-")
    spec = ("kill_serving_executor_at_request=3,only=replica-0,"
            "fuse={}".format(fuse))
    sc = _context(2, extra_env={"TFOS_CHAOS": spec})
    try:
        pol = AutoscalePolicy(min_replicas=1, max_replicas=2,
                              dead_after_s=1.5,
                              down_cooldown_s=3600.0)
        f = cluster.serving_fleet(dec, params, replicas=1,
                                  placement="executors", sc=sc,
                                  engine_kw={"slots": 2},
                                  spawn_timeout=180, supervise=True)
        ctl = f.autoscale(policy=pol, interval=0.2)
        try:
            snap = f.reservation.serving_snapshot()
            old = snap["replica-0"]["host"]
            old_epoch = snap["replica-0"]["epoch"]
            url = f.url("/v1/models/model:generate")
            outs = []
            for i in range(8):
                outs.append(_post_with_retry(
                    url, {"prompt": [1, 2, (i % 5) + 1],
                          "max_new_tokens": 6}, attempts=40))
            assert len(outs) == 8, "zero client-visible failures"
            assert os.path.exists(fuse), "the kill must have fired"
            # replacement serves under the same identity, elsewhere,
            # with a NEWER fencing epoch than the corpse held
            info = f.reservation.serving_snapshot()["replica-0"]
            assert info["host"]["pid"] != old["pid"]
            assert info["host"]["executor"] != old["executor"]
            assert info["epoch"] > old_epoch
            assert ctl.counters.snapshot()["counts"][
                "replacements"] >= 1
            # the supervisor ATTRIBUTED the loss (observe + quiesce;
            # repair stayed the autoscaler's)
            lost = f.supervisor.events.events("serving_replica_lost")
            assert lost and lost[-1]["replica"] == "replica-0"
            assert not f.supervisor.events.events("engine_restarted"), \
                "no restart-budget burn on the fenced corpse"
            # outputs stay bitwise through the whole episode
            for i, out in enumerate(outs):
                assert out["tokens"] == _solo(
                    dec, params, [1, 2, (i % 5) + 1], 6)
        finally:
            f.stop()
    finally:
        sc.stop()
        try:
            os.unlink(fuse)
        except OSError:
            pass
