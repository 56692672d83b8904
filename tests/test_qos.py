"""Multi-tenant QoS plane (PR 18): priority classes, weighted-fair
admission, per-tenant quotas — across the engine, the router, and the
autoscaler.

Three layers, matching the module's design:

- PURE policy — ``qos.FairScheduler`` table tests (deficit catch-up,
  weight-ratio convergence within 10% over 1k rounds, strict priority
  ordering, empty/one-tenant degeneracy), ``TokenBucket`` /
  ``QuotaTable`` with injected clocks (honest Retry-After, post-paid
  debt, admission never charges), identity validation, and the
  router's pure parse helpers.
- ENGINE integration — submit-time validation and defaults, FIFO
  degeneracy for a single tenant, high-class queue jump, class
  preemption with bitwise continuation at temp=0, engine-side quota
  429, tenant gauges on ``load_stats()``, concurrent multi-tenant
  admission (bitwise solo parity under thread churn), and the
  labeled-metrics live-scrape grammar check (reusing
  test_observability's strict OpenMetrics parser).
- FLEET — the router's own quota gate, a replica quota-429 passing
  through VERBATIM (no failover: quota is policy, not load), dedup
  replay never double-charging, burst spreading, the digest-driven
  pre-warm trigger, and the autoscaler's LOW-only-backlog tolerance.

The two-tenant antagonist e2e (aggressive tenant cannot move a quiet
tenant's p99 beyond a bounded factor; quota 429s carry an honest
Retry-After; preempted LOW continuations are bitwise) runs under the
chaos marker — collected by ``make chaos``, serial, never under
tier-1's concurrent load.
"""

import json
import math
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_observability
from tensorflowonspark_tpu import (chaos, fleet, generation, qos,
                                   reservation, serving, tracing)
from tensorflowonspark_tpu.autoscale import (AutoscalePolicy,
                                             ScaleDecision, decide)
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


def _first_token_times(handles):
    """time.monotonic() of each handle's FIRST streamed token —
    observable admission order (slot-constrained engines admit in
    plan order, and the first token lands at admission's prefill)."""
    times = [None] * len(handles)

    def watch(i):
        # no break: abandoning a stream CANCELS the request by design
        for _tok in handles[i].stream(120):
            if times[i] is None:
                times[i] = time.monotonic()
        handles[i].result(120)

    threads = [threading.Thread(target=watch, args=(i,))
               for i in range(len(handles))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert all(t is not None for t in times)
    return times


# -- identity validation ---------------------------------------------------


def test_validate_tenant_grammar_and_default():
    assert qos.validate_tenant(None) == qos.DEFAULT_TENANT == "default"
    assert qos.validate_tenant("team-a.prod_1") == "team-a.prod_1"
    assert qos.validate_tenant("A" * 64) == "A" * 64
    for bad in ("", "-x", ".x", "a b", "a\nb", 'a"b', "x" * 65, 3,
                ["a"], "tenant!"):
        with pytest.raises((TypeError, ValueError)):
            qos.validate_tenant(bad)


def test_validate_priority_and_rank():
    assert qos.validate_priority(None) == "normal"
    assert qos.validate_priority("HIGH") == "high"
    for bad in ("urgent", "", 1, None):
        if bad is None:
            continue
        with pytest.raises((TypeError, ValueError)):
            qos.validate_priority(bad)
    assert qos.priority_rank("high") < qos.priority_rank("normal") \
        < qos.priority_rank("low")
    # rank is a sort key, never a gate: unknowns rank as normal
    assert qos.priority_rank("???") == qos.priority_rank("normal")


def test_policy_from_spec_coercion_and_validation():
    p = qos.QosPolicy.from_spec(None)
    assert p.weight("anyone") == 1.0 and p.quota("anyone") is None
    p2 = qos.QosPolicy.from_spec(
        {"weights": {"a": 3}, "quotas": {"a": 5.0}, "burst_s": 1.0})
    assert p2.weight("a") == 3.0 and p2.quota("a") == 5.0
    assert qos.QosPolicy.from_spec(p2) is p2
    with pytest.raises(ValueError):
        qos.QosPolicy(weights={"a": 0})
    with pytest.raises(ValueError):
        qos.QosPolicy(quotas={"a": -1})
    with pytest.raises(TypeError):
        qos.QosPolicy.from_spec("fast")


# -- FairScheduler table tests ---------------------------------------------


def test_select_empty_and_one_tenant_degeneracy():
    s = qos.FairScheduler()
    assert s.select([]) is None
    # one tenant: always index 0, whatever has been charged — the
    # engine's queue order (FIFO) is untouched, the pre-QoS behavior
    for _ in range(10):
        assert s.select([("solo", "normal")]) == 0
        s.charge("solo", 1.0, backlogged={"solo"})
    assert abs(s.deficit("solo")) < 1e-9  # self-service is zero-sum


def test_priority_strictly_outranks_deficit():
    s = qos.FairScheduler()
    # pile deficit onto "a" by over-serving "b" while both backlogged
    for _ in range(50):
        s.charge("b", 1.0, backlogged={"a", "b"})
    assert s.deficit("a") > 20
    cands = [("a", "low"), ("b", "normal"), ("c", "high")]
    assert cands[s.select(cands)] == ("c", "high")
    cands = [("a", "normal"), ("b", "high")]
    assert cands[s.select(cands)] == ("b", "high")
    # within one class the starved tenant wins
    cands = [("a", "normal"), ("b", "normal")]
    assert cands[s.select(cands)] == ("a", "normal")


def test_deficit_starved_tenant_provably_catches_up():
    s = qos.FairScheduler()
    # force 10 admissions for "a" while "b" waits (equal weights):
    # b is owed exactly half the service it watched go by
    for _ in range(10):
        s.charge("a", 1.0, backlogged={"a", "b"})
    assert s.deficit("b") == pytest.approx(5.0)
    assert s.deficit("a") == pytest.approx(-5.0)
    # now let the scheduler choose: b must win until it has caught up,
    # then service alternates (exact fairness from then on)
    wins = []
    for _ in range(10):
        cands = [("a", "normal"), ("b", "normal")]
        w = cands[s.select(cands)][0]
        wins.append(w)
        s.charge(w, 1.0, backlogged={"a", "b"})
    assert wins[:10] == ["b"] * 10 or wins.count("b") >= 7
    assert abs(s.deficit("a") + s.deficit("b")) < 1e-9  # zero-sum


def test_weighted_shares_within_10pct_over_1k_rounds():
    policy = qos.QosPolicy(weights={"heavy": 3.0, "light": 1.0})
    s = qos.FairScheduler(policy)
    wins = {"heavy": 0, "light": 0}
    for _ in range(1000):
        cands = [("heavy", "normal"), ("light", "normal")]
        w = cands[s.select(cands)][0]
        wins[w] += 1
        s.charge(w, 1.0, backlogged={"heavy", "light"})
    ratio = wins["heavy"] / wins["light"]
    assert abs(ratio - 3.0) / 3.0 <= 0.10, wins
    # and with unequal costs (paged engines charge in blocks): the
    # SERVICE ratio converges, not the admission count
    s2 = qos.FairScheduler(policy)
    service = {"heavy": 0.0, "light": 0.0}
    costs = {"heavy": 2.0, "light": 3.0}
    for _ in range(1000):
        cands = [("heavy", "normal"), ("light", "normal")]
        w = cands[s2.select(cands)][0]
        service[w] += costs[w]
        s2.charge(w, costs[w], backlogged={"heavy", "light"})
    ratio = service["heavy"] / service["light"]
    assert abs(ratio - 3.0) / 3.0 <= 0.10, service


def test_charge_zero_sum_forget_and_credit_bound():
    s = qos.FairScheduler()
    rng = np.random.RandomState(3)
    tenants = ["a", "b", "c"]
    for _ in range(200):
        w = tenants[rng.randint(3)]
        s.charge(w, float(rng.randint(1, 5)), backlogged=set(tenants))
    assert abs(sum(s.snapshot().values())) < 1e-6
    s.forget("a")
    assert "a" not in s.snapshot()
    b = qos.FairScheduler(credit_bound=2.0)
    for _ in range(100):
        b.charge("x", 1.0, backlogged={"x", "y"})
    assert b.deficit("y") == pytest.approx(2.0)
    assert b.deficit("x") == pytest.approx(-2.0)


def test_idle_tenants_earn_no_credit():
    s = qos.FairScheduler()
    # y exists but is NOT backlogged: it must earn nothing while x
    # serves itself
    for _ in range(10):
        s.charge("x", 1.0, backlogged={"x"})
    assert s.deficit("y") == 0.0


# -- TokenBucket / QuotaTable ----------------------------------------------


def test_token_bucket_burst_debt_and_honest_retry_after():
    b = qos.TokenBucket(rate=10.0, burst_s=2.0, now=0.0)
    assert b.capacity == 20.0 and b.admissible(0.0)
    b.charge(30, now=0.0)  # post-paid: may go into debt
    assert b.level == pytest.approx(-10.0)
    assert not b.admissible(0.0)
    # honest: exactly the seconds until the level crosses zero
    assert b.retry_after(0.0) == pytest.approx(1.0)
    assert not b.admissible(0.5)
    assert b.admissible(1.01)
    b.refill(1000.0)
    assert b.level == pytest.approx(20.0)  # capped at capacity


def test_quota_table_admit_never_charges_post_paid_exact():
    clock = [0.0]
    qt = qos.QuotaTable(
        qos.QosPolicy(quotas={"t": 10.0}, burst_s=1.0),
        clock=lambda: clock[0])
    for _ in range(5):
        qt.admit("t")  # admission checks are free
    assert qt.snapshot()["t"] == pytest.approx(10.0)
    qt.charge("t", 25)
    assert qt.snapshot()["t"] == pytest.approx(-15.0)
    with pytest.raises(qos.QuotaExceeded) as err:
        qt.admit("t")
    assert err.value.tenant == "t"
    assert err.value.retry_after == pytest.approx(1.5)
    clock[0] = 1.4
    with pytest.raises(qos.QuotaExceeded):
        qt.admit("t")
    clock[0] = 1.6
    qt.admit("t")  # refilled past zero: admissible again
    # a dedup replay delivers nothing new -> charges nothing
    level = qt.snapshot()["t"]
    qt.charge("t", 0)
    assert qt.snapshot()["t"] == level


def test_quota_table_unlimited_tenant_has_no_bucket():
    qt = qos.QuotaTable(qos.QosPolicy())
    qt.admit("anyone")
    qt.charge("anyone", 10 ** 9)
    qt.admit("anyone")
    assert qt.snapshot() == {}


def test_quota_table_restore_never_forgives_debt():
    """Warm-standby takeover (PR 19): the promoted router restores the
    dead leader's quota snapshot so a tenant in debt cannot launder
    its backlog through the failover. Restore keeps the LOWER of the
    snapshot and the live level, clamps to capacity (a stale over-full
    snapshot must not mint burst credit), and skips tenants without a
    configured quota."""
    policy = qos.QosPolicy(quotas={"debtor": 10.0, "saver": 10.0},
                           burst_s=1.0)
    clock = [0.0]
    qt = qos.QuotaTable(policy, clock=lambda: clock[0])
    # the leader's last known state: debtor deep in debt
    leader_state = {"debtor": -15.0, "saver": 4.0,
                    "overfull": 999.0, "unlimited-tenant": 1.0}
    qt.restore(leader_state)
    assert qt.snapshot()["debtor"] == pytest.approx(-15.0)
    assert qt.snapshot()["saver"] == pytest.approx(4.0)
    assert "unlimited-tenant" not in qt.snapshot()  # no quota, no bucket
    with pytest.raises(qos.QuotaExceeded):
        qt.admit("debtor")  # the debt followed the failover
    qt.admit("saver")
    # restoring an over-full level clamps to capacity
    qt2 = qos.QuotaTable(policy, clock=lambda: clock[0])
    qt2.restore({"saver": 999.0})
    assert qt2.snapshot()["saver"] == pytest.approx(10.0)
    # restoring ONTO live charges keeps the lower level (never up)
    qt2.charge("saver", 8)
    qt2.restore({"saver": 10.0})
    assert qt2.snapshot()["saver"] == pytest.approx(2.0)


# -- engine integration ----------------------------------------------------


def test_submit_validates_identity_and_default_is_unchanged(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        with pytest.raises(ValueError):
            eng.submit([1, 2], 2, tenant="bad tenant!")
        with pytest.raises(ValueError):
            eng.submit([1, 2], 2, priority="urgent")
        prompt = [1, 2, 3]
        got = eng.submit(prompt, 6).result(120)
        assert got == _solo(dec, params, prompt, 6)
        tallies = eng.qos_tallies()
        assert tallies["admitted"] == {("default", "normal"): 1}


def test_single_tenant_fifo_degeneracy(lm):
    """One tenant, one class: admission must be the exact FIFO order
    the engine always had (the QoS scan degenerates to the old scan)."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        handles = [eng.submit([1 + i, 2, 3], 3) for i in range(4)]
        times = _first_token_times(handles)
    assert times == sorted(times)


def test_high_priority_jumps_the_queue(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        blocker = eng.submit([1, 2, 3, 4], 16)  # holds the only slot
        norm = eng.submit([5, 6], 4)
        high = eng.submit([7, 8], 4, tenant="vip", priority="high")
        t_norm, t_high = _first_token_times([norm, high])
        blocker.result(120)
    assert t_high < t_norm
    # outputs are untouched by scheduling order
    assert norm.result(1) == _solo(dec, params, [5, 6], 4)
    assert high.result(1) == _solo(dec, params, [7, 8], 4)


def test_class_preemption_bitwise_continuation(lm):
    """HIGH arrival with every slot held by LOW: the youngest LOW
    admission is preempted through the PR 8 machinery and its
    continuation must be bitwise at temp=0."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=16, prefix_cache=False) as eng:
        lows = [eng.submit([1 + i, 2, 3], 24, tenant="bg",
                           priority="low") for i in range(2)]
        # both LOW sequences must be IN slots before the HIGH arrives
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            stats = eng.load_stats()
            if stats["slot_occupancy"] == 2:
                break
            time.sleep(0.005)
        high = eng.submit([9, 8, 7], 4, tenant="vip", priority="high")
        assert high.result(120) == _solo(dec, params, [9, 8, 7], 4)
        for i, h in enumerate(lows):
            assert h.result(120) == \
                _solo(dec, params, [1 + i, 2, 3], 24)
        tallies = eng.qos_tallies()
    assert sum(tallies["preemptions"].values()) >= 1
    assert ("bg", "low") in tallies["preemptions"]


def test_engine_quota_429_and_recovery(lm):
    dec, params = lm
    policy = {"quotas": {"capped": 2.0}, "burst_s": 1.0}
    with serving.DecodeEngine(dec, params, slots=2,
                              qos_policy=policy) as eng:
        # capacity 2, generate 10 -> decisive debt even after the
        # slow-generation refill (2 t/s); post-paid, so the FIRST
        # request always runs
        got = eng.submit([1, 2], 10, tenant="capped").result(120)
        assert got == _solo(dec, params, [1, 2], 10)
        with pytest.raises(qos.QuotaExceeded) as err:
            eng.submit([3, 4], 2, tenant="capped")
        assert err.value.retry_after >= 1.0
        # other tenants are untouched by one tenant's debt
        assert eng.submit([5, 6], 3).result(120) == \
            _solo(dec, params, [5, 6], 3)
        assert eng.qos_tallies()["quota_rejections"] == {"capped": 1}


def test_load_stats_carry_tenant_and_class_gauges(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=1) as eng:
        blocker = eng.submit([1, 2, 3], 12, tenant="acme")
        queued = eng.submit([4, 5], 2, tenant="acme", priority="low")
        stats = eng.load_stats()
        blocker.result(120)
        queued.result(120)
    assert set(stats["queue_by_class"]) == set(qos.PRIORITIES)
    assert stats["queue_by_class"]["low"] >= 1
    acme = stats["tenants"]["acme"]
    assert acme["queued"] + acme["active"] >= 2


def test_concurrent_multitenant_admission_race_free(lm):
    """Six tenants submitting from six threads against a 4-slot paged
    engine: every output bitwise solo, every admission tallied — the
    QoS scan lives inside the same race-free plan_admission snapshot
    PR 14 pinned."""
    dec, params = lm
    prompts = {}
    results = {}
    with serving.DecodeEngine(dec, params, slots=4, kv_block_size=8,
                              kv_blocks=64) as eng:

        def client(t):
            tenant = "tenant-{}".format(t)
            rng = np.random.RandomState(40 + t)
            out = []
            for k in range(4):
                prompt = [int(x) for x in rng.randint(1, V, 3 + t % 3)]
                prio = qos.PRIORITIES[(t + k) % 3]
                h = eng.submit(prompt, 4, tenant=tenant, priority=prio)
                out.append((prompt, h.result(120)))
            results[tenant] = out

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120)
        tallies = eng.qos_tallies()
    assert len(results) == 6
    for tenant, out in results.items():
        for prompt, got in out:
            assert got == _solo(dec, params, prompt, 4), tenant
    # every request admitted exactly once, plus one RE-admission per
    # preempted-then-continued sequence
    assert sum(tallies["admitted"].values()) == \
        24 + sum(tallies["preemptions"].values())


def test_qos_plan_stays_cheap(lm):
    """The whole admission plan is timed as stage ``qos_plan``; its
    budget is <50us/plan — asserted here LOOSELY (1-core CI box,
    timer overhead)."""
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2) as eng:
        hs = [eng.submit([1 + i, 2], 4,
                         tenant="t{}".format(i % 3)) for i in range(8)]
        for h in hs:
            h.result(120)
        plan_ms = eng.timers.per_ms().get("qos_plan")
        assert eng.timers.counts().get("qos_plan", 0) > 0
    assert plan_ms is not None
    assert plan_ms < 5.0  # 5ms >> the 50us budget the profiler prints


# -- labeled metrics: live-scrape grammar ----------------------------------


def test_qos_metric_families_catalogued():
    for fam, (ftype, labels) in {
            "tfos_qos_admitted": ("counter", "tenant,class"),
            "tfos_qos_preemptions": ("counter", "tenant,class"),
            "tfos_qos_quota_rejections": ("counter", "tenant"),
            "tfos_qos_tokens": ("counter", "tenant"),
    }.items():
        assert tracing.METRIC_FAMILIES[fam][0] == ftype, fam
        assert tracing.METRIC_FAMILIES[fam][1] == labels, fam
    for prio in qos.PRIORITIES:
        fam = "tfos_qos_queue_wait_{}_seconds".format(prio)
        assert tracing.METRIC_FAMILIES[fam][0] == "histogram", fam


def test_live_scrape_renders_labeled_qos_families(lm):
    dec, params = lm
    policy = {"quotas": {"limited": 1.0}, "burst_s": 1.0}
    eng = serving.DecodeEngine(dec, params, slots=2, qos_policy=policy)
    srv = serving.ModelServer(None, name="lm", engine=eng, port=0)
    host, port = srv.start()
    url = "http://%s:%d" % (host, port)
    try:
        def gen(payload):
            req = urllib.request.Request(
                url + "/v1/models/lm:generate",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as r:
                return r.status, json.loads(r.read()), dict(r.headers)

        status, _, _ = gen({"prompt": [1, 2, 3], "max_new_tokens": 3,
                            "tenant": "alpha", "priority": "high"})
        assert status == 200
        status, _, _ = gen({"prompt": [4, 5], "max_new_tokens": 3,
                            "tenant": "limited"})
        assert status == 200  # post-paid: first request runs, debt
        with pytest.raises(urllib.error.HTTPError) as err:
            gen({"prompt": [6], "max_new_tokens": 1,
                 "tenant": "limited"})
        assert err.value.code == 429
        assert int(err.value.headers["Retry-After"]) >= 1
        assert json.loads(err.value.read())["kind"] == "QuotaExceeded"
        # malformed tenant: the authoritative 400
        with pytest.raises(urllib.error.HTTPError) as err:
            gen({"prompt": [6], "max_new_tokens": 1, "tenant": "a b"})
        assert err.value.code == 400

        with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
            text = r.read().decode()
        types, samples = test_observability._parse_openmetrics(text)
        for fam in ("tfos_qos_admitted", "tfos_qos_tokens",
                    "tfos_qos_quota_rejections"):
            assert types[fam] == "counter", fam
        assert types["tfos_qos_queue_wait_high_seconds"] == "histogram"
        by_fam = {}
        for fam, labels, value in samples:
            by_fam.setdefault(fam, []).append((labels, value))
        admitted = dict(by_fam["tfos_qos_admitted"])
        assert admitted['{tenant="alpha",class="high"}'] == 1.0
        assert admitted['{tenant="limited",class="normal"}'] == 1.0
        rejections = dict(by_fam["tfos_qos_quota_rejections"])
        assert rejections['{tenant="limited"}'] == 1.0
        tokens = dict(by_fam["tfos_qos_tokens"])
        assert tokens['{tenant="alpha"}'] == 3.0
    finally:
        srv.stop()


# -- router / fleet --------------------------------------------------------


def _post(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_router_qos_inputs_lenient_and_delivered_tokens():
    gi = fleet.FleetRouter._qos_inputs
    assert gi(json.dumps({"tenant": "t1", "priority": "LOW"}).encode()) \
        == ("t1", "low")
    assert gi(b"not json") == ("default", "normal")
    assert gi(json.dumps({"tenant": "a b", "priority": 7}).encode()) \
        == ("default", "normal")  # upstream answers the 400
    dt = fleet.FleetRouter._delivered_tokens
    assert dt(json.dumps({"tokens": [1, 2, 3]}).encode()) == 3
    assert dt(json.dumps({"tokens": [[1, 2], [3]]}).encode()) == 3
    assert dt(b"garbage") == 0
    assert dt(json.dumps({"tokens": "nope"}).encode()) == 0


def test_router_quota_gate_refuses_in_one_hop(lm):
    dec, params = lm
    with fleet.ServingFleet(
            dec, params, replicas=1, name="lm",
            engine_kw={"slots": 2},
            router_kw={"qos": {"quotas": {"flood": 2.0},
                               "burst_s": 1.0}}) as f:
        url = f.url("/v1/models/lm:generate")
        status, body = _post(url, {"prompt": [1, 2], "max_new_tokens": 4,
                                   "tenant": "flood"})
        assert status == 200  # post-paid: charged the 4 delivered
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url, {"prompt": [3], "max_new_tokens": 1,
                        "tenant": "flood"})
        assert err.value.code == 429
        assert int(err.value.headers["Retry-After"]) >= 1
        payload = json.loads(err.value.read())
        assert payload["kind"] == "QuotaExceeded"
        assert payload["tenant"] == "flood"
        counts = f.router.counters.snapshot()["counts"]
        assert counts.get("quota_rejections") == 1
        # other tenants sail through the gate
        status, _ = _post(url, {"prompt": [5], "max_new_tokens": 1})
        assert status == 200


def test_replica_quota_429_passes_through_verbatim(lm):
    """A replica's quota refusal is POLICY, not load: the router must
    NOT fail over (N replicas would multiply the tenant's effective
    quota by N) and must surface the replica's honest Retry-After."""
    dec, params = lm
    with fleet.ServingFleet(
            dec, params, replicas=1, name="lm",
            engine_kw={"slots": 2,
                       "qos_policy": {"quotas": {"capped": 2.0},
                                      "burst_s": 1.0}}) as f:
        url = f.url("/v1/models/lm:generate")
        status, _ = _post(url, {"prompt": [1, 2], "max_new_tokens": 6,
                                "tenant": "capped"})
        assert status == 200
        t0 = time.monotonic()
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(url, {"prompt": [3], "max_new_tokens": 1,
                        "tenant": "capped"})
        wall = time.monotonic() - t0
        assert err.value.code == 429
        assert json.loads(err.value.read())["kind"] == "QuotaExceeded"
        assert int(err.value.headers["Retry-After"]) >= 1
        # verbatim pass-through: no retry loop burned on a policy
        # refusal (a retriable 429 would spin the failover budget)
        assert wall < 2.0
        counts = f.router.counters.snapshot()["counts"]
        assert counts.get("failovers", 0) == 0
        # the replica behaved correctly: still routable, other
        # tenants unaffected
        status, _ = _post(url, {"prompt": [9], "max_new_tokens": 1})
        assert status == 200


def test_dedup_replay_never_double_charges(lm):
    dec, params = lm
    policy = {"quotas": {"t": 100.0}, "burst_s": 2.0}
    eng = serving.DecodeEngine(dec, params, slots=2, qos_policy=policy)
    srv = serving.ModelServer(None, name="lm", engine=eng, port=0)
    host, port = srv.start()
    url = "http://%s:%d/v1/models/lm:generate" % (host, port)
    try:
        def gen():
            req = urllib.request.Request(
                url, data=json.dumps(
                    {"prompt": [1, 2, 3], "max_new_tokens": 4,
                     "tenant": "t"}).encode(),
                headers={"Content-Type": "application/json",
                         "X-TFOS-Request-Id": "dup-1"})
            with urllib.request.urlopen(req, timeout=120) as r:
                return json.loads(r.read())

        first = gen()
        level = eng._quota.snapshot()["t"]
        replay = gen()  # dedup hit: replayed verbatim, generates nothing
        assert replay == first
        assert eng._quota.snapshot()["t"] == level
        assert eng.qos_tallies()["tokens"]["t"] == 4
    finally:
        srv.stop()


def _bare_router(**kw):
    resv = reservation.Server(0)
    resv.start(host="127.0.0.1")
    return resv, fleet.FleetRouter(resv, name="lm", **kw)


def test_spread_tenant_demotes_majority_leader():
    resv, router = _bare_router()
    try:
        views = [
            {"replica_id": "r0",
             "tenants": {"t": {"queued": 5, "active": 1}}},
            {"replica_id": "r1",
             "tenants": {"t": {"queued": 1, "active": 0}}},
            {"replica_id": "r2", "tenants": {}},
        ]
        order = router._spread_tenant("t", ["r0", "r1", "r2"], views)
        assert order == ["r2", "r0", "r1"]
        counts = router.counters.snapshot()["counts"]
        assert counts.get("tenant_spreads") == 1
        # no strict majority -> untouched (one queued request is not
        # a burst either)
        views[0]["tenants"]["t"] = {"queued": 2, "active": 0}
        views[1]["tenants"]["t"] = {"queued": 2, "active": 0}
        assert router._spread_tenant("t", ["r0", "r1", "r2"], views) \
            == ["r0", "r1", "r2"]
        assert router._spread_tenant(
            "u", ["r0", "r1"],
            [{"replica_id": "r0",
              "tenants": {"u": {"queued": 1, "active": 0}}},
             {"replica_id": "r1", "tenants": {}}]) == ["r0", "r1"]
    finally:
        router.stop()
        resv.stop()


def test_prewarm_trigger_ships_once_per_pair(monkeypatch):
    resv, router = _bare_router()
    calls = []
    started = threading.Event()
    release = threading.Event()

    def fake_http(addr, method, path, body=None, **kw):
        calls.append((tuple(addr), method, path,
                      json.loads(body.decode())))
        started.set()
        assert release.wait(30)
        return 200, b"{}", {}

    monkeypatch.setattr(fleet, "_http_request", fake_http)
    try:
        snapshot = {
            "warm": {"addr": ("127.0.0.1", 1111), "epoch": 3},
            "cold": {"addr": ("127.0.0.1", 2222), "epoch": 5},
        }
        router._maybe_prewarm({"warm"}, "cold", [1, 2, 3], "sess-1",
                              trace=7, snapshot=snapshot)
        assert started.wait(30)
        # in-flight dedup: the same (warm, cold) pair never ships twice
        # concurrently
        router._maybe_prewarm({"warm"}, "cold", [1, 2, 3], "sess-1",
                              trace=8, snapshot=snapshot)
        time.sleep(0.05)
        assert len(calls) == 1
        counts = router.counters.snapshot()["counts"]
        assert counts.get("prefix_prewarms") == 1
        addr, method, path, body = calls[0]
        assert addr == ("127.0.0.1", 1111)  # POSTed at the WARM side
        assert method == "POST" and path.endswith("lm:prefill")
        assert body["prompt"] == [1, 2, 3]
        assert body["src_epoch"] == 3
        assert body["ship"] == {"addr": "127.0.0.1:2222",
                                "replica_id": "cold", "epoch": 5}
        # degenerate triggers are no-ops
        router._maybe_prewarm(set(), "cold", [1], None, 9, snapshot)
        router._maybe_prewarm({"cold"}, "cold", [1], None, 9, snapshot)
        router._maybe_prewarm({"gone"}, "cold", [1], None, 9, snapshot)
        assert len(calls) == 1
    finally:
        release.set()
        time.sleep(0.02)
        router.stop()
        resv.stop()


# -- autoscale: per-priority breach view -----------------------------------


def _as_view(rid="r0", queue_depth=0, qwait=0.0, queue_by_class=None):
    view = {"replica_id": rid, "age": 0.1, "alive": True,
            "draining": False, "queue_depth": queue_depth,
            "slot_occupancy": 0, "slots": 4,
            "queue_wait_ewma_s": qwait, "kv_blocks_free": None,
            "kv_blocks_total": None, "completed": 10,
            "ttft_p99_s": None, "executor": None}
    if queue_by_class is not None:
        view["queue_by_class"] = queue_by_class
    return view


def _as_policy():
    return AutoscalePolicy(min_replicas=1, max_replicas=3,
                           queue_wait_slo_s=0.5, occupancy_high=0.85,
                           occupancy_low=0.25, up_cooldown_s=2.0,
                           down_cooldown_s=10.0, dead_after_s=3.0)


def test_autoscale_low_only_backlog_tolerated():
    views = [_as_view(queue_depth=3, qwait=1.0,
                      queue_by_class={"high": 0, "normal": 0, "low": 3})]
    d = decide(_as_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.HOLD
    assert "LOW-class-only" in d.reason
    assert d.evidence["queue_by_class"]["low"] == 3


def test_autoscale_high_class_breach_scales_up():
    views = [_as_view(queue_depth=3, qwait=1.0,
                      queue_by_class={"high": 1, "normal": 0, "low": 2})]
    d = decide(_as_policy(), views, {}, now=100.0)
    assert d.action == ScaleDecision.UP
    # legacy replicas (no class schema) keep the legacy scale-up: the
    # tally must account for the WHOLE queue before LOW-only holds
    d = decide(_as_policy(), [_as_view(queue_depth=3, qwait=1.0)],
               {}, now=100.0)
    assert d.action == ScaleDecision.UP


# -- chaos e2e: two-tenant antagonist --------------------------------------


def _pctl(walls, q):
    walls = sorted(walls)
    return walls[min(len(walls) - 1,
                     int(math.ceil(q * len(walls))) - 1)]


@pytest.mark.slow
@pytest.mark.chaos
def test_antagonist_cannot_starve_quiet_tenant(lm):
    """The PR's acceptance e2e, serial under ``make chaos``:

    - a flooding LOW-class antagonist cannot move a HIGH-class quiet
      tenant's p99 beyond a bounded factor of its solo baseline
      (class preemption + weighted-fair admission);
    - the antagonist's quota 429s carry an honest positive
      Retry-After;
    - every preempted LOW continuation is bitwise at temp=0.
    """
    dec, params = lm
    # the antagonist is UNLIMITED (sustained slot pressure is the
    # point); a separate tiny-quota tenant pins the honest-429 leg
    policy = {"quotas": {"burst": 2.0}, "burst_s": 1.0}
    quiet_prompts = [[1 + (i % 7), 2, 3] for i in range(25)]

    def quiet_pass(eng):
        walls = []
        for p in quiet_prompts:
            t0 = time.monotonic()
            got = eng.submit(p, 12, tenant="quiet",
                             priority="high").result(600)
            walls.append(time.monotonic() - t0)
            assert got == _solo(dec, params, p, 12)
        return walls[5:]  # drop warmup

    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=48, qos_policy=policy) as eng:
        solo = quiet_pass(eng)

        stop = threading.Event()
        low_outputs = []
        out_lock = threading.Lock()

        def antagonist(i):
            rng = np.random.RandomState(70 + i)
            while not stop.is_set():
                prompt = [int(x) for x in rng.randint(1, V, 4)]
                try:
                    got = eng.submit(prompt, 24, tenant="antag",
                                     priority="low").result(600)
                    with out_lock:
                        low_outputs.append((prompt, got))
                except Exception:  # noqa: BLE001 - teardown race
                    break

        threads = [threading.Thread(target=antagonist, args=(i,),
                                    daemon=True) for i in range(2)]
        for t in threads:
            t.start()
        time.sleep(0.3)  # flood reaches steady state
        flooded = quiet_pass(eng)

        # quota 429s carry an honest Retry-After, measured mid-flood:
        # capacity 2 at 2 t/s, deliver 10 -> decisive debt even after
        # the slow-generation refill; the refusal names the exact
        # refill horizon, and waiting it out readmits
        got = eng.submit([9, 9, 9], 10, tenant="burst").result(600)
        assert got == _solo(dec, params, [9, 9, 9], 10)
        with pytest.raises(qos.QuotaExceeded) as err:
            eng.submit([9, 9], 1, tenant="burst")
        assert 1.0 <= err.value.retry_after <= 10.0
        time.sleep(min(err.value.retry_after, 6.0) + 0.3)
        assert eng.submit([9, 8], 1, tenant="burst").result(600) == \
            _solo(dec, params, [9, 8], 1)

        stop.set()
        for t in threads:
            t.join(600)
        tallies = eng.qos_tallies()

    # bounded interference: HIGH quiet traffic preempts straight into
    # a slot, so its p99 tracks solo within the acceptance factor
    # (+50ms absolute grace for scheduler jitter on a 1-core box)
    assert _pctl(flooded, 0.99) <= 1.5 * _pctl(solo, 0.99) + 0.05, \
        (sorted(solo), sorted(flooded))
    assert tallies["quota_rejections"].get("burst", 0) >= 1
    # preemptions happened, and every completed LOW output — the
    # preempted ones included — is bitwise solo at temp=0
    assert sum(tallies["preemptions"].values()) >= 1
    assert low_outputs
    for prompt, got in low_outputs:
        assert got == _solo(dec, params, prompt, 24)
