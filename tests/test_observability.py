"""Unified observability plane (tracing.py PR 5), pinned layer by layer.

- :class:`tracing.Histogram` — log-bucket quantile error bounds against
  exact percentiles on known distributions.
- OpenMetrics exposition — a STRICT line-grammar parse of a live
  ModelServer's ``GET /metrics`` (TYPE-before-samples, sample syntax,
  ``# EOF`` terminator, cumulative buckets), catalog membership
  (every rendered family must be in ``tracing.METRIC_FAMILIES`` — the
  code half of the ``make metrics-lint`` drift gate), and counter
  monotonicity across scrapes.
- The published-number contract: the p99 a scrape's buckets imply must
  match the registry quantile bench.py publishes, to within bucket
  resolution.
- BEAT-piggybacked snapshot merge over the REAL reservation wire with
  two executors, plus the driver-side stats endpoint's labeled series.
- ``SupervisedCluster.metrics()`` on a real 2-executor cluster.
- FlightRecorder + scripts/trace_dump.py — Perfetto-loadable Chrome
  trace JSON schema: every span has pid/tid/ts/dur, and each request's
  queue/prefill/decode spans nest inside its admit->finish envelope.
- Supervisor incident evidence: a classified failure carries the
  executor's beat-carried metrics snapshot and the flight-recorder
  tail ([chaos] the feeder_stall e2e drives it through a real stalled
  consumer).
- EventLog ring bound + dropped counter; idempotent
  ``start_profiler_server``; ``scripts/metrics_lint.py`` green.
"""

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import cloudpickle
import numpy as np
import pytest

from tensorflowonspark_tpu import (chaos, cluster, metrics_report,
                                   reservation, serving, supervisor,
                                   tracing)
from tensorflowonspark_tpu.engine import Context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Executor processes cannot import this test module, so its map_funs
# must ship by value (the engine's cloudpickle serializer honors this).
cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture(autouse=True)
def _disarmed(monkeypatch):
    monkeypatch.delenv(chaos.ENV_VAR, raising=False)
    chaos.disarm()
    yield
    chaos.disarm()


# -- Histogram -------------------------------------------------------------

def test_histogram_quantile_error_bounds():
    """quantile(q) must land within one bucket (a factor of ``growth``)
    of the exact percentile, across distribution shapes spanning the
    bucket range."""
    rng = np.random.RandomState(0)
    for name, samples in (
            ("uniform_ms", rng.uniform(0.001, 0.5, size=4000)),
            ("lognormal", rng.lognormal(mean=-3, sigma=1.5, size=4000)),
            ("bimodal", np.concatenate([
                rng.uniform(0.002, 0.004, 2000),
                rng.uniform(1.0, 2.0, 2000)]))):
        hist = tracing.Histogram()
        for x in samples:
            hist.observe(float(x))
        for q in (0.5, 0.95, 0.99):
            approx = hist.quantile(q)
            # inverted-CDF percentile: the k-th order statistic, the
            # same discrete convention the histogram ranks by (linear
            # interpolation would invent values no sample is near on
            # the bimodal gap)
            exact = float(np.percentile(samples, q * 100,
                                        method="inverted_cdf"))
            ratio = approx / exact
            assert 1.0 / hist.growth <= ratio <= hist.growth, \
                (name, q, approx, exact)


def test_histogram_edges_and_degenerate_inputs():
    hist = tracing.Histogram()
    assert hist.quantile(0.5) is None  # empty
    hist.observe(0.25)
    assert hist.quantile(0.0) == 0.25  # single value: exact
    assert hist.quantile(1.0) == 0.25
    # out-of-range clamps into edge buckets but min/max stay honest
    hist.observe(1e-9)
    hist.observe(1e6)
    assert hist.count == 3
    assert hist.quantile(0.0) == 1e-9
    assert hist.quantile(1.0) == 1e6
    snap = hist.snapshot()
    assert sum(snap["counts"]) == 3 and snap["n"] == 3


def test_histogram_merge_sums_buckets():
    ra, rb = tracing.MetricsRegistry(), tracing.MetricsRegistry()
    for v in (0.01, 0.02, 0.04):
        ra.histogram("tfos_serving_ttft_seconds").observe(v)
    rb.histogram("tfos_serving_ttft_seconds").observe(1.5)
    merged = tracing.merge_snapshots([ra.snapshot(), rb.snapshot()])
    out = merged["hists"]["tfos_serving_ttft_seconds"]
    assert out["n"] == 4
    assert abs(out["sum"] - 1.57) < 1e-9
    assert out["min"] == 0.01 and out["max"] == 1.5


# -- OpenMetrics exposition on a live ModelServer --------------------------

def _tiny_engine():
    import jax

    from tensorflowonspark_tpu.models.decoder import DecoderLM

    kw = dict(vocab=64, hidden=32, num_heads=2, num_layers=1, max_len=64)
    train = DecoderLM(decode=False, **kw)
    dec = DecoderLM(decode=True, **kw)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, 64), np.int32))["params"]
    return serving.DecodeEngine(dec, params, slots=2, total_len=64,
                                flight=tracing.FlightRecorder())


@pytest.fixture(scope="module")
def live_server():
    """One tiny engine + ModelServer shared by the exposition tests
    (the decode/prefill programs compile once per module)."""
    eng = _tiny_engine()
    srv = serving.ModelServer(None, name="lm", engine=eng, port=0)
    host, port = srv.start()
    yield "http://%s:%d" % (host, port), eng
    srv.stop()


def _generate(url, prompts, max_new=4):
    req = urllib.request.Request(
        url + "/v1/models/lm:generate",
        data=json.dumps({"prompt": prompts,
                         "max_new_tokens": max_new}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _scrape(url):
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        assert r.headers["Content-Type"].startswith(
            "application/openmetrics-text")
        return r.read().decode("utf-8")


_SAMPLE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?P<labels>\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r' (?P<value>-?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?|\+Inf|NaN)'
    # optional OpenMetrics exemplar (PR 20): histogram buckets carry
    # the trace id of a recent observation so a slow scrape bucket
    # links straight to scripts/explain_request.py's input
    r'(?P<exemplar> # \{trace_id="[0-9]+"\}'
    r' -?[0-9]+(?:\.[0-9]+)?(?:[eE][+-]?[0-9]+)?)?$')
_META = re.compile(r"^# (TYPE|HELP) ([a-zA-Z_:][a-zA-Z0-9_:]*) (.+)$")

_HIST_SUFFIX = re.compile(r"_(bucket|sum|count)$")


def _parse_openmetrics(text):
    """Strict line-grammar parse: returns ({family: type},
    [(family, labels, value)]). Asserts on any malformed line, a
    sample without a preceding TYPE, or a missing # EOF terminator."""
    assert text.endswith("# EOF\n"), "missing OpenMetrics terminator"
    types = {}
    samples = []
    lines = text.splitlines()
    assert lines[-1] == "# EOF"
    for line in lines[:-1]:
        assert line, "blank line in exposition"
        if line.startswith("#"):
            m = _META.match(line)
            assert m, "malformed metadata line: %r" % line
            if m.group(1) == "TYPE":
                family = m.group(2)
                assert family not in types, \
                    "duplicate TYPE for %s" % family
                types[family] = m.group(3)
            continue
        m = _SAMPLE.match(line)
        assert m, "malformed sample line: %r" % line
        name = m.group("name")
        if m.group("exemplar"):
            assert name.endswith("_bucket"), \
                "exemplar on a non-bucket sample: %r" % line
        family = name
        if _HIST_SUFFIX.search(name) and \
                _HIST_SUFFIX.sub("", name) in types:
            family = _HIST_SUFFIX.sub("", name)
        elif name.endswith("_total") and name[:-len("_total")] in types:
            family = name[:-len("_total")]
        assert family in types, \
            "sample %r precedes/lacks its TYPE" % line
        ftype = types[family]
        if ftype == "counter":
            assert name.endswith("_total"), \
                "counter sample %r must end in _total" % name
        samples.append((family, m.group("labels") or "",
                        float(m.group("value"))
                        if m.group("value") not in ("+Inf", "NaN")
                        else m.group("value")))
    return types, samples


def test_metrics_exposition_grammar_and_catalog(live_server):
    url, eng = live_server
    _generate(url, [[1, 2, 3], [4, 5]], max_new=4)
    text = _scrape(url)
    types, samples = _parse_openmetrics(text)
    # every rendered family is cataloged with the same type — the code
    # half of the metrics-lint drift gate
    for family, ftype in types.items():
        assert family in tracing.METRIC_FAMILIES, \
            "uncataloged family %s" % family
        assert tracing.METRIC_FAMILIES[family][0] == ftype, family
    # the serving histograms the acceptance criteria name are present
    for family in ("tfos_serving_ttft_seconds",
                   "tfos_serving_token_latency_seconds",
                   "tfos_serving_decode_step_seconds"):
        assert types.get(family) == "histogram", family
    # histogram buckets are cumulative and +Inf == _count
    for family, ftype in types.items():
        if ftype != "histogram":
            continue
        buckets = [(labels, v) for f, labels, v in samples
                   if f == family and 'le="' in labels]
        counts = [v for _, v in buckets if isinstance(v, float)]
        assert counts == sorted(counts), "%s buckets not cumulative" \
            % family
        inf = [v for labels, v in buckets if 'le="+Inf"' in labels]
        # _count renders last within the family block
        total = [v for f, labels, v in samples if f == family][-1]
        assert inf and inf[0] == total


def test_steps_dispatched_ahead_is_scraped_beside_decode_steps(live_server):
    """How often the token loop keeps a step in flight is on
    ``/metrics``: ``steps_dispatched_ahead`` beside ``decode_steps``,
    of which it is a part; every step but the first of a request served
    alone was dispatched with its predecessor unread."""
    url, eng = live_server
    before = {f: v for f, _, v in _parse_openmetrics(_scrape(url))[1]}
    _generate(url, [[1, 2, 3, 4]], max_new=12)
    types, samples = _parse_openmetrics(_scrape(url))
    after = {f: v for f, _, v in samples}
    for family in ("tfos_serving_steps_dispatched_ahead",
                   "tfos_serving_decode_steps"):
        assert types[family] == "counter"
    steps = after["tfos_serving_decode_steps"] \
        - before.get("tfos_serving_decode_steps", 0)
    ahead = after["tfos_serving_steps_dispatched_ahead"] \
        - before.get("tfos_serving_steps_dispatched_ahead", 0)
    assert (steps, ahead) == (11, 10)
    assert after["tfos_serving_tokens_dropped_in_flight"] == 0


def test_histogram_exemplars_in_live_scrape(live_server):
    """The exemplar grammar pin (PR 20): traced observations render an
    OpenMetrics exemplar on their bucket line, the trace id is a real
    request trace present in /debug/trace, and exemplars never leak
    onto non-bucket samples (enforced inside _parse_openmetrics)."""
    url, eng = live_server
    _generate(url, [[2, 4, 6]], max_new=3)
    text = _scrape(url)
    _parse_openmetrics(text)  # grammar (incl. bucket-only placement)
    exemplars = re.findall(
        r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*_bucket)\{[^}]*\} '
        r'[0-9.eE+-]+ # \{trace_id="(?P<trace>[0-9]+)"\} '
        r'(?P<val>[0-9.eE+-]+)$', text, re.M)
    assert exemplars, "no exemplars rendered on any bucket line"
    families = {name[:-len("_bucket")] for name, _, _ in exemplars}
    assert "tfos_serving_ttft_seconds" in families
    with urllib.request.urlopen(url + "/debug/trace", timeout=30) as r:
        doc = json.loads(r.read())
    trace_ids = {int(e.get("tid", 0)) for e in doc["traceEvents"]
                 if e.get("ph") == "X"}
    for _, trace, _ in exemplars:
        assert int(trace) in trace_ids, \
            "exemplar trace %s not in the flight ring" % trace


def test_metrics_counters_monotonic_across_scrapes(live_server):
    url, eng = live_server
    _generate(url, [[1, 2, 3]], max_new=3)
    _, before = _parse_openmetrics(_scrape(url))
    _generate(url, [[4, 5, 6, 7]], max_new=5)
    types, after = _parse_openmetrics(_scrape(url))
    prev = {(f, labels): v for f, labels, v in before
            if isinstance(v, float)}
    curr = {(f, labels): v for f, labels, v in after
            if isinstance(v, float)}
    for key, value in prev.items():
        family = key[0]
        if types.get(family) in ("counter", "histogram"):
            assert curr.get(key, 0) >= value, \
                "counter went backwards: %s %s" % key
    assert curr[("tfos_serving_tokens", "")] > \
        prev[("tfos_serving_tokens", "")]


def test_scraped_p99_matches_registry_quantile(live_server):
    """The acceptance pin: the p99 implied by /metrics bucket counts
    must match the registry quantile bench.py publishes, to within
    bucket resolution (one growth factor)."""
    url, eng = live_server
    _generate(url, [[1, 2], [3, 4], [5, 6]], max_new=6)
    _, samples = _parse_openmetrics(_scrape(url))
    hist = eng.metrics.get_histogram("tfos_serving_ttft_seconds")
    published = hist.quantile(0.99)
    buckets = [(labels, v) for f, labels, v in samples
               if f == "tfos_serving_ttft_seconds" and 'le="' in labels
               and "+Inf" not in labels]
    count = [v for f, labels, v in samples
             if f == "tfos_serving_ttft_seconds" and labels == ""][-1]
    rank = max(1, int(np.ceil(0.99 * count)))
    scraped = None
    for labels, cum in buckets:
        if cum >= rank:
            scraped = float(re.search(r'le="([^"]+)"', labels).group(1))
            break
    assert scraped is not None
    # registry quantile interpolates inside the bucket whose upper
    # bound the scrape derivation returns: within one growth factor
    assert scraped / hist.growth <= published <= scraped * 1.0001, \
        (published, scraped)


def test_debug_trace_endpoint_schema(live_server):
    url, eng = live_server
    _generate(url, [[7, 8, 9]], max_new=3)
    with urllib.request.urlopen(url + "/debug/trace", timeout=30) as r:
        trace = json.loads(r.read())
    events = trace["traceEvents"]
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "no spans recorded"
    for e in spans:
        for key in ("name", "pid", "tid", "ts", "dur"):
            assert key in e, (key, e)
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
    assert any(e["name"] == "request" for e in spans)


# -- registry snapshots over the BEAT wire ---------------------------------

def _feed_like_snapshot(records=100, batches=10, decode_s=0.5):
    reg = tracing.MetricsRegistry()
    counts = tracing.Counters()
    counts.inc("records", records)
    counts.inc("batches", batches)
    reg.add_counters("tfos_feed", counts)
    timers = tracing.StageTimers()
    timers.add("decode", decode_s)
    timers.add("gather", decode_s / 2)
    reg.add_timers("tfos_feed_stage", timers)
    return reg.snapshot()


def test_beat_piggybacked_snapshot_merge_two_executors():
    """Two executors beat registry snapshots over the REAL reservation
    wire; the driver's merge must sum counters/timers per family and
    keep per-executor views addressable."""
    srv = reservation.Server(2)
    addr = srv.start(host="127.0.0.1")
    try:
        for eid in (0, 1):
            client = reservation.Client(addr)
            client.beat(eid, {
                "state": "running", "feed_hb": 5 + eid,
                "train_step": 3 + eid,
                "metrics": _feed_like_snapshot(records=100 * (eid + 1))})
            client.close()
        rollup = tracing.cluster_rollup(srv.metrics_snapshot())
        assert set(rollup["executors"]) == {0, 1}
        assert rollup["cluster"]["executors"] == 2
        assert rollup["cluster"]["train_step"] == {0: 3, 1: 4}
        merged = rollup["cluster"]["merged"]
        assert merged["counters"]["tfos_feed"]["counts"]["records"] == 300
        assert merged["counters"]["tfos_feed"]["counts"]["batches"] == 20
        assert abs(merged["timers"]["tfos_feed_stage"]["t"]["decode"]
                   - 1.0) < 1e-9
        # per-executor series stay addressable (not only the sum)
        per0 = rollup["executors"][0]["metrics"]
        assert per0["counters"]["tfos_feed"]["counts"]["records"] == 100
    finally:
        srv.stop()


def test_driver_stats_endpoint_renders_labeled_series():
    srv = reservation.Server(1)
    srv.start(host="127.0.0.1")
    try:
        client = reservation.Client(srv.addr)
        client.beat(0, {"state": "running", "feed_hb": 7, "train_step": 2,
                        "metrics": _feed_like_snapshot()})
        client.close()
        assert srv.stats_addr is not None
        base = "http://127.0.0.1:%d" % srv.stats_addr[1]
        text = urllib.request.urlopen(base + "/metrics",
                                      timeout=30).read().decode()
        assert text.endswith("# EOF\n")
        assert 'tfos_cluster_train_step{executor="0"} 2' in text
        assert 'tfos_cluster_feed_hb_batches{executor="0"} 7' in text
        assert 'tfos_feed_records_total{executor="0"} 100' in text
        # one TYPE line per family even with labeled per-executor rows
        assert text.count("# TYPE tfos_feed_records counter") == 1
        stats = json.loads(urllib.request.urlopen(
            base + "/stats", timeout=30).read())
        assert stats["cluster"]["executors"] == 1
    finally:
        srv.stop()


# -- SupervisedCluster.metrics() on a real 2-executor cluster --------------

def _metrics_train_fun(args, ctx):
    from tensorflowonspark_tpu import supervisor as _supervisor

    sup = _supervisor.attach(ctx)
    feed = ctx.get_data_feed(train_mode=True)
    step = 0
    while not feed.should_stop():
        batch = feed.next_batch(args["batch"])
        if not batch:
            continue
        step += 1
        sup.step(step)


def test_supervised_cluster_metrics_two_executors(tmp_path):
    """Acceptance pin: ``SupervisedCluster.metrics()`` returns merged
    per-executor feed-stage + step-rate series for a 2-executor run —
    harvested from the BEAT leases, surviving shutdown."""
    batch, parts = 4, 4
    records = list(range(batch * parts))
    sc = Context(num_executors=2, work_root=str(tmp_path / "engine"),
                 executor_env={"TFOS_FEED_TRANSPORT": "queue"})
    cfg = supervisor.SupervisorConfig(
        policy=supervisor.FailJob(), heartbeat_interval=0.25,
        heartbeat_timeout=20.0, poll_interval=0.1, classify_grace=10.0)
    try:
        tfc = cluster.run(sc, _metrics_train_fun, {"batch": batch},
                          num_executors=2,
                          input_mode=cluster.InputMode.SPARK,
                          supervise=cfg)
        tfc.train(sc.parallelize(records, parts), feed_timeout=60)
    finally:
        sc.stop()
    rollup = tfc.metrics()
    assert rollup is not None, "no metrics harvested"
    assert set(rollup["executors"]) == {0, 1}, rollup["executors"].keys()
    assert rollup["cluster"]["executors"] == 2
    # every executor beat a metrics snapshot (the feed publishes one at
    # construction even before its first batch)
    for eid, view in rollup["executors"].items():
        assert view["metrics"] is not None, eid
    # step-rate series: the feed ran somewhere, and its steps were beat
    steps = [s for s in rollup["cluster"]["train_step"].values() if s]
    assert steps and max(steps) >= 1, rollup["cluster"]["train_step"]
    merged = rollup["cluster"]["merged"]
    feed_counts = merged["counters"]["tfos_feed"]["counts"]
    assert feed_counts.get("records", 0) >= batch, feed_counts
    # feed-stage series: the queue transport's wait stage must appear
    assert "queue_wait" in merged["timers"]["tfos_feed_stage"]["t"], \
        merged["timers"]


# -- incident evidence ------------------------------------------------------

class _ScriptedLeases(object):
    """Minimal scripted lease server (test_recovery.py's idiom)."""

    def __init__(self):
        self._payloads = {}

    def set(self, eid, **payload):
        self._payloads[eid] = payload

    def lease_snapshot(self):
        return {eid: {"age": 0.0, "payload": dict(p)}
                for eid, p in self._payloads.items()}


def test_failure_evidence_carries_metrics_and_flight():
    """A classified feeder_stall must travel with the stalled
    executor's beat-carried stage breakdown AND the flight recorder's
    tail — the incident arrives with its own postmortem."""
    snapshot = _feed_like_snapshot(records=42)
    srv = _ScriptedLeases()
    srv.set(0, state="running", trainer_alive=True, feed_hb=42,
            feed_transport="queue", metrics=snapshot)
    sup = supervisor.Supervisor(
        server=srv, executors=[0],
        config=supervisor.SupervisorConfig(stall_timeout=10.0))
    now = time.monotonic()
    sup.poll_once(now=now)
    sup.poll_once(now=now + 11.0)
    failure = sup.first_failure()
    assert failure is not None and failure.kind == "feeder_stall"
    evidence = failure.as_dict()["evidence"]
    assert evidence["metrics"] == snapshot
    assert isinstance(evidence["flight"], list) and evidence["flight"]
    # the dump is taken after the classification records its EventLog
    # event, so the incident's own mirrored instant is in its tail
    assert "failure_detected" in {e["name"] for e in evidence["flight"]}


def _stall_train_fun(args, ctx):
    feed = ctx.get_data_feed(train_mode=True)
    while not feed.should_stop():
        feed.next_batch(args["batch"])  # chaos stalls inside here


@pytest.mark.chaos
@pytest.mark.slow
def test_chaos_feeder_stall_incident_has_flight_dump(tmp_path):
    """Acceptance e2e: a REAL stalled consumer (chaos
    ``stall_consumer_for``) freezes feed progress with a live trainer;
    the supervisor classifies ``feeder_stall`` and the incident's
    evidence carries the flight-recorder dump."""
    batch, parts = 4, 4
    records = list(range(batch * parts))
    sc = Context(num_executors=1, work_root=str(tmp_path / "engine"),
                 executor_env={
                     "TFOS_FEED_TRANSPORT": "queue",
                     chaos.ENV_VAR: "stall_consumer_for=25"})
    cfg = supervisor.SupervisorConfig(
        policy=supervisor.FailJob(), heartbeat_interval=0.25,
        heartbeat_timeout=20.0, stall_timeout=3.0,
        poll_interval=0.1, classify_grace=10.0)
    try:
        tfc = cluster.run(sc, _stall_train_fun, {"batch": batch},
                          num_executors=1,
                          input_mode=cluster.InputMode.SPARK,
                          supervise=cfg)
        with pytest.raises(RuntimeError):
            tfc.train(sc.parallelize(records, parts), feed_timeout=60)
    finally:
        sc.stop()
    rep = tfc.report()
    kinds = [f["kind"] for f in rep["failures"]]
    assert "feeder_stall" in kinds, kinds
    incident = rep["failures"][kinds.index("feeder_stall")]
    evidence = incident["evidence"]
    assert isinstance(evidence["flight"], list) and evidence["flight"]
    names = {e["name"] for e in evidence["flight"]}
    # supervision milestones mirrored into the black box
    assert "failure_detected" in names, names


# -- trace dump CLI ---------------------------------------------------------

def test_trace_dump_demo_is_perfetto_loadable(tmp_path):
    """scripts/trace_dump.py --demo (a 3-request serving run) must
    produce valid Chrome trace-event JSON: every span with
    pid/tid/ts/dur, one complete admit->finish span tree per request,
    child spans nested inside their request envelope."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import trace_dump
    finally:
        sys.path.pop(0)
    out = str(tmp_path / "trace.json")
    assert trace_dump.main(["--demo", "--requests", "3", "-o", out]) == 0
    trace = json.load(open(out))
    assert set(trace) >= {"traceEvents"}
    events = trace["traceEvents"]
    for e in events:
        assert {"name", "ph", "pid", "tid", "ts"} <= set(e), e
        if e["ph"] == "X":
            assert "dur" in e and e["dur"] >= 0, e
    requests = [e for e in events
                if e["ph"] == "X" and e["name"] == "request"]
    assert len(requests) == 3
    assert all(e["args"]["outcome"] == "finish" for e in requests)
    for req in requests:
        children = [e for e in events
                    if e["ph"] == "X" and e["tid"] == req["tid"]
                    and e is not req]
        names = {c["name"] for c in children}
        assert {"queue", "prefill", "decode"} <= names, names
        lo, hi = req["ts"], req["ts"] + req["dur"]
        for c in children:
            assert lo <= c["ts"] and c["ts"] + c["dur"] <= hi + 1000, \
                (req, c)
        # one admit instant opens the tree on the same row
        admits = [e for e in events
                  if e["ph"] == "i" and e["name"] == "admit"
                  and e["tid"] == req["tid"]]
        assert len(admits) == 1


# -- satellites -------------------------------------------------------------

def test_eventlog_is_ring_bounded_with_dropped_counter():
    log = tracing.EventLog(capacity=8)
    for i in range(20):
        log.record("tick", i=i)
    events = log.events()
    assert len(events) == 8
    assert [e["i"] for e in events] == list(range(12, 20))
    assert log.dropped == 12
    # span extraction still works over the retained window
    log.record("a")
    log.record("b")
    assert log.span("a", "b") is not None


def test_flight_recorder_ring_bounded():
    fr = tracing.FlightRecorder(capacity=4)
    now = time.monotonic()
    for i in range(10):
        fr.span("s", now, now, trace=i)
    assert len(fr.events()) == 4 and fr.dropped == 6
    assert [e["tid"] for e in fr.tail(2)] == [8, 9]


def test_start_profiler_server_idempotent(monkeypatch):
    import types

    calls = []

    def fake_start(port):
        calls.append(port)

    fake_jax = types.SimpleNamespace(
        profiler=types.SimpleNamespace(start_server=fake_start))
    monkeypatch.setitem(sys.modules, "jax", fake_jax)
    monkeypatch.setattr(tracing, "_PROFILER_PORT", None)
    assert tracing.start_profiler_server(9999) == 9999
    # re-calls return the LIVE port without touching jax again — even
    # when asked for a different one
    assert tracing.start_profiler_server(9999) == 9999
    assert tracing.start_profiler_server(1234) == 9999
    assert calls == [9999]


def test_metrics_lint_green():
    """The shipped catalog must pass its own drift gate (the same
    check ``make test`` runs as a prerequisite)."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "metrics_lint.py")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr or out.stdout


def test_metrics_report_helpers():
    assert metrics_report.median([3, 1, 2]) == 2
    hist = tracing.Histogram()
    for v in (0.1, 0.2, 0.3):
        hist.observe(v)
    q = metrics_report.quantiles_ms(hist)
    assert set(q) == {"p50_ms", "p95_ms", "p99_ms"}
    assert q["p50_ms"] is not None and q["p99_ms"] >= q["p50_ms"]
    empty = metrics_report.quantiles_ms(tracing.Histogram())
    assert empty["p99_ms"] is None
    timers = tracing.StageTimers()
    timers.add("decode", 0.2)
    timers.add("gather", 0.1)
    line = metrics_report.format_stage_ms(timers)
    assert line.startswith("decode=")  # sorted by cost, descending


# -- snapshot algebra (PR 10): the laws the goodput rollup leans on --------

def _random_registry(seed, families=("tfos_serving_ttft_seconds",
                                     "tfos_serving_queue_wait_seconds")):
    """A registry with randomized counters, gauges, timers, and
    histogram observations — one simulated executor's snapshot."""
    rng = np.random.RandomState(seed)
    reg = tracing.MetricsRegistry()
    counters = tracing.Counters()
    for key in ("alpha", "beta"):
        counters.inc(key, int(rng.randint(0, 50)))
    counters.gauge("depth", float(rng.uniform(0, 4)))
    reg.add_counters("tfos_prop", counters)
    timers = tracing.StageTimers()
    for stage in ("read", "decode"):
        for _ in range(int(rng.randint(1, 5))):
            timers.add(stage, float(rng.uniform(0.001, 0.2)))
    reg.add_timers("tfos_prop_stage", timers)
    samples = {}
    for family in families:
        hist = reg.histogram(family)
        vals = rng.lognormal(mean=-3, sigma=1.2,
                             size=int(rng.randint(10, 80)))
        for v in vals:
            hist.observe(float(v))
        samples[family] = list(vals)
    return reg, samples


def _approx_same(a, b, rel=1e-9):
    """Recursive structural equality with float tolerance (sums taken
    in different orders may differ in the last ulp)."""
    if isinstance(a, dict):
        return isinstance(b, dict) and a.keys() == b.keys() and \
            all(_approx_same(a[k], b[k], rel) for k in a)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and \
            all(_approx_same(x, y, rel) for x, y in zip(a, b))
    if isinstance(a, float) or isinstance(b, float):
        if a is None or b is None:
            return a == b
        return a == pytest.approx(b, rel=rel, abs=1e-12)
    return a == b


def test_merge_snapshots_is_commutative_and_associative():
    """The rollup laws: any grouping and any order of executor
    snapshots merges to the same cluster view — what lets BEAT-carried
    snapshots fold incrementally (and the goodput job report sum
    attempts) without coordination."""
    snaps = [_random_registry(seed)[0].snapshot() for seed in (1, 2, 3)]
    a, b, c = snaps
    merged = tracing.merge_snapshots([a, b, c])
    # commutativity: every permutation agrees
    for perm in ((a, c, b), (b, a, c), (b, c, a), (c, a, b), (c, b, a)):
        assert _approx_same(tracing.merge_snapshots(list(perm)), merged)
    # associativity: merge of merges == flat merge
    left = tracing.merge_snapshots(
        [tracing.merge_snapshots([a, b]), c])
    right = tracing.merge_snapshots(
        [a, tracing.merge_snapshots([b, c])])
    assert _approx_same(left, merged)
    assert _approx_same(right, merged)
    # identity: empty snapshots change nothing
    assert _approx_same(
        tracing.merge_snapshots([a, {}, b, None, c]), merged)


def _hist_from_snapshot(snap):
    hist = tracing.Histogram(lo=snap["lo"], growth=snap["growth"])
    assert len(hist._counts) == len(snap["counts"]), \
        "layout mismatch: cannot reconstruct"
    hist._counts = list(snap["counts"])
    hist._n = snap["n"]
    hist._sum = snap["sum"]
    hist._min = snap["min"]
    hist._max = snap["max"]
    return hist


def test_merged_quantile_matches_concatenated_observations():
    """The quantile of a MERGED histogram equals the quantile of a
    single histogram fed every executor's observations (same buckets,
    same ranks) — merged percentiles are not an approximation of the
    per-executor ones but the true fleet percentile, within one bucket
    of exact."""
    family = "tfos_serving_ttft_seconds"
    regs_samples = [_random_registry(seed) for seed in (11, 12, 13)]
    merged = tracing.merge_snapshots(
        [reg.snapshot() for reg, _ in regs_samples])
    concat = tracing.Histogram()
    all_samples = []
    for _, samples in regs_samples:
        for v in samples[family]:
            concat.observe(float(v))
            all_samples.append(float(v))
    remade = _hist_from_snapshot(merged["hists"][family])
    assert remade.count == concat.count == len(all_samples)
    for q in (0.5, 0.9, 0.99):
        assert remade.quantile(q) == pytest.approx(concat.quantile(q))
        # and both land within one bucket of the exact percentile
        exact = float(np.percentile(all_samples, q * 100,
                                    method="inverted_cdf"))
        ratio = remade.quantile(q) / exact
        assert 1.0 / remade.growth <= ratio <= remade.growth, (q, ratio)


def test_cluster_rollup_order_invariant():
    """cluster_rollup's merged view must not depend on executor
    iteration order (dict order differs across beat arrival orders)."""
    snaps = {eid: _random_registry(20 + eid)[0].snapshot()
             for eid in range(3)}
    views_fwd = {eid: {"metrics": snaps[eid], "train_step": eid}
                 for eid in (0, 1, 2)}
    views_rev = {eid: {"metrics": snaps[eid], "train_step": eid}
                 for eid in (2, 1, 0)}
    fwd = tracing.cluster_rollup(views_fwd)
    rev = tracing.cluster_rollup(views_rev)
    assert _approx_same(fwd["cluster"]["merged"],
                        rev["cluster"]["merged"])
    assert fwd["cluster"]["executors"] == 3
    assert fwd["cluster"]["train_step"] == rev["cluster"]["train_step"]
