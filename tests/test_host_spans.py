"""Stage timers as host spans of the profiler's trace (PR 27).

One ``with timers.timed(stage)`` is three things at once: the
``/metrics`` stage, the counter a benchmark's ``ratio`` reader reads,
and — under a ``jax.profiler`` capture — a host span named
``<plane>:<stage>`` on the device trace's clock. Pinned here: the span
is written by the profiler itself and only while it captures, a
process without jax is not made to import it, the decode scheduler's
loop is split where the work happens (and its parts add up), and the
whole thing costs next to nothing when nobody captures. The names of
the jitted programs and of the Pallas calls are checked where the
TPU's lowering is at hand: ``tests/test_chip_compile.py``.
"""

import glob
import os
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tensorflowonspark_tpu import infeed, manager, serving, tracing
from tensorflowonspark_tpu.datafeed import DataFeed
from tensorflowonspark_tpu.marker import EndFeed
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


def _host_events(trace_dir):
    """{event name: [(thread line's name, start_ns, end_ns)]} of the one
    trace under ``trace_dir``, read back with JAX's own ProfileData."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                events.setdefault(e.name, []).append(
                    (line.name, e.start_ns, e.start_ns + e.duration_ns))
    return events


# -- StageTimers: the span itself ----------------------------------------


@pytest.mark.parametrize("plane", ["engine", "feed"])
def test_timed_stage_is_a_host_span_under_capture(tmp_path, plane):
    timers = tracing.StageTimers(plane)
    with tracing.trace(str(tmp_path)):
        with timers.timed("outer"):
            with timers.timed("inner"):
                time.sleep(0.002)
    events = _host_events(str(tmp_path))
    (outer,), (inner,) = events[plane + ":outer"], events[plane + ":inner"]
    # on the thread that did the work, nested as the with blocks are,
    # on one clock
    assert outer[0] == inner[0]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 2e6
    assert timers.counts() == {"outer": 1, "inner": 1}
    assert timers.snapshot()["outer"] >= timers.snapshot()["inner"] >= 0.002


def test_no_capture_no_span_and_the_sample_still_counts(tmp_path):
    timers, bare = tracing.StageTimers("engine"), tracing.StageTimers()
    with timers.timed("before"):
        pass
    with tracing.trace(str(tmp_path)):
        with timers.timed("during"):
            pass
        with bare.timed("no_plane"):
            pass
        # a sample measured elsewhere is a sample, not a span
        timers.add("added", 0.5)
    with timers.timed("after"):
        pass
    names = set(_host_events(str(tmp_path)))
    assert "engine:during" in names
    assert not names & {"engine:before", "engine:after", "engine:added",
                        "no_plane", ":no_plane", "None:no_plane"}
    assert timers.counts() == {"before": 1, "during": 1, "added": 1,
                               "after": 1}
    assert bare.counts() == {"no_plane": 1}


def test_a_process_without_jax_times_a_stage_and_does_not_import_it():
    code = (
        "import sys\n"
        "from tensorflowonspark_tpu import tracing\n"
        "t = tracing.StageTimers('feed')\n"
        "with t.timed('ring_wait'):\n"
        "    pass\n"
        "assert t.counts() == {'ring_wait': 1}, t.counts()\n"
        "assert t.snapshot()['ring_wait'] >= 0.0\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "print('ok')\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=root, capture_output=True,
        text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_trace_helper_keeps_the_python_tracer_off(tmp_path):
    """With the Python tracer on every Python call is an event (named
    ``$file:line function``) and the capture measures the profiler."""
    def work(n):
        return sum(i * i for i in range(n))

    with tracing.trace(str(tmp_path)):
        with tracing.StageTimers("engine").timed("work"):
            for _ in range(50):
                work(100)
    names = set(_host_events(str(tmp_path)))
    assert "engine:work" in names
    assert not [n for n in names if n.startswith("$") or "work" in n
                and n != "engine:work"]


def test_timed_costs_next_to_nothing_with_no_capture():
    timers = tracing.StageTimers("engine")
    samples = []
    for _ in range(10000):
        t0 = time.perf_counter_ns()
        with timers.timed("x"):
            pass
        samples.append(time.perf_counter_ns() - t0)
    assert statistics.median(samples) < 25000  # ns; measured about 1,000
    assert timers.counts() == {"x": 10000}


# -- the feed plane's stages ---------------------------------------------


def test_feed_stages_are_spans_one_per_item_and_batch(tmp_path):
    mgr = manager.start(b"spankey", ["input", "output", "error"])
    q = mgr.get_queue("input")
    for chunk in ([1, 2, 3], [4, 5, 6]):
        q.put(chunk)
    q.put(EndFeed())
    feed = DataFeed(mgr, train_mode=True)

    def batches():
        while not feed.should_stop():
            batch = feed.next_batch(3)
            if batch:
                yield np.asarray(batch)

    with tracing.trace(str(tmp_path)):
        got = [np.asarray(b).tolist() for b in infeed.prefetch(
            batches(), timers=feed.timers)]
    assert got == [[1, 2, 3], [4, 5, 6]]
    counts = feed.timers.counts()
    # one wait per delivered item (two chunks and the end marker), one
    # put per batch
    assert counts["queue_wait"] == 3 and counts["device_put"] == 2
    events = _host_events(str(tmp_path))
    assert len(events["feed:queue_wait"]) == 3
    puts = sorted(events["feed:device_put"], key=lambda e: e[1])
    assert len(puts) == 2
    # the staging thread pulls a batch, then puts it: no stage of the
    # feed lies inside another
    waits = sorted(events["feed:queue_wait"], key=lambda e: e[1])
    assert waits[0][2] <= puts[0][1] and puts[0][2] <= puts[1][1]


# -- the decode scheduler's loop -----------------------------------------


def _serve(eng, n=3, max_new=6):
    rng = np.random.RandomState(3)
    handles = [eng.submit(rng.randint(0, V, size=5 + i).tolist(), max_new)
               for i in range(n)]
    return [h.result(300) for h in handles]


def test_engine_loop_is_split_into_named_stages(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=12) as eng:
        _serve(eng)
        # the loop parks once the last request is done; a park counts
        # when it ends, so wake it with one more request
        time.sleep(0.1)
        _serve(eng, n=1)
        sec, n = eng.timers.snapshot(), eng.timers.counts()
        counts = eng.counters.snapshot()["counts"]
    stages = {"park", "qos_plan", "admit", "prefill", "evict",
              "decode_step", "step_upload", "step_dispatch", "step_sync",
              "host_schedule", "queue_wait", "grow_blocks", "block_alloc"}
    assert stages <= set(n), stages - set(n)
    # one queue wait and one admission per request (nothing preempted)
    assert n["queue_wait"] == n["admit"] == n["prefill"] == 4
    assert counts.get("preemptions", 0) == 0
    # the three parts of a step: one each per step, inside decode_step
    steps = counts["decode_steps"]
    assert n["step_upload"] == n["step_dispatch"] == n["step_sync"] == steps
    # one step stays in flight, so a turn dispatches a step and reads
    # the one before it; a first step finds none to read, and the turn
    # that reads a last step dispatches none: a decode_step each
    ahead = counts["steps_dispatched_ahead"]
    assert 0 < ahead < steps
    assert n["decode_step"] == 2 * steps - ahead
    assert counts["tokens_dropped_in_flight"] == 0
    parts = sec["step_upload"] + sec["step_dispatch"] + sec["step_sync"]
    assert 0.0 < parts <= sec["decode_step"]
    # what the parts leave out is three span exits and the turn's own
    # bookkeeping between them: microseconds a step
    assert sec["decode_step"] - parts < 0.001 * steps
    # admit holds its prefill
    assert sec["prefill"] <= sec["admit"]
    assert sec["block_alloc"] <= sec["admit"] + sec["grow_blocks"]
    assert 1.0 <= counts["kv_block_steps"] / steps <= 12.0


def test_engine_stages_are_spans_on_the_scheduler_thread(lm, tmp_path):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=12) as eng:
        _serve(eng, n=1)  # compiled before the capture
        with tracing.trace(str(tmp_path)):
            _serve(eng, n=2)
    events = _host_events(str(tmp_path))
    steps = sorted(events["engine:decode_step"], key=lambda e: e[1])
    threads = {e[0] for e in steps}
    assert len(threads) == 1  # the scheduler's
    parts = {}
    for part in ("step_upload", "step_dispatch", "step_sync"):
        inside = events["engine:" + part]
        assert {e[0] for e in inside} == threads
        # each lies in a decode_step, and no decode_step holds two
        holders = [[i for i, (_, s0, s1) in enumerate(steps)
                    if s0 <= a and b <= s1] for _, a, b in inside]
        assert all(len(h) == 1 for h in holders)
        parts[part] = [h[0] for h in holders]
        assert len(set(parts[part])) == len(inside)
    # a turn uploads and dispatches together, and every step is read
    # once: in a later turn, or alone when nothing is left to dispatch
    assert parts["step_upload"] == parts["step_dispatch"]
    assert len(parts["step_sync"]) == len(parts["step_dispatch"])
    assert set(parts["step_sync"]) | set(parts["step_dispatch"]) \
        == set(range(len(steps)))
    for (_, d0, d1), (_, r0, r1) in zip(
            sorted(events["engine:step_dispatch"], key=lambda e: e[1]),
            sorted(events["engine:step_sync"], key=lambda e: e[1])):
        assert d1 <= r0  # a step is read after it was dispatched
    assert len(events["engine:admit"]) == 2
    # a cross-thread interval is a sample, never a span
    assert "engine:queue_wait" not in events
    # the programs carry their names in the host spans
    assert any("paged_decode_step" in name for name in events)
    assert not any("lambda" in name for name in events)


def test_speculative_round_holds_the_three_step_parts(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=16, speculate_k=3) as eng:
        _serve(eng, n=2)
        sec, n = eng.timers.snapshot(), eng.timers.counts()
    assert "decode_step" not in n
    assert n["spec_round"] == n["step_upload"] == n["step_dispatch"] \
        == n["step_sync"] >= 1
    assert sec["step_upload"] + sec["step_dispatch"] + sec["step_sync"] \
        <= sec["spec_round"]


@pytest.mark.parametrize("kind", ["paged", "speculative", "blocks"])
def test_a_step_is_dispatched_ahead_where_the_device_feeds_itself(lm, kind):
    """``steps_dispatched_ahead`` over ``decode_steps``: above 0.8 on a
    busy token engine, whose next input is the device's own output; 0
    where the host decides the next input from the answers (a
    speculative round's acceptance, a block's unmasking)."""
    dec, params = lm
    if kind == "blocks":
        from benchmarks.reference import sdar_moe as ref
        from tensorflowonspark_tpu.models import sdar_moe

        tiny = dict(vocab=97, hidden=64, num_heads=4, num_kv_heads=2,
                    head_dim=16, num_layers=2, num_experts=8,
                    experts_per_tok=2, moe_hidden=32, rope_theta=1e6,
                    rms_eps=1e-6, max_len=64, block_len=4, denoise_steps=4,
                    confidence_threshold=0.9, mask_token_id=96)
        dec = sdar_moe.SdarMoeLM(**tiny, dtype=jnp.float32, decode=True)
        params = ref.init_params(jax.random.PRNGKey(1), tiny)
    kw = {"paged": dict(kv_block_size=8, kv_blocks=24),
          "speculative": dict(kv_block_size=8, kv_blocks=24, speculate_k=3),
          "blocks": dict(kv_block_size=8, kv_blocks=24)}[kind]
    with serving.DecodeEngine(dec, params, slots=3, **kw) as eng:
        _serve(eng, n=6, max_new=24)
        counts = eng.counters.snapshot()["counts"]
        n = eng.timers.counts()
    steps = counts["decode_steps"] if kind != "speculative" \
        else n["spec_round"]
    assert steps >= 20
    ahead = counts["steps_dispatched_ahead"]
    if kind == "paged":
        assert ahead / steps > 0.8
        assert n["decode_step"] == 2 * steps - ahead
    else:
        assert ahead == 0 and counts["tokens_dropped_in_flight"] == 0
        # read, then schedule: every turn holds its own step's read
        assert n["step_sync"] == n["step_dispatch"] == steps


@pytest.mark.parametrize("short_of", ["slots", "blocks"])
def test_admission_scans_count_what_left_a_request_waiting(lm, short_of):
    dec, params = lm
    # blocks: each request grows to ceil((9 + 12) / 8) = 3 blocks and
    # needs 2 to prefill; a pool of 3 holds one of them at a time
    kw = dict(slots=1, kv_blocks=12) if short_of == "slots" \
        else dict(slots=2, kv_blocks=3)
    rng = np.random.RandomState(4)
    with serving.DecodeEngine(dec, params, kv_block_size=8,
                              prefix_cache=False, **kw) as eng:
        handles = [eng.submit(rng.randint(0, V, size=9).tolist(), 12)
                   for _ in range(2)]
        for h in handles:
            h.result(300)
        counts = eng.counters.snapshot()["counts"]
    other = "blocks" if short_of == "slots" else "slots"
    assert counts["admit_scans_blocked_" + short_of] >= 1
    assert counts.get("admit_scans_blocked_" + other, 0) == 0
    assert counts.get("preemptions", 0) == 0


def test_park_is_idle_for_want_of_work_not_scheduler_cost(lm):
    dec, params = lm
    with serving.DecodeEngine(dec, params, slots=2, kv_block_size=8,
                              kv_blocks=12) as eng:
        _serve(eng, n=1)
        time.sleep(0.3)  # nothing queued, nothing active
        _serve(eng, n=1)
        sec = eng.timers.snapshot()
    assert sec["park"] >= 0.25
    # the idle stretch is in no scheduler stage
    busy = sum(sec.get(k, 0.0) for k in (
        "qos_plan", "evict", "grow_blocks", "host_schedule"))
    assert busy < 0.25
