"""Language-neutral model serving — the JVM/Scala inference API analog.

Reference capability (SURVEY.md §2 L0 row): a Scala/JVM API so Spark
jobs written in Scala could run inference against trained models. A JVM
has no place in a TPU-native stack; the ecosystem-correct equivalent is
the TF-Serving REST wire protocol, which is exactly what JVM Spark
shops call from Scala (plain HTTP + JSON, no Python on the client):

    GET  /v1/models/<name>            -> model status
    GET  /v1/models/<name>/metadata   -> signature metadata
    POST /v1/models/<name>:predict    -> {"instances": [...]} row format
                                         or {"inputs": {...}} columnar

plus the operational surface (docs/observability.md): GET /healthz
(liveness + gauges), GET /metrics (OpenMetrics exposition of the
engine's MetricsRegistry — latency histograms, counters, stage
timers), and GET /debug/trace (per-request span timeline as
Perfetto-loadable Chrome trace JSON).

Backed by the framework's export format (export.py): the exported
``apply_fn`` + variables serve every request; one process owns the
accelerator and requests serialize through it (the TPU single-owner
rule, same as the trainer process).

Start in-process (:class:`ModelServer`) or from a shell::

    python -m tensorflowonspark_tpu.serving --model-dir EXPORT \
        --name mnist --port 8501

This is deliberately protocol-compatible with TF-Serving's REST surface
for the predict/metadata paths a Spark-Scala client uses, so reference
users' JVM-side HTTP code ports by changing the URL.

Two batching planes live here, serving different traffic shapes:

- :class:`_Batcher` — a collection-window coalescer for the GENERIC
  predict path (any exported apply_fn): same-signature concurrent
  requests merge into one model call. Run-to-completion: a merged group
  occupies the model until every row finishes. Kept as the baseline the
  serving bench measures against.
- :class:`DecodeEngine` — CONTINUOUS batching for the decoder-LM path:
  a scheduler thread owns a slot-structured KV cache and a single
  fixed-shape decode step; requests enter freed slots at step
  boundaries, exit individually on EOS/length, and prefill through
  shape buckets so compiles stay O(buckets), not O(request signatures).
  Mounted on a server it serves ``POST /v1/models/<name>:generate``.
"""

import collections
import itertools
import json
import logging
import math
import os
import queue as queue_mod
import random
import socket
import threading
import time

import numpy as np

from tensorflowonspark_tpu import chaos
from tensorflowonspark_tpu import frames
from tensorflowonspark_tpu import kvship
from tensorflowonspark_tpu import paging
from tensorflowonspark_tpu import qos
from tensorflowonspark_tpu import slo
from tensorflowonspark_tpu import tracing
from tensorflowonspark_tpu.qos import QuotaExceeded  # noqa: F401 - HTTP taxonomy re-export

logger = logging.getLogger(__name__)

#: content type a /metrics response declares (OpenMetrics exposition;
#: one shared contract with the driver-side stats endpoint)
OPENMETRICS_CONTENT_TYPE = tracing.OPENMETRICS_CONTENT_TYPE

_STREAM_DONE = object()

#: default replica-identity source (see DecodeEngine.replica_id)
_ENGINE_IDS = itertools.count()

#: the one refusal of an engine without a block pool, whichever way it
#: was asked for (``kv_block_size=0``, or a model without the fields)
_PAGED_ONLY = (
    "DecodeEngine reaches K and V through the paged block pool only: it "
    "needs kv_block_size > 0 (got {}) and a model with the paged-KV "
    "fields kv_block_size/kv_blocks/kv_dtype (got {}); "
    "generation.generate is the contiguous-cache path")


def _int32_shape(*shape):
    """An int32 argument of an engine program as its shape alone (what
    a program is lowered with before the host has built the array)."""
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.int32)


class Retriable(RuntimeError):
    """The request failed for a TRANSIENT serving-side reason — shed at
    admission, engine draining, or the engine mid-restart. The client
    should retry (the HTTP surface answers 503 with ``Retry-After``);
    nothing about the request itself was wrong."""

    #: advisory seconds before a retry is worth attempting
    retry_after = 1.0


class Shed(Retriable):
    """Admission control refused the request because its deadline is
    infeasible under the engine's measured rates: estimated queue wait
    plus prefill plus decode exceeds the time the client gave us.
    Shedding at the door is the load-shedding half of tail-latency
    control — doing the work anyway would burn a slot on an answer the
    client has already abandoned."""

    def __init__(self, msg, retry_after=1.0):
        super(Shed, self).__init__(msg)
        self.retry_after = max(1.0, float(retry_after))


class Draining(Retriable):
    """The engine/server is draining (graceful shutdown): in-flight
    requests finish, new work must go to another replica."""

    retry_after = 5.0


class EngineFailed(Retriable):
    """The decode scheduler died. Outstanding handles fail with this so
    clients retry (against this replica once the supervisor's
    RestartEngine policy rebuilds the engine, or against another)."""


class SpliceRejected(RuntimeError):
    """A shipped KV prefix was DELIBERATELY refused (PR 17): fenced
    source epoch, mismatched pool geometry/dtype, or pool pressure.
    NOT retriable-as-is — the decode side answers 409
    and the prefill side falls back to letting the decode replica
    re-prefill cold. ``reason`` is the bounded label the
    ``tfos_splice_failures_total{reason=...}`` counter carries."""

    def __init__(self, reason, msg):
        super(SpliceRejected, self).__init__(msg)
        self.reason = str(reason)


#: HTTP statuses a serving surface answers for TRANSIENT conditions —
#: 429 (QueueFull backpressure) and 503 (Shed / Draining / EngineFailed)
RETRIABLE_HTTP_STATUS = (429, 503)

#: fraction of a Retry-After floor added as jitter by retry_call: N
#: clients told the same "Retry-After: T" by one recovering replica
#: spread over [T, T*(1+this)] instead of stampeding it at exactly +T
RETRY_AFTER_JITTER = 0.25


def http_retriable(status, retry_after=None):
    """Map an upstream HTTP status to the matching client-side
    :class:`Retriable` (None when the status is not transient) — the
    one place the wire's 429/503 + ``Retry-After`` contract turns back
    into the exception :func:`retry_call` retries. ``retry_after`` is
    the response header value (seconds), if any."""
    if status not in RETRIABLE_HTTP_STATUS:
        return None
    err = Retriable("upstream answered {}".format(status))
    try:
        err.retry_after = max(0.0, float(retry_after))
    except (TypeError, ValueError):
        err.retry_after = 1.0 if status == 503 else 0.5
    return err


def retry_call(fn, attempts=4, base_delay=0.1, max_delay=5.0,
               sleep=time.sleep, rng=None):
    """Call ``fn()``, retrying ONLY :class:`Retriable` failures with
    bounded exponential backoff and full jitter.

    The one client-side retry loop (the fleet router and
    ``examples/generate``'s HTTP client both use it instead of ad-hoc
    loops): non-retriable errors — bad requests, real server faults,
    cancellations — propagate on the first raise; a retriable one is
    retried up to ``attempts`` total calls, sleeping
    ``uniform(0, min(max_delay, base_delay * 2**attempt))`` between
    tries (full jitter — N clients retrying a shed replica must not
    re-arrive in lockstep). ``exc.retry_after`` refines the delay: a
    POSITIVE value (the wire's ``Retry-After``) floors it, capped at
    ``max_delay``, PLUS up to ``RETRY_AFTER_JITTER`` of itself in
    jitter — the server said when a retry is worth attempting, and
    coming back sooner just buys another refusal, but N clients all
    told "Retry-After: 2" by the same recovering replica must not
    re-arrive at +2.000s in one synchronized stampede (the jitter is
    NOT capped by ``max_delay``: capping would re-synchronize exactly
    the clients whose floor hit the cap); an EXPLICIT
    ``retry_after == 0`` skips the sleep entirely — the router's
    failover shape, where the next attempt goes to a DIFFERENT
    replica and any wait is pure added latency; absent/None means
    plain jittered backoff. ``sleep``/``rng`` are injectable for
    deterministic tests; the final attempt's exception propagates
    unchanged."""
    rng = rng if rng is not None else random.random
    attempts = max(1, int(attempts))
    attempt = 0
    while True:
        try:
            return fn()
        except Retriable as e:
            attempt += 1
            if attempt >= attempts:
                raise
            try:
                retry_after = float(getattr(e, "retry_after", None))
            except (TypeError, ValueError):
                retry_after = None
            if retry_after is not None and retry_after <= 0.0:
                continue  # explicit immediate failover: no sleep
            delay = min(float(max_delay),
                        float(base_delay) * (2.0 ** (attempt - 1)))
            delay *= rng()
            if retry_after is not None:
                floor = min(retry_after, float(max_delay))
                delay = max(delay, floor * (1.0 + RETRY_AFTER_JITTER
                                            * rng()))
            if delay > 0.0:
                sleep(delay)


class Cancelled(RuntimeError):
    """The request was cancelled — ``handle.cancel()``, the consumer
    closed its :meth:`GenerationHandle.stream` generator, or the HTTP
    client disconnected. Its slot was freed at the next decode-step
    boundary."""


class DeadlineExceeded(Cancelled):
    """The request's deadline passed before it completed; the engine
    evicted it at the next decode-step boundary (a special case of
    cancellation — ``except Cancelled`` catches both)."""


class GenerationHandle(object):
    """One in-flight generation request against a :class:`DecodeEngine`.

    The scheduler thread emits tokens into the handle as each decode
    step completes; clients either iterate :meth:`stream` (tokens arrive
    one by one, the continuous-batching point) or block on
    :meth:`result` for the full sequence. ``latency`` is submit-to-
    completion wall time, the number the serving bench percentiles.

    Lifecycle control: ``deadline`` (absolute ``time.monotonic``) makes
    the engine evict the request at the first decode-step boundary past
    it; :meth:`cancel` requests the same eviction explicitly. Either
    way the slot frees immediately for queued work instead of decoding
    to ``max_new_tokens`` for a client that is gone, and
    :meth:`result`/:meth:`stream` raise :class:`DeadlineExceeded` /
    :class:`Cancelled`.
    """

    def __init__(self, prompt, max_new_tokens, deadline=None,
                 trace=None, session=None, tenant=None, priority=None):
        # constructed by DecodeEngine AFTER validate() normalized both
        self.prompt = list(prompt)
        self.max_new_tokens = max_new_tokens
        self.deadline = deadline  # absolute monotonic, or None
        self.submitted = time.monotonic()
        self.completed = None
        #: multi-tenant QoS identity (PR 18): validated upstream by
        #: qos.validate_tenant/validate_priority — the fair scheduler
        #: keys its deficit counters on tenant, strict class ordering
        #: and preemption on priority
        self.tenant = tenant if tenant is not None else qos.DEFAULT_TENANT
        self.priority = priority if priority is not None \
            else qos.DEFAULT_PRIORITY
        #: optional conversation identity (PR 16): an opaque client
        #: string riding the :generate payload end to end. The engine
        #: never interprets it — it exists so the fleet router's
        #: session-affinity map can key on it, and so per-request
        #: observability (flight spans, logs) can attribute work to a
        #: conversation.
        self.session = str(session) if session is not None else None
        #: request trace id: every span this request's lifecycle emits
        #: into the FlightRecorder lands on this timeline row. An
        #: externally minted id (the fleet router's ``X-TFOS-Trace``
        #: header) is ADOPTED verbatim, so a request that failed over
        #: between replicas shares one id across every engine's ring —
        #: the stitched end-to-end timeline's join key.
        self.trace = int(trace) if trace is not None \
            else tracing.next_trace_id()
        self._tokens = []
        # block-stepping engines only: for each token the pass of its
        # block (0 = the first) at which it was unmasked, and how many
        # tokens each delivery handed over
        self._passes = []
        self._deliveries = []
        self._q = queue_mod.Queue()
        self._done = threading.Event()
        self._error = None
        self._cancel_requested = False
        # observability cursors (scheduler thread writes)
        self._last_emit_at = None   # monotonic of the last emitted token
        self._decode_t0 = None      # monotonic of prefill completion
        self._preempt_at = None     # monotonic of the last eviction
        # (name, t0, t1) lifecycle spans accumulated for critical-path
        # attribution (slo.attribute_intervals) at request finish; the
        # scheduler thread is the only writer
        self._attr_spans = []

    # -- scheduler side --------------------------------------------------

    def _emit(self, token):
        self._tokens.append(int(token))
        self._q.put(int(token))

    def _emit_block(self, tokens, passes):
        """One delivery of a whole block: ``stream()`` still yields its
        tokens one by one, all at once."""
        self._tokens.extend(tokens)
        self._passes.extend(passes)
        self._deliveries.append(len(tokens))
        self._q.put(tuple(tokens))

    def _finish(self, error=None):
        self._error = error
        self.completed = time.monotonic()
        self._done.set()
        self._q.put(_STREAM_DONE)

    def _evictable(self, now):
        """(error or None) — why the scheduler should evict this request
        at the current step boundary."""
        if self._cancel_requested:
            return Cancelled("request cancelled")
        if self.deadline is not None and now > self.deadline:
            return DeadlineExceeded(
                "deadline exceeded after {} of {} tokens".format(
                    len(self._tokens), self.max_new_tokens))
        return None

    # -- client side -----------------------------------------------------

    def cancel(self):
        """Ask the engine to stop generating: the request is evicted at
        the next decode-step boundary and its slot freed. Returns True
        if the cancellation was registered, False if the request had
        already completed (its result stands). Idempotent."""
        if self._done.is_set():
            return False
        self._cancel_requested = True
        return True

    def stream(self, timeout=600.0):
        """Yield generated tokens as the engine emits them. ``timeout``
        bounds the wait for EACH token (TimeoutError, matching
        :meth:`result`'s surface).

        Abandoning the generator — ``close()``, or ``break``/a consumer
        exception followed by GC closing it — CANCELS the request: a
        consumer that stopped reading must not leave the slot decoding
        to ``max_new_tokens`` for nobody (the classic streaming slot
        leak). Iterate to the end if you want the request to finish.
        The per-token TimeoutError does NOT cancel by itself (it may be
        a poll signal; ``result()`` still works afterwards) — but note
        the raise FINISHES the generator, so close/GC after a timeout
        cannot detect abandonment anymore: a consumer that gives up
        after a TimeoutError must call :meth:`cancel` itself."""
        while True:
            try:
                item = self._q.get(timeout=timeout)
            except queue_mod.Empty:
                raise TimeoutError(
                    "no token within {}s".format(timeout))
            if item is _STREAM_DONE:
                if self._error is not None:
                    raise self._error
                return
            try:
                if isinstance(item, tuple):  # a block, delivered whole
                    for token in item:
                        yield token
                else:
                    yield item
            except GeneratorExit:
                # close()/GC landed at the yield: the consumer is gone
                # (cancel() is a no-op if the request already finished)
                self.cancel()
                raise

    def result(self, timeout=600.0):
        """Block until complete; returns prompt + generated tokens."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                "generation did not complete within {}s".format(timeout))
        if self._error is not None:
            raise self._error
        return list(self.prompt) + list(self._tokens)

    @property
    def generated(self):
        """Tokens emitted so far (complete once :meth:`result` returns)."""
        return list(self._tokens)

    @property
    def unmask_passes(self):
        """From a block-stepping engine: for each generated token the
        pass of its block (0 = the first) at which it was unmasked, so
        that every intermediate state of every block can be rebuilt
        from what was served. Empty from a token-stepping engine."""
        return list(self._passes)

    @property
    def deliveries(self):
        """From a block-stepping engine: how many tokens each delivery
        handed over, in order (``stream()`` yields them one by one; the
        tokens of one delivery arrive together)."""
        return list(self._deliveries)

    @property
    def latency(self):
        return (self.completed - self.submitted) \
            if self.completed is not None else None


class QueueFull(RuntimeError):
    """The engine's admission queue is at ``max_queue`` — backpressure;
    retry later. The HTTP surface answers 429 instead of queueing work
    for a client that will have timed out by the time it decodes."""


class Fenced(RuntimeError):
    """This replica's serving lease epoch was superseded (another
    holder registered for its identity — see ``reservation.Fenced``):
    it must not serve. NON-retriable: the HTTP surface answers 410
    (Gone) with ``kind: "Fenced"`` — a client or router should
    re-resolve to the current holder, never retry here."""


class DedupWindow(object):
    """Bounded TTL + LRU idempotency window for request replay (PR 12).

    The exactly-once half of partition-tolerant dispatch: a retry of a
    request this replica ALREADY executed (the ambiguous-timeout shape
    — the response was lost, not the work) must not execute twice.
    Keyed on the router's ``X-TFOS-Request-Id``; three cases:

    - **fresh** — no entry: the caller becomes the OWNER, executes,
      and publishes the outcome (``complete``) or withdraws
      (``fail`` — failed attempts are NOT cached, a later retry gets a
      clean execution).
    - **completed** — a finished entry inside the TTL: the stored
      response is REPLAYED verbatim (a dedup *hit*).
    - **in-flight** — the original is still executing: the retry JOINS
      it (waits on the owner's outcome) instead of racing a duplicate
      generation (a dedup *join*) — this is what makes a post-timeout
      failover that lands back on the same replica safe while the
      first execution is still running.

    Bounded two ways: ``ttl_s`` (entries expire — a replay window, not
    a permanent ledger) and ``capacity`` (LRU eviction — memory stays
    bounded under sustained traffic). Evicting an in-flight entry is
    safe: joiners hold the entry object itself, so the owner's outcome
    still resolves them; the id just stops deduplicating afterwards.
    Thread-safe (HTTP handler threads share it). ``now`` is injectable
    for deterministic TTL tests."""

    def __init__(self, capacity=2048, ttl_s=120.0, now=time.monotonic):
        self.capacity = max(1, int(capacity))
        self.ttl_s = float(ttl_s)
        self._now = now
        self._lock = threading.Lock()
        self._entries = collections.OrderedDict()  # rid -> _DedupEntry

    class _Entry(object):
        __slots__ = ("done", "response", "error", "created")

        def __init__(self, created):
            self.done = threading.Event()
            self.response = None
            self.error = None
            self.created = created

    def begin(self, request_id):
        """(entry, owner): ``owner`` True means the caller must execute
        and then call :meth:`complete` or :meth:`fail`; False means the
        entry belongs to an earlier arrival — replay/join it."""
        rid = str(request_id)
        now = self._now()
        with self._lock:
            self._expire_locked(now)
            entry = self._entries.get(rid)
            if entry is not None:
                # TTL is since-last-access: the refresh keeps the
                # OrderedDict's insertion order == recency order, so
                # head-scan expiry is exact
                entry.created = now
                self._entries.move_to_end(rid)
                return entry, False
            entry = self._Entry(now)
            self._entries[rid] = entry
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
            return entry, True

    def complete(self, request_id, entry, response):
        """Publish the owner's successful response for replay."""
        entry.response = response
        entry.done.set()

    def fail(self, request_id, entry, error):
        """Withdraw a failed execution: joiners already waiting get the
        error (they were the same request — hiding it would hang them),
        but the entry leaves the window so a LATER retry re-executes
        instead of replaying a transient failure forever."""
        entry.error = error
        entry.done.set()
        with self._lock:
            if self._entries.get(str(request_id)) is entry:
                del self._entries[str(request_id)]

    def _expire_locked(self, now):
        while self._entries:
            rid, entry = next(iter(self._entries.items()))
            if now - entry.created <= self.ttl_s:
                break
            del self._entries[rid]

    def stats(self):
        with self._lock:
            return {"entries": len(self._entries),
                    "capacity": self.capacity, "ttl_s": self.ttl_s}


class DecodeEngine(object):
    """Continuous-batching decode engine over a paged KV block pool.

    The serving answer to ``generate_jit``'s run-to-completion shape
    (and the window ``_Batcher``'s group-by-identical-signature shape):
    a persistent scheduler thread owns ONE pool of KV blocks
    (``[kv_blocks + 1, kv_block_size, heads * head_dim]`` per layer, row
    0 the scratch block) and runs a fixed-shape decode step over it
    forever. Each of the S slots independently holds one in-flight
    sequence at its own position and reaches its K and V through its row
    of a host-authoritative block table, which the step takes as an
    argument: a sequence holds ``ceil(len / kv_block_size)`` blocks as it
    grows, not ``total_len`` rows up front, and attention reads the pool
    through the table (``ops/paged_attention.py``: the Pallas kernel on
    the TPU, its blockwise ``lax`` form elsewhere). Requests are
    admitted into freed slots at decode-step boundaries (no run-to-max
    groups), exit individually on EOS or length, and prompts prefill
    through shape BUCKETS (padded to the next bucket length), so the
    whole engine compiles

        1 decode program per (slots, total_len) config
      + 1 prefill program per bucket

    instead of one whole-generation program per (batch, prompt_len,
    max_new) request signature. At ``temperature=0`` each request's
    output is bitwise-identical to a solo ``generation.generate`` call,
    which runs on the contiguous ``[batch, total_len]`` cache and is the
    oracle of every such pin (tests/test_decode_engine.py,
    tests/test_paged_kv.py). A token step's next input
    is the device's own output, so the loop keeps ONE step in flight:
    it dispatches step n+1 before it reads step n, and reads, delivers
    and schedules while the device computes (docs/serving.md, "One step
    in flight"); an engine whose next input is the host's decision (a
    block's unmasking, a speculative round's acceptance) reads first.

    Args:
      model: decode-mode DecoderLM-family flax module (``decode=True``)
        with the paged fields (``kv_block_size``, ``kv_blocks``,
        ``kv_dtype``); the engine serves a clone re-speced for its pool.
      params: its parameters.
      slots: concurrent sequences (S). Throughput lever.
      total_len: longest sequence a slot may hold; every request needs
        ``len(prompt) + max_new_tokens <= total_len``. Defaults to
        ``model.max_len``.
      buckets: ascending prefill bucket lengths (default: powers of two
        up to ``total_len``). Compile-count lever.
      temperature/top_k/top_p: sampling config (engine-wide; one
        program serves every request). 0 = greedy.
      eos_token: emitting it completes a request (eos included in the
        output, nothing after it — the slot frees immediately).
      rng: PRNG key for sampling (ignored at temperature=0).
      counters/timers: optional tracing.Counters / tracing.StageTimers
        to share; fresh ones are created otherwise and exposed as
        attributes. Counters: queue_depth + slot_occupancy gauges,
        tokens / decode_tokens / decode_steps / prefills /
        requests_completed counts (decode_tokens excludes the
        prefill-emitted first token, so decode occupancy stays bounded
        by ``slots``).
      max_queue: admission-queue bound — ``submit`` raises
        :class:`QueueFull` once this many requests are waiting for a
        slot (None = unbounded). Backpressure, not fairness: without
        it, sustained overload grows the queue without limit while
        every client times out and abandons work the engine still
        decodes to completion.
      kv_block_size: KV block size in tokens (PR 8). None (the default)
        picks the largest divisor of ``total_len`` up to 16; it must
        divide ``total_len``. 0 is refused: the engine has no
        contiguous per-slot cache (``generation.generate`` is that
        path).
      kv_blocks: pool size in blocks. Default:
        ``slots * total_len / kv_block_size`` — room for every slot at
        full length; shrink it to serve more slots from the same KV
        budget (admission gates on block availability, and a
        sequence outgrowing the pool preempts the youngest admission,
        which resumes seamlessly when blocks free).
      prefix_cache: share resident prefix blocks across requests
        (default: on wherever the model's caches can be shared, which
        True insists on). Full blocks of every prompt are
        registered under their exact token chain at admission, and
        full blocks DECODE fills are registered as the sequence grows
        (PR 11: generated-prefix registration) — so a multi-turn
        conversation's follow-up turn, whose prompt IS the prior
        prompt + reply, admits by pointing at the whole resident
        history and prefills only the new user message. A request
        whose prefix is resident admits by pointing its block table at
        the shared ref-counted blocks and prefills only the tail.
        Released registered blocks are RETAINED (LRU-evicted under
        pressure), so repeat system prompts — and conversation
        histories — keep hitting.
      speculate_k: draft-model speculation window (PR 15;
        None = off, else >= 2). Each scheduling round a reduced-depth
        weight-tied draft proposes k tokens (one scanned program) and
        the target verifies the whole window in ONE fused apply —
        each round emits 1..k tokens instead of exactly 1, cutting
        target steps per token by the acceptance rate. Greedy
        (temperature=0) outputs are BITWISE-identical to the plain
        engine (token-matching acceptance emits exactly the target's
        argmax chain — pinned in tests/test_speculative.py); at
        temperature>0 every emitted token is a true target sample but
        the PRNG stream differs (exact in distribution, not bitwise-
        reproducible). Admission, eviction, preemption-continuation,
        and drain semantics are untouched — speculation only changes
        what happens between two decode-step boundaries. Acceptance
        counters ``spec_proposed`` / ``spec_accepted`` /
        ``spec_rounds`` ride the registry; the live rate rides
        ``load_stats()`` and the fleet BEAT payload.
      draft_layers: depth of the weight-tied draft (with speculate_k
        only; default ``num_layers // 2``, min 1). The draft's params
        ARE the target's first ``draft_layers`` blocks + embeddings +
        head (``generation.draft_params`` — no separate weights, no
        training pipeline), so acceptance measures how much of the
        target's choice the early layers already decide.
      kv_dtype: KV pool storage (PR 15). None (or
        "fp32"/"float32") keeps the compute dtype; "int8" stores
        symmetric per-head absmax codes with float32 scales per token
        row of each block, quantizing at write time and dequantizing
        INSIDE the attention formulation (fused kernel and blockwise
        loop alike) — per-step KV bandwidth drops to the int8 bytes
        and the same byte budget buys ~3.2x the blocks at head_dim
        16. Lossy: outputs are pinned by top-1 agreement, not
        bitwise; see docs/serving.md for the error model.

    Request lifecycle (PR 4): ``submit(..., deadline_s=T)`` attaches a
    completion deadline. Admission SHEDS the request
    (:class:`Shed` -> HTTP 503 + Retry-After) when the deadline is
    infeasible under the engine's own measured rates (see
    :meth:`estimate_admission`); an admitted request past its deadline
    — or cancelled via ``handle.cancel()`` / stream abandonment — is
    EVICTED at the next decode-step boundary, freeing its slot for
    queued work. :meth:`drain` refuses new work and finishes every
    admitted request (graceful shutdown); :meth:`respawn` rebuilds a
    fresh engine from this one's construction config (the supervisor's
    RestartEngine recovery). Lifecycle counts ride ``counters``:
    ``shed`` / ``cancelled`` / ``deadline_exceeded`` /
    ``engine_restarts``.
    """

    def __init__(self, model, params, slots=8, total_len=None,
                 buckets=None, temperature=0.0, top_k=None, top_p=None,
                 eos_token=None, rng=None, counters=None, timers=None,
                 max_queue=1024, metrics=None, flight=None,
                 replica_id=None, kv_block_size=None, kv_blocks=None,
                 prefix_cache=None, speculate_k=None,
                 draft_layers=None, kv_dtype=None, tier=None,
                 qos_policy=None):
        import jax
        import jax.numpy as jnp

        from tensorflowonspark_tpu import generation

        #: stable serving identity (fleet plane): survives respawn() —
        #: the join key between scraped metric series, /healthz bodies,
        #: reservation-server serving leases, and router decisions. A
        #: fresh engine gets a process-unique default; a respawned one
        #: inherits its predecessor's verbatim.
        self.replica_id = str(replica_id) if replica_id is not None \
            else "engine-{}-{}".format(os.getpid(), next(_ENGINE_IDS))
        # construction config, verbatim, so respawn() can rebuild an
        # identical engine after a scheduler death (supervisor.py's
        # RestartEngine policy) — deliberately the ORIGINAL params
        # object, not any later mutation of self.params
        self._spawn_args = dict(
            model=model, params=params, slots=slots, total_len=total_len,
            buckets=buckets, temperature=temperature, top_k=top_k,
            top_p=top_p, eos_token=eos_token, rng=rng,
            max_queue=max_queue, replica_id=self.replica_id,
            kv_block_size=kv_block_size, kv_blocks=kv_blocks,
            prefix_cache=prefix_cache,
            speculate_k=speculate_k, draft_layers=draft_layers,
            kv_dtype=kv_dtype, tier=tier, qos_policy=qos_policy)
        self._generation = generation
        #: serving tier (PR 17 disaggregation): "prefill" engines take
        #: prompt work and ship resident KV blocks out, "decode"
        #: engines adopt shipped blocks and stream tokens, "mixed"
        #: (the default) does both — exactly the pre-PR-17 engine.
        #: Rides load_stats -> the BEAT lease -> router views ->
        #: autoscaler views, so two-stage dispatch and tier-aware
        #: sizing read it from the same one schema field.
        if tier is None:
            tier = "mixed"
        if tier not in ("prefill", "decode", "mixed"):
            raise ValueError(
                "tier must be 'prefill', 'decode', or 'mixed', "
                "got {!r}".format(tier))
        self.tier = str(tier)
        total_len = int(total_len or model.max_len)
        if total_len > model.max_len:
            raise ValueError(
                "total_len {} exceeds model.max_len {}".format(
                    total_len, model.max_len))
        if int(slots) < 1:
            raise ValueError("slots must be >= 1, got {}".format(slots))
        self.model = model
        self.params = params
        self.slots = int(slots)
        self.total_len = total_len
        self.buckets = tuple(sorted(int(b) for b in buckets)) if buckets \
            else generation.default_buckets(total_len)
        if self.buckets[-1] > total_len:
            raise ValueError(
                "largest bucket {} exceeds total_len {}".format(
                    self.buckets[-1], total_len))
        self.eos_token = None if eos_token is None else int(eos_token)
        self.max_queue = None if max_queue is None else int(max_queue)
        # same fail-loudly contract as generation.generate: top_k=0 /
        # top_p=0 would mask every logit and serve token 0 engine-wide
        generation.check_sampling_config(temperature, top_k, top_p, rng)
        self.counters = counters if counters is not None \
            else tracing.Counters()
        self.timers = timers if timers is not None \
            else tracing.StageTimers("engine")
        #: the engine's observability plane (PR 5): one MetricsRegistry
        #: carrying its counters, stage timers, and latency histograms
        #: — ModelServer's GET /metrics renders it.
        #: Registration is idempotent, so a respawned engine re-adds
        #: the same shared objects under the same family names.
        self.metrics = metrics if metrics is not None \
            else tracing.MetricsRegistry()
        self.metrics.add_counters("tfos_serving", self.counters)
        self.metrics.add_timers("tfos_serving_stage", self.timers)
        self._hist_ttft = self.metrics.histogram(
            "tfos_serving_ttft_seconds")
        self._hist_token = self.metrics.histogram(
            "tfos_serving_token_latency_seconds")
        self._hist_step = self.metrics.histogram(
            "tfos_serving_decode_step_seconds")
        self._hist_qwait = self.metrics.histogram(
            "tfos_serving_queue_wait_seconds")
        self._hist_request = self.metrics.histogram(
            "tfos_serving_request_seconds")
        self._hist_drain = self.metrics.histogram(
            "tfos_serving_drain_seconds")
        # per-request critical-path attribution (PR 20): at finish, the
        # request's lifecycle spans are partitioned into named stages
        # (slo.attribute_intervals, sum-to-wall by construction) and
        # each stage's seconds land in its own histogram
        self._hist_attrib = {
            stage: self.metrics.histogram(
                "tfos_slo_attrib_{}_seconds".format(stage))
            for stage in ("queue_wait", "admission", "prefill",
                          "decode", "preempted")}
        #: request trace timeline (PR 5): span events for every request
        #: (admit -> queue -> prefill -> decode -> finish/evict/shed)
        #: land in this bounded ring; GET /debug/trace and
        #: scripts/trace_dump.py render it as Chrome trace JSON
        self.flight = flight if flight is not None \
            else tracing.flight_recorder()
        # ring saturation is an exported signal, not a silent loss:
        # /metrics carries tfos_trace_spans_dropped_total
        tracing.expose_flight_drops(self.metrics, self.flight)
        # KV-ship observability (PR 17): PHYSICAL bytes/blocks over the
        # ship wire — codes + scales as stored, never the logical
        # dequantized size — plus per-ship wall time and per-reason
        # splice rejections. Writers are HTTP handler threads as well
        # as the scheduler, so unlike self.counters these mutate only
        # through the _cv-guarded note_ship()/note_splice_failure()
        # helpers (Counters itself is single-writer by convention).
        self.kv_counters = self.metrics.add_counters(
            "tfos_kv", tracing.Counters())
        self._hist_ship = self.metrics.histogram("tfos_kv_ship_ms")
        self._splice_failures = {}  # reason -> count (guarded by _cv)
        # -- multi-tenant QoS plane (PR 18) ----------------------------
        #: operator QoS config: per-tenant fair-share weights and
        #: token-rate quotas (qos.QosPolicy / kwargs dict / None)
        self.qos_policy = qos.QosPolicy.from_spec(qos_policy)
        # deficit-counter weighted-fair admission with strict priority
        # classes — replaces the FIFO head scan. Scheduler-thread
        # private: select/charge run only inside the admission scan.
        self._qos_sched = qos.FairScheduler(self.qos_policy)
        # per-tenant token buckets, post-paid: the scheduler thread
        # charges ACTUAL deliveries (exact usage; dedup replays deliver
        # nothing, so retries never double-charge), HTTP handler
        # threads check admission — QuotaTable has its own lock for
        # that two-population split.
        self._quota = qos.QuotaTable(self.qos_policy)
        # tenant-labeled tallies behind the tfos_qos_* families
        # (ModelServer.metrics_text renders them). All four mutate
        # under _cv: admitted/preemptions/tokens are scheduler-thread
        # writes inside _cv'd sections, quota rejections land from
        # HTTP handler threads via note_quota_rejection().
        self._qos_admitted = {}          # (tenant, class) -> requests
        self._qos_preemptions = {}       # (tenant, class) -> evictions
        self._qos_tokens = {}            # tenant -> generated tokens
        self._qos_quota_rejections = {}  # tenant -> refusals
        # queue-wait distribution per priority class — the isolation
        # number the antagonist bench pins (a flooded LOW class must
        # not move the HIGH class's wait)
        self._hist_qwait_class = {
            name: self.metrics.histogram(
                "tfos_qos_queue_wait_{}_seconds".format(name))
            for name in qos.PRIORITIES}
        self._temperature = float(temperature)
        #: positions per step and row: 0 for a model that yields one
        #: token per step, ``model.block_len`` for one that generates by
        #: diffusion over blocks (models/sdar_moe.py). Read off the
        #: MODEL: there is no engine option for it.
        self._block_len = int(getattr(model, "block_len", 0) or 0)
        # -- paged KV setup (PR 8) ------------------------------------
        # kv_block_size: None = the largest divisor of total_len up to
        # 16 (the divisibility makes the paged logical view exactly
        # total_len long, the bitwise-parity condition). The block pool
        # is the only way this engine reaches K and V.
        if kv_block_size is None:
            kv_block_size = next(b for b in range(16, 0, -1)
                                 if total_len % b == 0)
        self.kv_block_size = int(kv_block_size)
        if self.kv_block_size < 1 or not (
                hasattr(model, "kv_block_size") and hasattr(model, "clone")):
            raise ValueError(_PAGED_ONLY.format(
                self.kv_block_size, type(model).__name__))
        if self._block_len:
            self._check_block_mode(
                model, total_len, temperature=temperature, top_k=top_k,
                top_p=top_p, eos_token=eos_token,
                kv_block_size=self.kv_block_size,
                speculate_k=speculate_k, kv_dtype=kv_dtype, tier=tier)
            # denoising passes write a block's K/V before it is final,
            # so no block of such a sequence is ever registered for
            # sharing
            prefix_cache = False
        #: the kinds of cache a slot holds beside the full one, read
        #: off the MODEL as ``block_len`` is (models/mellum_moe.py:
        #: ``{"window": positions}``); none for a model whose layers
        #: all keep every position
        kinds = dict(getattr(model, "cache_kinds", None) or {})
        if kinds:
            self._check_cache_kinds(
                model, kinds, prefix_cache=prefix_cache,
                speculate_k=speculate_k, kv_dtype=kv_dtype, tier=tier)
        if prefix_cache is None:
            prefix_cache = not kinds
        norm_top_k = None if top_k is None else int(top_k)
        norm_top_p = None if top_p is None else float(top_p)
        # int8 KV knob (PR 15): None / "fp32" / "float32" keep the
        # compute-dtype pool; "int8" stores quantized codes + per-head
        # scales (models/decoder.py) and halves+ per-step KV bandwidth
        if kv_dtype in ("fp32", "float32"):
            kv_dtype = None
        if kv_dtype not in (None, "int8"):
            raise ValueError(
                "kv_dtype must be None/'fp32'/'float32' or 'int8', "
                "got {!r}".format(kv_dtype))
        self._kv_quant = kv_dtype == "int8"
        # speculative decoding knob (PR 15): k >= 2 proposal window
        if speculate_k is not None and int(speculate_k) < 2:
            raise ValueError(
                "speculate_k must be >= 2 (a 1-token window is a "
                "plain decode step plus a wasted draft), got "
                "{}".format(speculate_k))
        if speculate_k is None and draft_layers is not None:
            raise ValueError("draft_layers needs speculate_k")
        self._spec_k = 0 if speculate_k is None else int(speculate_k)
        if total_len % self.kv_block_size:
            raise ValueError(
                "kv_block_size {} must divide total_len {} (the "
                "paged logical view must equal the contiguous "
                "cache length for bitwise parity)".format(
                    self.kv_block_size, total_len))
        self._blocks_per_slot = total_len // self.kv_block_size
        # pool default: room for every slot at full length (slots x
        # total_len tokens) — shrink kv_blocks to trade memory for
        # admission pressure (paging makes short sequences stop paying
        # max_len worth of blocks)
        self.kv_blocks = int(kv_blocks) if kv_blocks is not None \
            else self.slots * self._blocks_per_slot
        if self.kv_blocks < 1:
            raise ValueError("kv_blocks must be >= 1, got {}".format(
                self.kv_blocks))
        self.prefix_cache = bool(prefix_cache)
        #: every kind of cache the slots hold (paging.CacheKinds): what
        #: a sequence needs, holds and gives back is asked of the kinds
        #: together. ``_pool``, ``_slot_blocks`` and the first columns
        #: of ``_tables`` are the FULL kind's, the one whose blocks are
        #: shared by prefix, shipped and mirrored by a draft, and the
        #: one ``kv_blocks`` sizes: a window kind's pool holds every
        #: slot's window and never runs short.
        self._kv = paging.CacheKinds(
            self.slots, self._blocks_per_slot, self.kv_block_size,
            self.kv_blocks, kinds,
            kv_dtype="int8" if self._kv_quant else "float32")
        self._pool = self._kv.full.pool
        self._other_kinds = self._kv.others()
        self._last_prefix_evictions = 0
        self._last_prefix_hits = 0
        self._last_prefix_misses = 0
        self._last_generated_registered = 0
        self._last_generated_hits = 0
        #: (head handle, available) when the queue head last failed
        #: the block gate — skips re-planning it until the pool
        #: changes (see the admission scan)
        self._head_block_memo = None
        clone_kw = dict(kv_block_size=self.kv_block_size,
                        kv_blocks=self.kv_blocks + 1)
        for kind in self._other_kinds:
            clone_kw["kv_{}_blocks".format(kind.name)] = \
                kind.pool.num_blocks + 1
        if self._kv_quant:
            clone_kw["kv_dtype"] = "int8"
        try:
            # the served model is the caller's, re-speced for the
            # pool (+1 device row: the scratch block pad writes
            # land in). Params are layout-identical — only the
            # cache collection's structure changes.
            model = model.clone(**clone_kw)
        except TypeError:
            raise ValueError(_PAGED_ONLY.format(
                self.kv_block_size, type(model).__name__))
        self._model = model
        if self._block_len:
            self._prefill_fn, self._decode_fn = \
                generation.paged_block_fns(model)
        else:
            self._prefill_fn, self._decode_fn = \
                generation.paged_step_fns(
                    model, self._temperature, norm_top_k, norm_top_p)
        if self._spec_k:
            # draft-model speculation (PR 15): a reduced-depth,
            # weight-TIED clone of the served model proposes
            # speculate_k tokens per round; the target verifies
            # them in one fused multi-token apply. The draft keeps
            # its own (smaller) pool pytree but shares the host
            # block tables and cursors, so ONE BlockPool governs
            # both and every target write has a mirrored draft
            # write — which is what keeps prefix-cache hits valid
            # against the draft pool too.
            n_layers = getattr(model, "num_layers", None)
            if n_layers is None:
                raise ValueError(
                    "speculate_k needs a model with a num_layers "
                    "field to derive a reduced-depth draft; {} "
                    "has none".format(type(model).__name__))
            if draft_layers is None:
                draft_layers = max(1, int(n_layers) // 2)
            draft_layers = int(draft_layers)
            if not 1 <= draft_layers <= int(n_layers):
                raise ValueError(
                    "draft_layers must be in [1, num_layers={}], "
                    "got {}".format(n_layers, draft_layers))
            self.draft_layers = draft_layers
            draft_model = model.clone(num_layers=draft_layers)
            self._draft_model = draft_model
            self._draft_params = generation.draft_params(
                params, draft_layers)
            self._round_fn = generation.speculative_step_fns(
                model, draft_model, self._spec_k,
                self._temperature, norm_top_k, norm_top_p)
            self._draft_prefill_fn = generation.paged_step_fns(
                draft_model, self._temperature, norm_top_k,
                norm_top_p)[0]
        else:
            self.draft_layers = 0
        self._key = rng if rng is not None else jax.random.PRNGKey(0)
        self._queue = collections.deque()
        # KV ship/splice jobs (PR 17): export and import must run on
        # the scheduler thread (pool mutation + cache access are its
        # monopoly), so client threads enqueue here under _cv and wait
        # on a per-job event — the same single-writer discipline the
        # request queue uses
        self._kv_jobs = collections.deque()
        self._cv = threading.Condition()
        self._stopping = False
        self._draining = False
        self._broken = None
        self._failed_requests = 0  # admitted-but-failed ledger (drain)
        #: cumulative requests submitted (chaos site: the
        #: kill_serving_executor_at_request count)
        self._requests_seen = 0
        # admission-control evidence: EWMAs of this engine's own recent
        # decode-step and prefill wall times (scheduler thread writes,
        # submit path reads under _cv). None until the first sample —
        # a cold engine never sheds (no evidence, no refusal).
        self._step_ewma = None
        self._prefill_ewma = None
        # speculation evidence (PR 15): tokens EMITTED per round per
        # active slot (EWMA, [1, speculate_k]) — the acceptance-scaled
        # divisor estimate_admission prices service time with (a
        # speculative engine's _step_ewma measures the whole
        # draft+verify ROUND, which emits several tokens). None until
        # the first round; 1.0-equivalent on a plain engine.
        self._tokens_round_ewma = None
        # queue-wait EWMA rides the fleet BEAT lease: the router's
        # least-loaded policy wants "how long does work wait HERE",
        # which gauges alone (depth, occupancy) don't price
        self._qwait_ewma = None
        self._ewma_alpha = 0.3
        self._slot_req = [None] * self.slots
        self._idx = np.zeros(self.slots, np.int32)
        self._last = np.zeros(self.slots, np.int32)
        # the token step in flight (dispatched, its tokens not read
        # yet): the device's answer ``picked [S]``, which is also the
        # next step's input, per slot the request it was dispatched for
        # (None: the row was idle) and when. A token engine keeps at
        # most one; block-stepping and speculative engines none, ever.
        # (behind the tokens, the routed experts of a model that sows
        # them: generation.with_routed)
        self._picked = jnp.zeros(
            (generation.answer_len(model, self.slots, self.slots),),
            jnp.int32)
        self._top_k = int(getattr(model, "experts_per_tok", 0))
        self._flight = None   # or (picked, [request or None] * S, t0)
        self._read_at = 0.0   # when a step's tokens were last read
        # how often it engages and what it cost, exported from the
        # start: an engine that never runs ahead reads 0, not absent
        self.counters.inc("steps_dispatched_ahead", 0)
        self.counters.inc("tokens_dropped_in_flight", 0)
        self.counters.inc("attn_grid_steps", 0)
        self.counters.inc("attn_table_slots", 0)
        for kind in self._other_kinds:
            for name in ("kv_{}_block_steps", "kv_{}_blocks_given_back",
                         "attn_{}_grid_steps", "attn_{}_table_slots"):
                self.counters.inc(name.format(kind.name), 0)
        # host-authoritative block tables: row s mirrors
        # _slot_blocks[s] padded with scratch (0). A freed slot's
        # row resets to scratch AND its cursor to 0, so the idle
        # slot's per-step write lands in the scratch block instead
        # of whatever its released blocks became.
        self._slot_blocks = self._kv.full.blocks
        self._tables = self._kv.tables
        self._admit_seq = itertools.count()
        self._slot_seq = [0] * self.slots
        # generated-prefix registration cursor (PR 11): how many
        # leading FULL blocks of each slot's sequence have been
        # published to the prefix registry — admission seeds it,
        # boundary crossings and completion advance it
        self._slot_registered = [0] * self.slots
        if self._block_len:
            # each slot's current block, the host's: its tokens, which
            # positions are still masked, at which pass of the block
            # each was unmasked (for the handle), how many leading
            # positions were given (prompt, or delivered before a
            # preemption), how many are inside the request, and the
            # number of denoising passes the block has had
            blk = (self.slots, self._block_len)
            self._blk_tok = np.zeros(blk, np.int32)
            self._blk_masked = np.ones(blk, bool)
            self._blk_when = np.full(blk, -1, np.int32)
            self._blk_given = np.zeros(self.slots, np.int32)
            self._blk_live = np.zeros(self.slots, np.int32)
            self._blk_pass = np.zeros(self.slots, np.int32)
        # the cache on the device: the POOLS only (cursors and tables
        # are this thread's numpy and reach a program inside its feed)
        self._cache = generation.init_pools(model)
        #: resolved pool storage dtype — the pinned schema string
        #: load_stats / /healthz / the fleet BEAT payload carry
        #: ("int8" on the quantized fast path, the compute dtype name
        #: otherwise; one source of truth: the live cache leaves)
        self.kv_dtype = next(
            (str(leaf.dtype) for _, leaf
             in generation.pool_leaves(self._cache)), "none")
        self._kv.set_block_bytes(
            generation.pool_leaves_by_table(model, self._cache))
        if self._spec_k:
            # the draft's own pools (draft_layers/num_layers of the
            # target's KV bytes); tables and cursors are host-shared
            self._draft_cache = generation.init_pools(self._draft_model)
        self._buffers_counted = False  # the loop's: _admit, once
        self._publish_kv_gauges()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tfos-decode-engine")
        self._thread.start()

    @staticmethod
    def _check_block_mode(model, total_len, temperature, top_k, top_p,
                          eos_token, kv_block_size, speculate_k, kv_dtype,
                          tier):
        """Refuse, with the reason, what an engine that steps by blocks
        does not do (docs/serving.md, "Stepping by blocks")."""
        b = int(model.block_len)
        why = None
        if temperature or top_k is not None or top_p is not None:
            why = "it unmasks by confidence at temperature 0: no " \
                  "temperature, top_k or top_p"
        elif eos_token is not None:
            why = "a request ends at max_new_tokens: no eos_token"
        elif speculate_k is not None:
            why = "a pass already yields several tokens: no speculate_k"
        elif kv_dtype is not None:
            why = "its K/V pool is stored at the model's dtype: no kv_dtype"
        elif tier != "mixed":
            why = "it neither ships nor adopts K/V blocks: no " \
                  "tier={!r}".format(tier)
        elif total_len % b or kv_block_size % b:
            # a block must never straddle two KV blocks: growth looks
            # one position ahead and a commit moves a whole block
            why = "block_len {} must divide total_len {} and the KV " \
                  "block size".format(b, total_len)
        elif b % int(model.denoise_steps):
            why = "denoise_steps {} must divide block_len {}".format(
                model.denoise_steps, b)
        if why:
            raise ValueError(
                "{} generates by diffusion over blocks of {}: {}".format(
                    type(model).__name__, b, why))

    @staticmethod
    def _check_cache_kinds(model, kinds, prefix_cache, speculate_k,
                           kv_dtype, tier):
        """Refuse, with the reason, what an engine does not do yet for
        a model whose layers keep more than one kind of cache
        (docs/serving.md, "Two kinds of cache")."""
        why = None
        if prefix_cache:
            why = "a prefill attends its own K and V from position 0 " \
                  "and a window layer keeps no block to share: no " \
                  "prefix_cache"
        elif speculate_k is not None:
            why = "a call of several positions is a prefill from " \
                  "position 0, not a verify: no speculate_k"
        elif kv_dtype not in (None, "fp32", "float32"):
            why = "its pools are stored at the model's dtype: no kv_dtype"
        elif tier != "mixed":
            why = "kvship frames one kind of block: no tier={!r}".format(
                tier)
        if why:
            raise ValueError("{} keeps {} caches a slot ({}): {}".format(
                type(model).__name__, 1 + len(kinds),
                ", ".join(["full"] + sorted(kinds)), why))

    # -- client API ------------------------------------------------------

    def validate(self, prompt, max_new_tokens):
        """Raise ValueError/TypeError if the request cannot be served;
        returns the normalized ``(prompt, max_new)``. Exposed so batch
        callers (ModelServer.generate) can vet a WHOLE body before
        submitting any of it — a mid-batch reject must not leave earlier
        prompts decoding for a client that already got its 400."""
        prompt = [int(t) for t in prompt]
        max_new = int(max_new_tokens)
        if not prompt:
            raise ValueError("prompt must be non-empty")
        vocab = getattr(self.model, "vocab", None)
        if vocab is not None:
            bad = next((t for t in prompt if not 0 <= t < vocab), None)
            if bad is not None:
                # nn.Embed would silently CLAMP out-of-range ids inside
                # jit — the client must get a 400, not a generation for
                # a prompt it never sent
                raise ValueError(
                    "prompt token {} outside vocab [0, {})".format(
                        bad, vocab))
        if max_new < 0:
            raise ValueError(
                "max_new_tokens must be >= 0, got {}".format(max_new))
        # raises if the prompt outgrows every bucket:
        self._generation.bucket_for(len(prompt), self.buckets)
        if len(prompt) + max_new > self.total_len:
            raise ValueError(
                "prompt {} + max_new_tokens {} exceeds total_len {}".format(
                    len(prompt), max_new, self.total_len))
        need = self._pool.blocks_for(len(prompt) + max_new)
        if need > self.kv_blocks:
            # permanent infeasibility, not load: the request's
            # worst case can never fit the pool even running alone
            raise ValueError(
                "request needs up to {} KV blocks but the pool has "
                "{} (kv_blocks)".format(need, self.kv_blocks))
        return prompt, max_new

    def submit(self, prompt, max_new_tokens, deadline_s=None,
               session=None, tenant=None, priority=None):
        """Queue one request; returns its :class:`GenerationHandle`.

        Validation happens HERE, on the caller's thread, so a malformed
        request raises to its client instead of poisoning the shared
        scheduler loop (same discipline as ``_Batcher.submit``).

        ``deadline_s`` (seconds from now) bounds the request's whole
        life: admission sheds it when the deadline is infeasible under
        measured rates (:class:`Shed`), and an admitted request past
        its deadline is evicted at the next decode-step boundary
        (:class:`DeadlineExceeded` from ``result``/``stream``).

        ``session``: opaque conversation id threaded onto the handle
        (the fleet router's affinity key); the engine itself does not
        interpret it.

        ``tenant`` / ``priority`` (PR 18): QoS identity. Omitted =
        the ``default`` tenant at ``normal`` class — every pre-QoS
        caller is unchanged. Malformed values raise ``ValueError``
        (HTTP 400); a tenant whose token bucket is in debt raises
        :class:`qos.QuotaExceeded` (HTTP 429 + Retry-After).
        """
        return self._submit_many([self.validate(prompt, max_new_tokens)],
                                 deadline_s=deadline_s,
                                 session=session, tenant=tenant,
                                 priority=priority)[0]

    def estimate_admission(self, max_new_tokens, prompt=None):
        """{'queue_wait_s', 'service_s'} — what admitting a request of
        ``max_new_tokens`` now would plausibly cost, from the engine's
        own measured rates (EWMA decode-step and prefill wall times).

        The model: queued requests each owe one serial prefill; decode
        steps are shared, so the token backlog (queued max_new plus
        what in-flight slots still owe) drains at ``slots`` tokens per
        step. ``service_s`` is the request's own prefill + max_new
        steps. ``prompt`` (the token list) lets a PAGED engine price
        block availability too: a request whose prefill blocks are not
        obtainable cannot start before an in-flight sequence finishes
        and frees some, so its queue wait is floored at the earliest
        possible release. Zeros until the engine has served anything —
        admission control sheds on EVIDENCE, never on a cold engine's
        guess.
        """
        with self._cv:
            return self._estimate_locked(int(max_new_tokens),
                                         prompt=prompt)

    def _estimate_locked(self, max_new, extra_requests=0, extra_tokens=0,
                         prompt=None, extra_blocks=0):
        """``extra_requests``/``extra_tokens``/``extra_blocks``: work
        ahead of this request that is not in the queue yet — the
        earlier members of the same multi-prompt body during whole-body
        shed vetting. A body's members queue together, so member k
        waits behind members 0..k-1 exactly as it would behind queued
        strangers."""
        step = self._step_ewma or 0.0
        prefill = self._prefill_ewma or 0.0
        # speculation-adjusted per-token cost (PR 15): a speculative
        # round costs _step_ewma but emits tokens-per-round EWMA
        # tokens per slot, so the effective per-token step time is the
        # ratio — shed decisions stay honest when k is on instead of
        # pricing every token at the (heavier) round cost
        # (a block-stepping engine emits FEWER than one token per row
        # and step: block_len of them in denoise_steps + 1 passes)
        tpr = max(self._tokens_round_ewma or 1.0,
                  1.0 / (self._block_len + 1))
        step = step / tpr
        backlog = extra_tokens + sum(h.max_new_tokens
                                     for h in self._queue)
        remaining = []
        for s in range(self.slots):
            handle = self._slot_req[s]
            if handle is not None:
                owed = max(handle.max_new_tokens - len(handle._tokens), 0)
                backlog += owed
                remaining.append(owed)
        wait = (len(self._queue) + extra_requests) * prefill \
            + backlog * step / self.slots
        if prompt is not None and step:
            # block-pressure pricing (PR 8): when the pool cannot
            # supply this request's prefill blocks right now, no slot
            # math helps — it waits until an in-flight sequence
            # finishes and releases blocks. Floor the wait at the
            # EARLIEST possible release so a tight deadline sheds at
            # the door (503 + Retry-After) instead of queueing into a
            # certain 504.
            # ONE atomic pool snapshot (plan_admission): plan and
            # capacity from separate lock holds can straddle a
            # scheduler-side acquire/release — the torn read
            # double-counts the deficit (spurious shed) or masks it
            # (admit into a certain 504)
            shared, need, lru_shared, allocatable, _ = \
                self._pool.plan_admission(prompt)
            deficit = need + lru_shared + extra_blocks - allocatable
            if deficit > 0 and remaining:
                wait = max(wait, min(remaining) * step)
        return {"queue_wait_s": wait,
                "service_s": prefill + max_new * step}

    def _submit_many(self, vetted, deadline_s=None, trace=None,
                     session=None, tenant=None, priority=None):
        """Atomically queue a whole vetted body: either every request is
        admitted or none is (QueueFull / Shed / stopped / draining /
        broken raise before any handle exists), so a mid-batch refusal
        never leaves earlier prompts of the same body decoding for a
        client that already got its error. max_new==0 requests complete
        inline (the prompt IS the answer) but still pass the liveness
        checks — a dead engine must refuse degenerate requests as
        loudly as real ones. ``trace``: adopt an externally minted
        trace id (the router's ``X-TFOS-Trace``) for every handle of
        the body — one propagated id, one Perfetto row. ``tenant`` /
        ``priority``: validated QoS identity for the whole body (one
        client, one class); a quota-indebted tenant is refused BEFORE
        any handle exists, same atomicity as QueueFull."""
        if deadline_s is not None:
            deadline_s = float(deadline_s)
            if not deadline_s > 0:
                raise ValueError(
                    "deadline_s must be > 0, got {}".format(deadline_s))
        tenant = qos.validate_tenant(tenant)
        priority = qos.validate_priority(priority)
        # quota gate (PR 18): post-paid token buckets — usage is
        # charged by the scheduler at ACTUAL delivery, so this check
        # never charges (a dedup-keyed retry that replays a stored
        # completion costs nothing). Checked outside _cv: QuotaTable
        # has its own lock, and a refused tenant must not serialize
        # against the scheduler.
        try:
            self._quota.admit(tenant)
        except qos.QuotaExceeded:
            self.note_quota_rejection(tenant, requests=len(vetted))
            raise
        with self._cv:
            # chaos site (PR 13): kill_serving_executor_at_request
            # fires on the K-th submitted request — whole-executor
            # SIGKILL for the autoscaler's replacement path. Counted
            # under _cv (concurrent HTTP handlers submit in parallel;
            # an unlocked read-modify-write would drift the fire
            # point) and BEFORE admission, so the K-th request itself
            # never answers (its router attempt fails over). O(1)
            # when unarmed.
            self._requests_seen += len(vetted)
            chaos.on_serving_request(self._requests_seen,
                                     ident=self.replica_id)
            # draining outranks stopped: a drained engine ends with
            # BOTH flags set, and a request that raced past the HTTP
            # layer's drain check must still get the retriable 503
            # ("go to another replica"), never a 500 'engine stopped'
            if self._draining:
                raise Draining(
                    "engine is draining; not accepting new requests")
            if self._stopping:
                raise RuntimeError("engine stopped")
            if self._broken is not None:
                raise EngineFailed(
                    "engine failed: {}".format(self._broken))
            queueing = sum(1 for _, mn in vetted if mn > 0)
            if self.max_queue is not None and \
                    len(self._queue) + queueing > self.max_queue:
                raise QueueFull(
                    "admission queue full ({} waiting, max_queue {})"
                    .format(len(self._queue), self.max_queue))
            if deadline_s is not None:
                # shed the WHOLE body if any member's deadline is
                # infeasible under measured rates — same atomicity as
                # QueueFull (nothing of a refused body may decode).
                # Members are priced CUMULATIVELY: member k queues
                # behind members 0..k-1 of its own body, so a jointly-
                # infeasible body (each member cheap, the sum not)
                # refuses instead of admitting work that will 504.
                # max_new==0 members complete inline — they never
                # queue, prefill, or decode, so they are neither
                # priced nor charged to later members
                ahead_requests = ahead_tokens = ahead_blocks = 0
                for prompt, max_new in vetted:
                    if max_new == 0:
                        continue
                    est = self._estimate_locked(
                        max_new, extra_requests=ahead_requests,
                        extra_tokens=ahead_tokens, prompt=prompt,
                        extra_blocks=ahead_blocks)
                    need = est["queue_wait_s"] + est["service_s"]
                    if need > deadline_s:
                        self.counters.inc("shed", len(vetted))
                        self.flight.instant(
                            "shed", requests=len(vetted),
                            deadline_s=deadline_s,
                            queue_wait_s=round(est["queue_wait_s"], 3),
                            service_s=round(est["service_s"], 3))
                        raise Shed(
                            "deadline {:.2f}s infeasible: estimated "
                            "queue wait {:.2f}s + service {:.2f}s"
                            .format(deadline_s, est["queue_wait_s"],
                                    est["service_s"]),
                            retry_after=math.ceil(est["queue_wait_s"]))
                    ahead_requests += 1
                    ahead_tokens += max_new
                    ahead_blocks += self._pool.blocks_for(len(prompt))
            deadline = None if deadline_s is None \
                else time.monotonic() + deadline_s
            handles = []
            for prompt, max_new in vetted:
                handle = GenerationHandle(prompt, max_new,
                                          deadline=deadline,
                                          trace=trace,
                                          session=session,
                                          tenant=tenant,
                                          priority=priority)
                self.flight.instant("admit", trace=handle.trace,
                                    prompt_len=len(prompt),
                                    max_new=max_new,
                                    deadline_s=deadline_s,
                                    session=handle.session or "",
                                    tenant=tenant, priority=priority)
                if max_new == 0:
                    handle._finish()
                    self._trace_finish(handle, "finish",
                                       record_latency=False)
                else:
                    self._queue.append(handle)
                handles.append(handle)
            if queueing:
                self.counters.gauge("queue_depth", len(self._queue))
                self._cv.notify()
        return handles

    def generate(self, prompt, max_new_tokens, timeout=600.0):
        """Blocking convenience: submit + result."""
        return self.submit(prompt, max_new_tokens).result(timeout)

    def healthy(self):
        """Scheduler-liveness report: {alive, scheduler_thread, stopping,
        draining, broken}. ``alive`` is the serving-fitness verdict —
        False once the scheduler thread died (uncaught loop error),
        broke, or the engine was stopped. A DRAINING engine is still
        alive (it is finishing admitted work); it just refuses new
        requests. supervisor.Supervisor.watch polls this and
        ModelServer's /healthz reports it (503 when not alive)."""
        with self._cv:
            broken = self._broken
            stopping = self._stopping
            draining = self._draining
        thread_alive = self._thread.is_alive()
        return {"alive": thread_alive and not stopping and broken is None,
                "scheduler_thread": thread_alive,
                "stopping": stopping,
                "draining": draining,
                "broken": str(broken) if broken is not None else None}

    def load_stats(self):
        """Live load + liveness gauges for the fleet plane — the small
        dict each serving replica's BEAT lease carries and the router's
        least-loaded dispatch prices: queue depth, slot occupancy,
        queue-wait EWMA (seconds a request recently waited for a slot),
        slot count, and the alive/draining verdicts. Cheap (no device
        work) and safe from any thread."""
        with self._cv:
            queue_depth = len(self._queue)
            occupancy = len(self._active_slots())
            qwait = self._qwait_ewma
            # QoS view (PR 18): queue split by priority class (the
            # autoscaler's per-priority breach view) and per-tenant
            # backlog/usage (the router's burst-spreading signal).
            # Always present — a tenant-less engine publishes the zero
            # schema (all-zero classes, empty tenants), never absent
            # keys, matching every other load_stats field.
            queue_by_class = dict.fromkeys(qos.PRIORITIES, 0)
            tenant_queued = {}
            for h in self._queue:
                queue_by_class[h.priority] = \
                    queue_by_class.get(h.priority, 0) + 1
                tenant_queued[h.tenant] = \
                    tenant_queued.get(h.tenant, 0) + 1
            tenant_active = {}
            for s in self._active_slots():
                handle = self._slot_req[s]
                if handle is not None:
                    tenant_active[handle.tenant] = \
                        tenant_active.get(handle.tenant, 0) + 1
            qos_tokens = dict(self._qos_tokens)
        health = self.healthy()
        stats = {"replica_id": self.replica_id,
                 "queue_depth": queue_depth,
                 "slot_occupancy": occupancy,
                 "slots": self.slots,
                 "queue_wait_ewma_s": round(qwait, 6)
                 if qwait is not None else 0.0,
                 "alive": health["alive"],
                 "draining": health["draining"],
                 "queue_by_class": queue_by_class,
                 "tenants": {t: {"queued": tenant_queued.get(t, 0),
                                 "active": tenant_active.get(t, 0),
                                 "tokens": qos_tokens.get(t, 0)}
                             for t in set(tenant_queued)
                             | set(tenant_active) | set(qos_tokens)}}
        # speculative decoding + int8 KV config (PR 15): which fast
        # paths serve this replica, and the LIVE acceptance rate —
        # mirrored into /healthz and the fleet BEAT payload so
        # heterogeneous rollouts (some replicas speculating, some
        # quantized) stay legible from one probe. Engines with both
        # features off report the zero schema (speculate_k 0, rate
        # 0.0, the pool's compute dtype) — no presence checks needed.
        proposed = self.counters.get("spec_proposed")
        stats["speculate_k"] = self._spec_k
        stats["spec_acceptance_rate"] = round(
            self.counters.get("spec_accepted") / proposed, 4) \
            if proposed else 0.0
        stats["kv_dtype"] = self.kv_dtype
        # disaggregation plane (PR 17): which tier this engine serves,
        # plus shipped-KV accounting. Byte fields are PHYSICAL — the
        # codes + scales actually transferred (frames.frame_bytes of
        # the wire buffers), never the logical dequantized size, so an
        # int8 pool's ships read ~3.2x smaller than a float pool's for
        # the same chain — that ratio IS the feature, not a bug.
        stats["tier"] = self.tier
        with self._cv:
            stats["kv_ship_bytes"] = self.kv_counters.get("ship_bytes")
            stats["kv_ship_blocks"] = self.kv_counters.get("ship_blocks")
            stats["kv_spliced_bytes"] = \
                self.kv_counters.get("spliced_bytes")
            stats["kv_spliced_blocks"] = \
                self.kv_counters.get("spliced_blocks")
        # block-pool view (PR 8): rides the fleet BEAT payload and
        # /healthz so routers and operators see memory headroom, not
        # just slot occupancy (an engine can be slot-free but
        # block-bound, or the reverse)
        ps = self._pool.stats()
        stats["kv_blocks_total"] = ps["total"]
        stats["kv_blocks_free"] = ps["free"]
        stats["prefix_hit_rate"] = round(ps["hit_rate"], 4)
        stats["generated_prefix_hit_blocks"] = ps["generated_hits"]
        stats["generated_prefix_registered"] = \
            ps["generated_registered"]
        # prefix-chain digest (PR 16): the top-K hottest resident
        # chains as [truncated hash, depth-in-blocks] pairs, the
        # bounded warmth signal the fleet router's prefix-aware
        # dispatch matches prompts against. Rides every beat —
        # bounded at paging.PREFIX_DIGEST_TOP_K entries, so the
        # lease payload stays small at any pool size;
        # digest_truncated is the honesty flag for what was cut.
        dig = self._pool.prefix_digest()
        stats["prefix_digest"] = dig["top"]
        stats["prefix_digest_block_size"] = dig["block_size"]
        stats["digest_truncated"] = dig["truncated"]
        return stats

    def kv_cache_bytes(self):
        """Resident KV-cache bytes: the block pool (including the
        scratch row, and the per-head scales an int8 pool carries
        alongside its codes), plus the draft model's pool when
        speculating."""
        caches = [self._cache]
        if self._spec_k:
            caches.append(self._draft_cache)
        return sum(leaf.size * leaf.dtype.itemsize for cache in caches
                   for _, leaf in self._generation.pool_leaves(cache))

    def kv_bytes_per_token(self):
        """``{kind: bytes one cached token costs over the kind's
        layers}``, by the pool leaves' own shapes
        (paging.CacheKinds.set_block_bytes)."""
        return self._kv.bytes_per_token()

    def outstanding(self):
        """Queued + in-flight request count (the number drain waits on)."""
        with self._cv:
            return len(self._queue) + len(self._active_slots())

    def drain(self, timeout=None):
        """Graceful shutdown: refuse new submissions (:class:`Draining`),
        finish every ADMITTED request — queued and in-flight — then stop
        the scheduler. Returns True when nothing admitted was lost;
        False when ``timeout`` (seconds) expired first or the engine
        broke mid-drain, in which case the stragglers fail with the
        stop/break error. ``timeout=None`` waits as long as the work
        takes (the zero-loss posture). Idempotent with :meth:`stop` —
        and honest about it: drain on an engine that already stopped
        (or broke) with requests in flight reports False, because
        those requests were FAILED, not finished (the emptied queue is
        a loss ledger, not a clean one).
        """
        t_drain0 = time.monotonic()
        with self._cv:
            if self._stopping:
                return self.outstanding() == 0 \
                    and self._failed_requests == 0
            if not self._draining:
                self._draining = True
                logger.info(
                    "decode engine draining: %d queued, %d in flight",
                    len(self._queue), len(self._active_slots()))
            failed_before = self._failed_requests
        deadline = None if timeout is None \
            else time.monotonic() + float(timeout)
        while True:
            with self._cv:
                left = len(self._queue) + len(self._active_slots())
                dead = self._broken is not None \
                    or not self._thread.is_alive()
            if left == 0 or dead:
                break
            if deadline is not None and time.monotonic() > deadline:
                logger.warning(
                    "drain timed out with %d request(s) outstanding; "
                    "they will fail with the stop error", left)
                break
            time.sleep(0.02)
        self.stop()
        self._hist_drain.observe(time.monotonic() - t_drain0)
        self.flight.instant("drain", outstanding=left)
        # a loop death mid-drain fails-and-clears outstanding work, so
        # left==0 alone would misreport lost requests as a clean drain
        return left == 0 and self._failed_requests == failed_before

    def respawn(self):
        """A fresh engine built from this engine's construction config
        (original model/params/slots/sampling/queue bound), SHARING its
        counters, timers, metrics registry, and flight recorder so
        lifecycle counts — ``engine_restarts``, tokens, shed/cancel
        tallies — and latency histograms continue across the restart
        (one /metrics series, not a reset). The supervisor's
        RestartEngine policy rebuilds through this after a scheduler
        death; call :meth:`stop` on the dead engine first."""
        return DecodeEngine(counters=self.counters, timers=self.timers,
                            metrics=self.metrics, flight=self.flight,
                            **self._spawn_args)

    def compile_stats(self):
        """Live program counts for the engine's jitted fns (shared per
        (model, sampling-config) via ``generation.paged_step_fns``, so
        the counts span every engine on that pair — the compile-bound
        contract the tests assert). ``_cache_size`` is private jax jit
        API; counts come back None if a jax upgrade drops it, so stats
        degrade instead of breaking the serving path."""
        def n_programs(fn):
            size = getattr(fn, "_cache_size", None)
            return size() if callable(size) else None
        stats = {"decode_programs": n_programs(self._decode_fn),
                 "prefill_programs": n_programs(self._prefill_fn),
                 "buckets": len(self.buckets),
                 "decode_call_buffers": self._step_call_buffers()}
        if self._spec_k:
            # a speculative engine's loop runs the fused round instead
            # of the plain decode fn (decode_programs stays 0); same
            # ONE-program-per-engine-config contract
            stats["spec_round_programs"] = n_programs(self._round_fn)
        return stats

    def _step_call(self):
        """``(jitted fn, arguments)`` of one call of this engine's step
        program (a token step, a block step or a speculative round) as
        the loop makes it: the engine's own parameters and pools, the
        feed as shapes."""
        int32 = _int32_shape
        s, width = self._tables.shape
        if self._spec_k:
            return self._round_fn, (
                self.params, self._draft_params, self._cache,
                self._draft_cache, int32(s), int32(s), int32(s, width),
                self._key)
        if self._block_len:
            return self._decode_fn, (
                self.params, self._cache,
                int32(s, self._block_len + 1 + width))
        return self._decode_fn, (self.params, self._cache, self._picked,
                                 int32(s, 2 + width), self._key)

    def _step_call_buffers(self):
        """Buffers the host hands over plus buffers it takes back in
        one call of the step program, read off the program's lowering
        (the trace is the one the call makes, or made): what the
        dispatch of a step costs the host goes by this count
        (generation.py, "What crosses the jit boundary"). The loop
        exports it once as the gauge ``decode_call_buffers``
        (:meth:`_admit`), and from then on that is what is read."""
        import jax

        known = self.counters.snapshot()["gauges"].get("decode_call_buffers")
        if known is not None:
            return known
        fn, args = self._step_call()
        lowered = fn.lower(*args)
        return len(jax.tree.leaves(lowered.args_info)) \
            + len(jax.tree.leaves(lowered.out_info))

    def precompile(self):
        """Compile every prefill bucket's program and the decode step
        side by side, before the first request: a cold start then waits
        for the compiler's threads to get through them together and not
        for one program after another (eight programs of a sparse-expert
        model on 13 cores: 76 s for 121, PERF.md PR 36; from a warm
        persistent cache they load no faster than one by one, and no
        slower). The first calls find them compiled: the lowering made
        here is the one a call makes. A token engine's only (no block
        stepping, no speculation: their programs take other arguments);
        call it while nothing is queued or in flight, since it reads the
        shapes of the engine's own cache. Returns the programs
        compiled."""
        import concurrent.futures

        if self._block_len or self._spec_k:
            raise ValueError(
                "precompile() knows a token engine's programs only (no "
                "block stepping, no speculate_k)")
        if self.outstanding():
            raise RuntimeError("precompile() needs an idle engine")

        int32 = _int32_shape
        # the largest bucket first: it is the longest to compile
        calls = [(self._prefill_fn, (
            self.params, self._cache, int32(self._tables.shape[1]),
            int32(b), int32(), int32(), self._key))
            for b in sorted(self.buckets, reverse=True)]
        calls.append(self._step_call())
        with concurrent.futures.ThreadPoolExecutor(len(calls)) as pool:
            list(pool.map(lambda c: c[0].lower(*c[1]).compile(), calls))
        return len(calls)

    def stop(self):
        """Stop the scheduler; queued and in-flight requests fail fast
        with RuntimeError (drain with ``handle.result()`` BEFORE stop if
        you need completions). Idempotent.

        The LOOP owns failing the outstanding handles (its exit path),
        never this thread: if the scheduler is wedged inside a long
        device call past the join timeout, mutating its slot state here
        would race it — instead we log and leave the handles to be
        failed whenever the loop next reaches its stopping check."""
        with self._cv:
            if self._stopping:
                return
            self._stopping = True
            self._cv.notify_all()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            logger.warning(
                "decode engine scheduler still inside a device call "
                "after 30s; outstanding requests will fail when it "
                "returns")

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- scheduler loop --------------------------------------------------

    def _next_key(self):
        import jax

        if not self._temperature:
            return self._key  # greedy pick never consumes it
        self._key, sub = jax.random.split(self._key)
        return sub

    def _active_slots(self):
        return [s for s in range(self.slots)
                if self._slot_req[s] is not None]

    def _idle_locked(self):
        """Nothing queued, nothing in a slot, not stopping: the loop
        parks (caller holds ``_cv``)."""
        return not (self._stopping or self._queue or self._kv_jobs
                    or self._active_slots())

    def _step_feed(self, jnp):
        """The per-step inputs of a block step or a speculative round:
        what ``step_upload`` times (a token step: :meth:`_token_feed`)."""
        if self._block_len:
            # one array, handed over as numpy: the call transfers
            # it itself, with no ``jnp.asarray`` round trip before it
            self._count_attn_grid(self._idx, self._block_len)
            return [self._generation.pack_block_feed(
                self._block_feed(), self._idx, self._tables)]
        return [jnp.asarray(self._last), jnp.asarray(self._idx),
                jnp.asarray(self._tables), self._next_key()]

    def _in_flight(self, slot):
        """Whether the step in flight computes a token of the request
        now in ``slot`` (a slot freed and admitted again since the
        dispatch holds another request: not its token)."""
        handle = self._slot_req[slot]
        return handle is not None and self._flight is not None \
            and self._flight[1][slot] is handle

    def _owes_step(self, slot):
        """Whether the request in ``slot`` needs a row in the next
        step: not when the tokens it holds and the one in flight make
        up its ``max_new_tokens`` (finish by length is known before
        the read)."""
        handle = self._slot_req[slot]
        return len(handle._tokens) + self._in_flight(slot) \
            < handle.max_new_tokens

    def _token_feed(self, rows):
        """The host's part of a token step for the slots ``rows``
        (generation.pack_step_feed): a row whose last token is still
        on the device (the step in flight computes it) feeds that back,
        a row admitted or resumed since is given the host's; every
        other row runs idle at cursor 0 over the scratch table, an
        active slot that owes no step among them (it keeps its blocks
        until its last token is delivered)."""
        take = np.zeros(self.slots, bool)
        take[rows] = True
        given = np.full(self.slots, -1, np.int32)
        for s in rows:
            if not self._in_flight(s):
                given[s] = self._last[s]
        cursors = np.where(take, self._idx, 0)
        self._count_attn_grid(cursors, 1)
        return self._generation.pack_step_feed(
            given, cursors, np.where(take[:, None], self._tables, 0))

    def _count_attn_grid(self, cursors, span):
        """What the paged kernel's grid takes in one call of the step
        being fed (ops/paged_attention.py: a work list of live blocks),
        beside the table slots a grid over every slot would: a slot at
        ``cursors[s]`` sees the blocks up to the last of the ``span``
        positions it feeds, an idle one at cursor 0 its one block.
        ``attn_grid_steps`` over ``attn_table_slots`` is the share of
        the tables that is walked (one call's; every layer's of a kind
        is the same, and a kind beside the full one counts under its
        own name: ``attn_window_grid_steps``)."""
        for kind in self._kv:
            live = kind.grid_steps(cursors, span)
            of = "attn_" if kind is self._kv.full \
                else "attn_{}_".format(kind.name)
            self.counters.inc(of + "grid_steps", int(live.sum()))
            self.counters.inc(of + "table_slots", live.size * kind.width)

    def _ewma(self, prev, sample):
        return sample if prev is None \
            else self._ewma_alpha * sample \
            + (1.0 - self._ewma_alpha) * prev

    def _trace_finish(self, handle, outcome, error=None,
                      record_latency=True):
        """Close a request's span tree in the flight recorder: the
        decode span (prefill end -> last activity) when it decoded at
        all, the outer request span (admit -> done), and a terminal
        instant named for the outcome. The request-latency histogram
        observes NORMAL engine-served completions only — evictions
        would poison the p99 the bench publishes with client-chosen
        deadlines, and ``record_latency=False`` keeps inline max_new=0
        finishes out too: they do no engine work (zero-latency samples
        would skew the distribution) AND they complete on the CALLER's
        thread, where an observe would break the histogram's
        single-writer-scheduler contract. The flight recorder is
        internally locked, so their spans still record."""
        now = handle.completed if handle.completed is not None \
            else time.monotonic()
        # a request evicted BETWEEN preemption and re-admission never
        # resumed decoding: its decode-so-far span was already closed
        # by _preempt, and stretching a new one over the evicted gap
        # would misattribute the wait as decode
        resumed = (handle._preempt_at is None
                   or (handle._decode_t0 is not None
                       and handle._decode_t0 > handle._preempt_at))
        if handle._decode_t0 is not None and resumed:
            self.flight.span("decode", handle._decode_t0, now,
                             trace=handle.trace,
                             tokens=len(handle._tokens))
            handle._attr_spans.append(("decode", handle._decode_t0, now))
        self.flight.span("request", handle.submitted, now,
                         trace=handle.trace, outcome=outcome,
                         tokens=len(handle._tokens),
                         error=None if error is None else str(error))
        self.flight.instant(outcome, trace=handle.trace)
        if outcome == "finish" and record_latency:
            self._hist_request.observe(now - handle.submitted,
                                       trace=handle.trace)
            self._observe_attribution(handle, now)

    def _observe_attribution(self, handle, now):
        """Partition the finished request's wall into named stages and
        feed the per-stage attribution histograms (scheduler thread
        only, like every engine histogram). The sweep is pure and runs
        over a handful of lifecycle spans — well under the <1%-of-wall
        overhead bar."""
        intervals = list(handle._attr_spans)
        intervals.append(("request", handle.submitted, now))
        report = slo.attribute_intervals(intervals)
        for stage, hist in self._hist_attrib.items():
            seconds = report["stages"].get(stage)
            if seconds:
                hist.observe(seconds, trace=handle.trace)

    def _evict(self, handle, err):
        handle._finish(err)
        self.counters.inc("deadline_exceeded"
                          if isinstance(err, DeadlineExceeded)
                          else "cancelled")
        self._trace_finish(handle, "evict", error=err)
        logger.info("evicted request after %d/%d tokens: %s",
                    len(handle._tokens), handle.max_new_tokens, err)

    def _prune_queue_locked(self, now):
        """Drop cancelled/expired requests from the admission queue
        (caller holds ``_cv``) — they must never reach a prefill."""
        if not any(h._evictable(now) for h in self._queue):
            return
        kept = collections.deque()
        for handle in self._queue:
            err = handle._evictable(now)
            if err is None:
                kept.append(handle)
            else:
                self._evict(handle, err)
        self._queue = kept

    def _evict_expired(self, now):
        """Free every active slot whose request is cancelled or past
        its deadline — THE step-boundary eviction: the slot is reusable
        by the very next admission scan instead of decoding to
        ``max_new_tokens`` for a client that is gone. Scheduler thread
        only (slot state is its own)."""
        for s in self._active_slots():
            err = self._slot_req[s]._evictable(now)
            if err is not None:
                self._evict(self._slot_req[s], err)
                self._slot_req[s] = None
                self._release_slot(s)

    def _plan_admission_locked(self):
        """Weighted-fair admission plan (PR 18); caller holds ``_cv``
        at a decode-step boundary. Returns ``(admits, victims)``.

        Replaces the FIFO head scan: queue entries group into
        per-(tenant, class) FIFO buckets and ``qos.FairScheduler``
        picks each admission — strict priority classes first, largest
        deficit within the strongest class — so a starved tenant
        provably catches up while the single ``_queue`` deque stays
        the source of truth for drain/evict/estimate. One tenant at
        one class degenerates to exactly the old FIFO scan (one
        bucket, heads in queue order), so every existing caller sees
        unchanged behavior.

        Block-aware admission (PR 8) is unchanged in substance: the
        selected head only enters a slot when its prefill blocks are
        obtainable NOW, verdict and capacity from ONE
        ``plan_admission`` snapshot (PR 14), and there is no bypass
        past a block-starved winner — completions free blocks and the
        scan reruns every step. The blocked-head memo generalizes to
        the blocked WINNER: selection is deterministic under unchanged
        deficits (nothing was charged after the blocked pick), so an
        unchanged pool epoch means the old verdict stands. Fairness is
        priced in the resource that actually gates entry: KV blocks on
        a paged engine (min 1 so a fully-shared prefix still pays for
        its slot), slots otherwise.

        ``victims`` are slot ids to preempt AFTER ``_cv`` is released
        (``_preempt`` re-acquires it to requeue): when a strictly
        stronger class is still waiting — for a slot or for blocks —
        the weakest-class youngest active slot is evicted, at most one
        per scan (the scan reruns every step, so catch-up is quick and
        churn stays bounded). The continuation re-prefills seamlessly
        via the PR 8 preemption machinery, bitwise at temperature=0.
        """
        admits = []
        if not self._queue:
            return admits, []
        free = [s for s in range(self.slots)
                if self._slot_req[s] is None]
        planned_blocks = 0
        block_starved = False
        # per-(tenant, class) FIFO buckets; deque order is preserved
        # inside each bucket so one tenant's own requests never reorder
        buckets = collections.OrderedDict()
        for h in self._queue:
            buckets.setdefault((h.tenant, h.priority), []).append(h)
        backlogged = {t for t, _ in buckets}
        while free and buckets:
            keys = list(buckets)
            winner = keys[self._qos_sched.select(keys)]
            head = buckets[winner][0]
            # blocked-winner memo: while the winner waits for
            # blocks, re-walking its prefix chain every decode
            # step is O(prompt) wasted on the scheduler thread.
            # Keyed on the pool's MUTATION EPOCH — every event
            # that could change the verdict bumps it, and with an
            # unchanged epoch this scan's planned_blocks is
            # provably 0, so the old verdict stands.
            if self._head_block_memo == (head, self._pool.epoch()):
                block_starved = True
                break
            toks = head.prompt + head._tokens
            shared, need, lru_shared, allocatable, \
                epoch = self._pool.plan_admission(toks)
            if need + lru_shared + planned_blocks > allocatable:
                self._head_block_memo = (head, epoch)
                block_starved = True
                break
            self._head_block_memo = None
            planned_blocks += need + lru_shared
            cost = float(max(1, need + lru_shared))
            s = free.pop(0)
            # occupy the slot AT pop time: every popped handle must be
            # findable by the failure paths (_fail_outstanding) even
            # if an EARLIER admit's prefill dies before this one runs.
            # deque.remove matches by identity (no __eq__ on handles).
            self._queue.remove(head)
            buckets[winner].pop(0)
            if not buckets[winner]:
                del buckets[winner]
            self._slot_req[s] = head
            admits.append((s, head))
            self._qos_sched.charge(winner[0], cost,
                                   backlogged=backlogged)
            self._qos_admitted[winner] = \
                self._qos_admitted.get(winner, 0) + 1
        if buckets:
            # the scan leaves a head waiting: for blocks the pool
            # cannot supply, else for a slot
            self.counters.inc("admit_scans_blocked_blocks" if block_starved
                              else "admit_scans_blocked_slots")
        victims = []
        # class preemption rides PR 8's paged preemption machinery
        # (continuation re-prefill of prompt + emitted tokens)
        if buckets and (block_starved or not free):
            # a head is still waiting; if its class is strictly
            # stronger than some in-flight sequence, that sequence
            # yields — weakest class first, youngest within the class
            # (so the oldest of the strongest class always progresses:
            # no preemption livelock)
            waiting = min(qos.priority_rank(p) for _, p in buckets)
            admitted = {s for s, _ in admits}
            cands = [
                s for s in self._active_slots()
                if s not in admitted
                and qos.priority_rank(self._slot_req[s].priority)
                > waiting]
            if cands:
                victims.append(max(
                    cands, key=lambda v: (
                        qos.priority_rank(self._slot_req[v].priority),
                        self._slot_seq[v])))
        return admits, victims

    def _loop(self):
        import jax.numpy as jnp

        steps = 0
        try:
            while True:
                if not self._active_slots():
                    # every request of the step in flight is gone
                    # (cancelled, evicted): read it off before parking
                    self._retire()
                with self._cv:
                    if self._idle_locked():
                        # idle for want of work: its own stage, so
                        # nobody reads a quiet engine as scheduler cost
                        with self.timers.timed("park"):
                            while self._idle_locked():
                                self._cv.wait()
                    if self._stopping:
                        # a last token in flight completes its request
                        self._retire()
                        self._fail_outstanding(
                            RuntimeError("engine stopped"))
                        return
                    # KV ship/splice jobs drain under the lock, run
                    # outside it (export gathers device rows to host,
                    # import scatters — both too slow for _cv). Taking
                    # them on the scheduler thread is the whole safety
                    # story: no admission or decode step interleaves
                    # with pool surgery.
                    kv_jobs = list(self._kv_jobs)
                    self._kv_jobs.clear()
                    self._prune_queue_locked(time.monotonic())
                    # QoS admission (PR 18): weighted-fair pick order
                    # replaces the FIFO head scan; the stage timer
                    # proves the scheduler stays off the hot path
                    with self.timers.timed("qos_plan"):
                        admits, victims = self._plan_admission_locked()
                    self.counters.gauge("queue_depth", len(self._queue))
                # class preemption OUTSIDE the lock (_preempt
                # re-acquires _cv to requeue its victim — _cv is
                # non-reentrant): the slot and blocks it frees admit
                # the waiting stronger-class head on the very next
                # scan — one decode step of latency, the same boundary
                # every other scheduling decision lands on
                for s in victims:
                    if self._slot_req[s] is not None:
                        with self.timers.timed("preempt"):
                            self._preempt(s)
                if kv_jobs:
                    self._retire()
                for job in kv_jobs:
                    with self.timers.timed("kv_job"):
                        self._run_kv_job(job)
                # prefill OUTSIDE the lock: submit() must never block on
                # device work
                for s, handle in admits:
                    with self.timers.timed("admit"):
                        self._admit(s, handle)
                # step-boundary eviction: cancelled / past-deadline
                # requests free their slots BEFORE the step computes
                # for them, so the next admission scan can reuse them
                with self.timers.timed("evict"):
                    self._evict_expired(time.monotonic())
                if self._other_kinds:
                    # what a window has left goes back to its pool in
                    # this very turn, a stage of its own, BEFORE the
                    # turn's growth: where a block is left and the next
                    # begun in one turn (window = 1 mod block size) a
                    # slot never holds more than its window's blocks,
                    # which is all its pool has for it
                    with self.timers.timed("trim_blocks"):
                        self._trim_active_blocks()
                # lazy block growth (and, under exhaustion,
                # youngest-first preemption) for every slot whose
                # NEXT write crosses a block boundary
                with self.timers.timed("grow_blocks"):
                    self._grow_active_blocks()
                active = self._active_slots()
                self.counters.gauge("slot_occupancy", len(active))
                if not active:
                    continue
                # serving chaos sites: stall_decode_for / a scheduler
                # kill lands here, between steps — the same boundary
                # every other scheduling decision uses (replica_id
                # scopes an only=<replica> injection to THIS engine of
                # an in-process fleet)
                chaos.on_decode_step(steps, self.replica_id)
                if not (self._spec_k or self._block_len):
                    # a token step's next input is the device's own
                    # output, so one step stays in flight while the
                    # host reads and schedules; where the next input
                    # is a decision the host makes from the answers
                    # (below) the turn reads before it schedules
                    rows = [s for s in active if self._owes_step(s)]
                    self._token_turn(rows, steps)
                    steps += bool(rows)
                    continue
                t0 = time.monotonic()
                if self._spec_k:
                    drafts, targets = self._spec_round(jnp)
                else:
                    # upload, the jitted call until it returns (the
                    # host path of a dispatch: the device works on
                    # after it), the blocking read of the answers
                    with self.timers.timed("decode_step"):
                        with self.timers.timed("step_upload"):
                            feed = self._step_feed(jnp)
                        with self.timers.timed("step_dispatch"):
                            self._cache, toks = self._decode_fn(
                                self.params, self._cache, *feed)
                        with self.timers.timed("step_sync"):
                            # the per-step host sync (tokens,
                            # confidences and the routed experts in
                            # one array)
                            toks = np.asarray(toks)
                t1 = time.monotonic()
                self._step_ewma = self._ewma(self._step_ewma, t1 - t0)
                self._hist_step.observe(t1 - t0)
                # engine-row span (tid 0): the step every request's
                # tokens in this round came from
                self.flight.span("decode_step", t0, t1,
                                 active=len(active), step=steps)
                steps += 1
                self.counters.inc("decode_steps")
                # blocks held by in-flight sequences, summed over
                # the steps: kv_block_steps / decode_steps is the
                # mean occupancy of the pool the steps saw
                self.counters.inc(
                    "kv_block_steps",
                    self._pool.num_blocks - self._pool.allocatable())
                with self.timers.timed("host_schedule"):
                    if self._spec_k:
                        delivered = self._spec_deliver(active, drafts,
                                                       targets)
                    else:
                        delivered = self._block_advance(active, toks)
                    self.counters.inc("tokens", delivered)
                    # decode_tokens excludes prefill-emitted firsts, so
                    # rate("decode_tokens", "decode_steps") is true
                    # decode occupancy (bounded by slots; under
                    # speculation, tokens per ROUND — the acceptance
                    # win read straight off the counters)
                    self.counters.inc("decode_tokens", delivered)
                    # re-publish occupancy AFTER deliveries: when the
                    # last slot frees on a completion the loop parks in
                    # cv.wait, and a gauge frozen at the pre-step value
                    # would read "occupied" on an idle engine forever
                    self.counters.gauge("slot_occupancy",
                                        len(self._active_slots()))
        except BaseException as e:  # noqa: BLE001 - fail every client
            logger.exception("decode engine loop died")
            self._flight = None  # a dead loop's answers are not read
            with self._cv:
                self._broken = e
                self._fail_outstanding(
                    EngineFailed("decode engine failed: {}".format(e)))

    def _token_turn(self, rows, step):
        """One turn of a token engine: upload and dispatch a step for
        the slots ``rows`` (none: nothing owes one), THEN read the
        step dispatched a turn ago and deliver its tokens, so that the
        read, the deliveries and the next turn's scheduling run while
        the device computes. ``step_sync`` is the wait for the OLDER
        step; a cursor moves on at dispatch (the program writes the
        fed token there), not at delivery."""
        older, t0 = self._flight, time.monotonic()
        with self.timers.timed("decode_step"):
            if rows:
                with self.timers.timed("step_upload"):
                    feed, key = self._token_feed(rows), self._next_key()
                with self.timers.timed("step_dispatch"):
                    self._cache, self._picked = self._decode_fn(
                        self.params, self._cache, self._picked, feed, key)
                    self._picked.copy_to_host_async()
                self._idx[rows] += 1
            self._flight = None
            if rows:
                owners = [None] * self.slots
                for s in rows:
                    owners[s] = self._slot_req[s]
                self._flight = (self._picked, owners, t0)
            if older is not None:
                with self.timers.timed("step_sync"):
                    toks, routed = self._generation.split_routed(
                        older[0], self.slots, self.slots, self._top_k)
        t1 = time.monotonic()
        # engine-row span (tid 0): this turn's share of the loop
        self.flight.span("decode_step", t0, t1, active=len(rows),
                         step=step)
        if rows:
            self.counters.inc("decode_steps")
            self.counters.inc("steps_dispatched_ahead",
                              int(older is not None))
            # blocks held by in-flight sequences, summed over the
            # steps: kv_block_steps / decode_steps is the mean
            # occupancy of the pool the steps saw
            self.counters.inc(
                "kv_block_steps",
                self._pool.num_blocks - self._pool.allocatable())
            for kind in self._other_kinds:
                self.counters.inc("kv_{}_block_steps".format(kind.name),
                                  kind.in_use())
        if older is None:
            return
        # the pace, read to read (dispatch to read after a pause):
        # what a client waits per token, the device's step or the
        # host's turn, whichever is longer
        pace = t1 - max(self._read_at, older[2])
        self._read_at = t1
        self._step_ewma = self._ewma(self._step_ewma, pace)
        self._hist_step.observe(pace)
        with self.timers.timed("host_schedule"):
            if routed is not None:
                # the rows the step was dispatched for: an idle slot's
                # row is routed and multiplied too, and counted nowhere
                self._count_expert_rows(
                    routed, np.array([h is not None for h in older[1]]))
            delivered = 0
            for s, handle in enumerate(older[1]):
                if handle is None:
                    continue
                if self._slot_req[s] is handle:
                    self._deliver(s, int(toks[s]))
                    delivered += 1
                else:
                    # finished on EOS, cancelled or evicted since the
                    # dispatch: the row's token is nobody's
                    self.counters.inc("tokens_dropped_in_flight")
            self.counters.inc("tokens", delivered)
            # decode_tokens excludes prefill-emitted firsts, so
            # rate("decode_tokens", "decode_steps") is true decode
            # occupancy (bounded by slots)
            self.counters.inc("decode_tokens", delivered)
            # occupancy AFTER deliveries: see _loop
            self.counters.gauge("slot_occupancy",
                                len(self._active_slots()))

    def _retire(self):
        """Read and deliver the step in flight, if there is one, with
        nothing dispatched after it: for whatever needs a slot's tokens
        whole (a preemption's re-prefill, a KV job, stop, parking)."""
        if self._flight is not None:
            self._token_turn((), None)

    def _fail_outstanding(self, err):
        """Fail every queued and in-flight handle (scheduler thread
        only, caller holds ``_cv``): the loop's exit paths — stop and
        death — both land here so no client is ever stranded."""
        failed = [self._slot_req[s] for s in self._active_slots()]
        for s in range(self.slots):
            self._slot_req[s] = None
            self._release_slot(s)
        failed.extend(self._queue)
        self._queue.clear()
        # pending KV ship/splice jobs are client threads parked on a
        # per-job event — wake them with the same error so a ship RPC
        # against a dying engine fails fast instead of timing out
        for job in self._kv_jobs:
            job["error"] = err
            job["done"].set()
        self._kv_jobs.clear()
        for handle in failed:
            handle._finish(err)
            self.flight.instant("failed", trace=handle.trace,
                                error=str(err))
        # the loss ledger drain()'s verdict reads: these requests were
        # ADMITTED and did not finish — an emptied queue must not be
        # mistaken for "nothing was lost"
        self._failed_requests += len(failed)
        # the gauges must tell the truth on a dead/stopped engine:
        # nothing is queued or occupied anymore
        self.counters.gauge("queue_depth", 0)
        self.counters.gauge("slot_occupancy", 0)

    # -- speculative decoding round (PR 15; scheduler thread only) -------

    def _spec_round(self, jnp):
        """Device half of one speculative round, as ONE fused program
        (one dispatch, one host sync): the draft proposes
        ``speculate_k`` tokens per slot via a scanned program, and the
        target scores the whole window — ``[last, d_1..d_{k-1}]``,
        wired draft→verify on device — in one fused multi-token apply
        against the paged pool (the PR 2 multi-token prefill branch
        pointed at decode). Both writes ride the shared block tables
        at the shared cursors, so the draft pool mirrors the target
        pool position for position. Returns ``(drafts [S, k],
        targets [S, k])`` host arrays."""
        with self.timers.timed("spec_round"):
            with self.timers.timed("step_upload"):
                feed = self._step_feed(jnp)
            with self.timers.timed("step_dispatch"):
                self._cache, self._draft_cache, drafts, targets = \
                    self._round_fn(
                        self.params, self._draft_params, self._cache,
                        self._draft_cache, *feed)
            with self.timers.timed("step_sync"):
                drafts = np.asarray(drafts)   # the per-round host sync
                targets = np.asarray(targets)
        return drafts, targets

    def _spec_deliver(self, active, drafts, targets):
        """Host half: token-matching acceptance + per-token delivery.
        ``a`` = longest prefix where the draft's proposal equals the
        target's own pick; the round emits ``targets[:a+1]`` (``a``
        accepted draft tokens — which ARE the target picks — plus the
        target's correction), or all k on a full match. Every emitted
        token is therefore a target-model choice: at temperature=0
        exactly the plain engine's argmax chain (bitwise pin), at
        temperature>0 a true target sample (exact in distribution,
        PRNG stream not bitwise-reproducible — docs/serving.md states
        this honestly). Rejected positions' K/V is garbage PAST the
        new cursor, overwritten by the next round's window before the
        visibility mask can reach it — the same discipline as
        bucket-pad scratch writes. Counters tally only the EMITTABLE
        window ``min(k, remaining)`` — a request one token from its
        length cap gets one useful proposal, and counting the whole
        k-window would skew the fleet-visible acceptance rate on
        short-request workloads (tail positions beyond ``remaining``
        were never even granted real blocks). Counter arithmetic
        (pinned): ``spec_rounds <= spec_proposed <= k * spec_rounds``
        and ``spec_accepted <= spec_proposed``."""
        k = self._spec_k
        delivered = 0
        for s in active:
            handle = self._slot_req[s]
            window = min(k, max(1, handle.max_new_tokens
                                - len(handle._tokens)))
            a = 0
            while a < window and drafts[s, a] == targets[s, a]:
                a += 1
            self.counters.inc("spec_rounds")
            self.counters.inc("spec_proposed", window)
            self.counters.inc("spec_accepted", a)
            for tok in targets[s, :min(a + 1, window)]:
                if self._slot_req[s] is None:
                    break  # completed mid-window (EOS / length)
                self._idx[s] += 1
                self._deliver(s, int(tok))
                delivered += 1
        if active:
            self._tokens_round_ewma = self._ewma(
                self._tokens_round_ewma, delivered / len(active))
        return delivered

    # -- stepping by blocks (scheduler thread only) ----------------------
    #
    # The host half of generation by diffusion over blocks
    # (generation.py, "block-stepping primitives"). Each slot is in its
    # own phase: denoising pass k of its block, or the commit. A pass is
    # the same program at the same cursor; only after the pass that ran
    # a block's final tokens does the cursor move on.

    def _block_feed(self):
        """``[slots, block_len]`` tokens of the next pass: each slot's
        block as it stands, MASK where a position is still masked (a
        boolean per position, never a comparison with the MASK id)."""
        return np.where(self._blk_masked, self._model.mask_token_id,
                        self._blk_tok).astype(np.int32)

    def _fresh_block(self, slot, handle):
        """Start the block at the slot's cursor: positions the sequence
        already holds (the prompt's last ``len % block_len`` tokens, or
        tokens delivered before a preemption) are given, the others
        masked; positions past the end of the request stay masked for
        good and are never unmasked."""
        seq = handle.prompt + handle._tokens
        start = int(self._idx[slot])
        given = len(seq) - start
        self._blk_given[slot] = given
        self._blk_live[slot] = min(
            self._block_len,
            len(handle.prompt) + handle.max_new_tokens - start)
        self._blk_tok[slot] = 0
        self._blk_tok[slot, :given] = seq[start:]
        self._blk_masked[slot] = np.arange(self._block_len) >= given
        self._blk_when[slot] = -1
        self._blk_pass[slot] = 0

    def _block_advance(self, active, answers):
        """What the pass just run (``answers``: the jitted block step's
        one array) means for each active slot. Where
        masks were left, unmask by the rule (generation.unmask) and
        stay; where none was, that pass was the COMMIT: its K/V is
        final, so move the cursor on, deliver the block in one
        delivery and start the next. Returns the tokens delivered.
        The denoising rows are advanced all at once (array operations
        over ``[active, block_len]``): this runs between every two
        passes with the device waiting, and only a commit needs a
        row's turn of its own."""
        model = self._model
        best, conf, expert_ids = self._generation.unpack_block_step(
            answers, self.slots, self._block_len, model.experts_per_tok)
        quota = max(1, self._block_len // model.denoise_steps)
        act = np.asarray(active)
        self.counters.inc("row_passes", len(act))
        # positions inside their request; those past its end stay
        # masked for good and are never unmasked
        inside = np.arange(self._block_len) < self._blk_live[act, None]
        of_requests = np.zeros(self._blk_masked.shape, bool)
        of_requests[act] = inside
        self._count_expert_rows(expert_ids, of_requests.reshape(-1))
        masked = self._blk_masked[act] & inside
        denoising = masked.any(axis=1)
        # a row in its commit has no mask left: nothing is chosen there
        chosen = self._generation.unmask(
            conf[act], masked, quota, model.confidence_threshold)
        self._blk_tok[act] = np.where(chosen, best[act], self._blk_tok[act])
        self._blk_when[act] = np.where(chosen, self._blk_pass[act, None],
                                       self._blk_when[act])
        self._blk_masked[act] &= ~chosen
        self._blk_pass[act] += denoising
        self.counters.inc("tokens_unmasked", int(chosen.sum()))
        delivered = 0
        commits = act[~denoising].tolist()
        for s in commits:
            with self.timers.timed("block_commit"):
                handle = self._slot_req[s]
                given, live = int(self._blk_given[s]), int(self._blk_live[s])
                tokens = self._blk_tok[s, given:live].tolist()
                self._idx[s] += self._block_len
                self._deliver(
                    s, tokens[-1],
                    block=(tokens, self._blk_when[s, given:live].tolist()))
                if self._slot_req[s] is handle:
                    self._fresh_block(s, handle)
                delivered += len(tokens)
        self.counters.inc("commit_row_passes", len(commits))
        self._tokens_round_ewma = self._ewma(
            self._tokens_round_ewma, delivered / len(active))
        return delivered

    def _count_expert_rows(self, expert_ids, live):
        """The experts' load in one call, from the program's own output
        ``expert_ids [layers, rows, top_k]`` (the experts each row was
        routed to) over the rows that belong to a request, ``live
        [rows]``: an idle slot's rows, a block's positions past the end
        of its request and a prefill's padding are routed and
        multiplied like any other and are counted nowhere. Per layer
        the fullest expert's rows and the mean, summed
        (``expert_rows_max`` over ``expert_rows_mean`` is the peak
        load), and what the grouped product had to touch."""
        ids = expert_ids[:, live]
        layers, experts = ids.shape[0], self._model.num_experts
        rows = np.bincount(
            (ids + experts * np.arange(layers)[:, None, None]).ravel(),
            minlength=layers * experts).reshape(layers, experts)
        self.counters.inc("expert_calls", layers)
        self.counters.inc("expert_rows_max", int(rows.max(axis=1).sum()))
        self.counters.inc("expert_rows_mean",
                          float(rows.mean(axis=1).sum()))
        self.counters.inc("expert_rows", int(rows.sum()))
        self.counters.inc("experts_touched", int((rows > 0).sum()))

    # -- paged-KV block management (PR 8; scheduler thread only) ---------

    def _publish_kv_gauges(self):
        """Refresh the block-pool gauges (kv_blocks_free / total /
        cached) and roll the pool's monotonic tallies (hits / misses /
        LRU evictions) into the prefix counters."""
        stats = self._pool.stats()
        self.counters.gauge("kv_blocks_total", stats["total"])
        self.counters.gauge("kv_blocks_free", stats["free"])
        self.counters.gauge("kv_blocks_cached", stats["cached"])
        for kind in self._other_kinds:
            self.counters.gauge("kv_{}_blocks_total".format(kind.name),
                                kind.pool.num_blocks)
            self.counters.gauge("kv_{}_blocks_free".format(kind.name),
                                kind.pool.allocatable())
        # digest exposition (PR 16): how many chains the beat-carried
        # digest currently publishes, and whether the top-K bound cut
        # anything (the truncation-honesty flag, scrapeable)
        dig = self._pool.prefix_digest()
        self.counters.gauge("prefix_digest_chains", len(dig["top"]))
        self.counters.gauge("prefix_digest_truncated",
                            1 if dig["truncated"] else 0)
        # roll the pool's own monotonic tallies into the counters —
        # the pool's chain walk is the ONE place hit/miss/eviction
        # semantics live (no re-derived formulas to desync)
        for counter, tally, attr in (
                ("prefix_evictions", stats["evictions"],
                 "_last_prefix_evictions"),
                ("prefix_hit_blocks", stats["hits"],
                 "_last_prefix_hits"),
                ("prefix_miss_blocks", stats["misses"],
                 "_last_prefix_misses"),
                ("generated_prefix_registered",
                 stats["generated_registered"],
                 "_last_generated_registered"),
                ("generated_prefix_hit_blocks",
                 stats["generated_hits"],
                 "_last_generated_hits")):
            delta = tally - getattr(self, attr)
            if delta > 0:
                self.counters.inc(counter, delta)
                setattr(self, attr, tally)

    def _release_slot(self, slot):
        """Return a freed slot's blocks to the pool and park its table
        row on scratch / cursor at 0, so the idle slot's per-step write
        lands in the scratch block instead of its released — possibly
        already re-allocated — blocks. Private blocks go back to the
        free list; registered prefix blocks decref into the LRU cache
        (still hittable, evicted only under pressure)."""
        for kind in self._kv:
            kind.release(slot)
        self._idx[slot] = 0
        self._slot_registered[slot] = 0
        self._publish_kv_gauges()

    def _register_generated(self, slot, handle):
        """Publish every not-yet-registered FULL block of ``slot``'s
        sequence into the prefix registry — the generated-prefix half
        of PR 11: a block DECODE filled (cursor crossed its end) holds
        the K/V of ``(prompt + emitted)[:block_end]``, exactly the
        chain a follow-up conversation turn's prompt starts with.
        Called at block-boundary crossings (_grow_active_blocks) and
        at completion (_deliver) — together those cover every fill,
        since admission registers the prompt's own full blocks.
        Origin-tagged so multi-turn reuse is countable apart from
        repeated system prompts. Scheduler thread only; must run while
        the slot still holds its block references (before release)."""
        if not self.prefix_cache:
            return
        bs = self.kv_block_size
        # full by the tokens the host HOLDS: every position before the
        # last of them is written by a program dispatched already; the
        # cursor may be a step further, over a token not read yet
        n_prompt = len(handle.prompt)
        full = min((n_prompt + len(handle._tokens) - 1) // bs,
                   len(self._slot_blocks[slot]))
        if full <= self._slot_registered[slot]:
            return
        chain = handle.prompt + handle._tokens
        for j in range(self._slot_registered[slot], full):
            end = (j + 1) * bs
            self._pool.register(
                chain, end, self._slot_blocks[slot][j],
                origin="prompt" if end <= n_prompt else "generated")
        self._slot_registered[slot] = full
        self._publish_kv_gauges()

    # -- KV-block shipping (PR 17 disaggregation) ------------------------
    #
    # export_prefix / import_prefix are the engine half of prefill/
    # decode disaggregation. Both execute ON the scheduler thread (via
    # the _kv_jobs queue drained at the top of _loop): pool surgery and
    # cache access stay single-writer, so an export never races an
    # admission's acquire and an import's scatter never tears a decode
    # step. Client threads (the server's /kv/splice and :prefill
    # handlers) park on a per-job event.

    def export_prefix(self, tokens, src_epoch=None, timeout=30.0):
        """Pack ``tokens``'s resident full-block KV chain into wire
        buffers — the prefill-tier half of a shipment. Returns
        ``(buffers, meta)`` (:func:`kvship.pack` output plus the header
        it embeds) or ``None`` when nothing is resident (the prompt
        spans no full block, or its blocks were evicted). The buffers
        carry the pool rows AS STORED — int8 codes + per-head scales on
        a quantized pool, no dequant round-trip — so physical ship cost
        is exactly ``frames.frame_bytes(buffers)``. ``src_epoch`` is
        this replica's fencing epoch, stamped into the header so the
        receiver can refuse shipments from a fenced-out incarnation."""
        return self._kv_call({"kind": "export", "tokens": list(tokens),
                              "src_epoch": src_epoch}, timeout)

    def import_prefix(self, meta, rows, timeout=30.0):
        """Adopt a shipment: splice its novel blocks into this engine's
        pool by block-table pointer surgery — alloc, scatter the
        shipped rows (bytes as stored, no requant), register the chain
        — so a temp=0 decode over the spliced prefix is bitwise
        identical to having prefilled locally. Idempotent: blocks
        already resident (an earlier splice, or local traffic) are
        skipped by resident-chain dedupe, which is what makes duplicate
        deliveries (chaos ``dup`` verdicts, post-timeout re-ships)
        safe. Raises :class:`SpliceRejected` (reason-tagged) on
        geometry/dtype mismatch, malformed rows, or pool pressure.
        Returns ``{'spliced_blocks', 'skipped_blocks', 'bytes'}`` —
        ``bytes`` is the physical size of the NOVEL rows only."""
        return self._kv_call({"kind": "import", "meta": meta,
                              "rows": rows}, timeout)

    def _kv_call(self, job, timeout):
        """Enqueue a KV job for the scheduler thread and wait for its
        verdict (safe from any thread)."""
        if self._block_len:
            raise ValueError(
                "an engine that steps by blocks neither ships nor adopts "
                "K/V blocks (no block of it is registered for sharing)")
        if self._other_kinds:
            raise ValueError(
                "an engine whose slots hold {} kinds of cache neither "
                "ships nor adopts K/V blocks (kvship frames one kind)"
                .format(len(self._kv.kinds)))
        job["done"] = threading.Event()
        job["error"] = None
        job["result"] = None
        with self._cv:
            if self._broken is not None:
                raise EngineFailed(
                    "engine failed: {}".format(self._broken))
            if self._stopping:
                raise EngineFailed("engine stopped")
            self._kv_jobs.append(job)
            self._cv.notify_all()
        if not job["done"].wait(timeout):
            raise TimeoutError(
                "kv {} job not scheduled within {}s"
                .format(job["kind"], timeout))
        if job["error"] is not None:
            raise job["error"]
        return job["result"]

    def _run_kv_job(self, job):
        """Execute one drained KV job (scheduler thread, outside
        ``_cv``). Job-scoped failures — SpliceRejected, malformed
        shipments — fail ONLY the job's waiter; a non-Exception
        (KeyboardInterrupt and kin) still propagates to the loop's
        failure path after waking the waiter."""
        try:
            if job["kind"] == "export":
                job["result"] = self._kv_export(job["tokens"],
                                                job.get("src_epoch"))
            else:
                job["result"] = self._kv_import(job["meta"], job["rows"])
        except BaseException as e:  # noqa: BLE001 - job-scoped verdict
            job["error"] = e
            if not isinstance(e, Exception):
                job["done"].set()
                raise
        job["done"].set()

    def _kv_export(self, tokens, src_epoch):
        """Scheduler-thread half of :meth:`export_prefix`."""
        # walk-and-pin atomically: a concurrent drop_cache between the
        # walk and a separate acquire could free a block mid-export
        chain = self._pool.resident_chain(tokens, acquire=True)
        if not chain:
            return None
        ids = [bid for bid, _ in chain]
        t0 = time.monotonic()
        try:
            rows = self._generation.gather_block_rows(self._cache, ids)
        finally:
            self._pool.release(ids)
        bs = self.kv_block_size
        meta = {"tokens": list(tokens)[:len(ids) * bs],
                "block_size": bs,
                "kv_dtype": self.kv_dtype,
                "origins": [origin for _, origin in chain],
                "src_replica": self.replica_id,
                "src_epoch": src_epoch}
        buffers = kvship.pack(meta, rows)
        t1 = time.monotonic()
        self.flight.span("kv.pack", t0, t1, blocks=len(ids),
                         bytes=frames.frame_bytes(buffers))
        return buffers, meta

    def _kv_import(self, meta, rows):
        """Scheduler-thread half of :meth:`import_prefix`."""
        bs = self.kv_block_size
        if int(meta.get("block_size") or 0) != bs:
            raise SpliceRejected(
                "block_size",
                "shipment block_size {!r} != pool block_size {}"
                .format(meta.get("block_size"), bs))
        if meta.get("kv_dtype") != self.kv_dtype:
            raise SpliceRejected(
                "kv_dtype",
                "shipment kv_dtype {!r} != pool kv_dtype {!r} — ship "
                "endpoints must share pool dtype (no requant on splice)"
                .format(meta.get("kv_dtype"), self.kv_dtype))
        tokens = list(meta.get("tokens") or ())
        n = len(tokens) // bs
        if n <= 0:
            return {"spliced_blocks": 0, "skipped_blocks": 0, "bytes": 0}
        rows = [(key, np.asarray(arr)) for key, arr in rows]
        for key, arr in rows:
            if arr.shape[:1] != (n,):
                raise SpliceRejected(
                    "malformed",
                    "row {!r} carries {} block(s), token chain spans {}"
                    .format(key, arr.shape[0] if arr.ndim else 0, n))
        origins = list(meta.get("origins") or ())
        origins += ["prompt"] * (n - len(origins))
        t0 = time.monotonic()
        # resident-chain dedupe = idempotence: whatever prefix of the
        # shipped chain this pool already holds (an earlier delivery of
        # this same shipment, or plain local traffic) is skipped, so a
        # double splice is a no-op and never double-allocates
        skip = len(self._pool.resident_chain(tokens))
        if skip >= n:
            return {"spliced_blocks": 0, "skipped_blocks": n, "bytes": 0}
        try:
            ids = self._pool.alloc(n - skip)
        except paging.PoolExhausted as e:
            raise SpliceRejected("pool_exhausted", str(e))
        novel = [(key, arr[skip:n]) for key, arr in rows]
        try:
            self._cache = self._generation.scatter_block_rows(
                self._cache, ids, novel)
        except ValueError as e:
            self._pool.release(ids)  # unregistered -> straight to free
            raise SpliceRejected("malformed", str(e))
        except Exception:
            self._pool.release(ids)
            raise
        for j, bid in enumerate(ids):
            # first-writer-wins: a chain link registered concurrently
            # by local traffic keeps ITS block; ours stays private and
            # the release below returns it to the free list — no leak
            self._pool.register(tokens, (skip + j + 1) * bs, bid,
                                origin=origins[skip + j])
        # registered blocks park in the LRU (hittable, evictable) —
        # exactly the state a locally-prefilled-and-released prefix
        # would be in, which is why the follow-up :generate admission
        # path needs no disaggregation awareness at all
        self._pool.release(ids)
        self._publish_kv_gauges()
        t1 = time.monotonic()
        n_bytes = sum(int(arr.nbytes) for _, arr in novel)
        self.flight.span("kv.splice", t0, t1, blocks=len(ids),
                         bytes=n_bytes)
        with self._cv:
            self.kv_counters.inc("spliced_blocks", len(ids))
            self.kv_counters.inc("spliced_bytes", n_bytes)
        return {"spliced_blocks": len(ids), "skipped_blocks": skip,
                "bytes": n_bytes}

    def note_ship(self, blocks, n_bytes, seconds):
        """Record one SUCCESSFUL shipment leaving this replica:
        physical wire bytes (codes + scales as transferred) and wall
        time. Handler threads are multi-writer and ``Counters.inc`` is
        read-modify-write, so mutation happens under ``_cv`` — same
        rule for every kv_counters writer."""
        with self._cv:
            self.kv_counters.inc("ship_blocks", int(blocks))
            self.kv_counters.inc("ship_bytes", int(n_bytes))
        self._hist_ship.observe(seconds * 1000.0)

    def note_splice_failure(self, reason):
        """Count one refused/failed splice under its bounded reason
        label (rendered as ``tfos_splice_failures_total{reason=...}``
        by the server's metrics surface)."""
        with self._cv:
            self._splice_failures[reason] = \
                self._splice_failures.get(reason, 0) + 1

    def splice_failures(self):
        """``{reason: count}`` snapshot for the metrics surface."""
        with self._cv:
            return dict(self._splice_failures)

    def note_quota_rejection(self, tenant, requests=1):
        """Count quota refusals (429 QuotaExceeded). Handler threads
        are multi-writer, so the tally mutates under ``_cv`` — same
        rule as every other cross-thread counter here."""
        with self._cv:
            self._qos_quota_rejections[tenant] = \
                self._qos_quota_rejections.get(tenant, 0) + int(requests)

    def qos_tallies(self):
        """One consistent snapshot of the QoS counters for the metrics
        surface: ``{'admitted': {(tenant, class): n}, 'preemptions':
        {(tenant, class): n}, 'quota_rejections': {tenant: n},
        'tokens': {tenant: n}}``."""
        with self._cv:
            return {"admitted": dict(self._qos_admitted),
                    "preemptions": dict(self._qos_preemptions),
                    "quota_rejections": dict(self._qos_quota_rejections),
                    "tokens": dict(self._qos_tokens)}

    def _preempt(self, slot):
        """Free a slot's blocks under pool exhaustion and requeue its
        request at the queue FRONT: it re-admits as soon as blocks
        free, with a continuation re-prefill of prompt + the tokens it
        already emitted — the client's stream continues seamlessly, and
        at temperature=0 bitwise-identically (pinned in
        tests/test_paged_kv.py)."""
        # the re-prefill needs the tokens whole: land the step in
        # flight first (which may finish the victim: nothing to do)
        self._retire()
        handle = self._slot_req[slot]
        if handle is None:
            return
        self._slot_req[slot] = None
        self._release_slot(slot)
        now = time.monotonic()
        if handle._decode_t0 is not None:
            # close the decode-so-far segment: attribution must not
            # lose the work done before eviction, and the preempted
            # stage starts HERE, not at the last decode step
            self.flight.span("decode", handle._decode_t0, now,
                             trace=handle.trace,
                             tokens=len(handle._tokens),
                             preempted=True)
            handle._attr_spans.append(("decode", handle._decode_t0, now))
        handle._preempt_at = now
        with self._cv:
            self._queue.appendleft(handle)
            key = (handle.tenant, handle.priority)
            self._qos_preemptions[key] = \
                self._qos_preemptions.get(key, 0) + 1
            self.counters.gauge("queue_depth", len(self._queue))
        self.counters.inc("preemptions")
        self.flight.instant("preempt", trace=handle.trace,
                            tokens=len(handle._tokens))
        logger.info(
            "preempted request after %d/%d tokens (kv pool pressure); "
            "requeued at front", len(handle._tokens),
            handle.max_new_tokens)

    def _grow_active_blocks(self):
        """Ensure every active slot owns the blocks this round's
        writes land in, allocating as the sequence crosses block
        boundaries — the lazy-growth half of paging (a sequence
        consumes blocks as it grows, never ``max_len`` up front). A
        PLAIN round writes one position, so the lookahead is 1; a
        SPECULATIVE round writes up to ``speculate_k`` positions, so
        growth covers ``min(k, tokens the request can still emit)`` —
        writes past that clamp are rejected-proposal garbage that may
        land in scratch (table entry 0) because no cursor will ever
        make them visible. Under exhaustion the WEAKEST-class YOUNGEST
        admission is preempted (class-aware LIFO victims, PR 18 — with
        one priority class this is exactly the old youngest-first
        rule), so the oldest request of the strongest class always
        progresses: no preemption livelock, and ``validate``'s
        worst-case-fits-the-pool bound guarantees that request alone
        can always satisfy its own lookahead."""
        bs = self.kv_block_size
        look = self._spec_k or 1
        for s in sorted(self._active_slots(),
                        key=lambda v: self._slot_seq[v]):
            handle = self._slot_req[s]
            if handle is None:
                continue  # preempted by an earlier slot's growth
            # publish every fully-written block into the prefix
            # registry (generated-prefix registration, PR 11) while
            # the slot still references them — checked every round,
            # not only when growth is needed: speculative lookahead
            # pre-allocates blocks AHEAD of the cursor, so a crossing
            # no longer implies a growth event (a crossing-gated call
            # would delay registration — and the prefix hit a twin
            # admission could have had — by up to a block). Cheap: an
            # early return when nothing new completed.
            self._register_generated(s, handle)
            if not self._owes_step(s):
                continue  # its last token is in flight: no write left
            cover = min(look,
                        max(1, handle.max_new_tokens
                            - len(handle._tokens) - self._in_flight(s)))
            upto = (int(self._idx[s]) + cover - 1) // bs
            kinds = [k for k in self._kv if k.lacks(s, upto) > 0]
            if not kinds:
                continue
            for kind in kinds:
                while self._slot_req[s] is not None \
                        and kind.lacks(s, upto) > 0:
                    try:
                        with self.timers.timed("block_alloc"):
                            kind.grow(s, upto)
                    except paging.PoolExhausted:
                        victim = max(
                            self._active_slots(),
                            key=lambda v: (
                                qos.priority_rank(
                                    self._slot_req[v].priority),
                                self._slot_seq[v]))
                        # preempting s itself clears its slot_req and
                        # ends the while
                        self._preempt(victim)
            self._publish_kv_gauges()

    def _trim_active_blocks(self):
        """Give back, for every active slot, the blocks that no step
        from its cursor on reads (paging.WindowKind.trim: the blocks a
        window has left). The step in flight may still read one of
        them: whatever is written there next is a LATER program's, and
        the device runs them in order."""
        given_back = {}
        for s in self._active_slots():
            for kind in self._other_kinds:
                n = kind.trim(s, int(self._idx[s]))
                if n:
                    given_back[kind.name] = given_back.get(kind.name, 0) + n
        for name, n in given_back.items():
            self.counters.inc("kv_{}_blocks_given_back".format(name), n)
        if given_back:
            self._publish_kv_gauges()

    def _admit(self, slot, handle):
        """Prefill ``handle``'s prompt into ``slot`` and emit its first
        token (a max_new_tokens=1 request completes right here): point
        the slot's block table at any resident shared-prefix blocks,
        allocate private blocks for the rest, and prefill ONLY the
        un-shared tail (the warm-prefix TTFT win — a resident prefix
        costs a table write, not a forward pass). Also the preemption re-entry path: a requeued handle
        re-prefills prompt + already-emitted tokens and resumes."""
        import jax.numpy as jnp

        full = handle.prompt + handle._tokens
        n = len(full)
        bs = self.kv_block_size
        shared = []
        if self.prefix_cache:
            with self.timers.timed("prefix_lookup"):
                # a preemption continuation (the handle already
                # decoded) re-walks onto its OWN registered blocks:
                # real prefill savings, but not multi-turn reuse —
                # keep it out of the generated-hit signal
                shared = self._pool.match_prefix(
                    full, count_generated=handle._decode_t0 is None)
            # hit/miss counters roll from the pool's own tallies in
            # _publish_kv_gauges — one formula, no desync
        start = len(shared) * bs
        with self.timers.timed("block_alloc"):
            # acquire BEFORE alloc: shared blocks may sit in the LRU
            # (refcount 0), and an alloc running first could evict the
            # very blocks this admission is about to share
            self._pool.acquire(shared)
            try:
                new_ids = self._pool.alloc(
                    self._pool.blocks_for(n) - len(shared))
            except paging.PoolExhausted:
                self._pool.release(shared)
                raise
            # the other kinds hold what the decode will still see of
            # the prompt (a window layer: its last window), out of a
            # pool that has it for every slot
            for kind in self._other_kinds:
                kind.admit(slot, n)
        ids = list(shared) + new_ids
        self._kv.full.place(slot, ids)
        row = self._tables[slot]    # every kind's columns, side by side
        self._slot_seq[slot] = next(self._admit_seq)
        # a block-stepping model prefills the sequence's WHOLE blocks
        # and samples nothing; what is left over starts its first block
        fill = n - n % self._block_len if self._block_len else n
        tail = full[start:fill]
        try:
            bucket = self._generation.bucket_for(len(tail), self.buckets)
        except ValueError:
            # a preemption continuation's prompt+generated tail can
            # outgrow CUSTOM buckets (validate only vets the original
            # prompt); one total_len-shaped program beats crashing the
            # scheduler
            bucket = self.total_len
        toks = np.zeros(bucket, np.int32)
        toks[:len(tail)] = tail
        t0 = time.monotonic()
        if handle._decode_t0 is None:
            # queue-wait metrics describe FIRST admissions only; a
            # preemption re-entry is a continuation, not a queue wait
            # (the stage is a sample, not a span: the interval began
            # on the submitting thread)
            self.timers.add("queue_wait", t0 - handle.submitted)
            self._hist_qwait.observe(t0 - handle.submitted)
            self._hist_qwait_class.get(
                handle.priority,
                self._hist_qwait_class[qos.DEFAULT_PRIORITY]).observe(
                    t0 - handle.submitted)
            self._qwait_ewma = self._ewma(self._qwait_ewma,
                                          t0 - handle.submitted)
            self.flight.span("queue", handle.submitted, t0,
                             trace=handle.trace, slot=slot)
            handle._attr_spans.append(("queue", handle.submitted, t0))
        elif handle._preempt_at is not None:
            # preemption continuation: everything since the eviction
            # was time the request spent OUT of its slot
            self.flight.span("preempted", handle._preempt_at, t0,
                             trace=handle.trace, slot=slot)
            handle._attr_spans.append(
                ("preempted", handle._preempt_at, t0))
        with self.timers.timed("prefill"):
            if not self._block_len:
                self._cache, first = self._prefill_fn(
                    self.params, self._cache, jnp.asarray(row),
                    jnp.asarray(toks), jnp.int32(len(tail)),
                    jnp.int32(start), self._next_key())
                first, routed = self._generation.split_routed(
                    first, 1, bucket, self._top_k)
                first = int(first[0])
                if routed is not None:
                    self._count_expert_rows(
                        routed, np.arange(bucket) < len(tail))
            elif tail:
                self._cache, routed = self._prefill_fn(
                    self.params, self._cache, jnp.asarray(row),
                    jnp.asarray(toks), jnp.int32(start))
                self._count_expert_rows(np.asarray(routed),
                                        np.arange(bucket) < len(tail))
        t1 = time.monotonic()
        self._prefill_ewma = self._ewma(self._prefill_ewma, t1 - t0)
        self.flight.span("prefill", t0, t1, trace=handle.trace,
                         bucket=bucket, prompt_len=n,
                         prefix_blocks=len(shared))
        handle._attr_spans.append(("prefill", t0, t1))
        handle._decode_t0 = t1
        self.counters.inc("prefills")
        if not self._buffers_counted:
            # once, behind the first prefill that went through (the
            # parameters fit the model): the step it lowers is the one
            # the next turn calls
            self.counters.gauge("decode_call_buffers",
                                self._step_call_buffers())
            self._buffers_counted = True
        if self._spec_k:
            # mirror the tail into the DRAFT pool (PR 15): the draft
            # attends the same prefix through the same table row, so
            # its cache must hold the prompt's K/V too (a prefix-cache
            # hit skips both prefills together — shared blocks were
            # mirrored when their original writer prefilled/decoded).
            # The draft's own first-token pick is discarded; this call
            # exists for its writes.
            with self.timers.timed("draft_prefill"):
                self._draft_cache, _ = self._draft_prefill_fn(
                    self._draft_params, self._draft_cache,
                    jnp.asarray(row), jnp.asarray(toks),
                    jnp.int32(len(tail)), jnp.int32(start),
                    self._next_key())
        if self.prefix_cache:
            # publish every FULL block of the admitted sequence (now
            # holding valid K/V) under its token-chain key;
            # re-registration of shared blocks is a no-op, and a
            # losing racer of two identical cold prompts just keeps
            # its blocks private. Blocks past the ORIGINAL prompt
            # exist only on preemption re-entry (``full`` includes
            # emitted tokens there) — tag those "generated"
            for j in range(n // bs):
                end = (j + 1) * bs
                self._pool.register(
                    full, end, ids[j],
                    origin="prompt" if end <= len(handle.prompt)
                    else "generated")
            self._slot_registered[slot] = n // bs
        else:
            self._slot_registered[slot] = 0
        self._publish_kv_gauges()
        self._idx[slot] = fill
        if self._block_len:
            self._fresh_block(slot, handle)
            return
        self._last[slot] = first
        self._deliver(slot, first)
        self.counters.inc("tokens")

    def _deliver(self, slot, token, block=None):
        """Append one emitted token to the slot's request; complete and
        free the slot on EOS or length. Cursor discipline: ``_idx[slot]``
        always holds the position the NEXT step dispatched writes: that
        of ``_last[slot]``, or, with a token step in flight, of the
        token that step computes (a token engine advances the cursor at
        dispatch, the others for tokens already in the cache, before
        they deliver them). ``block`` = ``(tokens, passes)`` hands
        a committed block over as ONE delivery (``token`` is its
        last)."""
        handle = self._slot_req[slot]
        if block is None:
            handle._emit(token)
        else:
            handle._emit_block(*block)
        emitted = 1 if block is None else len(block[0])
        now = time.monotonic()
        if handle._last_emit_at is None:
            self._hist_ttft.observe(now - handle.submitted,
                                    trace=handle.trace)
        else:
            self._hist_token.observe(now - handle._last_emit_at,
                                     trace=handle.trace)
        handle._last_emit_at = now
        self._last[slot] = token
        # QoS usage accounting (PR 18), post-paid at ACTUAL delivery:
        # the quota bucket drains by tokens the engine really emitted,
        # so a dedup-replayed retry (which delivers nothing new) can
        # never double-charge. _qos_tokens rides load_stats() to the
        # fleet, hence mutates under _cv; QuotaTable has its own lock.
        self._quota.charge(handle.tenant, emitted)
        with self._cv:
            self._qos_tokens[handle.tenant] = \
                self._qos_tokens.get(handle.tenant, 0) + emitted
        done = (self.eos_token is not None and token == self.eos_token) \
            or len(handle._tokens) >= handle.max_new_tokens
        if done:
            # a sequence can finish with its last decode-filled
            # block complete but never crossing another boundary —
            # publish it before the slot releases its references
            self._register_generated(slot, handle)
            handle._finish()
            self._slot_req[slot] = None
            self._release_slot(slot)
            self.counters.inc("requests_completed")
            self._trace_finish(handle, "finish")
            # fair-share hygiene: a tenant that went fully idle drops
            # its deficit counter, keeping the table bounded by LIVE
            # tenants (an idle tenant earns no credit anyway — shares
            # only accrue to backlogged tenants)
            with self._cv:
                live = any(h.tenant == handle.tenant
                           for h in self._queue) \
                    or any(self._slot_req[s] is not None
                           and self._slot_req[s].tenant == handle.tenant
                           for s in range(self.slots))
                if not live:
                    self._qos_sched.forget(handle.tenant)
        elif chaos.on_token(len(handle._tokens)):
            # chaos disconnect_client_at_token: the client vanished
            # mid-stream; eviction happens at the next step boundary,
            # exactly like a real disconnect-driven cancel
            handle.cancel()


class _BadRequest(ValueError):
    pass


def _as_array(name, value):
    """Client JSON column -> ndarray; ragged/mistyped rows are a 400.

    np.asarray turns rows of differing lengths into a ValueError (or,
    worse, a dtype=object array that explodes inside the model apply) —
    both are the client's malformed request, not a server fault."""
    try:
        arr = np.asarray(value)
    except ValueError as e:
        raise _BadRequest("input %r is ragged or mistyped: %s" % (name, e))
    if arr.dtype == object:
        raise _BadRequest(
            "input %r rows have inconsistent shapes or types" % name)
    if arr.dtype.kind in "USV":
        # mixed numeric/string rows coerce to a numpy str dtype rather
        # than object; the exported apply_fn is a jnp program with no
        # string tensors, so any non-numeric dtype is a client fault
        raise _BadRequest(
            "input %r is non-numeric (dtype %s)" % (name, arr.dtype))
    return arr


def _to_batch(payload, signature):
    """TF-Serving request JSON -> {name: ndarray} batch dict."""
    if not isinstance(payload, dict):
        raise _BadRequest("request body must be a JSON object")
    if "instances" in payload:
        rows = payload["instances"]
        if not isinstance(rows, list) or not rows:
            raise _BadRequest("'instances' must be a non-empty list")
        if isinstance(rows[0], dict):
            names = rows[0].keys()
            cols = {n: [] for n in names}
            for i, row in enumerate(rows):
                if not isinstance(row, dict) or row.keys() != names:
                    raise _BadRequest(
                        "instance %d keys differ from instance 0" % i)
                for n in names:
                    cols[n].append(row[n])
        else:
            # single unnamed input: take the signature's (or 'x')
            inputs = signature.get("inputs") or ["x"]
            if len(inputs) != 1:
                raise _BadRequest(
                    "unnamed instances need a single-input signature")
            cols = {inputs[0]: rows}
        return {n: _as_array(n, v) for n, v in cols.items()}
    if "inputs" in payload:
        cols = payload["inputs"]
        if isinstance(cols, dict):
            return {n: _as_array(n, v) for n, v in cols.items()}
        inputs = signature.get("inputs") or ["x"]
        if len(inputs) != 1:
            raise _BadRequest("unnamed inputs need a single-input signature")
        return {inputs[0]: _as_array(inputs[0], cols)}
    raise _BadRequest("request needs 'instances' or 'inputs'")


def _to_json(outputs, row_format):
    """apply_fn outputs -> TF-Serving response dict."""
    def listify(x):
        return np.asarray(x).tolist()

    if isinstance(outputs, dict):
        cols = {k: listify(v) for k, v in outputs.items()}
    elif isinstance(outputs, (tuple, list)):
        cols = {"output_%d" % i: listify(v) for i, v in enumerate(outputs)}
    else:
        cols = {"output": listify(outputs)}
    if not row_format:
        return {"outputs": cols if len(cols) > 1
                else next(iter(cols.values()))}
    names = list(cols)
    n = len(cols[names[0]])
    if len(names) == 1:
        return {"predictions": cols[names[0]]}
    return {"predictions": [
        {name: cols[name][i] for name in names} for i in range(n)]}


class _Batcher(object):
    """Cross-request batching window for the accelerator's benefit.

    Concurrent small requests (the generative path's typical shape: one
    prompt per HTTP call) serialize through the single-owner lock as N
    model calls of batch 1 — the worst way to use a TPU. With a window,
    the first request opens a ~`window_ms` collection period; everything
    that arrives with the SAME input signature (names, trailing dims,
    dtypes) is concatenated along axis 0 into ONE apply, and the outputs
    are split back per request. Requests with a different signature run
    in their own group — batching never changes results, only the call
    count.
    """

    def __init__(self, apply_fn, variables, window_ms, max_batch=64,
                 submit_timeout=600.0):
        import queue as _q

        self._apply = apply_fn
        self._variables = variables
        self._window_s = window_ms / 1000.0
        self._max_batch = max_batch
        self._submit_timeout = submit_timeout
        self._stopping = False
        self._q = _q.Queue()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="tfos-serving-batcher")
        self._thread.start()

    def submit(self, batch):
        """Blocking: returns this request's slice of the batched outputs.

        Validates the batch SHAPE here, before it can reach the shared
        batcher thread: an empty dict or a 0-d input would otherwise
        crash the loop and brick every queued request. The wait is
        bounded for the same reason — a dead batcher must surface as
        per-request 500s, never as silently hung clients."""
        if not batch:
            raise _BadRequest("empty input batch")
        lens = set()
        for k, v in batch.items():
            if getattr(v, "ndim", 0) < 1:
                raise _BadRequest(
                    "input %r is 0-d; batchable inputs need a leading "
                    "batch axis" % k)
            lens.add(len(v))
        if len(lens) != 1:
            raise _BadRequest(
                "inputs disagree on batch size: %s" % sorted(lens))
        if self._stopping:
            raise RuntimeError("server is stopping")
        done = threading.Event()
        item = {"batch": batch, "done": done}
        self._q.put(item)
        if not done.wait(self._submit_timeout):
            raise RuntimeError(
                "batched predict timed out after {}s".format(
                    self._submit_timeout))
        if "error" in item:
            raise item["error"]
        return item["out"]

    @staticmethod
    def _sig(batch):
        return tuple(sorted((k, v.shape[1:], str(v.dtype))
                            for k, v in batch.items()))

    @staticmethod
    def _rows(item):
        return len(next(iter(item["batch"].values())))

    def _loop(self):
        import queue as _q

        while True:
            first = self._q.get()
            if first is None:
                return
            group = [first]
            try:
                deadline = time.monotonic() + self._window_s
                sig = self._sig(first["batch"])
                group_rows = self._rows(first)
                passed_on = []
                while group_rows < self._max_batch:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        break
                    try:
                        nxt = self._q.get(timeout=left)
                    except _q.Empty:
                        break
                    if nxt is None:
                        passed_on.append(None)
                        break
                    # admission is clamped by remaining capacity so the
                    # padded bucket never exceeds max_batch (the compile-
                    # cache bound below depends on it)
                    if (self._sig(nxt["batch"]) == sig and
                            group_rows + self._rows(nxt) <=
                            self._max_batch):
                        group.append(nxt)
                        group_rows += self._rows(nxt)
                    else:
                        passed_on.append(nxt)  # next round
                for item in passed_on:
                    self._q.put(item)
            except Exception as e:  # noqa: BLE001 - never kill the loop
                for item in group:
                    item["error"] = e
                    item["done"].set()
                continue
            self._run_group(group)

    def _run_group(self, group):
        try:
            rows = [len(next(iter(i["batch"].values()))) for i in group]
            if len(group) == 1:
                merged = group[0]["batch"]
            else:
                names = group[0]["batch"].keys()
                merged = {n: np.concatenate([i["batch"][n] for i in group])
                          for n in names}
            # pad the merged batch up to a power-of-two bucket (by
            # repeating the last row; the padding is sliced off below):
            # a jitted apply compiles per input SHAPE, so free-running
            # batch sizes would compile once per distinct size — buckets
            # cap the cache at log2(max_batch) programs for all grouped
            # traffic. A SINGLE request larger than max_batch runs at
            # its natural size, exactly as it would without the window.
            total = sum(rows)
            bucket = 1
            while bucket < total:
                bucket *= 2
            if total > self._max_batch:
                bucket = total
            if bucket > total:
                merged = {n: np.concatenate(
                    [v, np.repeat(v[-1:], bucket - total, axis=0)])
                    for n, v in merged.items()}
            outputs = self._apply(self._variables, merged)
            if bucket > total:
                outputs = _slice_outputs(outputs, 0, total)
            if len(group) == 1:
                group[0]["out"] = outputs
            else:
                lo = 0
                for item, n in zip(group, rows):
                    item["out"] = _slice_outputs(outputs, lo, lo + n)
                    lo += n
        except Exception as e:  # noqa: BLE001 - delivered per request
            for item in group:
                item["error"] = e
        finally:
            for item in group:
                item["done"].set()

    def stop(self):
        import queue as _q

        self._stopping = True
        self._q.put(None)
        self._thread.join(timeout=10)
        # a request that raced stop() past the sentinel would wait its
        # full submit timeout; fail it now instead
        while True:
            try:
                item = self._q.get(False)
            except _q.Empty:
                break
            if item is not None:
                item["error"] = RuntimeError("server stopped")
                item["done"].set()


def _slice_outputs(outputs, lo, hi):
    """Row-slice an apply_fn result of any supported shape."""
    if isinstance(outputs, dict):
        return {k: v[lo:hi] for k, v in outputs.items()}
    if isinstance(outputs, (tuple, list)):
        return type(outputs)(v[lo:hi] for v in outputs)
    return outputs[lo:hi]


class ModelServer(object):
    """HTTP server exposing one exported model, TF-Serving REST shaped.

    ``batch_window_ms``: 0 (default) serves each request as its own
    model call behind the single-owner lock; > 0 coalesces concurrent
    same-signature requests inside the window into one batched call
    (see :class:`_Batcher`) — the generative path's throughput lever.
    """

    def __init__(self, model_dir, name="model", host="127.0.0.1", port=8501,
                 batch_window_ms=0, engine=None, replica_id=None,
                 dedup_capacity=2048, dedup_ttl_s=120.0):
        from tensorflowonspark_tpu import export as export_lib

        if model_dir is not None:
            apply_fn, variables, signature = export_lib.load_model(model_dir)
        elif engine is None:
            raise ValueError("ModelServer needs a model_dir, an engine, "
                             "or both")
        else:  # engine-only server: :generate is the whole surface
            apply_fn, variables, signature = None, None, {}
        self.name = name
        self.signature = signature or {}
        self._apply = apply_fn
        self._variables = variables
        self._lock = threading.Lock()  # one owner: requests serialize
        self._batcher = (_Batcher(apply_fn, variables, batch_window_ms)
                         if batch_window_ms and apply_fn is not None
                         else None)
        #: optional DecodeEngine behind POST :generate — the continuous-
        #: batching LM path; concurrent HTTP requests just submit() and
        #: the engine's scheduler interleaves them at step granularity
        self.engine = engine
        #: stable serving identity for the fleet plane; defaults to the
        #: mounted engine's (which survives respawn), so /healthz and
        #: /metrics series join to router decisions per replica
        self._replica_id = None if replica_id is None else str(replica_id)
        self._httpd = None
        self._thread = None
        self._host, self._port = host, port
        #: set by supervisor.Supervisor.watch (or any operator hook) when
        #: the serving path is known-bad; /healthz then answers 503
        self._unhealthy = None
        #: lease-fencing latch (PR 12): set by the fleet Replica when
        #: its beat comes back FENCED (a replacement holds a newer
        #: lease epoch). While set, :generate/:predict answer 410
        #: ``kind: "Fenced"`` (NON-retriable — re-resolve, don't retry)
        #: and /healthz answers 503 ``status: "fenced"``, so a router
        #: probe can never readmit a superseded replica
        self._fenced = None
        #: idempotent dispatch (PR 12): replay window keyed on the
        #: router's ``X-TFOS-Request-Id`` — a retried/hedged/duplicated
        #: :generate this server already executed is replayed (or
        #: joined in-flight), never generated twice. Server-level so it
        #: survives ``attach_engine`` swaps (the retry that matters
        #: most arrives right after a recovery)
        self._dedup = DedupWindow(capacity=dedup_capacity,
                                  ttl_s=dedup_ttl_s)
        self._dedup_hits = 0
        self._dedup_joined = 0
        self._dedup_obs_lock = threading.Lock()
        #: graceful-drain latch (drain() / SIGTERM): /healthz answers a
        #: distinct 503 'draining' and POST routes refuse with 503 while
        #: admitted work finishes. The lock + memo make drain()
        #: genuinely idempotent — a second caller (double SIGTERM)
        #: waits for the first drain and returns its verdict
        self._draining = False
        self._drain_lock = threading.Lock()
        self._drain_result = None
        #: POST requests currently inside a handler (admitted work's
        #: RESPONSES count too: drain must not stop the server while a
        #: finished generation is still being written to a slow client
        #: — handler threads are daemons and die at interpreter exit)
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        #: remote lifecycle RPCs (PR 13): ``POST /admin/<name>`` routes
        #: to callables registered via :meth:`register_admin` — how a
        #: driver reaches an EXECUTOR-HOSTED replica for drain /
        #: respawn / re_register / stop (rolling drains and autoscale
        #: retirement need a transport, and the replica's own HTTP
        #: server is it). Empty by default: a server that registered
        #: nothing (driver-local fleets, plain model servers) answers
        #: 404 for the rest of the /admin/ space.
        self._admin = {}
        #: splice fence floors (PR 17): src replica_id -> minimum
        #: ACCEPTED epoch (exclusive). A shipment claiming an epoch at
        #: or below the floor — or none — is refused 409 "fenced": the
        #: supervisor raises the floor (broadcast /admin/ship_fence)
        #: the moment it replaces/retires a prefill replica, so an
        #: orphaned in-flight shipment from the dead incarnation can
        #: never splice after its blocks' identity was reallocated.
        self._ship_fence = {}
        self._ship_fence_lock = threading.Lock()
        # pre-registered (unlike the lifecycle RPCs above): every
        # replica must accept fence broadcasts, including driver-local
        # ones that never registered drain/respawn
        self.register_admin("ship_fence", self._admin_ship_fence)
        #: control-epoch floor (PR 19): the ADMIN-plane fence. Every
        #: admin RPC a driver issues is stamped with its control epoch
        #: (X-TFOS-Control-Epoch); this floor rises monotonically to
        #: the highest stamp seen (or an explicit /admin/control_fence
        #: broadcast), and any stamped call BELOW it is refused 409
        #: ``kind: "ControlFenced"`` — a deposed driver's late
        #: ship_fence/drain/stop can never land after a warm-standby
        #: takeover. Unstamped calls pass (pre-PR-19 drivers).
        self._control_epoch = 0
        self._control_lock = threading.Lock()
        self._control_counters = tracing.Counters()
        self.register_admin("control_fence", self._admin_control_fence)

    # -- request handling ------------------------------------------------

    def predict(self, payload):
        """{'instances'|'inputs': ...} -> TF-Serving response dict."""
        if self._apply is None:
            raise _BadRequest(
                "no exported model mounted; this server only serves "
                ":generate (decode engine)")
        row_format = "instances" in payload
        batch = _to_batch(payload, self.signature)
        if self._batcher is not None:
            outputs = self._batcher.submit(batch)
        else:
            with self._lock:
                outputs = self._apply(self._variables, batch)
        return _to_json(outputs, row_format)

    def generate(self, payload, client_gone=None, trace=None,
                 request_id=None):
        """Idempotent :generate entry point: with a ``request_id`` (the
        fleet router's ``X-TFOS-Request-Id`` header, reused verbatim by
        every failover retry and hedge attempt of one client request),
        the dedup window makes re-execution safe — a request this
        server ALREADY answered is replayed from the stored response
        (dedup hit), and one still executing is JOINED (the retry waits
        on the original's outcome) instead of racing a duplicate
        generation. Failed executions are withdrawn, so a later retry
        runs clean. Without a ``request_id`` (direct clients) this is a
        plain execution. See :meth:`_generate_once` for the payload
        contract.

        Raises :class:`Fenced` while the server's lease epoch is
        superseded — direct API callers must not serve through a
        fenced replica any more than HTTP clients (whose 410 the
        handler answers from the same latch)."""
        if self._fenced is not None:
            raise Fenced("replica is fenced: " + self._fenced)
        if request_id is None:
            return self._generate_once(payload, client_gone, trace)
        entry, owner = self._dedup.begin(request_id)
        if not owner:
            hit = entry.done.is_set()
            with self._dedup_obs_lock:
                if hit:
                    self._dedup_hits += 1
                else:
                    self._dedup_joined += 1
            counters = getattr(self.engine, "counters", None)
            if counters is not None:
                with self._dedup_obs_lock:
                    counters.inc("dedup_hits" if hit else "dedup_joined")
            logger.info("request %s deduplicated (%s)", request_id,
                        "replayed" if hit else "joined in-flight")
            deadline = time.monotonic() + 600.0
            while not entry.done.wait(0.05):
                if client_gone is not None and client_gone():
                    # OUR client vanished; the owner's client may not
                    # have — never cancel the original's work from here
                    raise Cancelled(
                        "client disconnected while joined to an "
                        "in-flight duplicate")
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        "joined in-flight duplicate did not complete "
                        "within 600s")
            if entry.error is not None:
                raise entry.error
            return entry.response
        try:
            out = self._generate_once(payload, client_gone, trace)
        except BaseException as e:
            # transient failures are NOT cached: withdraw so a later
            # retry re-executes (joiners already waiting get the error
            # — they asked for the same doomed execution)
            self._dedup.fail(request_id, entry, e)
            raise
        self._dedup.complete(request_id, entry, out)
        return out

    def _generate_once(self, payload, client_gone=None, trace=None):
        """{'prompt': [[...], ...], 'max_new_tokens': N} -> {'tokens': ...}.

        ``trace``: an externally minted trace id (the fleet router's
        ``X-TFOS-Trace`` request header) adopted for the body's engine
        spans — a failed-over request's spans share one id across
        replicas, stitchable into a single end-to-end timeline.

        Each prompt becomes one engine request; the handles resolve
        concurrently (slot-interleaved), so a multi-prompt body — or many
        single-prompt clients — shares the same decode steps. A single
        flat prompt list is accepted and answered un-nested.

        Lifecycle fields: ``deadline_s`` (seconds the client will wait)
        rides the body into the engine — infeasible deadlines shed at
        admission (503 + Retry-After), expired in-flight requests evict
        at the next step boundary (504). ``client_gone`` (a callable
        from the HTTP layer) is polled while waiting; a disconnected
        client CANCELS its requests — no slot keeps decoding for a
        closed socket.
        """
        # snapshot: stop() nulls the attribute, and a handler already
        # past this check must reach the engine's own clean "stopped"
        # refusal rather than an AttributeError 500
        engine = self.engine
        if engine is None:
            raise _BadRequest("no decode engine mounted on this server")
        if not isinstance(payload, dict) or "prompt" not in payload:
            raise _BadRequest("request needs a 'prompt' field")
        prompts = payload["prompt"]
        if not isinstance(prompts, list) or not prompts:
            raise _BadRequest("'prompt' must be a non-empty list")
        flat = not isinstance(prompts[0], (list, tuple))
        if flat:
            prompts = [prompts]
        max_new = payload.get("max_new_tokens", 16)
        try:
            max_new = int(max_new)
        except (TypeError, ValueError):
            raise _BadRequest("max_new_tokens must be an integer")
        deadline_s = payload.get("deadline_s")
        if deadline_s is not None:
            try:
                deadline_s = float(deadline_s)
            except (TypeError, ValueError):
                raise _BadRequest("deadline_s must be a number")
            if not deadline_s > 0:
                raise _BadRequest("deadline_s must be > 0")
        # optional conversation identity (PR 16): an opaque string the
        # fleet router keys its session-affinity map on; threaded onto
        # the body's GenerationHandles, never interpreted here
        session = payload.get("session")
        if session is not None and not isinstance(session, str):
            raise _BadRequest("session must be a string")
        # tenant identity (PR 18): absent fields keep the default
        # tenant/class, so every existing caller is unchanged; a
        # MALFORMED value is the client's error (400), never silently
        # coerced into someone else's accounting bucket
        try:
            tenant = qos.validate_tenant(payload.get("tenant"))
            priority = qos.validate_priority(payload.get("priority"))
        except (TypeError, ValueError) as e:
            raise _BadRequest(str(e))
        try:
            # vet the WHOLE body before submitting any of it: a 400 must
            # not leave earlier prompts of the same body decoding for a
            # client that already got its error
            vetted = [engine.validate(p, max_new) for p in prompts]
        except (ValueError, TypeError) as e:
            raise _BadRequest(str(e))
        # atomic whole-body admission: QueueFull surfaces as 429 (and a
        # Shed as 503) with nothing queued, instead of part of the body
        # decoding for a client that got an error
        handles = engine._submit_many(vetted, deadline_s=deadline_s,
                                      trace=trace, session=session,
                                      tenant=tenant, priority=priority)
        try:
            tokens = [self._await_handle(h, handles, client_gone)
                      for h in handles]
        except BaseException:
            # the response is an error for the WHOLE body: siblings
            # still decoding would burn slots for an answer the client
            # will never see — cancel them on the way out
            for h in handles:
                h.cancel()
            raise
        return {"tokens": tokens[0] if flat else tokens}

    @staticmethod
    def _await_handle(handle, body, client_gone, poll_s=0.05,
                      timeout=600.0):
        """result() that also watches the client's socket: a client
        that disconnected mid-wait cancels the WHOLE body's requests
        (their slots free at the next step boundary) instead of the
        server decoding on for a closed connection."""
        if client_gone is None:
            return handle.result(timeout)
        deadline = time.monotonic() + timeout
        while not handle._done.wait(poll_s):
            if client_gone():
                cancelled = [h for h in body if h.cancel()]
                logger.info("client disconnected mid-generate; "
                            "cancelled %d request(s)", len(cancelled))
                raise Cancelled("client disconnected")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    "generation did not complete within {}s"
                    .format(timeout))
        return handle.result(0.1)

    # -- KV shipping surface (PR 17 disaggregation) ------------------------

    def prefill(self, payload, trace=None):
        """POST :prefill — the prefill-tier entry point of two-stage
        dispatch. ``{'prompt': [t, ...], 'session'?, 'src_epoch'?,
        'ship'?: {'addr': 'host:port', 'replica_id'?, 'epoch'?}}``.

        Runs the prompt through the NORMAL admission path as a 1-token
        generation (so bucketing, admission control, chaos sites and
        prefix registration all apply), then exports the now-resident
        block chain and — when ``ship`` names a decode-tier peer —
        delivers it to that peer's ``/kv/splice``. Ship failure is NOT
        request failure: the response still answers 200 with
        ``shipped: false`` and a reason, and the decode replica simply
        re-prefills cold on the follow-up :generate — correctness
        never rides the shipment. ``src_epoch`` (this replica's lease
        epoch, stamped by the router) travels in the shipment header
        so the receiver's fence floor can veto a superseded sender."""
        engine = self.engine
        if engine is None:
            raise _BadRequest("no decode engine mounted on this server")
        if self._fenced is not None:
            raise Fenced("replica is fenced: " + self._fenced)
        if not isinstance(payload, dict) or "prompt" not in payload:
            raise _BadRequest("request needs a 'prompt' field")
        prompt = payload["prompt"]
        if not isinstance(prompt, list) or not prompt \
                or isinstance(prompt[0], (list, tuple)):
            raise _BadRequest(":prefill takes ONE flat token list")
        session = payload.get("session")
        if session is not None and not isinstance(session, str):
            raise _BadRequest("session must be a string")
        try:
            vetted = engine.validate(prompt, 1)
        except (ValueError, TypeError) as e:
            raise _BadRequest(str(e))
        handles = engine._submit_many([vetted], trace=trace,
                                      session=session)
        handles[0].result(600.0)
        out = {"prefilled": True, "blocks": 0, "shipped": False}
        export = engine.export_prefix(
            prompt, src_epoch=payload.get("src_epoch"))
        if export is None:
            # nothing resident to ship (sub-block prompt) — the
            # prefill itself still happened
            return out
        buffers, meta = export
        out["blocks"] = len(meta["origins"])
        ship = payload.get("ship")
        if not isinstance(ship, dict) or not ship.get("addr"):
            return out
        n_bytes = frames.frame_bytes(buffers)
        t0 = time.monotonic()
        try:
            status, body, transport = kvship.ship(
                ship["addr"], buffers, src=self.replica_id,
                dst=ship.get("replica_id"))
        except (kvship.ShipError, chaos.NetPartitioned) as e:
            out["reason"] = str(e)
            return out
        t1 = time.monotonic()
        if status != 200:
            try:
                out["reason"] = json.loads(body).get("error", "")
            except (ValueError, AttributeError):
                out["reason"] = "splice answered {}".format(status)
            return out
        # accounting only on a CONFIRMED splice: a dropped response
        # (chaos) raised above, so shipped bytes are never claimed for
        # a delivery this side cannot prove
        engine.note_ship(out["blocks"], n_bytes, t1 - t0)
        engine.flight.span("kv.ship", t0, t1, trace=trace or 0,
                           blocks=out["blocks"], bytes=n_bytes,
                           transport=transport)
        out["shipped"] = True
        out["bytes"] = n_bytes
        out["transport"] = transport
        try:
            out["splice"] = json.loads(body)
        except ValueError:
            pass
        return out

    def splice_shipment(self, meta, rows):
        """Fence-check one decoded shipment, then splice it into the
        mounted engine (the body of ``POST /kv/splice``). All refusal
        paths count into ``tfos_splice_failures_total{reason=...}``."""
        engine = self.engine
        if engine is None or not hasattr(engine, "import_prefix"):
            raise SpliceRejected("engine", "no decode engine mounted")
        src = meta.get("src_replica")
        epoch = meta.get("src_epoch")
        with self._ship_fence_lock:
            floor = None if src is None \
                else self._ship_fence.get(str(src))
        if floor is not None and \
                (epoch is None or int(epoch) <= int(floor)):
            # the PR 12 epoch fence, applied to the SHIP plane: a
            # shipment from a replaced/retired incarnation must never
            # splice — its pool identity is gone and a replacement may
            # be shipping the same chains under a newer epoch
            engine.note_splice_failure("fenced")
            raise SpliceRejected(
                "fenced",
                "shipment from {} at epoch {} is below fence floor {}"
                .format(src, epoch, floor))
        try:
            return engine.import_prefix(meta, rows)
        except SpliceRejected as e:
            engine.note_splice_failure(e.reason)
            raise
        except (Retriable, TimeoutError):
            engine.note_splice_failure("engine")
            raise

    def ship_fence(self, replica_id, min_epoch):
        """Raise the splice fence floor for shipments claiming
        ``replica_id`` (monotonic — a floor never lowers). Exposed as
        ``POST /admin/ship_fence``; the fleet supervisor broadcasts it
        to every live replica when it replaces or retires a prefill
        replica, BEFORE the replacement spawns."""
        rid = str(replica_id)
        with self._ship_fence_lock:
            cur = self._ship_fence.get(rid)
            if cur is None or int(min_epoch) > cur:
                self._ship_fence[rid] = int(min_epoch)
            floor = self._ship_fence[rid]
        logger.info("ship fence: shipments from %s now need epoch > %d",
                    rid, floor)
        return {"replica_id": rid, "min_epoch": floor}

    def _admin_ship_fence(self, payload):
        if not isinstance(payload, dict) or \
                payload.get("replica_id") is None:
            raise ValueError("ship_fence needs a replica_id")
        return self.ship_fence(payload["replica_id"],
                               payload.get("min_epoch", 0))

    # -- control-epoch fence (PR 19) --------------------------------------

    def admit_control_epoch(self, epoch):
        """Admission check + adoption for a stamped admin RPC's
        control epoch: a stamp at or above the floor is admitted and
        ADOPTED (the floor rises to it — any replica the takeover
        broadcast missed still fences the moment the new leader's
        first stamped call arrives); a stamp below it is refused —
        the caller is a deposed driver. Returns ``(admitted, floor)``.
        Monotonic under its own lock; never lowers."""
        epoch = int(epoch)
        with self._control_lock:
            if epoch >= self._control_epoch:
                self._control_epoch = epoch
                return True, epoch
            self._control_counters.inc("admin_rejections")
            floor = self._control_epoch
        self._mount_control_counters()
        logger.warning(
            "refusing admin RPC stamped control epoch %d < floor %d "
            "(caller is a deposed driver)", epoch, floor)
        return False, floor

    def control_epoch_floor(self):
        """Current admin-plane control-epoch floor (0 = never saw a
        stamped call — every stamp is admitted)."""
        with self._control_lock:
            return self._control_epoch

    def _admin_control_fence(self, payload):
        """POST /admin/control_fence {"control_epoch": N}: the
        takeover broadcast. Raises the floor like any admitted stamp;
        idempotent and monotonic, so re-broadcasts are harmless."""
        if not isinstance(payload, dict) or \
                payload.get("control_epoch") is None:
            raise ValueError("control_fence needs a control_epoch")
        epoch = int(payload["control_epoch"])
        with self._control_lock:
            if epoch > self._control_epoch:
                self._control_epoch = epoch
            floor = self._control_epoch
        logger.info("control fence: admin RPCs now need control epoch "
                    ">= %d", floor)
        return {"control_epoch": floor}

    def _mount_control_counters(self):
        """Expose the control-plane counters on the CURRENT engine's
        /metrics registry (tfos_control_admin_rejections_total).
        Idempotent (add_counters replaces by prefix) and engine-swap
        tolerant — re-mounted on every rejection, so a respawned
        engine's registry picks the counters back up."""
        metrics = getattr(self.engine, "metrics", None)
        if metrics is not None:
            metrics.add_counters("tfos_control", self._control_counters)

    def metadata(self):
        return {"model_spec": {"name": self.name,
                               "signature_name": "serving_default"},
                "metadata": {"signature_def": self.signature,
                             "format": "tfos-tpu-export-v1"}}

    def register_admin(self, name, fn):
        """Mount ``fn(payload_dict) -> response_dict`` as ``POST
        /admin/<name>`` — the remote lifecycle RPC surface an
        executor-hosted replica exposes (fleet.ServingNode registers
        drain / respawn / re_register / stop). Admin routes bypass the
        fenced and draining gates BY DESIGN: fencing and draining are
        verdicts about SERVING traffic, and the operator RPCs that
        resolve those very states (re_register a fenced replica, stop a
        drained one) must still be reachable."""
        self._admin[str(name)] = fn

    # -- health (supervision plane) ---------------------------------------

    @property
    def replica_id(self):
        """The server's stable serving identity: an explicit
        construction-time id, else the mounted engine's (stable across
        ``respawn()``), else None (a bare predict server has no fleet
        identity)."""
        if self._replica_id is not None:
            return self._replica_id
        return getattr(self.engine, "replica_id", None)

    def attach_engine(self, engine):
        """(Re-)arm the :generate path with ``engine`` and clear any
        unhealthy mark — the supervisor's RestartEngine policy calls
        this after rebuilding a dead engine, flipping /healthz back to
        200 so load balancers resume routing."""
        self.engine = engine
        self._unhealthy = None
        logger.info("serving re-armed with a fresh decode engine")

    def mark_unhealthy(self, reason):
        """Flip /healthz to 503. Called by supervisor.Supervisor.watch
        when the watched engine's scheduler dies, or by any operator
        hook; load balancers drain the replica instead of timing out
        against a server whose accept loop is fine but whose decode
        plane is gone."""
        self._unhealthy = str(reason)
        logger.error("serving marked unhealthy: %s", reason)

    def fence(self, reason):
        """Refuse to serve: this replica's lease epoch was superseded
        (fleet.Replica calls this on a FENCED beat). :generate and
        :predict answer 410 ``kind: "Fenced"`` — NON-retriable, the
        client/router must go to the current lease holder — and
        /healthz answers 503 ``status: "fenced"`` so no probe loop can
        readmit a superseded replica. The engine keeps running (its
        in-flight work finishes; only NEW work is refused): fencing is
        an identity verdict, not an engine fault."""
        self._fenced = str(reason)
        logger.error("serving FENCED: %s", reason)

    def unfence(self):
        """Clear the fenced latch (``Replica.re_register`` — a fresh
        lease epoch was deliberately acquired)."""
        self._fenced = None
        logger.info("serving unfenced (fresh lease epoch)")

    def healthz(self):
        """(status_code, body) for GET /healthz.

        503 once the supervisor marked the server unhealthy OR the
        mounted engine's scheduler is dead (checked live, so even an
        unwatched server stops answering 200 over a dead decode plane).
        A DRAINING server answers a distinct 503 ``status: "draining"``
        — the load balancer's cue to stop routing while admitted work
        finishes (an LB cannot tell "dying" from "retiring" through a
        bare 503, and the two warrant different alerting). The body
        carries the engine's liveness detail plus the queue-depth /
        slot-occupancy gauges and token counts from its
        tracing.Counters — the numbers an operator needs to tell
        "dead" from "saturated" from "retiring"."""
        body = {"status": "ok", "model": self.name}
        rid = self.replica_id
        if rid is not None:
            # pinned schema (fleet plane): the id a scrape or router
            # joins this replica's series and decisions on
            body["replica_id"] = rid
        # idempotent-dispatch visibility: window occupancy + absorbed
        # duplicates (the partition-flap bench's proof that retries
        # were deduplicated, not re-executed)
        with self._dedup_obs_lock:
            body["dedup"] = dict(self._dedup.stats(),
                                 hits=self._dedup_hits,
                                 joined=self._dedup_joined)
        if self._fenced is not None:
            # fenced outranks EVERYTHING: a superseded replica must
            # never answer 200 (a router probe would readmit it into
            # the exact split-brain fencing closed)
            body["status"] = "fenced"
            body["reason"] = self._fenced
            return 503, body
        engine = self.engine
        if engine is not None:
            health = engine.healthy()
            snap = engine.counters.snapshot()
            body["engine"] = health
            body["queue_depth"] = snap["gauges"].get("queue_depth", 0)
            body["slot_occupancy"] = snap["gauges"].get("slot_occupancy", 0)
            body["counts"] = snap["counts"]
            # block-pool headroom (PR 8): same pinned keys the fleet
            # BEAT payload carries, so an operator curl and a router
            # decision read one schema.
            # getattr: supervision fakes duck-type only healthy() +
            # counters, and a health probe must not 500 over a gauge
            load_stats = getattr(engine, "load_stats", None)
            if callable(load_stats):
                load = load_stats()
                for key in ("kv_blocks_total", "kv_blocks_free",
                            "prefix_hit_rate",
                            "generated_prefix_hit_blocks",
                            "generated_prefix_registered",
                            "speculate_k", "spec_acceptance_rate",
                            "kv_dtype"):
                    body[key] = load[key]
            if self._draining:
                # draining outranks the liveness checks below: mid-
                # drain the engine transitions draining -> stopped by
                # DESIGN, and reporting that as "unhealthy" would page
                # an operator for a planned retirement
                body["status"] = "draining"
                body["reason"] = "server is draining; " \
                    "{} request(s) still in flight".format(
                        engine.outstanding()
                        if health["scheduler_thread"] else 0)
                return 503, body
            if not health["alive"]:
                body["status"] = "unhealthy"
                body["reason"] = health.get("broken") or \
                    "decode engine scheduler is not running"
                return 503, body
        if self._draining:
            body["status"] = "draining"
            body["reason"] = "server is draining"
            return 503, body
        if self._unhealthy is not None:
            body["status"] = "unhealthy"
            body["reason"] = self._unhealthy
            return 503, body
        return 200, body

    def status(self):
        return {"model_version_status": [{
            "version": "1", "state": "AVAILABLE",
            "status": {"error_code": "OK", "error_message": ""}}]}

    # -- observability (GET /metrics, GET /debug/trace) --------------------

    def metrics_text(self):
        """OpenMetrics exposition of the mounted engine's registry —
        the body ``GET /metrics`` serves (scrapeable by Prometheus; see
        docs/observability.md for the metric catalog). An engine-less
        predict server exposes an empty-but-valid document, so a scrape
        job can target every replica uniformly."""
        engine = self.engine
        registry = getattr(engine, "metrics", None)
        text = tracing.MetricsRegistry().render() if registry is None \
            else registry.render()
        info = ""
        rid = self.replica_id
        if rid is not None:
            # info-pattern gauge: a constant-1 sample whose label IS the
            # payload, so every scraped tfos_serving_* series from this
            # replica joins to its stable identity (group_left in
            # PromQL) without re-labeling the whole exposition
            info += ('# TYPE tfos_serving_replica_info gauge\n'
                     'tfos_serving_replica_info{{replica_id="{}"}} 1\n'
                     .format(rid))
        kv_dtype = getattr(engine, "kv_dtype", None)
        if kv_dtype is not None:
            # same info pattern for the KV storage dtype (PR 15):
            # which replicas run the int8 fast path during a
            # quantization rollout
            info += ('# TYPE tfos_serving_kv_dtype gauge\n'
                     'tfos_serving_kv_dtype{{dtype="{}"}} 1\n'
                     .format(kv_dtype))
        # per-reason splice refusals (PR 17): label-valued counter
        # rendered here because the engine's Counters carry no labels;
        # sample name keeps the mandatory _total suffix the scrape
        # contract (tests/test_observability.py) enforces
        failures = getattr(engine, "splice_failures", None)
        if callable(failures):
            counts = failures()
            if counts:
                info += "# TYPE tfos_splice_failures counter\n"
                for reason in sorted(counts):
                    info += ('tfos_splice_failures_total'
                             '{{reason="{}"}} {}\n'
                             .format(reason, counts[reason]))
        # tenant-labeled QoS counters (PR 18): same hand-rendered
        # label pattern — the engine's Counters carry no labels, and
        # tenant names are client-bounded by qos._TENANT_RE (64 chars
        # of [A-Za-z0-9._-]), so label values need no escaping
        tallies = getattr(engine, "qos_tallies", None)
        if callable(tallies):
            t = tallies()
            if t["admitted"]:
                info += "# TYPE tfos_qos_admitted counter\n"
                for tenant, cls in sorted(t["admitted"]):
                    info += ('tfos_qos_admitted_total'
                             '{{tenant="{}",class="{}"}} {}\n'
                             .format(tenant, cls,
                                     t["admitted"][(tenant, cls)]))
            if t["preemptions"]:
                info += "# TYPE tfos_qos_preemptions counter\n"
                for tenant, cls in sorted(t["preemptions"]):
                    info += ('tfos_qos_preemptions_total'
                             '{{tenant="{}",class="{}"}} {}\n'
                             .format(tenant, cls,
                                     t["preemptions"][(tenant, cls)]))
            if t["quota_rejections"]:
                info += "# TYPE tfos_qos_quota_rejections counter\n"
                for tenant in sorted(t["quota_rejections"]):
                    info += ('tfos_qos_quota_rejections_total'
                             '{{tenant="{}"}} {}\n'
                             .format(tenant,
                                     t["quota_rejections"][tenant]))
            if t["tokens"]:
                info += "# TYPE tfos_qos_tokens counter\n"
                for tenant in sorted(t["tokens"]):
                    info += ('tfos_qos_tokens_total'
                             '{{tenant="{}"}} {}\n'
                             .format(tenant, t["tokens"][tenant]))
        if info:
            text = text.replace("# EOF\n", info + "# EOF\n")
        return text

    def debug_trace(self):
        """Chrome trace-event JSON of the request trace timeline — the
        body ``GET /debug/trace`` serves (loads directly in Perfetto /
        chrome://tracing; scripts/trace_dump.py is the file-writing
        CLI). Uses the mounted engine's FlightRecorder, falling back to
        the process-global one so supervision instants are dumpable
        even without an engine."""
        flight = getattr(self.engine, "flight", None)
        if flight is None:
            flight = tracing.flight_recorder()
        return flight.chrome_trace()

    # -- graceful drain ----------------------------------------------------

    def drain(self, timeout=None):
        """Graceful shutdown, in load-balancer order: flip /healthz to
        the distinct ``draining`` 503 (LBs stop routing), refuse new
        POST work (503 + Retry-After), let every ADMITTED request
        finish — the engine's :meth:`DecodeEngine.drain` zero-loss
        contract, plus DELIVERY of their responses — then stop the HTTP
        server and engine. ``timeout`` is ONE overall bound covering
        both engine completion and response delivery; ``timeout=None``
        waits for the engine as long as the work takes but caps the
        post-drain delivery wait at 30s (a client that stops READING
        its response is indistinguishable from a dead one — waiting
        forever on its socket would wedge the shutdown). Returns True
        only when every admitted request finished AND its response was
        handed to the HTTP layer; False on any expiry. Idempotent, and
        safe from any thread: a concurrent second call (a double
        SIGTERM spawns two drain threads) blocks until the first drain
        finishes and returns its verdict instead of re-running the
        teardown."""
        # flip the latch BEFORE queueing on the lock: healthz and the
        # POST routes must refuse immediately even while another
        # caller's drain is mid-flight
        self._draining = True
        with self._drain_lock:
            if self._drain_result is not None:
                return self._drain_result
            logger.info("serving %r draining", self.name)
            overall = None if timeout is None \
                else time.monotonic() + max(float(timeout), 0.0)
            engine = self.engine
            drained = True
            if engine is not None:
                drained = engine.drain(
                    timeout=None if overall is None
                    else max(overall - time.monotonic(), 0.0))
            # zero loss includes DELIVERY: the engine finishing a
            # handle is not the client having its tokens — wait for
            # in-flight POST handlers (daemon threads the interpreter
            # would otherwise kill mid-write) to finish responding.
            # The batcher must still be alive here: an admitted
            # :predict inside this window finishes through it, so its
            # teardown comes AFTER the wait
            delivery_deadline = overall if overall is not None \
                else time.monotonic() + 30.0
            while True:
                with self._inflight_lock:
                    left = self._inflight
                if left == 0:
                    break
                if time.monotonic() >= delivery_deadline:
                    logger.warning(
                        "drain: %d response(s) still being written at "
                        "the delivery deadline", left)
                    drained = False  # undelivered responses ARE loss
                    break
                time.sleep(0.02)
            if self._batcher is not None:
                self._batcher.stop()
                self._batcher = None
            self.stop()
            logger.info("serving %r drained (%s) and stopped", self.name,
                        "zero loss" if drained else "TIMED OUT with "
                        "requests outstanding")
            self._drain_result = drained
            return drained

    def install_sigterm_drain(self, timeout=None):
        """Arm SIGTERM -> :meth:`drain` (the k8s/rolling-restart
        contract: the orchestrator sends SIGTERM, the replica finishes
        admitted work and exits instead of killing it). Must run on the
        MAIN thread (the ``signal`` module's rule); the handler hands
        the drain to a helper thread so the signal frame returns
        immediately. Returns the previous handler."""
        import signal as signal_mod

        def _on_sigterm(signum, frame):
            logger.warning("SIGTERM: draining serving %r", self.name)
            # daemon=False is the CONTRACT, not an omission: the
            # interpreter joins non-daemon threads at exit, so the
            # drain finishes before the process dies — a daemon drain
            # would be killed mid-zero-loss exactly when SIGTERM-then-
            # exit is the whole point
            # tfos: unjoined(non-daemon: interpreter exit IS the join)
            threading.Thread(target=self.drain,
                             kwargs={"timeout": timeout},
                             name="tfos-serving-drain",
                             daemon=False).start()

        return signal_mod.signal(signal_mod.SIGTERM, _on_sigterm)

    # -- http plumbing ---------------------------------------------------

    def start(self):
        """Start serving in a daemon thread; returns (host, port)."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from tensorflowonspark_tpu import util

        util.enable_compile_cache()  # this process compiles what it serves
        server = self

        class Handler(BaseHTTPRequestHandler):
            def _send(self, code, obj, headers=None):
                body = json.dumps(obj).encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for name, value in (headers or {}).items():
                    self.send_header(name, value)
                self.end_headers()
                self.wfile.write(body)

            def _send_text(self, code, text, content_type):
                body = text.encode("utf-8")
                self.send_response(code)
                self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _client_gone(self):
                """True once the client closed its connection: the
                request socket is readable with EOF (nothing more was
                sent, and a live client waiting on its response sends
                nothing). Polled by the generate wait loop so a
                disconnect cancels the engine work it was waiting on."""
                import select
                try:
                    readable, _, _ = select.select(
                        [self.connection], [], [], 0)
                    if not readable:
                        return False
                    return self.connection.recv(
                        1, socket.MSG_PEEK) == b""
                except (OSError, ValueError):
                    return True

            def _kv_splice(self):
                """POST /kv/splice (PR 17): adopt one shipped KV
                prefix. Body is the raw frames-coded shipment — or
                empty with ``X-TFOS-KV-Via: shm``, in which case the
                shipment sits in the named shm ring (the co-hosted
                zero-copy path) and this request is just the notify.
                Splicing happens while the source buffer is alive
                (the rows are zero-copy views), then the ring slot
                releases."""
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                except ValueError:
                    n = 0
                # always consume the body first: even refusal paths
                # must leave the connection in a sane state
                body = self.rfile.read(n) if n else b""
                if server._fenced is not None:
                    return self._send(
                        410, {"error": "replica is fenced: "
                              + server._fenced, "kind": "Fenced"})
                if server._draining:
                    return self._send(
                        503, {"error": "server is draining",
                              "kind": "Draining"},
                        headers={"Retry-After": "5"})
                try:
                    if self.headers.get("X-TFOS-KV-Via") == "shm":
                        ring, lock = kvship.consumer_ring(
                            self.headers.get("X-TFOS-KV-Ring", ""))
                        with lock:
                            view, release = ring.read_view(timeout=5.0)
                            try:
                                meta, rows = kvship.unpack(view)
                                result = server.splice_shipment(
                                    meta, rows)
                            finally:
                                release()
                    else:
                        meta, rows = kvship.unpack(body)
                        result = server.splice_shipment(meta, rows)
                except SpliceRejected as e:
                    # deliberate refusal: 409, reason-tagged — the
                    # shipping side gives up (no retry loop can fix a
                    # fence or a dtype mismatch) and lets the decode
                    # replica re-prefill cold
                    return self._send(
                        409, {"error": str(e), "reason": e.reason,
                              "kind": "SpliceRejected"})
                except ValueError as e:
                    # malformed frame / unknown wire version
                    engine = server.engine
                    if hasattr(engine, "note_splice_failure"):
                        engine.note_splice_failure("malformed")
                    return self._send(400, {"error": str(e)})
                except OSError as e:
                    # named ring unreachable (producer died / swept)
                    engine = server.engine
                    if hasattr(engine, "note_splice_failure"):
                        engine.note_splice_failure("engine")
                    return self._send(503, {"error": str(e)},
                                      headers={"Retry-After": "1"})
                except (Retriable, TimeoutError) as e:
                    return self._send(503, {"error": str(e)},
                                      headers={"Retry-After": "1"})
                except Exception as e:  # noqa: BLE001 - surface 500
                    logger.exception("/kv/splice failed")
                    return self._send(500, {"error": str(e)})
                return self._send(200, result)

            def do_GET(self):
                if self.path == "/healthz":
                    return self._send(*server.healthz())
                if self.path == "/metrics":
                    return self._send_text(200, server.metrics_text(),
                                           OPENMETRICS_CONTENT_TYPE)
                if self.path == "/debug/trace":
                    trace = server.debug_trace()
                    # ring saturation travels with the dump: a reader
                    # must know when spans were evicted under it
                    return self._send(
                        200, trace,
                        headers={"X-TFOS-Trace-Dropped":
                                 str(trace.get("dropped", 0))})
                base = "/v1/models/%s" % server.name
                if self.path == base:
                    return self._send(200, server.status())
                if self.path == base + "/metadata":
                    return self._send(200, server.metadata())
                return self._send(404, {"error": "not found: %s" % self.path})

            def do_POST(self):
                with server._inflight_lock:
                    server._inflight += 1
                try:
                    return self._do_post_tracked()
                finally:
                    with server._inflight_lock:
                        server._inflight -= 1

            def _do_post_tracked(self):
                if self.path.startswith("/admin/"):
                    # lifecycle RPCs bypass the fenced/draining gates
                    # below: they exist to RESOLVE those states
                    fn = server._admin.get(self.path[len("/admin/"):])
                    if fn is None:
                        return self._send(
                            404, {"error": "not found: %s" % self.path})
                    # control-epoch fence (PR 19): a stamped call below
                    # the floor is a DEPOSED driver's — refuse before
                    # the verb runs. Unstamped calls pass (back-compat;
                    # the fence guards against a stale LEADER, which
                    # always stamps).
                    raw_ce = self.headers.get("X-TFOS-Control-Epoch")
                    if raw_ce is not None:
                        try:
                            ce = int(raw_ce)
                        except ValueError:
                            return self._send(
                                400, {"error": "malformed X-TFOS-"
                                      "Control-Epoch: %r" % raw_ce})
                        admitted, floor = server.admit_control_epoch(ce)
                        if not admitted:
                            return self._send(
                                409, {"error": "control epoch %d is "
                                      "below this replica's floor %d "
                                      "(a newer driver took over)"
                                      % (ce, floor),
                                      "kind": "ControlFenced",
                                      "control_epoch": floor})
                    try:
                        n = int(self.headers.get("Content-Length", "0"))
                        payload = json.loads(self.rfile.read(n) or b"{}")
                        return self._send(200, fn(payload or {}))
                    except json.JSONDecodeError as e:
                        return self._send(400, {"error": str(e)})
                    except Exception as e:  # noqa: BLE001 - surface 500
                        logger.exception("admin %s failed", self.path)
                        return self._send(500, {"error": str(e)})
                # trace-context propagation (fleet plane): a router-
                # minted X-TFOS-Trace id is adopted as the engine trace
                # id so this replica's spans join the fleet timeline
                trace = None
                raw_trace = self.headers.get("X-TFOS-Trace")
                if raw_trace:
                    try:
                        trace = int(raw_trace)
                    except ValueError:
                        trace = None  # malformed header: local id
                # idempotency key (PR 12): every failover retry / hedge
                # / net-duplicated delivery of one client request
                # carries the same id — the dedup window's join key
                request_id = self.headers.get("X-TFOS-Request-Id") \
                    or None
                if self.path == "/kv/splice":
                    # raw octet-stream branch (PR 17): the body is a
                    # frames-coded shipment (or an shm notify), never
                    # JSON — it must branch before the JSON parse below
                    return self._kv_splice()
                routes = {"/v1/models/%s:predict" % server.name:
                          server.predict,
                          "/v1/models/%s:generate" % server.name:
                          lambda payload: server.generate(
                              payload, client_gone=self._client_gone,
                              trace=trace, request_id=request_id),
                          "/v1/models/%s:prefill" % server.name:
                          lambda payload: server.prefill(
                              payload, trace=trace)}
                handler = routes.get(self.path)
                if handler is None:
                    return self._send(404,
                                      {"error": "not found: %s" % self.path})
                if server._fenced is not None:
                    # NON-retriable 410: this replica's lease epoch is
                    # superseded — serving would double-serve alongside
                    # the current holder. Clients/routers re-resolve;
                    # only a deliberate re_register clears it
                    return self._send(
                        410, {"error": "replica is fenced: "
                              + server._fenced, "kind": "Fenced"})
                if server._draining:
                    # drain contract: no new work — in-flight requests
                    # finish, fresh ones go to another replica
                    return self._send(
                        503, {"error": "server is draining",
                              "status": "draining",
                              "kind": "Draining"},
                        headers={"Retry-After": "5"})
                try:
                    n = int(self.headers.get("Content-Length", "0"))
                    payload = json.loads(self.rfile.read(n) or b"{}")
                    return self._send(200, handler(payload))
                except (_BadRequest, json.JSONDecodeError) as e:
                    # malformed JSON is the client's fault: 400, not 500
                    return self._send(400, {"error": str(e)})
                except Fenced as e:
                    # a fence that landed AFTER the pre-dispatch check:
                    # same non-retriable 410 contract
                    return self._send(410, {"error": str(e),
                                            "kind": "Fenced"})
                except QueueFull as e:
                    # backpressure, not failure: retry later
                    return self._send(429, {"error": str(e)})
                except QuotaExceeded as e:
                    # per-tenant rate quota (PR 18): 429 like QueueFull
                    # but NOT a failover signal — the quota follows the
                    # tenant, not the replica, so the router passes it
                    # through verbatim. Retry-After is the bucket's
                    # honest refill time.
                    return self._send(
                        429, {"error": str(e),
                              "kind": "QuotaExceeded",
                              "tenant": e.tenant},
                        headers={"Retry-After":
                                 str(int(math.ceil(e.retry_after)))})
                except DeadlineExceeded as e:
                    # admitted but evicted past its deadline — the
                    # gateway-timeout shape, not a server fault
                    return self._send(504, {"error": str(e)})
                except Cancelled as e:
                    # request cancelled (usually: this client hung up);
                    # 499 is the de-facto client-closed-request code.
                    # The write is best-effort — the socket is likely
                    # gone, and a broken pipe here must not crash the
                    # handler thread into socketserver's stderr dump
                    try:
                        return self._send(499, {"error": str(e)})
                    except OSError:
                        return
                except Retriable as e:
                    # shed / draining / engine mid-restart: transient
                    # by definition, so tell the client WHEN to retry.
                    # ``kind`` names WHICH transient condition: the
                    # fleet router treats an EngineFailed as replica
                    # unhealthiness but a Shed as mere load — both are
                    # 503 on the wire
                    return self._send(
                        503, {"error": str(e),
                              "kind": type(e).__name__},
                        headers={"Retry-After":
                                 str(int(math.ceil(e.retry_after)))})
                except Exception as e:  # noqa: BLE001 - surface as 500
                    logger.exception("%s failed", self.path)
                    return self._send(500, {"error": str(e)})

            def log_message(self, fmt, *args):  # quiet by default
                logger.debug("serving: " + fmt, *args)

        self._httpd = ThreadingHTTPServer((self._host, self._port), Handler)
        self._port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="tfos-serving",
            daemon=True)
        self._thread.start()
        logger.info("serving %r on %s:%d", self.name, self._host, self._port)
        return self._host, self._port

    def stop(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._thread.join(timeout=10)
            self._httpd = None
        if self._batcher is not None:
            self._batcher.stop()
            self._batcher = None
        if self.engine is not None:
            self.engine.stop()
            self.engine = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        description="Serve an exported model over TF-Serving-shaped REST")
    ap.add_argument("--model-dir", required=True)
    ap.add_argument("--name", default="model")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--port", type=int, default=8501)
    ap.add_argument("--batch-window-ms", type=float, default=0,
                    help="coalesce concurrent same-shape requests into "
                         "one batched model call inside this window "
                         "(0 = off); the generative path's throughput "
                         "lever")
    ap.add_argument("--drain-timeout", type=float, default=None,
                    help="bound (seconds) on the SIGTERM graceful "
                         "drain; default: wait for all admitted work "
                         "(zero loss)")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    server = ModelServer(args.model_dir, name=args.name,
                         host=args.host, port=args.port,
                         batch_window_ms=args.batch_window_ms)
    host, port = server.start()
    # rolling-restart contract: SIGTERM flips /healthz to 'draining',
    # admitted requests finish, then the serve thread exits and main
    # returns — the orchestrator's grace period does the rest
    server.install_sigterm_drain(timeout=args.drain_timeout)
    print("serving %s at http://%s:%d/v1/models/%s" % (
        args.model_dir, host, port, args.name))
    try:
        server._thread.join()
    except KeyboardInterrupt:
        server.stop()


if __name__ == "__main__":
    main()
