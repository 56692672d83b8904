"""Dump a serving request trace timeline as Chrome trace-event JSON.

The serving plane's FlightRecorder (tracing.py, PR 5) keeps a bounded
ring of span events — one trace id per request, spans admit -> queue ->
prefill -> decode -> finish/evict/shed, plus engine-row decode steps.
This CLI renders it as the Chrome trace-event JSON format, which loads
directly in Perfetto (https://ui.perfetto.dev) or chrome://tracing:
open the output file and every request is a labeled row whose spans
nest inside its admit->finish envelope.

Three sources:

    # a live server's ring (ModelServer GET /debug/trace; a fleet
    # ROUTER's URL dumps the STITCHED cross-replica timeline instead):
    python scripts/trace_dump.py --url http://HOST:PORT -o trace.json

    # hermetic demo: a tiny in-process engine serves --requests
    # mixed-length generations and dumps their spans (CPU, no server):
    JAX_PLATFORMS=cpu \
    python scripts/trace_dump.py --demo [--requests 3] -o trace.json

    # hermetic TRAINING demo (goodput plane, PR 10): a tiny Trainer
    # runs a few steps with the goodput ledger mirroring its
    # compile / train_step / checkpoint / feed-wait intervals into a
    # private FlightRecorder — the training-run timeline (traces were
    # serving-only before):
    JAX_PLATFORMS=cpu \
    python scripts/trace_dump.py --train-demo [--steps 6] -o trace.json

``-o -`` (default) writes to stdout. The schema tests in
tests/test_observability.py pin the output shape: every span event
carries name/ph/ts/dur/pid/tid, and each request's child spans nest
within its ``request`` span.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _fetch(url):
    import urllib.request

    with urllib.request.urlopen(url.rstrip("/") + "/debug/trace",
                                timeout=30) as resp:
        return json.loads(resp.read())


def _demo(n_requests):
    """Run ``n_requests`` mixed-length generations through a tiny
    DecodeEngine with a PRIVATE FlightRecorder (so the dump contains
    exactly this run) and return its Chrome trace."""
    import jax
    import numpy as np

    from tensorflowonspark_tpu import serving, tracing
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    kw = dict(vocab=64, hidden=32, num_heads=2, num_layers=1, max_len=64)
    train = DecoderLM(decode=False, **kw)
    dec = DecoderLM(decode=True, **kw)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, 64), np.int32))["params"]
    flight = tracing.FlightRecorder()
    rng = np.random.RandomState(0)
    with serving.DecodeEngine(dec, params, slots=2, total_len=64,
                              flight=flight) as engine:
        handles = []
        for i in range(n_requests):
            prompt = rng.randint(0, 64, size=int(rng.choice(
                (2, 4, 8)))).tolist()
            handles.append(engine.submit(prompt, 4 + 2 * i))
        for handle in handles:
            handle.result(300)
        return engine.flight.chrome_trace()


def _train_demo(n_steps):
    """Run ``n_steps`` tiny training steps through ``training.Trainer``
    with a PRIVATE ledger+recorder: the goodput ledger mirrors every
    interval (compile / train_step / checkpoint_save / feed_wait) into
    the ring, so the dump is a training-run timeline."""
    import flax.linen as nn
    import jax
    import numpy as np
    import optax

    from tensorflowonspark_tpu import goodput, tracing, training

    class TinyMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(4)(nn.relu(nn.Dense(8)(x)))

    flight = tracing.FlightRecorder()
    ledger = goodput.GoodputLedger(flight=flight)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    trainer = training.Trainer(model=TinyMLP(),
                               optimizer=optax.sgd(1e-2), mesh=mesh)
    rng = np.random.RandomState(0)
    sample = {"x": rng.randn(4, 8).astype(np.float32),
              "y": rng.randint(0, 4, size=4)}
    state = trainer.init(jax.random.PRNGKey(0), sample["x"])

    def batches():
        import time as _time
        for _ in range(n_steps):
            with ledger.track("feed_wait"):  # a stand-in feed stall
                _time.sleep(0.002)
            yield {"x": rng.randn(4, 8).astype(np.float32),
                   "y": rng.randint(0, 4, size=4)}

    trainer.train_loop(state, batches(), log_every=0, ledger=ledger)
    return flight.chrome_trace()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="dump a serving trace timeline as Perfetto-loadable "
                    "Chrome trace JSON")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--url", help="ModelServer base URL; reads its "
                                   "GET /debug/trace ring")
    src.add_argument("--demo", action="store_true",
                     help="hermetic in-process engine run (CPU)")
    src.add_argument("--train-demo", action="store_true",
                     help="hermetic in-process TRAINING run (CPU): "
                          "goodput-ledger spans on the timeline")
    ap.add_argument("--requests", type=int, default=3,
                    help="demo-mode request count (default 3)")
    ap.add_argument("--steps", type=int, default=6,
                    help="train-demo step count (default 6)")
    ap.add_argument("-o", "--out", default="-",
                    help="output path ('-' = stdout)")
    args = ap.parse_args(argv)

    if args.demo:
        trace = _demo(args.requests)
    elif args.train_demo:
        trace = _train_demo(args.steps)
    else:
        trace = _fetch(args.url)
    spans = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    if args.out == "-":
        json.dump(trace, sys.stdout)
        sys.stdout.write("\n")
    else:
        with open(args.out, "w") as f:
            json.dump(trace, f)
        print("wrote {} ({} events, {} spans) — open in "
              "https://ui.perfetto.dev".format(
                  args.out, len(trace["traceEvents"]), spans),
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
