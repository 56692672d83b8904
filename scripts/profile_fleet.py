"""Routing-overhead breakdown of the serving fleet (PR 6).

What does putting a ``fleet.FleetRouter`` in front of the decode
engines COST per request? Runs the shared mixed-length workload over
HTTP through an in-process N-replica fleet and prints the attribution
the router's own observability plane collects:

- ``pick``     — dispatch-policy time (lease snapshot -> view build ->
                 least-loaded ordering), per attempt
- ``upstream`` — the proxied POST against the chosen replica (this is
                 the request actually being served; everything else is
                 routing overhead)

plus the three router histograms (request wall / upstream wall / their
difference = route overhead), failover tallies (zero on a clean run),
and the per-replica dispatch spread. Everything is read through the
shared ``metrics_report`` helpers from the SAME ``MetricsRegistry``
the router's ``GET /metrics`` renders — published numbers and scraped
series are two views of one histogram. The run harness itself is
``bench._fleet_leg``, so the attribution describes exactly the run
shape ``bench.py serving_fleet`` publishes.

Usage (CPU, hermetic):

    JAX_PLATFORMS=cpu \
    python scripts/profile_fleet.py [--replicas 2] [--requests 16] \
        [--slots 8] [--total-len 256] [--hidden 64] [--layers 2] [--json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _spawn_to_first_token(dec, params, slots, executors):
    """Time the autoscaler's capacity-add latency: a 1-replica fleet
    calls ``spawn_replica()`` (what a scale-up does — bootstrap +
    lease + wire-verified healthz) and the new replica is then asked
    for ONE token directly, so ``spawn_to_first_token_s`` is the wall
    from the scale decision to the first token the added capacity
    could serve. ``executors`` > 0 hosts the fleet on engine executors
    and times the EXECUTOR-side spawn (task dispatch + jax import +
    engine build in a fresh process — the honest number for
    placement='executors'); 0 times the driver-local spawn (programs
    shared, so this is the floor)."""
    import time as time_mod
    import urllib.request

    from tensorflowonspark_tpu import fleet as fleet_mod

    sc = None
    kw = {}
    if executors:
        from tensorflowonspark_tpu.engine.context import Context
        # this process built the params, so it holds the chip where
        # there is one: the executor-hosted replica runs on the CPU,
        # and what is timed is the control plane of a spawn
        sc = Context(executors, executor_env={"JAX_PLATFORMS": "cpu"})
        kw = dict(placement="executors", sc=sc, spawn_timeout=300)
    f = fleet_mod.ServingFleet(dec, params, replicas=1,
                               engine_kw={"slots": slots}, **kw)
    try:
        f.start()
        t0 = time_mod.monotonic()
        replica = f.spawn_replica()
        spawn_s = time_mod.monotonic() - t0
        addr = replica.addr
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_new_tokens": 1}).encode()
        req = urllib.request.Request(
            "http://{}:{}/v1/models/model:generate".format(*addr),
            data=body, headers={"Content-Type": "application/json"})
        t1 = time_mod.monotonic()
        with urllib.request.urlopen(req, timeout=600) as r:
            r.read()
        first_token_s = time_mod.monotonic() - t1
        return {"placement": "executors" if executors else "driver",
                "spawn_s": round(spawn_s, 3),
                "first_token_s": round(first_token_s, 3),
                "spawn_to_first_token_s": round(
                    spawn_s + first_token_s, 3)}
    finally:
        f.stop()
        if sc is not None:
            sc.stop()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--total-len", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--json", action="store_true",
                    help="print one JSON blob instead of the table")
    ap.add_argument("--spawn", action="store_true",
                    help="additionally time spawn-to-first-token for "
                         "a scale-up replica (the autoscaler's "
                         "capacity-add latency)")
    ap.add_argument("--executors", type=int, default=0,
                    help="with --spawn: host the fleet on N engine "
                         "executors and time the EXECUTOR-side spawn "
                         "(bootstrap task + engine build + lease + "
                         "healthz); 0 = driver-local spawn")
    args = ap.parse_args(argv)
    if args.total_len < 16:
        ap.error("--total-len must be >= 16 (the mixed workload draws "
                 "prompts from range(8, total_len//2 + 1, 8))")

    import jax
    import numpy as np

    from tensorflowonspark_tpu.models.decoder import DecoderLM

    # bench.py's harness + workload — ONE fleet-measurement
    # implementation, shared so this attribution describes the benched
    # run shape
    from bench import _fleet_leg, _serving_workload

    train = DecoderLM(vocab=args.vocab, hidden=args.hidden, num_heads=4,
                      num_layers=args.layers, max_len=args.total_len,
                      decode=False)
    dec = DecoderLM(vocab=args.vocab, hidden=args.hidden, num_heads=4,
                    num_layers=args.layers, max_len=args.total_len,
                    decode=True)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, args.total_len), np.int32))["params"]
    reqs = _serving_workload(args.requests, args.total_len, args.vocab,
                             seed=args.seed)

    tps, quantiles, stats = _fleet_leg(dec, params, reqs, args.replicas,
                                       slots=args.slots)
    out = {"config": {"replicas": args.replicas,
                      "requests": args.requests, "slots": args.slots,
                      "total_len": args.total_len,
                      "total_new_tokens": sum(mn for _, mn in reqs)},
           "tokens_per_sec": round(tps, 1),
           "request": quantiles, **stats}
    if args.spawn:
        out["spawn"] = _spawn_to_first_token(dec, params, args.slots,
                                             args.executors)

    if args.json:
        print(json.dumps(out))
        return
    print("config: {}".format(out["config"]))
    print("\n{} tokens in {}s through {} replica(s) -> {} tok/s"
          .format(out["tokens"], out["wall_s"], args.replicas,
                  out["tokens_per_sec"]))
    print("  request (router-observed, ms):   {}".format(quantiles))
    print("  upstream attempt (ms):           {}".format(
        out["upstream"]))
    print("  route overhead (request-upstream, ms): {}".format(
        out["route_overhead"]))
    print("  router stages (mean ms/call):    {}".format(
        out["stage_ms"]))
    print("  failovers: {}  no_replica: {}".format(
        out["failovers"], out["no_replica"]))
    if args.spawn:
        print("  spawn-to-first-token ({}): spawn {}s + first token "
              "{}s = {}s".format(
                  out["spawn"]["placement"], out["spawn"]["spawn_s"],
                  out["spawn"]["first_token_s"],
                  out["spawn"]["spawn_to_first_token_s"]))


if __name__ == "__main__":
    main()
