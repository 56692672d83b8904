"""Train a small decoder LM, then continue prompts with the KV cache.

Demonstrates the generation surface (beyond the reference, whose
inference is batch scoring only): a decoder-only LM trains on synthetic
periodic sequences, and ``generation.generate_jit`` continues prompts
with cached O(1)-per-token decode — greedy or top-k sampling.

``--serve N`` additionally pushes N mixed-length prompts through the
continuous-batching serving engine (``serving.DecodeEngine``): requests
share a slot-structured KV cache, enter freed slots at decode-step
boundaries, and every output is verified token-identical to a solo
``generate`` call — the serving path and the offline path agree.

CPU dev run::

    JAX_PLATFORMS=cpu \
    python examples/generate/lm_generate.py --steps 150 --serve 8
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=8)
    ap.add_argument("--period", type=int, default=4)
    ap.add_argument("--seq_len", type=int, default=24)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--steps", type=int, default=150)
    ap.add_argument("--batch_size", type=int, default=16)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--max_new", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top_k", type=int, default=None)
    ap.add_argument("--serve", type=int, default=0, metavar="N",
                    help="also serve N mixed-length prompts through the "
                         "continuous-batching DecodeEngine and report "
                         "tokens/sec + solo-parity")
    ap.add_argument("--fleet", type=int, default=0, metavar="R",
                    help="also serve the same prompts over HTTP through "
                         "an R-replica serving fleet (fleet.ServingFleet "
                         "router), with the shared serving.retry_call "
                         "client retry policy, and report solo-parity")
    ap.add_argument("--executors", type=int, default=0, metavar="N",
                    help="with --fleet: host the replicas INSIDE N "
                         "engine executor processes (the PR 13 "
                         "executor-role serving bootstrap) instead of "
                         "the driver — the demo prints each replica's "
                         "executor + pid so the placement is visible")
    ap.add_argument("--tenant", default=None,
                    help="tenant id attached to every --serve/--fleet "
                         "request (PR 18 QoS plane); omitted => the "
                         "engine's default tenant, identical behaviour "
                         "to older builds")
    ap.add_argument("--priority", default=None,
                    choices=["high", "normal", "low"],
                    help="priority class for the --serve/--fleet "
                         "requests (default: normal)")
    ap.add_argument("--out", default=None,
                    help="write {loss, prompt, generated} JSON here")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from tensorflowonspark_tpu import generation
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    max_len = args.seq_len * 2
    train = DecoderLM(vocab=args.vocab, hidden=args.hidden, num_heads=4,
                      num_layers=2, max_len=max_len, decode=False)
    dec = DecoderLM(vocab=args.vocab, hidden=args.hidden, num_heads=4,
                    num_layers=2, max_len=max_len, decode=True)

    rng = np.random.RandomState(0)

    def batch():
        starts = rng.randint(0, args.period, size=(args.batch_size, 1))
        seq = (starts + np.arange(args.seq_len + 1)) % args.period
        return jnp.asarray(seq, jnp.int32)

    params = train.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, args.seq_len), jnp.int32))["params"]
    opt = optax.adam(args.lr)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, toks):
        def loss_fn(p):
            logits = train.apply({"params": p}, toks[:, :-1])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, toks[:, 1:]).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    loss = None  # --steps 0: decode-only run, loss never computed
    for i in range(args.steps):
        params, opt_state, loss = step(params, opt_state, batch())
        if i % 50 == 0:
            print("step %d loss %.4f" % (i, float(loss)))

    prompt = jnp.asarray(
        [[(i % args.period) for i in range(6)]], jnp.int32)
    out = generation.generate_jit(
        dec, params, prompt, args.max_new,
        temperature=args.temperature,
        rng=jax.random.PRNGKey(1), top_k=args.top_k)
    generated = np.asarray(out[0, prompt.shape[1]:]).tolist()
    print("prompt   ", np.asarray(prompt[0]).tolist())
    print("generated", generated)

    serve_stats = None
    if args.serve:
        import time

        from tensorflowonspark_tpu import serving

        rs = np.random.RandomState(1)
        reqs = []
        for _ in range(args.serve):
            n = int(rs.randint(3, args.seq_len))
            start = int(rs.randint(0, args.period))
            reqs.append(([(start + i) % args.period for i in range(n)],
                         int(rs.randint(2, args.seq_len))))
        with serving.DecodeEngine(dec, params, slots=4,
                                  total_len=max_len) as eng:
            t0 = time.monotonic()
            handles = [eng.submit(p, mn, tenant=args.tenant,
                                  priority=args.priority)
                       for p, mn in reqs]
            outs = [h.result(600) for h in handles]
            wall = time.monotonic() - t0
            tokens = eng.counters.snapshot()["counts"]["tokens"]
            occupancy = eng.counters.rate("decode_tokens", "decode_steps")
        # the serving path must agree with the offline path, request by
        # request (greedy => token-identical)
        mismatches = 0
        for (p, mn), got in zip(reqs, outs):
            solo = generation.generate_jit(
                dec, params, jnp.asarray([p], jnp.int32), mn)
            if got != np.asarray(solo)[0].tolist():
                mismatches += 1
        serve_stats = {"requests": len(reqs), "tokens": int(tokens),
                       "tokens_per_sec": round(tokens / wall, 1),
                       "tokens_per_step": round(occupancy, 2),
                       "solo_mismatches": mismatches}
        print("served   ", serve_stats)
        if mismatches:
            raise SystemExit(
                "continuous-batching outputs diverged from solo generate")

    fleet_stats = None
    if args.fleet:
        import time
        import urllib.error
        import urllib.request

        from tensorflowonspark_tpu import cluster, serving

        rs = np.random.RandomState(2)
        reqs = []
        for _ in range(max(args.serve, 4)):
            n = int(rs.randint(3, args.seq_len))
            start = int(rs.randint(0, args.period))
            reqs.append(([(start + i) % args.period for i in range(n)],
                         int(rs.randint(2, args.seq_len))))
        sc = None
        fleet_kw = {}
        if args.executors:
            # executor-hosted path (PR 13): replicas bootstrap inside
            # executor processes and register their real HTTP addrs
            # over BEAT; the router routes to them unchanged
            if args.executors < args.fleet:
                raise SystemExit(
                    "--executors {} < --fleet {}: each replica needs "
                    "its own executor".format(args.executors,
                                              args.fleet))
            from tensorflowonspark_tpu.engine.context import Context
            # THIS process trained the model, so it holds this host's
            # chips, and a chip belongs to one process: the replicas
            # its executors host run on the CPU. A serving executor on
            # a TPU host owns that host's chips for its lifetime
            # (docs/serving.md) — one per host, under a driver that
            # stays off JAX.
            sc = Context(args.executors,
                         executor_env={"JAX_PLATFORMS": "cpu"})
            fleet_kw = dict(placement="executors", sc=sc,
                            spawn_timeout=300)
        fl = cluster.serving_fleet(dec, params, replicas=args.fleet,
                                   name="lm", engine_kw={"slots": 4},
                                   **fleet_kw)
        try:
            if args.executors:
                placement = {
                    rid: info.get("host")
                    for rid, info in
                    fl.reservation.serving_snapshot().items()}
                print("placement", placement,
                      "(driver pid {})".format(os.getpid()))
            url = fl.url("/v1/models/lm:generate")

            def post(payload):
                # the SHARED client retry policy (serving.retry_call):
                # transient 429/503s — a shedding or draining replica,
                # an engine mid-restart — retry with bounded backoff +
                # full jitter, honoring the router's Retry-After;
                # anything else propagates
                def attempt():
                    req = urllib.request.Request(
                        url, data=json.dumps(payload).encode(),
                        headers={"Content-Type": "application/json"})
                    try:
                        with urllib.request.urlopen(req, timeout=300) as r:
                            return json.loads(r.read())
                    except urllib.error.HTTPError as e:
                        retriable = serving.http_retriable(
                            e.code, e.headers.get("Retry-After"))
                        if retriable is not None:
                            raise retriable
                        raise
                return serving.retry_call(attempt)

            t0 = time.monotonic()
            # each request carries a session id (PR 16): the router
            # pins follow-up turns of a conversation to the replica
            # whose prefix cache is warm for it — same wire contract,
            # one optional field
            # tenant / priority (PR 18) ride the same body: the router
            # and the replica both read them, absent fields mean the
            # default tenant at normal priority
            qos_fields = {}
            if args.tenant is not None:
                qos_fields["tenant"] = args.tenant
            if args.priority is not None:
                qos_fields["priority"] = args.priority
            outs = [post(dict({"prompt": p, "max_new_tokens": mn,
                               "session": "demo-{}".format(i)},
                              **qos_fields))["tokens"]
                    for i, (p, mn) in enumerate(reqs)]
            wall = time.monotonic() - t0
            mismatches = 0
            for (p, mn), got in zip(reqs, outs):
                solo = generation.generate_jit(
                    dec, params, jnp.asarray([p], jnp.int32), mn)
                if got != np.asarray(solo)[0].tolist():
                    mismatches += 1
            tokens = sum(len(got) - len(p)
                         for (p, _), got in zip(reqs, outs))
            counts = fl.router.counters.snapshot()["counts"]
            fleet_stats = {"replicas": args.fleet,
                           "requests": len(reqs), "tokens": tokens,
                           "tokens_per_sec": round(tokens / wall, 1),
                           "failovers": counts.get("failovers", 0),
                           "affinity_hits": counts.get(
                               "affinity_hits", 0),
                           "solo_mismatches": mismatches}
            print("fleet    ", fleet_stats)
        finally:
            fl.stop()
            if sc is not None:
                sc.stop()
        if fleet_stats["solo_mismatches"]:
            raise SystemExit(
                "fleet-served outputs diverged from solo generate")

    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"loss": None if loss is None else float(loss),
                       "prompt": np.asarray(prompt[0]).tolist(),
                       "generated": generated,
                       "serve": serve_stats,
                       "fleet": fleet_stats}, f)


if __name__ == "__main__":
    main()
