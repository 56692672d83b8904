"""Stage breakdown of the continuous-batching decode engine (PR 2).

Where does a served token's time go? Runs a mixed-length generation
workload through serving.DecodeEngine and prints the per-stage
attribution the engine's own tracing hooks collect:

- ``prefill``       — per-admission fused prompt pass (one jit call per
                      request, compiled per shape bucket; on a warm
                      prefix hit this is the TAIL only)
- ``decode_step``   — the fixed-shape S-slot step, including the
                      per-step host sync that reads the emitted tokens
- ``host_schedule`` — pure scheduler bookkeeping between steps
                      (admission scans, EOS checks, stream delivery)
- ``qos_plan``      — multi-tenant QoS (PR 18): the weighted-fair
                      admission plan (bucket grouping + deficit
                      selection + quota/preemption decisions) inside
                      each scheduler pass — budget is <50µs/plan,
                      pinned loosely in tests/test_qos.py
- ``prefix_lookup`` — paged KV (PR 8): prefix-cache chain match at
                      admission (the TTFT attribution for warm hits)
- ``block_alloc``   — paged KV: free-list allocation + LRU eviction at
                      admission and at decode-time block growth
- ``attn``          — fused paged attention (PR 11): the engine's
                      standalone attention probe at its live shapes
                      (one layer per decode step; multiply by layers),
                      so ``--attn-impl gather`` vs the fused default
                      attributes the kernel-vs-gather delta per step
- ``spec_round``    — speculative decoding (PR 15, ``--speculate-k``):
                      the fused draft+verify round the loop runs (one
                      program; replaces ``decode_step``)
- ``draft``/``verify`` — the round's two halves probed STANDALONE
                      (``engine.measure_spec`` — per-op timing is
                      invisible inside one program), plus
                      ``draft_prefill`` at admission
- ``dequant``       — int8 KV (PR 15, ``--kv-dtype int8``): one
                      whole-pool dequantize at live shapes (the
                      fast path's add-on cost, beside ``attn``'s
                      view of what it saves)

plus the engine's counters (tokens/step = effective slot occupancy,
prefills, steps), compile stats (programs vs buckets), the request-
lifecycle tallies (shed / cancelled / deadline_exceeded /
engine_restarts — all zero on this clean workload; nonzero means the
harness itself is evicting benched traffic), and a cold/warm split so
compile cost is attributed separately from steady-state decode.

Usage (CPU, hermetic):

    JAX_PLATFORMS=cpu \
    python scripts/profile_serving.py [--requests 32] [--slots 8] \
        [--total-len 256] [--hidden 64] [--layers 2] [--seed 0] \
        [--attn-impl fused|gather] [--json]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _run(dec, params, reqs, slots, label, out, **engine_kw):
    # bench.py's harness — ONE engine-measurement implementation, so
    # the profiler's stage attribution describes the benched run shape.
    # Latency quantiles arrive already read from the engine's
    # MetricsRegistry histograms (tensorflowonspark_tpu.metrics_report)
    # — the same distributions GET /metrics exposes.
    from bench import _engine_leg

    tps, lat, stats = _engine_leg(dec, params, reqs, slots, **engine_kw)
    out[label] = dict(tokens_per_sec=round(tps, 1), **dict(lat, **stats))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--total-len", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--attn-impl", choices=("fused", "gather"),
                    default=None,
                    help="paged attention formulation (default: the "
                         "engine's fused kernel; 'gather' runs the "
                         "PR 8 reference for a per-stage comparison)")
    ap.add_argument("--speculate-k", type=int, default=None,
                    help="speculative decoding window (>= 2): a "
                         "weight-tied reduced-depth draft proposes k "
                         "tokens per round, the target verifies them "
                         "in one fused apply; adds the spec_round "
                         "loop stage and the draft/verify probes")
    ap.add_argument("--draft-layers", type=int, default=None,
                    help="draft depth (with --speculate-k; default "
                         "num_layers // 2)")
    ap.add_argument("--kv-dtype", choices=("int8",), default=None,
                    help="int8 paged-KV fast path: quantized pool + "
                         "per-head scales, dequantized in-kernel; "
                         "adds the dequant probe stage")
    ap.add_argument("--json", action="store_true",
                    help="print one JSON blob instead of the table")
    args = ap.parse_args(argv)
    if args.total_len < 16:
        ap.error("--total-len must be >= 16: the mixed workload draws "
                 "prompts from range(8, total_len//2 + 1, 8), which is "
                 "empty below that")

    import jax
    import numpy as np

    from tensorflowonspark_tpu.models.decoder import DecoderLM

    train = DecoderLM(vocab=args.vocab, hidden=args.hidden, num_heads=4,
                      num_layers=args.layers, max_len=args.total_len,
                      decode=False)
    dec = DecoderLM(vocab=args.vocab, hidden=args.hidden, num_heads=4,
                    num_layers=args.layers, max_len=args.total_len,
                    decode=True)
    params = train.init(jax.random.PRNGKey(0),
                        np.zeros((1, args.total_len), np.int32))["params"]
    # the SAME generator bench.py's serving_decode block measures, so
    # the profiler's stage attribution describes the benched workload
    from bench import _serving_workload
    reqs = _serving_workload(args.requests, args.total_len, args.vocab,
                             seed=args.seed)

    out = {"config": {"requests": args.requests, "slots": args.slots,
                      "total_len": args.total_len, "hidden": args.hidden,
                      "layers": args.layers,
                      "total_new_tokens": sum(mn for _, mn in reqs)}}
    engine_kw = {}
    if args.attn_impl is not None:
        engine_kw["attn_impl"] = args.attn_impl
    if args.speculate_k is not None:
        engine_kw["speculate_k"] = args.speculate_k
        if args.draft_layers is not None:
            engine_kw["draft_layers"] = args.draft_layers
    if args.kv_dtype is not None:
        engine_kw["kv_dtype"] = args.kv_dtype
    jax.clear_caches()
    _run(dec, params, reqs, args.slots, "cold", out,
         **engine_kw)                                  # includes compiles
    _run(dec, params, reqs, args.slots, "warm", out,
         **engine_kw)                                  # steady state

    if args.json:
        print(json.dumps(out))
        return
    print("config: {}".format(out["config"]))
    for leg in ("cold", "warm"):
        r = out[leg]
        print("\n[{}] {} tokens in {}s -> {} tok/s  "
              "(p50 {}ms, p99 {}ms)".format(
                  leg, r["tokens"], r["wall_s"], r["tokens_per_sec"],
                  r["p50_ms"], r["p99_ms"]))
        print("  occupancy: {} tokens/step over {} steps, {} prefills"
              .format(r["tokens_per_step"], r["decode_steps"],
                      r["prefills"]))
        print("  stages (mean ms/call): {}".format(r["stage_ms"]))
        print("  stages (total s):      {}".format(r["stage_s_total"]))
        print("  histograms (registry quantiles, ms):")
        for key in ("ttft", "per_token", "decode_step", "queue_wait"):
            print("    {:<12} {}".format(key, r["hist"][key]))
        print("  compile: {}".format(r["compile"]))
        print("  lifecycle: {}".format(r["lifecycle"]))
        print("  attn_impl: {}  kv_dtype: {}".format(
            r["attn_impl"], r["kv_dtype"]))
        if "spec" in r:
            print("  speculative: {}".format(r["spec"]))
        if "kv" in r:
            print("  kv blocks: {}".format(r["kv"]))


if __name__ == "__main__":
    main()
