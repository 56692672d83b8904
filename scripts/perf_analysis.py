"""Single-chip step analysis (BASELINE primary metric).

For the bench ResNet-50 train step at the given batch/dtype config:

  - XLA compiled cost analysis: FLOPs/step, bytes accessed, and the
    roofline time each implies on this device (MXU peak vs HBM BW) — the
    ceiling argument for the measured rate.
  - compiled memory analysis (temp/argument/output bytes),
  - donation check (donated input buffers reported by the executable),
  - measured step time, images/sec/chip and MFU,
  - optional ``--trace DIR``: a ``jax.profiler`` trace of 3 steps for
    TensorBoard's profile plugin / xprof.

Runs on any backend (CPU smoke uses the tiny model) so the harness is
testable without the chip; the numbers that matter come from a TPU run:
``python scripts/perf_analysis.py --batch 256`` on the chip.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: public TPU spec sheet: (device_kind substring, bf16 peak FLOP/s, HBM B/s)
_SPECS = (
    ("v6 lite", 918e12, 1640e9), ("v6e", 918e12, 1640e9),
    ("v5 lite", 197e12, 819e9), ("v5e", 197e12, 819e9),
    ("v5p", 459e12, 2765e9), ("v5", 459e12, 2765e9),
    ("v4", 275e12, 1228e9),
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--image", type=int, default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--bn-dtype", default=os.environ.get(
        "TFOS_BENCH_BN_DTYPE", "float32"))
    ap.add_argument("--trace", default=None,
                    help="directory for a jax.profiler trace of 3 steps")
    args = ap.parse_args()

    os.environ["TFOS_BENCH_BN_DTYPE"] = args.bn_dtype
    import jax
    import numpy as np
    import optax

    import bench
    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.parallel import build_mesh

    on_tpu = jax.default_backend() != "cpu"
    batch = args.batch or (256 if on_tpu else 16)
    image = args.image or (224 if on_tpu else 32)

    model = bench._bench_model(on_tpu)
    mesh = build_mesh({"data": len(jax.devices())})
    trainer = training.Trainer(model, optax.sgd(0.1, momentum=0.9), mesh,
                               remat=bench._bench_remat())
    rng = np.random.RandomState(0)
    x = rng.rand(batch, image, image, 3).astype(np.float32)
    y = (np.arange(batch) % (1000 if on_tpu else 10)).astype(np.int64)
    batch_data = jax.device_put({"x": x, "y": y}, trainer.batch_sharding)
    state = trainer.init(jax.random.PRNGKey(0), x)

    # ensure the jit step exists, then analyze the compiled executable
    state, _ = trainer.step(state, batch_data)
    compiled = trainer._jit_step.lower(state, batch_data).compile()

    report = {"config": {"batch": batch, "image": image,
                         "bn_dtype": args.bn_dtype,
                         "remat": bench._bench_remat(),
                         "backend": jax.default_backend(),
                         "device": str(jax.devices()[0].device_kind)}}

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    if cost:
        flops = float(cost.get("flops", 0.0))
        nbytes = float(cost.get("bytes accessed", 0.0))
        report["cost"] = {"flops_per_step": flops,
                          "bytes_accessed": nbytes,
                          "arithmetic_intensity":
                          round(flops / nbytes, 2) if nbytes else None}
        kind = jax.devices()[0].device_kind.lower()
        spec = next(((p, bw) for key, p, bw in _SPECS if key in kind), None)
        if spec:
            peak_flops, hbm_bw = spec
            report["roofline"] = {
                "compute_bound_ms": round(flops / peak_flops * 1e3, 3),
                "memory_bound_ms": round(nbytes / hbm_bw * 1e3, 3),
                "bound": "compute" if flops / peak_flops > nbytes / hbm_bw
                         else "memory",
            }

    mem = compiled.memory_analysis()
    if mem is not None:
        report["memory"] = {
            k: int(getattr(mem, k))
            for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                      "output_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)}

    try:
        donated = compiled.input_layouts  # probe: not all versions expose
        del donated
    except Exception:  # noqa: BLE001
        pass
    # donation shows up as aliased outputs in the HLO; cheapest check is
    # the trainer's own setting plus the executable text marker
    hlo = compiled.as_text()
    report["donation"] = {"donate_state": trainer._donate,
                          "hlo_aliases": hlo.count("donated") +
                          hlo.count("alias")}

    # measured rate
    for _ in range(3):
        state, metrics = trainer.step(state, batch_data)
    float(jax.device_get(metrics["loss"]))
    t0 = time.monotonic()
    for _ in range(args.steps):
        state, metrics = trainer.step(state, batch_data)
    float(jax.device_get(metrics["loss"]))
    dt = (time.monotonic() - t0) / args.steps
    n_dev = len(jax.devices())
    rate = batch / dt / n_dev
    report["measured"] = {"step_ms": round(dt * 1e3, 3),
                          "images_per_sec_per_chip": round(rate, 2)}
    if "cost" in report and report["cost"]["flops_per_step"]:
        kind = jax.devices()[0].device_kind.lower()
        spec = next(((p, bw) for key, p, bw in _SPECS if key in kind), None)
        if spec:
            report["measured"]["mfu"] = round(
                report["cost"]["flops_per_step"] / n_dev / dt / spec[0], 4)

    if args.trace:
        from tensorflowonspark_tpu import tracing
        with tracing.trace(args.trace):
            for _ in range(3):
                state, metrics = trainer.step(state, batch_data)
            float(jax.device_get(metrics["loss"]))
        report["trace_dir"] = args.trace

    print(json.dumps(report, indent=2))


if __name__ == "__main__":
    main()
