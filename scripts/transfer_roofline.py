"""Host<->device transfer roofline for the fed path.

The cluster-fed headline (bench.py) moves one uint8 image batch from the
executor process into device HBM per step. On a co-located host that
link is PCIe/DMA and the feed plane is the suspect; over a slow link the
link itself is the ceiling (at ~10 MB/s a 38.5 MB batch-256 payload
costs ~3.8 s/step regardless of how fast the ring delivers it). This
harness measures the link alone, with no framework in the path, so the
fed number can be judged against the medium it rode on:

  - dispatch latency: tiny-op round trip (median of ``--reps``),
  - h2d bandwidth: ``device_put`` of uint8 payloads at several sizes,
    synced via an on-device reduce + scalar read-back (a sync that
    provably drains the dispatch queue — see bench.py's device_get
    note),
  - d2h bandwidth: ``device_get`` of the same buffers,
  - overlap: two buffers device_put back-to-back, synced once — whether
    the transport pipelines consecutive transfers.

With ``--fed-json`` (a bench.py artifact), prints the fed path's
effective bytes/s over the best transport and the fraction of the raw
h2d ceiling it achieves: ``fed_frac_of_wire`` ~= 1.0 means the feed
plane adds nothing on top of the link — the honest denominator when
``fed_frac_of_device`` is link-bound: a roofline naming the binding
ceiling.

Prints ONE JSON line. Runs on any backend (CPU validates the harness;
the numbers that matter come from a TPU run).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# Same upper-median as bench.py's helper, duplicated on purpose: this
# script must stay importable/runnable without pulling in the bench
# module (the merge mode runs with no jax at all).
def _median(values):
    return sorted(values)[len(values) // 2]


def _sync_scalar(jnp, buf):
    """Force completion of everything queued on ``buf``'s device."""
    import jax
    return float(jax.device_get(jnp.sum(buf[:1, :1])))


def measure(sizes_mb, reps, image=224):
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    out = {"device": str(dev), "platform": dev.platform}

    # dispatch latency: scalar round trip, compile paid up front
    one = jax.device_put(np.float32(1.0))
    add = jax.jit(lambda a: a + 1.0)
    float(jax.device_get(add(one)))  # compile
    lats = []
    for _ in range(reps):
        t0 = time.monotonic()
        float(jax.device_get(add(one)))
        lats.append(time.monotonic() - t0)
    out["dispatch_latency_ms"] = round(_median(lats) * 1e3, 3)

    h2d = {}
    d2h = {}
    rng = np.random.RandomState(0)
    # dedupe by row count: two requested sizes that quantize to the same
    # payload would otherwise silently overwrite each other's key
    row_counts = sorted({max(1, int(mb * 1e6) // (image * image * 3))
                         for mb in sizes_mb})
    for rows in row_counts:
        arr = rng.randint(0, 255, size=(rows, image * image * 3),
                          dtype=np.uint8)
        actual = arr.nbytes
        rates_up = []
        rates_down = []
        buf = None
        for _ in range(reps):
            t0 = time.monotonic()
            buf = jax.device_put(arr)
            _sync_scalar(jnp, buf)
            rates_up.append(actual / (time.monotonic() - t0))
            t0 = time.monotonic()
            host = jax.device_get(buf)
            rates_down.append(host.nbytes / (time.monotonic() - t0))
        key = "{:.1f}MB".format(actual / 1e6)
        h2d[key] = round(_median(rates_up) / 1e6, 2)
        d2h[key] = round(_median(rates_down) / 1e6, 2)
        del buf
    out["h2d_MBps"] = h2d
    out["d2h_MBps"] = d2h
    out["h2d_ceiling_MBps"] = max(h2d.values())

    # overlap: two puts back-to-back, one sync — pipelined transports
    # finish in ~1 transfer time + overlap; serial ones in ~2.
    nbytes = int(sizes_mb[-1] * 1e6)
    rows = max(1, nbytes // (image * image * 3))
    a = rng.randint(0, 255, size=(rows, image * image * 3), dtype=np.uint8)
    b = a.copy()
    seq = []
    for _ in range(reps):
        t0 = time.monotonic()
        ba = jax.device_put(a)
        bb = jax.device_put(b)
        # ONE sync depending on both buffers: separate syncs would add a
        # serialized round trip each and misread a pipelining transport
        # as serial on a high-latency link
        float(jax.device_get(jnp.sum(ba[:1, :1]) + jnp.sum(bb[:1, :1])))
        seq.append((a.nbytes + b.nbytes) / (time.monotonic() - t0))
    out["h2d_paired_MBps"] = round(_median(seq) / 1e6, 2)
    out["h2d_overlap_ratio"] = round(
        out["h2d_paired_MBps"] / out["h2d_ceiling_MBps"], 2)
    return out


def fed_vs_wire(out, fed_json, image):
    """Effective fed bytes/s vs the raw wire ceiling."""
    try:
        with open(fed_json) as f:
            rec = json.load(f)
    except Exception as e:  # noqa: BLE001 - missing artifact is reportable
        out["fed_json_error"] = str(e)
        return
    if not out.get("h2d_ceiling_MBps"):
        out["fed_json_error"] = ("no h2d_ceiling_MBps in the wire "
                                 "artifact: roofline stage incomplete?")
        return
    best_fed = max((rec.get(k) or 0.0
                    for k in ("cluster_fed_shm", "cluster_fed_queue",
                              "cluster_fed_auto")),
                   default=0.0)
    if not best_fed:
        out["fed_json_error"] = "no fed rate in {}".format(fed_json)
        return
    img_bytes = image * image * 3  # uint8 HWC, the fed payload
    fed_mbps = best_fed * img_bytes / 1e6
    out["fed_images_per_sec"] = round(best_fed, 2)
    out["fed_effective_MBps"] = round(fed_mbps, 2)
    out["fed_frac_of_wire"] = round(fed_mbps / out["h2d_ceiling_MBps"], 3)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default=None,
                    help="comma list of payload sizes (default by backend)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--image", type=int, default=224,
                    help="image side for the fed-payload row size")
    ap.add_argument("--fed-json", default=None,
                    help="bench.py artifact to compute fed_frac_of_wire")
    ap.add_argument("--from", dest="from_json", default=None,
                    help="prior roofline artifact: merge fed_frac_of_wire "
                         "offline without touching the device (windows are "
                         "fragile; the wire numbers may already be safe on "
                         "disk when the fed bench lands)")
    args = ap.parse_args()

    if args.from_json:
        # The prior artifact may be empty/truncated (a timeout killed the
        # roofline stage before its JSON line printed) — report, don't
        # traceback; the merge stage's record must always be valid JSON.
        try:
            with open(args.from_json) as f:
                out = json.load(f)
        except Exception as e:  # noqa: BLE001 - any unreadable artifact
            out = {"from_error": "unreadable {}: {}".format(
                args.from_json, e)}
    else:
        import jax
        on_tpu = jax.default_backend() != "cpu"
        if args.sizes_mb:
            sizes = [float(s) for s in args.sizes_mb.split(",")]
        else:
            # 38.5MB = the batch-256 fed payload; bracket it
            sizes = [4.0, 16.0, 38.5] if on_tpu else [0.5, 2.0]
        out = measure(sizes, args.reps, args.image)
    if args.fed_json:
        fed_vs_wire(out, args.fed_json, image=args.image)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
