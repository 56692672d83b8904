"""The CPU rehearsal: every runner's functions at a tiny size with
``JAX_PLATFORMS=cpu``, the trace reduction against the small recorded
TPU trace under ``data/``, each FLOP/byte function against a hand
count, and ``BENCHMARK.json`` against the files and the contract's
limits on names and lengths.

    JAX_PLATFORMS=cpu python benchmarks/selfcheck.py [part ...]

It prints counts and comparisons only: a time, a rate or a share from
a CPU run is never printed under the name of a device metric.
"""

import copy
import json
import os
import re
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402

TINY_RESNET = {"stage_sizes": [1, 1], "width": 8, "num_classes": 10}
TINY_GPT2 = {"vocab": 128, "hidden": 64, "num_heads": 4, "head_dim": 16,
             "num_layers": 2, "max_len": 64}
#: limits for the tiny rehearsals only (small leaves are noisier in
#: bfloat16 than the cells' own); the cells' limits are in cells/*.json
TINY_TRAIN_LIMITS = {"loss_gap.2": 0.05,
                     "loss_gap.3": 0.05, "grad_gap": 0.3,
                     "change_gap": 0.5, "grad_gap_median": 0.05,
                     "change_gap_median": 0.05}


def tiny_ctx(cell_name, seed=3, seconds=1.5, trace=False):
    """The context run.py would build, with every size made tiny."""
    import importlib

    run_py = importlib.import_module("benchmarks.run")
    cell, config, traffic = run_py.load_cell(cell_name)
    cell, config, traffic = (copy.deepcopy(x)
                             for x in (cell, config, traffic))
    if "optimizer" in config:
        config["model"] = dict(TINY_RESNET)
        traffic.update(batch=8, image=32, pool_records=32, warm_steps=1,
                       records_per_part=4096, class_run=2)
        cell["limits"] = dict(TINY_TRAIN_LIMITS)
    else:
        config["model"] = dict(TINY_GPT2)
        config["serving"] = {"slots": 4, "kv_block_size": 8,
                             "kv_blocks": 32}
        traffic.update(buckets=[16, 32, 64], total_len=64,
                       prompt_len={"dist": "loguniform", "lo": 4, "hi": 40},
                       output_len={"dist": "loguniform", "lo": 2, "hi": 16})
        traffic["arrivals"] = dict(traffic["arrivals"], rate_rps=6.0)
        cell["limits"] = {"served_gap_max": 0.0005}
    cell["trace_seconds"] = 0.5
    return common.make_ctx("selfcheck-" + cell_name, cell, config, traffic,
                           seed, seconds, trace, platform="cpu")


def rehearse(cell_name, **kw):
    import importlib

    ctx = tiny_ctx(cell_name, **kw)
    runner = importlib.import_module("benchmarks.runners."
                                     + ctx["cell"]["runner"])
    result = runner.run(ctx)
    shutil.rmtree(ctx["work_dir"], ignore_errors=True)
    print("{}: correct={} attempted={} failed={} checks={}".format(
        cell_name, result["correct"], result["attempted"],
        result["failed"], json.dumps(result["checks"])))
    if not result["correct"]:
        raise SystemExit("selfcheck: {} is not correct at the tiny "
                         "size".format(cell_name))
    return result


def part_resident():
    rehearse("resnet50-resident")


def part_fed():
    rehearse("resnet50-fed")


def part_serving():
    rehearse("gpt2-large-chat")


def part_flops():
    from benchmarks.flops import gpt2, resnet50

    model = common.load_json("configs", "resnet50.json")["model"]
    first = resnet50.conv_layers(model, 224)[0]
    # by hand: 7x7x3x64 kernel over a 112x112 output
    assert first[1:] == (112, 7, 3, 64), first
    macs = resnet50.forward_macs_per_image(model, 224)
    # by hand: the published 4.09 GMAC of ResNet-50 v1.5 at 224 px
    assert abs(macs - 4.09e9) / 4.09e9 < 0.01, macs
    assert resnet50.parameter_count(model) == 25557032, \
        resnet50.parameter_count(model)
    g = common.load_json("configs", "gpt2-large.json")["model"]
    # by hand: 12 * 1280^2 per layer, 1280 * 50257 in the head
    assert gpt2.matmul_params(g) == (19660800, 64328960)
    # one decode token at position 99: 100 keys, 4*h FLOPs per pair/layer
    assert gpt2.attention_flops(g, 1, 99) == 4 * 1280 * 100 * 36
    assert gpt2.attention_kv_bytes(g, 1, 99) == 2 * 100 * 1280 * 4 * 36
    assert gpt2.attention_flops(g, 3) == 4 * 1280 * 6 * 36  # 1+2+3 pairs
    n = gpt2.parameter_count(g)
    assert abs(n - 838e6) / 838e6 < 0.005, n
    print("flops: resnet50 {:.4g} MAC/image forward, {:.4g} FLOP/image "
          "trained; gpt2-large {} parameters".format(
              macs, resnet50.train_flops_per_image(model, 224), n))


def part_trace():
    from benchmarks import trace_reduce

    path = os.path.join(ROOT, "benchmarks", "data", "small_trace.xplane.pb")
    from benchmarks import run as bench_run

    # the recorded trace: 3 rounds of a 4-matmul chain and one paged
    # attention call (16-token blocks, 4 heads of 64) on a v5e, with a
    # 10 ms sleep between them; the committed metric's own patterns pick
    # the call by its pool operands, and no call at another head count
    spec = {"paged": common.load_json("layer_metrics",
                                      "paged_attn_roofline.json")}
    sizes = {"kv_block_size": 16, "num_heads": 4, "head_dim": 64}
    r = trace_reduce.reduce(path, dict(
        bench_run.event_patterns(spec, {"model": sizes}),
        other=bench_run.event_patterns(
            spec, {"model": dict(sizes, num_heads=20)})["paged"]))
    r["matched"]["kernel"] = r["matched"]["paged"]
    assert r["devices"] == 1 and r["matched"]["kernel"]["events"] == 3, r
    assert r["matched"]["other"]["events"] == 0, r["matched"]
    assert 0 < r["busy_s"] < r["window_s"], r
    assert abs(r["busy_s"] - 227.36e-6) < 1e-6, r["busy_s"]
    assert r["device_ops"][0][0].startswith("convolution_tanh_fusion"), r
    assert r["idle_gaps"][0][0] == "$time sleep", r["idle_gaps"]
    assert trace_reduce.op_label(
        "%copy.5 = s32[4,8,1]{2,1,0:T(8,128)S(1)} copy(s32[4,8,1] %x)") \
        == "copy s32[4,8,1]"
    print("trace: reduction of the recorded trace agrees ({} device "
          "events matched the kernel)".format(
              r["matched"]["kernel"]["events"]))


_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
_UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def part_manifest():
    """BENCHMARK.json is what the files give, and inside the contract's
    limits on names, units and lengths."""
    from benchmarks import make_manifest

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    b = json.loads(text)
    assert b == make_manifest.build(), "BENCHMARK.json is stale: run " \
        "python benchmarks/make_manifest.py"
    assert len(text) <= 64 * 1024
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert _NAME.match(w["name"]) and _NAME.match(w["traffic"]), w
        assert 1 <= len(w["why"]) <= 200, (w["name"], len(w["why"]))
        assert w["chips"] in (1, 4)
    for c in b["configs"]:
        assert _NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200, c
        assert 1 <= len(c["source"]) <= 200
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for m in b["end_to_end"] + b["per_layer"]:
        assert _NAME.match(m["name"]) and _UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    assert "setup_s" in e2e and all(
        0 < m["bound"] <= 0.1 for m in b["end_to_end"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e and 1 <= len(m["layer"]) <= 200, m
        for w in m["workloads"]:  # each has to report what it moves
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells), (m["name"], w)
    print("manifest: {} cells, {} end-to-end and {} per-layer metrics, "
          "{} bytes".format(len(cells), len(e2e), len(b["per_layer"]),
                            len(text)))


PARTS = {"flops": part_flops, "trace": part_trace,
         "manifest": part_manifest, "resident": part_resident,
         "fed": part_fed, "serving": part_serving}


def main(argv):
    if os.environ.get("JAX_PLATFORMS", "") != "cpu":
        raise SystemExit("selfcheck is the CPU rehearsal: run it with "
                         "JAX_PLATFORMS=cpu")
    common.fix_compile_cache()
    for name in argv or list(PARTS):
        PARTS[name]()
    print("selfcheck: ok")


if __name__ == "__main__":
    main(sys.argv[1:])
