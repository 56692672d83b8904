"""``laguna-s-2.1-codegen`` rehearsed on the CPU, and its yardsticks.

``selfcheck.tiny_ctx`` gives every serving configuration GPT-2's tiny
sizes, so this file carries its own tiny context, built with
``common.make_ctx`` from the cell's own files with only sizes changed:
the cell's five layers (a dense full one, sliding, sliding, sliding,
full) at a window of 16 positions in blocks of 8 under sequences of up
to 96, 4 and 6 query heads over 2 K/V heads, and HALF the routed
experts held (8 of 16), as the cell holds 128 of 256. The runner
unbroken is ``correct``; the float8 control is not, and neither is a
program that leaves out one mechanism: the head gates, the shared
expert, or turning only half of a full layer's head. The limits here
are the tiny model's: see ``TINY_LIMITS``.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import copy
import dataclasses
import importlib
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import append_manifest, common, make_manifest  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.flops import laguna as flops  # noqa: E402
from benchmarks.runners import serve_tokens  # noqa: E402
from tensorflowonspark_tpu.models import laguna  # noqa: E402
from tensorflowonspark_tpu.models.mellum_moe import (  # noqa: E402
    KindAttention)

CELL = "laguna-s-2.1-codegen"
TINY_MODEL = dict(vocab=211, hidden=64, num_heads=4, sliding_num_heads=6,
                  num_kv_heads=2, head_dim=16, num_layers=5, num_experts=16,
                  experts_per_tok=4, moe_hidden=32, shared_hidden=32,
                  dense_hidden=128, max_len=128, sliding_window=16,
                  yarn_original_max_len=32, first_expert=0, expert_count=8)
TINY_BLOCK = 8
#: the widest gap: between what sound runs read on seeds 3, 4, 5
#: (0.0011, 0.0013, 0.0002) and what the float8 control reads (0.041 at
#: the least of the three; the planted omissions 0.054-0.39): 5.4 times
#: over the one, 5.8 times under the other. The mean gap of the compared
#: tokens: sound runs 1.3e-6-1.4e-5, the control 1.5e-3-2.4e-3, the
#: omissions 3.3e-3 (the shared expert) to 0.097 (the gates)
TINY_LIMITS = {"served_gap_max": 0.007, "served_gap_mean": 1.5e-4}


def tiny_ctx(seed=3, seconds=2.0, trace=False):
    cell, config, traffic = (copy.deepcopy(x)
                             for x in bench_run.load_cell(CELL))
    config["model"].update(TINY_MODEL)
    config["serving"] = {"slots": 4, "kv_block_size": TINY_BLOCK,
                         "kv_blocks": 48}
    traffic.update(buckets=[16, 32, 64], total_len=96,
                   prompt_len={"dist": "loguniform", "lo": 5, "hi": 60},
                   output_len={"dist": "loguniform", "lo": 8, "hi": 30})
    traffic["arrivals"] = dict(traffic["arrivals"], rate_rps=6.0)
    cell["limits"] = dict(TINY_LIMITS)
    cell["trace_seconds"] = 0.5
    return common.make_ctx("tiny-" + CELL, cell, config, traffic, seed,
                           seconds, trace, platform="cpu")


@pytest.fixture
def ctx():
    made = []

    def make(**kw):
        made.append(tiny_ctx(**kw))
        return made[-1]

    yield make
    for c in made:
        shutil.rmtree(c["work_dir"], ignore_errors=True)


def _failed(result):
    return sorted(k for k, (v, limit) in result["checks"].items()
                  if v is None or not v <= limit)


@pytest.fixture(scope="module")
def sound():
    c = tiny_ctx()
    result = serve_tokens.run(c, control="float8")
    shutil.rmtree(c["work_dir"], ignore_errors=True)
    return result


def test_unbroken_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sorted(sound["checks"]) == [
        "compiled_in_window", "requests_failed", "served_gap_max",
        "served_gap_mean"]
    counts = sound["counters"]["engine"]["counts"]
    assert counts["kv_window_blocks_given_back"] > 0


def test_the_float8_control_is_not_correct(sound):
    """The reference with every product's operands rounded to
    ``float8_e4m3fn``, one step below the bfloat16 the configuration
    states, fails the limits by what it would have served."""
    c = sound["counters"]
    assert c["control_gap_max"] > TINY_LIMITS["served_gap_max"]
    assert c["control_gap_mean"] > TINY_LIMITS["served_gap_mean"]
    assert c["compared_tokens"] > 60


class _Ungated(laguna.LagunaLM):
    """``LagunaLM`` traced while ``models/laguna.py`` builds its
    attention without the heads' gates: a class of its own, so that no
    program of the sound model is taken from a cache for it."""


def _without(monkeypatch, **fields):
    """Prefill and step of a model with one mechanism left out (a field
    of ``LagunaLM`` that switches it, or ``gate_heads=False`` for the
    layers' ``KindAttention``), on the same weights."""
    from tensorflowonspark_tpu import generation

    def tamper(engine):
        wrong = engine._model.clone(
            **{k: v for k, v in fields.items() if k != "gate_heads"})
        if "gate_heads" in fields:
            monkeypatch.setattr(laguna, "KindAttention", lambda **kw:
                                KindAttention(**dict(kw, gate_heads=False)))
            wrong = _Ungated(**{f.name: getattr(wrong, f.name)
                                for f in dataclasses.fields(wrong)
                                if f.name not in ("parent", "name")})
        engine._prefill_fn, engine._decode_fn = \
            generation.paged_step_fns(wrong, 0.0, None, None)

    return tamper


@pytest.mark.parametrize("fields", [
    dict(gate_heads=False), dict(shared_hidden=0),
    dict(partial_rotary_factor=1.0)],
    ids=["gate_dropped", "shared_expert_dropped", "rotary_over_the_head"])
def test_a_mechanism_left_out_is_not_correct(ctx, fields, monkeypatch):
    result = serve_tokens.run(ctx(), tamper=_without(monkeypatch, **fields))
    assert not result["correct"]
    assert _failed(result) == ["served_gap_max", "served_gap_mean"], \
        result["checks"]


def test_every_per_layer_name_of_the_cell_resolves():
    cell = common.load_json("cells", CELL + ".json")
    assert len(set(cell["per_layer"])) == len(cell["per_layer"])
    for name in cell["per_layer"]:
        spec = common.load_json("layer_metrics", name + ".json")
        assert spec["name"] == name
        reader = importlib.import_module(
            "benchmarks.readers." + spec["reader"])
        assert callable(reader.read)
        assert spec["moves"] in cell["end_to_end"], (name, spec["moves"])
        if "work_fn" in spec:
            module, _, fn = spec["work_fn"].partition(":")
            assert callable(getattr(importlib.import_module(
                "benchmarks.flops." + module), fn))
    _, config, traffic = bench_run.load_cell(CELL)
    for key in ("reference", "flops"):
        importlib.import_module("benchmarks.{}.{}".format(
            key, config[key]))
    importlib.import_module("benchmarks.generators." + traffic["generator"])
    module, _, name = config["model_class"].partition(":")
    assert hasattr(importlib.import_module(
        "tensorflowonspark_tpu.models." + module), name)


def test_the_traffic_is_one_order_for_every_seed():
    """``open_loop_fixed`` offers the same arrivals and lengths at the
    same moments on every seed (``open_loop``'s sets of gaps and
    lengths, in one order); only the prompts' tokens follow the seed."""
    from benchmarks.generators import open_loop, open_loop_fixed

    _, _, traffic = bench_run.load_cell(CELL)
    seconds = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
        "run_seconds"]
    a, b = (open_loop_fixed.schedule(traffic, s, seconds, 50176)
            for s in (2 ** 31 + 7, 11))

    def shape(reqs):
        return [(r["due_s"], len(r["prompt"]), r["max_new"]) for r in reqs]

    assert shape(a) == shape(b)
    assert a[0]["prompt"] != b[0]["prompt"]
    assert len(a) == round(traffic["arrivals"]["rate_rps"] * seconds)
    assert all(0.0 <= r["due_s"] < seconds for r in a)
    shuffled = open_loop.schedule(traffic, 11, seconds, 50176)
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, shuffled))


def test_the_cell_is_the_published_cut_and_its_traffic():
    cell, config, traffic = bench_run.load_cell(CELL)
    assert (cell["chips"], cell["runner"]) == (1, "serve_tokens")
    assert sorted(cell["limits"]) == ["served_gap_max", "served_gap_mean"]
    assert sorted(cell["limits_why"]) == sorted(cell["limits"])
    assert traffic["generator"] == "open_loop_fixed"
    assert traffic["prompt_len"] == {"dist": "loguniform", "lo": 1024,
                                     "hi": 8192}
    assert traffic["output_len"] == {"dist": "loguniform", "lo": 256,
                                     "hi": 2048}
    assert traffic["total_len"] == 10240
    assert traffic["buckets"] == [1024, 2048, 4096, 8192]
    assert traffic["arrivals"]["rate_rps"] == pytest.approx(
        0.8 * traffic["rate_from"]["knee_rps"])
    serve, model = config["serving"], config["model"]
    assert serve["slots"] == 32
    # the full layers' pool holds every slot at the longest sequence
    assert serve["kv_blocks"] >= 32 * (10240 // serve["kv_block_size"])
    assert config["reduced"] == ["num_hidden_layers", "vocab_size",
                                 "expert_count"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "vocab_size": 100352,
                                   "expert_count": 256}
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["expert_count"], config["first_expert"]) == \
        (model["num_layers"], model["vocab"], model["expert_count"],
         model["first_expert"]) == (5, 50176, 128, 0)
    assert (model["hidden"], model["num_heads"], model["sliding_num_heads"],
            model["num_kv_heads"], model["head_dim"], model["num_experts"],
            model["experts_per_tok"], model["moe_hidden"],
            model["shared_hidden"], model["dense_hidden"],
            model["sliding_window"], model["routed_scale"]) == (
        config["hidden_size"], config["num_attention_heads"], 72,
        config["num_key_value_heads"], config["head_dim"],
        config["num_experts"], config["num_experts_per_tok"],
        config["moe_intermediate_size"],
        config["shared_expert_intermediate_size"],
        config["intermediate_size"], config["sliding_window"],
        config["moe_routed_scaling_factor"])
    kinds = {"sliding_attention": "sliding", "full_attention": "full"}
    assert model["layer_types"] == [kinds[k]
                                    for k in config["layer_types"][:5]]
    assert [model["sliding_num_heads"] if k == "sliding"
            else model["num_heads"] for k in model["layer_types"]] \
        == config["num_attention_heads_per_layer"][:5]
    assert model["mlp_only_layers"] == config["mlp_only_layers"]
    rope = config["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert (model["rope_theta"], model["yarn_factor"],
            model["yarn_original_max_len"], model["yarn_beta_fast"],
            model["yarn_beta_slow"], model["yarn_attention_factor"],
            model["partial_rotary_factor"], model["sliding_rope_theta"]) \
        == (full["rope_theta"], full["factor"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"], full["attention_factor"],
            full["partial_rotary_factor"], sliding["rope_theta"])
    assert sliding["partial_rotary_factor"] == 1


def test_counter_metrics_of_the_cell_read_from_a_tiny_run(sound):
    cell, config, _ = bench_run.load_cell(CELL)
    specs = {n: s for n, s in bench_run.layer_specs(cell).items()
             if s["reader"] in ("ratio", "counter", "mfu")}
    peaks = {"flops_per_s": 1.0, "bytes_per_s": 1.0}
    read = bench_run.per_layer_metrics(specs, cell, config, sound, None,
                                       peaks)
    assert sorted(read) == sorted(specs), sorted(set(specs) - set(read))
    counts = sound["counters"]["engine"]["counts"]
    # four sparse layers of five route, 4 experts a live row; half the
    # experts are held here
    assert counts["expert_rows_routed"] % (4 * 4) == 0
    assert counts["expert_rows"] < counts["expert_rows_routed"]
    assert 30.0 < read["expert_held_share"]["value"] < 70.0
    assert read["expert_load_peak"]["value"] >= 1.0
    assert 0 < read["kv_window_share"]["value"] < 100.0


def test_the_expert_kernels_roofline_reads_the_held_share():
    """The grouped product's least time from the run's held counters:
    rows and experts touched of the HELD experts only, so the share is
    the kernel's own work, not twice it."""
    from benchmarks.flops import sdar_moe
    from benchmarks.readers import kernel_roofline

    _, config, _ = bench_run.load_cell(CELL)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    spec = common.load_json("layer_metrics", "expert_gmm_roofline.json")
    # 32 rows a step: 160 held assignments over 4 layers, 92 experts
    work = {"expert_calls": 4, "expert_rows": 4 * 160,
            "experts_touched": 4 * 92}
    least, bound = sdar_moe.expert_gmm_least_seconds(
        config["model"], work, peaks)
    assert bound == "bytes"
    # 368 experts' three matrices of 3072 x 1024 bfloat16: 6.9 GB
    assert 8.45e-3 < least < 8.55e-3
    run = {"trace": {"matched": {spec["name"]: {"seconds": 2 * least,
                                                "events": 12}}},
           "counters": {"traced_work": work}, "config": config,
           "peaks": peaks}
    assert kernel_roofline.read(spec, run) == pytest.approx(50.0)


def test_the_paged_kernels_roofline_reads_the_traced_work():
    """The share from a made-up trace: the least time of the decode
    positions (both kinds of layer, each by its visible keys) over the
    kernel's seconds, nothing where no event matched."""
    from benchmarks.readers import kernel_roofline

    _, config, _ = bench_run.load_cell(CELL)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    work = {"prefills": [4096], "decode_positions": [300, 5000] * 40}
    name = "paged_attn_roofline.laguna"
    spec = common.load_json("layer_metrics", name + ".json")
    least, bound = flops.paged_attention_least_seconds(
        config["model"], work, peaks)
    assert bound == "bytes"
    run = {"trace": {"matched": {name: {"seconds": 4 * least,
                                        "events": 21}}},
           "counters": {"traced_work": work}, "config": config,
           "peaks": peaks}
    assert kernel_roofline.read(spec, run) == pytest.approx(25.0)
    run["trace"]["matched"] = {}
    assert kernel_roofline.read(spec, run) is None


def test_the_held_share_reads_nothing_where_nothing_is_routed():
    """``expert_held_share`` over a program that counts no routed rows
    (one without ``expert_rows_routed``, as before this cell) is left
    out, not 0 and not an error."""
    from benchmarks.readers import ratio

    spec = common.load_json("layer_metrics", "expert_held_share.json")
    counts = {"expert_rows": 50}
    run = {"counters": {"engine": {"counts": counts}}}
    assert ratio.read(spec, run) is None
    counts["expert_rows_routed"] = 100
    assert ratio.read(spec, run) == pytest.approx(50.0)


def test_flop_and_byte_functions_against_a_hand_count():
    """The published widths, five layers, counted by hand: a full
    layer's attention 44,187,648 (Wq and Wo 18,874,368 each at 48 heads,
    Wk and Wv 3,145,728 each, the gate 147,456), a sliding one's
    63,135,744 (72 heads, gate 221,184), QK-norm 256 and
    two norms 6,144 each; the dense MLP 113,246,208; a sparse layer's
    router 786,432, shared expert 9,437,184 and 128 held experts of
    9,437,184; embedding and head 2 x 50,176 x 3,072."""
    _, config, _ = bench_run.load_cell(CELL)
    m = config["model"]
    full_attn, slide_attn = 44187648, 63135744
    assert flops.attention_params(m, "full") == full_attn
    assert flops.attention_params(m, "sliding") == slide_attn
    expert = 3 * 3072 * 1024
    sparse = 786432 + expert
    norms = 256 + 6144
    total = (full_attn + norms + 3 * 3072 * 12288) \
        + 3 * (slide_attn + norms + sparse + 128 * expert) \
        + (full_attn + norms + sparse + 128 * expert) \
        + 3072 + 2 * 50176 * 3072
    assert flops.parameter_count(m) == total
    # 11.14 GB of bfloat16
    assert 11.13e9 < 2 * total < 11.15e9
    # a sparse position multiplies 5 held assignments in expectation
    assert flops.feed_forward_params(m, 1) == sparse + 5 * expert
    per_position = 2 * (2 * full_attn + 3 * slide_attn
                        + 3 * 3072 * 12288 + 4 * (sparse + 5 * expert))
    assert flops.position_flops(m, head=False) == per_position
    assert flops.position_flops(m) == per_position + 2 * 3072 * 50176
    # a query at 5,000 sees 5,001 keys in a full layer (48 heads), 512
    # in a sliding one (72 heads)
    assert flops.step_token_flops(m, 5000) == flops.position_flops(m) \
        + 4 * 128 * (2 * 48 * 5001 + 3 * 72 * 512)
    assert flops.sequence_flops(m, 3, 2) == 4 * per_position \
        + 2 * 2 * 3072 * 50176 \
        + 4 * 128 * (2 * 48 + 3 * 72) * (1 + 2 + 3 + 4)
    # K and V of a token and layer: 2 x 8 heads x 128 x 2 bytes
    assert flops.attention_least_bytes(m, 5000) == \
        4096 * (2 * 5001 + 3 * 512)


def test_manifest_holds_the_cell_and_its_metrics():
    """``BENCHMARK.json`` is what ``append_manifest.py`` gives: the cell,
    its configuration and its two new metrics are in it, and the
    accepted metrics it reports list it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    held, built = json.loads(text), make_manifest.build()
    assert text == json.dumps(
        append_manifest.in_held_order(built, held), indent=1) + "\n"
    assert len(text.encode()) < 64 * 1024
    assert CELL in [w["name"] for w in held["workloads"]]
    assert "laguna-s-2.1" in [c["name"] for c in held["configs"]]
    metrics = {m["name"]: m for m in held["per_layer"]}
    cell = common.load_json("cells", CELL + ".json")
    for name in cell["per_layer"]:
        assert CELL in metrics[name]["workloads"], name
    assert metrics["expert_held_share"]["workloads"] == [CELL]
    assert metrics["paged_attn_roofline.laguna"]["workloads"] == [CELL]
