"""``sdar-30b-a3b-blockgen`` rehearsed on the CPU, and its yardsticks.

``selfcheck.tiny_ctx`` gives every serving configuration GPT-2's tiny
sizes, so this file carries its own tiny context, built with
``common.make_ctx`` from the cell's own files with only sizes changed.
The runner unbroken is ``correct``; a token altered where it is
delivered, a commit pass skipped, the unmasking rule turned round and
the float8 control are not. The limits here are the tiny model's (its
logits are small), each between what the sound runs read on three
seeds (3, 4, 5; about 200 positions compared a run) and what a fault
or the control reads, a factor of two and a half and more from both:
``served_gap_max`` 0.0012 at most (sound, or the rule turned round)
against the control's 0.022 at the least; ``order_gap_mean`` 0.0000045
at most against the control's 0.0010 and the turned rule's 0.0124.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import copy
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
from benchmarks.flops import sdar_moe as flops  # noqa: E402
from benchmarks.runners import serve_blockdiff  # noqa: E402

CELL = "sdar-30b-a3b-blockgen"
TINY_MODEL = dict(vocab=211, hidden=64, num_heads=8, num_kv_heads=2,
                  head_dim=16, num_layers=2, num_experts=16,
                  experts_per_tok=4, moe_hidden=32, max_len=128,
                  mask_token_id=210, expert_count=16)
TINY_LIMITS = {"served_gap_max": 0.004, "order_gap_mean": 0.0004}


def tiny_ctx(seed=3, seconds=2.0, trace=False):
    cell, config, traffic = (copy.deepcopy(x)
                             for x in bench_run.load_cell(CELL))
    config["model"].update(TINY_MODEL)
    config["serving"] = {"slots": 4, "kv_block_size": 8, "kv_blocks": 48}
    traffic.update(buckets=[16, 32, 64], total_len=96,
                   prompt_len={"dist": "loguniform", "lo": 5, "hi": 60},
                   output_len={"dist": "loguniform", "lo": 18, "hi": 18})
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
    result = serve_blockdiff.run(c, control="float8")
    shutil.rmtree(c["work_dir"], ignore_errors=True)
    return result


def test_unbroken_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert sorted(sound["checks"]) == [
        "compiled_in_window", "order_gap_mean", "requests_failed",
        "served_gap_max"]
    # named in the cell's file as not compared: printed, not judged
    assert list(sound["counters"]["not_compared"]) == ["order_gap_max"]


def test_the_float8_control_is_not_correct(sound):
    """The reference with every product's operands rounded to
    ``float8_e4m3fn``, one step below the bfloat16 the configuration
    states, fails a limit by what it would have served."""
    c = sound["counters"]
    assert c["control_gap_max"] > TINY_LIMITS["served_gap_max"] \
        or c["control_order_gap_mean"] > TINY_LIMITS["order_gap_mean"]
    assert c["compared_tokens"] > 150


def test_a_gap_is_a_delivery_gap_over_its_tokens(sound):
    """128 tokens in blocks of 4 are 32 deliveries: the gaps counted are
    the deliveries' gaps, so their median is a step's worth times
    passes per token and never the zero between tokens of one block."""
    assert sound["end_to_end"]["gap_p50_ms"] > 0.5
    counts = sound["counters"]["engine"]["counts"]
    assert counts["tokens_unmasked"] == 18 * sound["attempted"]


def test_token_altered_where_it_is_delivered_is_not_correct(ctx):
    c = ctx()
    vocab = c["config"]["model"]["mask_token_id"]

    def tamper(engine):
        deliver, seen = engine._deliver, [0]

        def altered(slot, token, block=None):
            seen[0] += 1
            if block is not None and seen[0] % 3 == 0:
                tokens, passes = block
                block = ([(tokens[0] + 1) % vocab] + tokens[1:], passes)
            deliver(slot, token, block=block)

        engine._deliver = altered

    result = serve_blockdiff.run(c, tamper=tamper)
    assert not result["correct"]
    assert "served_gap_max" in _failed(result), result["checks"]


def test_commit_pass_skipped_is_not_correct(ctx):
    """The commit feeds what the last denoising pass fed (the position
    it unmasked still MASK), so the block's K/V stay as that pass wrote
    them: every later block reads a stale key and value."""
    def tamper(engine):
        feed = engine._block_feed

        def stale():
            tokens = feed()
            last = engine._blk_when == (engine._blk_pass - 1)[:, None]
            commit = ~engine._blk_masked.any(axis=1)
            tokens[commit[:, None] & last] = engine._model.mask_token_id
            return tokens

        engine._block_feed = stale

    result = serve_blockdiff.run(ctx(), tamper=tamper)
    assert not result["correct"]
    assert set(_failed(result)) & {"served_gap_max", "order_gap_mean"}, \
        result["checks"]


def test_least_confident_position_unmasked_is_not_correct(ctx):
    """The rule turned round (the least confident masked position goes
    first): every token is still the model's best at its position, so
    only the order's gap shows it."""
    import types

    from tensorflowonspark_tpu import generation

    def tamper(engine):
        fake = types.SimpleNamespace(**vars(generation))
        fake.unmask = lambda conf, masked, quota, threshold: \
            generation.unmask(1.0 - conf, masked, quota, 2.0)
        engine._generation = fake

    result = serve_blockdiff.run(ctx(), tamper=tamper)
    assert not result["correct"]
    assert _failed(result) == ["order_gap_mean"], result["checks"]


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


def test_counter_metrics_of_the_cell_read_from_a_tiny_run(sound):
    cell, config, _ = bench_run.load_cell(CELL)
    specs = {n: s for n, s in bench_run.layer_specs(cell).items()
             if s["reader"] in ("ratio", "counter", "mfu")}
    peaks = {"flops_per_s": 1.0, "bytes_per_s": 1.0}
    read = bench_run.per_layer_metrics(specs, cell, config, sound, None,
                                       peaks)
    assert sorted(read) == sorted(specs), sorted(set(specs) - set(read))
    assert 0.5 < read["tokens_per_row_pass"]["value"] <= 0.8
    assert 20.0 <= read["commit_pass_share"]["value"] < 30.0
    assert read["expert_load_peak"]["value"] >= 1.0
    parts = read["step_dispatch_ms"]["value"] + read["step_sync_ms"]["value"]
    assert 0.9 * read["decode_step_ms"]["value"] < parts \
        <= read["decode_step_ms"]["value"]


def test_kernel_metrics_read_the_traced_work():
    """Both rooflines from a made-up trace: the least time of the work
    over the kernel's seconds, nothing where no event matched."""
    from benchmarks.readers import kernel_roofline

    _, config, _ = bench_run.load_cell(CELL)
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    work = {"prefills": [512], "pass_starts": [256] * 160,
            "expert_calls": 7, "experts_touched": 7 * 128,
            "expert_rows": 7 * 1024}
    for name, fn in [("expert_gmm_roofline", flops.expert_gmm_least_seconds),
                     ("paged_attn_roofline.gqa",
                      flops.paged_attention_least_seconds)]:
        spec = common.load_json("layer_metrics", name + ".json")
        least, bound = fn(config["model"], work, peaks)
        assert bound == "bytes"
        run = {"trace": {"matched": {name: {"seconds": 2 * least,
                                            "events": 21}}},
               "counters": {"traced_work": work}, "config": config,
               "peaks": peaks}
        assert kernel_roofline.read(spec, run) == pytest.approx(50.0)
        run["trace"]["matched"] = {}
        assert kernel_roofline.read(spec, run) is None


def test_flop_and_byte_functions_against_a_hand_count():
    """The published widths, seven layers, counted by hand (ISSUE 31):
    a layer's attention 18,874,368, router 262,144, experts 128 x
    4,718,592; embedding and head 2 x 311,164,928."""
    _, config, _ = bench_run.load_cell(CELL)
    m = config["model"]
    assert flops.parameter_count(m) == 4984176384
    assert flops.layer_matmul_params(m) == 18874368 + 262144 + 8 * 4718592
    per_position = 2 * 7 * 56885248 + 2 * 311164928
    assert flops.position_flops(m) == per_position == 1418723328
    assert flops.position_flops(m, head=False) == 2 * 7 * 56885248
    # a pass over the block at 256: 4 positions, each seeing 260 keys,
    # 4 * 4096 FLOPs a pair and layer
    assert flops.block_pass_flops(m, 256) == \
        4 * per_position + 4 * 4096 * 7 * 4 * 260
    # a prefill of 8 tokens: blocks of 4 seeing 4 and 8 keys
    assert flops.prefill_flops(m, 8) == \
        8 * 2 * 7 * 56885248 + 4 * 4096 * 7 * (4 * 4 + 4 * 8)
    assert flops.kv_bytes(m, 260) == 2 * 260 * 512 * 2 * 7
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    # one call with every expert touched by 1,024 rows: 128 x 3 x
    # 2048 x 768 weights and 1,024 x 3 x (2048 + 768) activations, at 2
    # bytes; 1,024 x 3 x 2 x 2048 x 768 FLOPs
    least, bound = flops.expert_gmm_least_seconds(
        m, {"experts_touched": 128, "expert_rows": 1024}, peaks)
    nbytes = 2 * (128 * 3 * 2048 * 768 + 1024 * 3 * (2048 + 768))
    assert bound == "bytes" and least == pytest.approx(nbytes / 819e9)
    assert 1024 * 6 * 2048 * 768 / 197e12 < least
    least, bound = flops.paged_attention_least_seconds(
        m, {"prefills": [], "pass_starts": [256, 0]}, peaks)
    assert bound == "bytes"
    assert least == pytest.approx(
        (flops.kv_bytes(m, 260) + flops.kv_bytes(m, 4)) / 819e9)


def test_manifest_holds_what_the_files_give():
    """``BENCHMARK.json`` holds, entry for entry and by name, what
    ``make_manifest.build()`` gives from the files - none missing, none
    more, none altered - in whatever order it has
    (``append_manifest.py`` keeps the accepted order and puts what a PR
    adds last; ``make_manifest.py`` sorts); it is under 64 KB and every
    ``why`` fits 200 characters. No entry's place is pinned here: the
    next cell or metric adds files and runs the script again."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        text = f.read()
    held, built = json.loads(text), make_manifest.build()
    assert len(text.encode()) < 64 * 1024
    assert text == json.dumps(
        append_manifest.in_held_order(built, held), indent=1) + "\n"
    for entry in held["configs"] + held["workloads"]:
        assert 0 < len(entry["why"]) <= 200, entry["name"]
    assert CELL in [w["name"] for w in held["workloads"]]


def test_append_manifest_keeps_the_held_order_and_puts_the_rest_last():
    """From a manifest that lacks the cell and the metric that sort
    FIRST and holds the others the other way round: what was there
    stays where it was, what the files add comes after it (in a
    metric's ``workloads`` too), and every entry is ``build()``'s."""
    built = make_manifest.build()
    held = copy.deepcopy(built)
    cell, metric = built["workloads"][0]["name"], built["per_layer"][0]["name"]
    held["workloads"] = [w for w in held["workloads"][::-1]
                         if w["name"] != cell]
    held["per_layer"] = [m for m in held["per_layer"][::-1]
                         if m["name"] != metric]
    for m in held["per_layer"]:
        m["workloads"] = [w for w in m["workloads"] if w != cell]
    out = append_manifest.in_held_order(built, held)
    for key, added in (("workloads", cell), ("per_layer", metric)):
        assert [e["name"] for e in out[key]] == \
            [e["name"] for e in held[key]] + [added]
    for m in out["per_layer"]:
        want = {e["name"]: e for e in built["per_layer"]}[m["name"]]
        assert sorted(m["workloads"]) == sorted(want["workloads"])
        assert dict(m, workloads=None) == dict(want, workloads=None)
        if cell in m["workloads"] and len(m["workloads"]) > 1:
            assert m["workloads"][-1] == cell
    assert out["configs"] == built["configs"]

