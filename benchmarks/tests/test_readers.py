"""The readers a per-layer metric names, on dumps made by hand, and the
two metrics of the engine's pauses (``gc_ms_per_step``,
``loop_unstaged_ms_per_step``) on the dump a serving runner makes of a
tiny engine's stages.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import gc
import importlib
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.readers import residual  # noqa: E402

PAUSE_METRICS = ("gc_ms_per_step", "loop_unstaged_ms_per_step")


def _dump(seconds, samples):
    return {"counters": {"engine": {"stage_seconds": seconds,
                                    "stage_samples": samples}}}


SPEC = {"num": "engine.stage_seconds.loop",
        "minus": ["engine.stage_seconds.park",
                  "engine.stage_seconds.decode_step",
                  "engine.stage_seconds.spec_round"],
        "den": "engine.stage_samples.decode_step", "scale": 1000.0}


def test_residual_is_the_stage_less_its_listed_children_per_sample():
    run = _dump({"loop": 2.5, "park": 1.0, "decode_step": 1.25,
                 "step_sync": 1.0}, {"decode_step": 50})
    # spec_round is absent and counts 0; step_sync is not listed
    assert residual.read(SPEC, run) == pytest.approx(0.25 / 50 * 1000.0)
    assert residual.read(dict(SPEC, scale=1.0), run) \
        == pytest.approx(0.25 / 50)


@pytest.mark.parametrize("seconds, samples", [
    ({"park": 1.0}, {"decode_step": 50}),        # no loop stage
    ({"loop": 1.0}, {}),                         # no denominator
    ({"loop": 1.0}, {"decode_step": 0}),         # a denominator of 0
])
def test_residual_reads_nothing_where_there_is_nothing_to_read(seconds,
                                                               samples):
    assert residual.read(SPEC, _dump(seconds, samples)) is None


@pytest.mark.parametrize("name", PAUSE_METRICS)
def test_pause_metric_resolves_to_a_reader_of_the_engine_layer(name):
    spec = common.load_json("layer_metrics", name + ".json")
    assert spec["name"] == name
    assert (spec["unit"], spec["better"], spec["source"], spec["layer"],
            spec["moves"]) == ("ms", "lower", "program_span",
                               "Decode engine", "gap_p50_ms")
    reader = importlib.import_module("benchmarks.readers." + spec["reader"])
    assert callable(reader.read)
    # beside sched_ms_per_step: parts of one turn, over the same samples
    assert spec["den"] == common.load_json(
        "layer_metrics", "sched_ms_per_step.json")["den"]


def test_pause_metrics_read_a_tiny_engines_dump():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.runners import serve_openloop
    from tensorflowonspark_tpu import serving
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    kw = dict(vocab=17, hidden=32, num_heads=4, num_layers=2, max_len=64)
    params = DecoderLM(decode=False, **kw).init(
        jax.random.PRNGKey(7), jnp.zeros((2, 64), jnp.int32))["params"]
    rng = np.random.RandomState(3)
    with serving.DecodeEngine(DecoderLM(decode=True, **kw), params, slots=2,
                              kv_block_size=8, kv_blocks=12) as eng:
        eng.submit([1, 2, 3], 2).result(300)
        snap0 = serve_openloop._timer_snapshot(eng)
        handles = [eng.submit(rng.randint(0, 17, size=5).tolist(), 8)
                   for _ in range(3)]
        gc.collect()
        for h in handles:
            h.result(300)
        snap1 = serve_openloop._timer_snapshot(eng)
    # the runners' difference over the counted stretch
    d = {k: v - snap0["seconds"].get(k, 0.0)
         for k, v in snap1["seconds"].items()}
    n = {k: v - snap0["samples"].get(k, 0)
         for k, v in snap1["samples"].items()}
    run = _dump(d, n)
    read = {}
    for name in PAUSE_METRICS:
        spec = common.load_json("layer_metrics", name + ".json")
        read[name] = importlib.import_module(
            "benchmarks.readers." + spec["reader"]).read(spec, run)
    assert n["gc_full"] >= 1
    assert read["gc_ms_per_step"] >= d["gc_full"] / n["decode_step"] * 1e3
    # the pass holds its stages: what is left is the pass's own time
    assert 0.0 <= read["loop_unstaged_ms_per_step"] \
        < d["loop"] / n["decode_step"] * 1e3
