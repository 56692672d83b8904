"""The control comes out as not correct: the plain reference, put in
the program's place and computed one precision below what the
configuration states (``control_precision`` of the configuration's
file), fails at least one of the numbers compared. Kept here at a size
a test run can hold; the readings at the cells' own sizes, on the chip,
are in PERF.md.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import selfcheck  # noqa: E402


def test_training_control_is_not_correct():
    import jax

    from benchmarks.generators import image_records as gen
    from benchmarks.reference import resnet50 as ref
    from benchmarks.runners import train_common

    ctx = selfcheck.tiny_ctx("resnet50-resident", seed=11)
    cfg, traffic = ctx["config"], ctx["traffic"]
    batches = gen.resident_batches(traffic, ctx["seed"])
    key = jax.random.PRNGKey(ctx["seed"])
    make = jax.jit(lambda k: ref.init_params(k, cfg["model"]))
    sides = {p: ref.follow(make(key), batches, cfg["model"],
                           cfg["optimizer"], precision=p)
             for p in ("float32", cfg["compute_dtype"],
                       cfg["control_precision"])}
    cell = ctx["cell"]
    stated, _ = train_common.judge(
        train_common.gaps(sides[cfg["compute_dtype"]], sides["float32"]),
        cell["limits"], cell["not_compared"])
    control, _ = train_common.judge(
        train_common.gaps(sides[cfg["control_precision"]],
                          sides["float32"]),
        cell["limits"], cell["not_compared"])
    assert stated.ok, stated.as_dict()
    assert not control.ok, control.as_dict()


def test_serving_control_is_not_correct():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import gpt2 as ref

    ctx = selfcheck.tiny_ctx("gpt2-large-chat", seed=11)
    cfg = ctx["config"]
    model = cfg["model"]
    params = jax.jit(lambda k: ref.init_params(k, model))(
        jax.random.PRNGKey(11))
    rng = np.random.RandomState(11)
    worst_ref = worst_control = 0.0
    for _ in range(8):
        prompt = rng.randint(0, model["vocab"], size=24).tolist()
        # greedy tokens as the float32 reference itself would serve them
        served = []
        for _ in range(16):
            z = ref.logits(params, jnp.asarray(prompt + served), model)
            served.append(int(jnp.argmax(z[-1])))
        g = ref.served_gaps(params, prompt, served, model,
                            model["max_len"], cfg["control_precision"])
        worst_ref = max(worst_ref, float(g["served"].max()))
        worst_control = max(worst_control, float(g["control"].max()))
    limit = ctx["cell"]["limits"]["served_gap_max"]
    assert worst_ref <= limit
    assert worst_control > limit, worst_control


def test_a_number_with_no_limit_is_an_error_not_a_pass():
    import pytest

    from benchmarks.runners import train_common

    numbers = {"loss_gap.1": 0.5, "loss_gap.2": 0.001}
    checks, left_out = train_common.judge(numbers, {"loss_gap.2": 0.01},
                                          ["loss_gap.1"])
    assert checks.ok and left_out == {"loss_gap.1": 0.5}
    with pytest.raises(KeyError):  # a deleted key weakens nothing unseen
        train_common.judge(numbers, {}, ["loss_gap.1"])
