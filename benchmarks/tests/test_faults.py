"""The comparison that decides ``correct`` has been shown to fail.

Each test drives a runner as ``run.py`` does, at the selfcheck's tiny
sizes on the CPU (the look for a chip is skipped by asking for the
``cpu`` platform), with the timed path broken underneath, and sees
``correct`` come out false - once for each fault a one-chip cell can
have. The unbroken runs come out true.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import selfcheck  # noqa: E402
from benchmarks.runners import serve_openloop, train_resident  # noqa: E402


@pytest.fixture
def ctx():
    made = []

    def make(cell, **kw):
        c = selfcheck.tiny_ctx(cell, **kw)
        made.append(c)
        return c

    yield make
    for c in made:
        shutil.rmtree(c["work_dir"], ignore_errors=True)


def _failed(result):
    return sorted(k for k, (v, limit) in result["checks"].items()
                  if v is None or not v <= limit)


def test_resident_unbroken_is_correct(ctx):
    result = train_resident.run(ctx("resnet50-resident"))
    assert result["correct"], result["checks"]


def test_state_returned_unchanged_is_not_correct(ctx):
    import jax
    import jax.numpy as jnp

    def broken(step):
        def same_state(state, batch):
            _, metrics = step(jax.tree.map(jnp.copy, state), batch)
            return state, metrics
        return same_state

    result = train_resident.run(ctx("resnet50-resident"), broken=broken)
    assert not result["correct"]
    # nothing moved: both norms read 0 against the reference's, gap 1
    assert {"grad_gap", "change_gap", "steps_missing"} <= set(
        _failed(result)), result["checks"]
    assert result["checks"]["change_gap"][0] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(ctx):
    def broken(step):
        def half(state, batch):
            n = batch["x"].shape[0] // 2
            return step(state, {k: v[:n] for k, v in batch.items()})
        return half

    result = train_resident.run(ctx("resnet50-resident"), broken=broken)
    assert not result["correct"]
    assert "grad_gap" in _failed(result), result["checks"]


def test_serving_unbroken_is_correct(ctx):
    result = serve_openloop.run(ctx("gpt2-large-chat"))
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0


def test_token_altered_where_it_is_produced_is_not_correct(ctx):
    c = ctx("gpt2-large-chat")
    vocab = c["config"]["model"]["vocab"]

    def tamper(engine):
        deliver, seen = engine._deliver, [0]

        def altered(slot, token):
            seen[0] += 1
            deliver(slot, (token + 1) % vocab if seen[0] % 5 == 0
                    else token)

        engine._deliver = altered

    result = serve_openloop.run(c, tamper=tamper)
    assert not result["correct"]
    assert _failed(result) == ["served_gap_max"], result["checks"]
