"""Laguna (models/laguna.py) against its plain reference
(benchmarks/reference/laguna.py), at small sizes on seeded random
weights: the forward pass with a dense first layer, a shared expert, a
routed scale, per-head gates, partial rotary on the full layers and
query head counts (hence groups) that differ by layer kind; prefill
then paged decode through both kinds of cache, by hand and through
``serving.DecodeEngine``; the chip's share of the experts; and the
engine's expert counters over a share.

Tolerances. The reference is float32 at ``highest`` precision on the
bfloat16-rounded weights. A float32 instance of the model differs from
it only by the order of float32 sums: logits of size 4 (the weights
are eight times the seed's, so that a wrong mask or a missing term
moves a logit by tenths) agree to ``F32_TOL``. A bfloat16 instance
rounds every activation to 8 bits of mantissa, a few roundings in a
row: ``BF16_TOL`` is a sixth of the logits' own spread (about 0.93),
held by the MEDIAN position's worst logit. Not by every position's: a
position whose top-3 of 8 experts is a near tie under the rounding
takes another expert, whose output at a routed scale of 2.5 moves that
position's logits by 0.3-0.8 as a whole (one position in six on these
seeds). A mechanism left out has to move some logit by more than
``BF16_TOL`` in the float32 model, whose comparison holds to
``F32_TOL``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import laguna as ref
from tensorflowonspark_tpu import generation, paging, serving, tracing
from tensorflowonspark_tpu.models import laguna
from tensorflowonspark_tpu.models.mellum_moe import KindAttention

F32_TOL = 5e-5
BF16_TOL = 0.15
WINDOW, BLOCK = 8, 4

#: a dense full layer, then sliding, sliding, sliding, full (a whole
#: period after the dense one); 4 query heads on a full layer and 6 on a
#: sliding one over 2 K/V heads (groups 2 and 3); half of each full
#: head turned; a window of 8 under sequences of 40; the YaRN ramp
#: placed where tiny positions reach it
TINY = dict(vocab=97, hidden=32, num_heads=4, sliding_num_heads=6,
            num_kv_heads=2, head_dim=8, num_layers=5, num_experts=8,
            experts_per_tok=3, moe_hidden=16, shared_hidden=16,
            routed_scale=2.5, dense_hidden=48, mlp_only_layers=[0],
            rms_eps=1e-6, max_len=64,
            layer_types=["full", "sliding", "sliding", "sliding"],
            sliding_window=WINDOW, sliding_rope_theta=1e4, rope_theta=5e5,
            partial_rotary_factor=0.5, yarn_factor=16.0,
            yarn_original_max_len=16, yarn_beta_fast=32.0,
            yarn_beta_slow=1.0, yarn_attention_factor=1.2772588722239782)


def _weights(seed=0, model=TINY, dtype=jnp.float32):
    params = ref.init_params(jax.random.PRNGKey(seed), model)
    params = jax.tree.map(
        lambda a: (a.astype(jnp.float32) * (8 if a.ndim > 1 else 1))
        .astype(jnp.bfloat16), params)
    return params, jax.tree.map(lambda a: a.astype(dtype), params)


def _fields(model=TINY, **kw):
    fields = dict(model, **kw)
    return {k: tuple(v) if isinstance(v, list) else v
            for k, v in fields.items()}


def _model(dtype=jnp.float32, model=TINY, **kw):
    return laguna.LagunaLM(dtype=dtype, prefill_chunk=8,
                           **_fields(model, **kw))


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    """The reference attends ``Q_BLOCK`` queries at once and wants longer
    sequences to be a multiple of it."""
    monkeypatch.setattr(ref, "Q_BLOCK", 8)


def _ref_logits(params, tokens, model=TINY, rows=None):
    padded = list(tokens) + [0] * (-len(tokens) % 8)
    rows = list(range(len(tokens))) if rows is None else rows
    return np.asarray(ref.logits(params, padded, model, rows))


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, 97, size=n)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_is_the_reference_forward(dtype):
    """Five layers over a sequence five windows long: the dense layer,
    both masks, both RoPEs (YaRN over half of a full head), both head
    counts, the gates, QK-norm, the routed experts and the shared one,
    every logit (a bfloat16 model: the median position's; see the
    module's docstring)."""
    params, cast = _weights(dtype=dtype)
    tokens = _tokens(1, 43)
    got = _model(dtype).apply({"params": cast}, jnp.asarray(tokens)[None])[0]
    want = _ref_logits(params, tokens.tolist())
    assert np.abs(want).max() > 1.0
    worst = np.abs(np.asarray(got) - want).max(axis=-1)
    if dtype == jnp.float32:
        assert worst.max() < F32_TOL
    else:
        assert np.median(worst) < BF16_TOL


def test_the_parameters_are_the_references_layout():
    """What ``LagunaLM.init`` declares is what the reference's weights
    hold, shape for shape: each kind's head count in Wq, Wo and the
    gate, a dense ``mlp`` in layer 0 and a router, experts and a shared
    expert in every other."""
    _, cast = _weights()
    declared = _model().init(jax.random.PRNGKey(0),
                             jnp.zeros((1, 8), jnp.int32))["params"]
    shapes = jax.tree.map(lambda a: a.shape, declared)
    assert shapes == jax.tree.map(lambda a: a.shape, cast)
    assert shapes["layer_0"]["attn"]["w_gate"] == (32, 4)
    assert shapes["layer_1"]["attn"]["wq"] == (32, 6 * 8)
    assert "mlp" in shapes["layer_0"] and "moe" not in shapes["layer_0"]
    assert shapes["layer_2"]["moe"]["shared"]["down"] == (16, 32)


def ungated(monkeypatch):
    """Build every ``KindAttention`` of ``models/laguna.py`` without its
    heads' gates, for as long as the test runs."""
    def attention(**fields):
        return KindAttention(**dict(fields, gate_heads=False))

    monkeypatch.setattr(laguna, "KindAttention", attention)


@pytest.mark.parametrize("left_out", [
    "gate", dict(partial_rotary_factor=1.0),
    dict(shared_hidden=0), dict(routed_scale=1.0),
    dict(mlp_only_layers=[])],
    ids=["gate", "partial_rotary", "shared_expert", "routed_scale",
         "dense_layer"])
def test_each_mechanism_left_out_moves_the_logits(left_out, monkeypatch):
    """The model with one mechanism taken away (the gates, turning only
    half of a full head, the shared expert, the routed scale, the dense
    first layer made sparse) against the reference of the whole model:
    some logit moves by more than ``BF16_TOL``."""
    if left_out == "gate":
        ungated(monkeypatch)
        left_out = {}
    params, _ = _weights()
    tokens = _tokens(2, 32)
    want = _ref_logits(params, tokens.tolist())
    other = dict(TINY, **left_out)
    _, cast = _weights(model=other)
    got = np.asarray(_model(model=other).apply(
        {"params": cast}, jnp.asarray(tokens)[None])[0])
    assert np.abs(got - want).max() > BF16_TOL


def test_the_groups_differ_by_kind_in_the_paged_kernel():
    """A step's queries at both kinds' widths fold into whole groups of
    their K/V heads: 6 over 2 (groups of 3) in a window layer and 4 over
    2 (groups of 2) in a full one, the kernel against its gather
    oracle."""
    import importlib

    pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")
    rng = np.random.RandomState(4)
    b, kv, d, bs, mb = 2, 2, 8, 4, 6
    k_pool = jnp.asarray(rng.randn(1 + b * mb, bs, kv * d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(1 + b * mb, bs, kv * d), jnp.float32)
    table = 1 + np.arange(b * mb).reshape(b, mb)
    pos = np.array([[5], [19]])
    for heads, window in ((6, WINDOW), (4, None)):
        q = jnp.asarray(rng.randn(b, 1, heads, d), jnp.float32)
        want = pa.paged_attention(q, k_pool, v_pool, table, pos,
                                  impl="gather", window=window)
        got = pa.paged_attention(q, k_pool, v_pool, table, pos,
                                 impl="pallas", interpret=True,
                                 window=window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


# -- prefill then paged decode -------------------------------------------


def _by_hand(model, cast, prompt, steps, total=48):
    """Prefill ``prompt`` and decode ``steps`` tokens of the model's own
    choice through the paged caches, the host's part done by
    ``paging.CacheKinds`` as the engine does it. Returns ``(logits of
    every call [1 + steps, vocab], tokens fed)``."""
    width = total // BLOCK
    kv = paging.CacheKinds(1, width, BLOCK, width, model.cache_kinds)
    dec = model.clone(decode=True, kv_block_size=BLOCK, kv_blocks=width + 1,
                      kv_window_blocks=kv.kinds[1].pool.num_blocks + 1)
    cache = generation.init_cache(dec, 1, total)
    n = len(prompt)
    for kind in kv:
        kind.admit(0, n)
    toks = np.zeros(-(-n // 8) * 8, np.int32)
    toks[:n] = prompt
    logits, upd = dec.apply(
        {"params": cast,
         "cache": generation._slot_view(dec, cache, kv.tables[0], 0)},
        jnp.asarray(toks)[None], last=jnp.asarray([n - 1]),
        mutable=["cache", "intermediates"])
    cache = upd["cache"]
    out, fed = [np.asarray(logits[0, 0])], []
    for i in range(steps):
        cursor = n + i
        token = int(np.argmax(out[-1]))
        for kind in kv:
            kind.trim(0, cursor)
            kind.grow(0, cursor // BLOCK)
        stepped = generation._set_paged_leaves(
            dec, cache, jnp.asarray([cursor]), jnp.asarray(kv.tables))
        logits, upd = dec.apply({"params": cast, "cache": stepped},
                                jnp.asarray([[token]]),
                                mutable=["cache", "intermediates"])
        cache = upd["cache"]
        out.append(np.asarray(logits[0, 0]))
        fed.append(token)
        # the dense layer sows nothing: four sparse layers route
        assert len(jax.tree.leaves(upd["intermediates"])) == 4
    return np.stack(out), fed


@pytest.mark.parametrize("prompt_len,steps", [(5, 9), (21, 6), (40, 5)],
                         ids=["short", "longer_than_window", "five_windows"])
def test_prefill_then_decode_through_both_caches_is_the_full_forward(
        prompt_len, steps):
    """Logits of the prefill's last position and of every decode step
    against the reference's full forward over the finished sequence."""
    params, cast = _weights(seed=prompt_len)
    prompt = _tokens(prompt_len, prompt_len).tolist()
    got, fed = _by_hand(_model(), cast, prompt, steps)
    seq = prompt + fed
    want = _ref_logits(params, seq,
                       rows=list(range(prompt_len - 1, len(seq))))
    assert np.abs(got - want).max() < F32_TOL


def _engine(model, cast, **kw):
    kw = dict(dict(slots=3, total_len=48, buckets=(8, 16, 32),
                   kv_block_size=BLOCK), **kw)
    return serving.DecodeEngine(model.clone(decode=True), cast, **kw)


def test_engine_serves_each_request_at_the_references_logits():
    """Three requests at once through ``DecodeEngine``'s token path,
    one step in flight: every served token's logit under the
    reference's full forward is the row's best, to ``F32_TOL``."""
    params, cast = _weights(seed=7)
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(0, 97, size=p).tolist(), n)
            for p, n in ((5, 9), (21, 14), (30, 12))]
    with _engine(_model(), cast) as eng:
        handles = [eng.submit(p, n) for p, n in reqs]
        got = [h.result(300) for h in handles]
        counts = eng.counters.snapshot()["counts"]
    for (prompt, n), out in zip(reqs, got):
        assert len(out) == len(prompt) + n
        want = _ref_logits(params, out,
                           rows=list(range(len(prompt) - 1, len(out) - 1)))
        served = np.asarray(out[len(prompt):])
        gaps = want.max(axis=-1) - want[np.arange(len(served)), served]
        assert gaps.max() < F32_TOL
    assert counts["kv_window_blocks_given_back"] > 0
    # every expert held: what was routed is what was counted
    assert counts["expert_rows"] == counts["expert_rows_routed"] > 0


@pytest.mark.parametrize("kw,why", [
    (dict(prefix_cache=True), "prefix_cache"),
    (dict(speculate_k=2), "speculate_k"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tier="decode"), "tier='decode'"),
])
def test_engine_refuses_what_it_refuses_mellum(kw, why):
    _, cast = _weights()
    with pytest.raises(ValueError) as e:
        _engine(_model(), cast, **kw)
    assert why in str(e.value) and "full, window" in str(e.value)


# -- the chip's share of the experts -------------------------------------


def _layer(model, first=0, count=0):
    """The program's sparse layer 1 of ``model`` (a sliding one), built
    as ``LagunaLM`` builds it, holding experts ``first ..``."""
    m = _model(model=model, first_expert=first, expert_count=count)
    attn = dict(num_heads=m.sliding_num_heads, num_kv_heads=m.num_kv_heads,
                head_dim=m.head_dim, window=m.sliding_window,
                rope_theta=m.sliding_rope_theta, inv_freq=None,
                rope_factor=None, gate_heads=True, rms_eps=m.rms_eps,
                dtype=jnp.float32, prefill_chunk=8)
    moe = dict(num_experts=m.num_experts, experts_per_tok=m.experts_per_tok,
               moe_hidden=m.moe_hidden, dtype=jnp.float32,
               first_expert=first, expert_count=count,
               routed_scale=m.routed_scale, shared_hidden=m.shared_hidden)
    return laguna.LagunaLayer(attn, moe, m.dense_hidden, m.rms_eps,
                              jnp.float32)


def _held(layer, first, count):
    moe = dict(layer["moe"])
    for k in ("gate", "up", "down"):
        moe[k] = moe[k][first:first + count]
    return dict(layer, moe=moe)


def test_the_two_halves_add_up_to_the_uncut_layer():
    """16 experts, 4 a position, held by two chips (experts 0-7 and
    8-15): each routes over all 16 and computes its own experts' part;
    the two outputs, with what both compute alike (the input, attention,
    the router and the shared expert) counted once, sum to the uncut
    reference layer. The program and the reference agree on each half."""
    model = dict(TINY, num_experts=16, experts_per_tok=4, num_layers=2)
    params, cast = _weights(6, model)
    x = jnp.asarray(np.random.RandomState(2).randn(16, 32), jnp.float32)
    whole = np.asarray(ref.layer_output(params, x, model, layer=1))
    # the input, attention and the shared expert: no routed expert adds
    alike = dict(params, layer_1=dict(params["layer_1"], moe=dict(
        params["layer_1"]["moe"],
        down=jnp.zeros_like(params["layer_1"]["moe"]["down"]))))
    common = np.asarray(ref.layer_output(alike, x, model, layer=1))
    halves = []
    for first in (0, 8):
        share = dict(model, first_expert=first, expert_count=8)
        mine = dict(params, layer_1=_held(params["layer_1"], first, 8))
        want = np.asarray(ref.layer_output(mine, x, share, layer=1))
        got = np.asarray(_layer(model, first, 8).apply(
            {"params": _held(cast["layer_1"], first, 8)}, x[None])[0])
        np.testing.assert_allclose(got, want, atol=F32_TOL)
        halves.append(want)
    assert np.abs(halves[0] - halves[1]).max() > BF16_TOL
    np.testing.assert_allclose(halves[0] + halves[1] - common, whole,
                               atol=F32_TOL)


def _count(held, ids, live, num_experts=8):
    """``DecodeEngine._count_expert_rows`` of a model holding experts
    ``held`` (a range) of ``num_experts``, on its own counters."""
    class _Engine(object):
        _model = _model(num_experts=num_experts, first_expert=held.start,
                        expert_count=len(held) % num_experts)
        counters = tracing.Counters()

    serving.DecodeEngine._count_expert_rows(
        _Engine, np.asarray(ids), np.asarray(live))
    return _Engine.counters.snapshot()["counts"]


def test_expert_counters_count_the_held_share_only():
    """Experts 4-7 of 8 held: of two layers' live rows' assignments
    only those to 4-7 count in ``expert_rows``, ``experts_touched`` and
    the max and mean (over the four held experts); every assignment of
    a live row counts in ``expert_rows_routed``; an idle row counts
    nowhere."""
    ids = [[[0, 4], [5, 4], [7, 1], [6, 6]],      # layer 0, rows 0-3
           [[3, 2], [4, 0], [1, 2], [7, 7]]]      # layer 1
    live = [True, True, True, False]
    got = _count(range(4, 8), ids, live)
    # layer 0: 4 twice, 5 once, 7 once; layer 1: 4 once
    assert got["expert_rows"] == 5
    assert got["expert_rows_routed"] == 2 * 3 * 2
    assert got["experts_touched"] == 3 + 1
    assert got["expert_rows_max"] == 2 + 1
    assert got["expert_rows_mean"] == pytest.approx(4 / 4 + 1 / 4)
    assert got["expert_calls"] == 2
    # held whole, the same calls read as they always did
    whole = _count(range(0, 8), ids, live)
    assert whole["expert_rows"] == whole["expert_rows_routed"] == 12
    assert whole["experts_touched"] == 5 + 5
    assert whole["expert_rows_max"] == 2 + 2
    assert whole["expert_rows_mean"] == pytest.approx(6 / 8 + 6 / 8)
