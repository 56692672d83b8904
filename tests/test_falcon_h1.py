"""Falcon-H1 (models/falcon_h1.py) against its plain reference
(benchmarks/reference/falcon_h1.py), at small sizes on seeded random
weights: the whole forward, the chunked scan against the recurrence
token by token, the state's step kernel (ops/ssm_step.py) against plain
``jnp``, the mixer's own output and its states through prefill and
decode, the engine (a slot reused, an admission with a step in flight,
preemption and re-entry, many slots against one), the state kind's
accounting and what the engine refuses for it.

Tolerances, each of the compared values' largest. The reference is
float32 at ``highest`` precision on the bfloat16-rounded weights. A
float32 instance of the model differs from it by the order of float32
sums only (the scan's chunks against the recurrence's positions):
``F32_REL``. A bfloat16 instance rounds every activation to 8 bits of
mantissa, several roundings in a row before a logit or the mixer's
output (sound readings up to 0.004: ``BF16_REL``) and fewer before a
state, which is float32 (sound readings up to 0.0023:
``BF16_STATE_REL``). The benchmark's configuration serves the state
rows in bfloat16, the model's dtype (a bfloat16 ``FalconH1LM`` keeps
its rows at its dtype, as transformers' cache does): a slow head's increments
then fall under a row's last bit, and after 60 steps the rows read 0.011
to 0.018 off (``BF16_ROW_REL``). The negative controls (a state stored
one step under the precision it is read at: bfloat16 under float32,
float8 under bfloat16; the inter-chunk term dropped, a slot that keeps
its previous occupant's state, a bucket's pads let into the state) must
each miss the tolerance of the precision they are read at: the scan's
by ten times and more, the state's by twice and more.
"""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import falcon_h1 as ref
from tensorflowonspark_tpu import generation, paging, serving
from tensorflowonspark_tpu.models import falcon_h1 as fh

ss = importlib.import_module("tensorflowonspark_tpu.ops.ssm_step")

F32_REL = 2e-5
BF16_REL = 0.02
BF16_STATE_REL = 0.004
BF16_ROW_REL = 0.03
BLOCK = 4
V = 97

_CONFIG = json.load(open(os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "benchmarks", "configs", "falcon-h1-34b.json")))

#: the published multipliers at tiny widths: two groups of two heads,
#: chunks of 8 so that a prompt crosses several
TINY = dict(_CONFIG["model"], vocab=V, hidden=32, num_heads=4,
            num_kv_heads=2, head_dim=8, num_layers=2, mlp_hidden=64,
            ssm_heads=4, ssm_head_dim=8, ssm_groups=2, ssm_state=16,
            conv_kernel=4, chunk=8, rope_theta=1e4, max_len=64)


def _fields(model=TINY, **kw):
    fields = {k: tuple(v) if isinstance(v, list) else v
              for k, v in dict(model, **kw).items()}
    return fields


def _model(dtype=jnp.float32, **kw):
    return fh.FalconH1LM(dtype=dtype, prefill_chunk=8, **_fields(**kw))


def _weights(seed=0, dtype=jnp.float32):
    params = ref.init_params(jax.random.PRNGKey(seed), TINY)
    return params, jax.tree.map(lambda a: a.astype(dtype), params)


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    """The reference attends ``Q_BLOCK`` queries at once and wants longer
    sequences to be a multiple of it."""
    monkeypatch.setattr(ref, "Q_BLOCK", 8)


def _pad8(tokens):
    return list(tokens) + [0] * (-len(tokens) % 8)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


# -- the forward and the scan --------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_REL),
                                       (jnp.bfloat16, BF16_REL)])
def test_forward_is_the_reference_forward(dtype, tol):
    params, cast = _weights(seed=1, dtype=dtype)
    tokens = np.random.RandomState(1).randint(0, V, size=40)
    got = _model(dtype).apply({"params": cast}, jnp.asarray(tokens)[None])
    want = ref.logits(params, tokens, TINY)
    assert _rel(got[0], want) < tol


def _recurrence(x, dt, a, b, c):
    """The definition, position by position, in float64 numpy: ``(y
    without D x, final state)``."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    t, nh, p = x.shape
    rep = nh // b.shape[1]
    s = np.zeros((nh, p, b.shape[2]))
    y = np.zeros((t, nh, p))
    for i in range(t):
        bh, ch = np.repeat(b[i], rep, 0), np.repeat(c[i], rep, 0)
        s = np.exp(dt[i] * a)[:, None, None] * s \
            + (dt[i][:, None] * x[i])[:, :, None] * bh[:, None, :]
        y[i] = np.einsum("hpn,hn->hp", s, ch)
    return y, s


def _scan_inputs(t, seed, pads=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    nh, p, g, n = 4, 8, 2, 16
    x = jax.random.normal(k[0], (t + pads, nh, p))
    dt = jax.nn.softplus(jax.random.normal(k[1], (t + pads, nh)) - 2.0)
    a = -jnp.exp(jax.random.uniform(k[2], (nh,), minval=0.0, maxval=2.7))
    b = jax.random.normal(k[3], (t + pads, g, n))
    c = jax.random.normal(k[4], (t + pads, g, n))
    return x, dt, a, b, c


@pytest.mark.parametrize("t", [1, 127, 128, 129, 300])
def test_chunked_scan_is_the_recurrence_token_by_token(t):
    x, dt, a, b, c = _scan_inputs(t, seed=t)
    y, s = fh.ssd_scan(x, dt, a, b, c, 128)
    want_y, want_s = _recurrence(x, dt, a, b, c)
    assert _rel(y, want_y) < F32_REL and _rel(s, want_s) < F32_REL


def test_a_padded_bucket_leaves_the_state_at_the_last_real_position():
    """Pads with ``dt = 0`` and no input decay nothing and add nothing;
    let into the scan with their dt, they move the state by far more
    than the tolerance (a negative control)."""
    x, dt, a, b, c = _scan_inputs(100, seed=5, pads=156)
    want_y, want_s = _recurrence(x[:100], dt[:100], a, b[:100], c[:100])
    real = (jnp.arange(256) < 100)[:, None]
    y, s = fh.ssd_scan(jnp.where(real[..., None], x, 0),
                       jnp.where(real, dt, 0.0), a, b, c, 128)
    assert _rel(y[:100], want_y) < F32_REL and _rel(s, want_s) < F32_REL
    _, leaked = fh.ssd_scan(x, dt, a, b, c, 128)
    assert _rel(leaked, want_s) > 10 * F32_REL


@pytest.mark.parametrize("t", [129, 300])
def test_dropping_the_inter_chunk_term_misses_the_tolerance(t):
    """A negative control: each chunk from a zero state, as if nothing
    came in from the chunks before it."""
    x, dt, a, b, c = _scan_inputs(t, seed=t)
    want_y, _ = _recurrence(x, dt, a, b, c)
    pieces = [fh.ssd_scan(x[i:i + 128], dt[i:i + 128], a, b[i:i + 128],
                          c[i:i + 128], 128)[0] for i in range(0, t, 128)]
    assert _rel(jnp.concatenate(pieces), want_y) > 10 * F32_REL


# -- the state's step kernel ---------------------------------------------


def _step_inputs(seed, rows=6, batch=5, state_dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    nh, p, n, g, k1 = 8, 16, 32, 2, 3
    width = nh * p + 2 * g * n
    return dict(
        state=jax.random.normal(k[0], (rows, nh, p, n)).astype(state_dtype),
        conv=jax.random.normal(k[1], (rows, k1, width)).astype(jnp.bfloat16),
        x=jax.random.normal(k[2], (batch, nh, p)).astype(jnp.bfloat16),
        dt=jax.nn.softplus(jax.random.normal(k[3], (batch, nh))),
        a=-jnp.exp(jax.random.uniform(k[4], (nh,))),
        b=jax.random.normal(k[5], (batch, g, n)).astype(jnp.bfloat16),
        c=jax.random.normal(k[6], (batch, g, n)).astype(jnp.bfloat16),
        d=jnp.ones((nh,)),
        xbc=jax.random.normal(k[7], (batch, width)).astype(jnp.bfloat16),
        rows=jnp.array([4, 0, 2, 5, 1][:batch], jnp.int32))


@pytest.mark.parametrize("state_dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("live", [[1, 0, 1, 1, 0], [0] * 5, [1] * 5],
                         ids=["some_live", "none_live", "all_live"])
def test_ssm_step_kernel_is_plain_jnp_and_leaves_idle_rows(live,
                                                           state_dtype):
    """The Pallas kernel in the interpreter against the plain form and a
    numpy oracle of the module's equations; the rows no live batch row
    names are left bit for bit, state and convolution alike."""
    args = _step_inputs(3, state_dtype=state_dtype)
    live = jnp.asarray(live, bool)
    y1, s1, c1 = ss.ssm_step(*args.values(), live, impl="xla")
    y2, s2, c2 = ss.ssm_step(*args.values(), live, impl="pallas",
                             interpret=True)
    if live.any():
        assert _rel(y2, y1) < F32_REL
    np.testing.assert_allclose(np.asarray(s2, np.float32),
                               np.asarray(s1, np.float32), rtol=1e-5,
                               atol=1e-5)
    assert (np.asarray(c2) == np.asarray(c1)).all()
    for at in range(6):
        mine = [i for i in range(5) if int(args["rows"][i]) == at
                and bool(live[i])]
        old_s, old_c = np.asarray(args["state"][at]), np.asarray(
            args["conv"][at])
        if not mine:
            assert (np.asarray(s2[at]) == old_s).all()
            assert (np.asarray(c2[at]) == old_c).all()
            continue
        i = mine[0]
        x = np.asarray(args["x"][i], np.float64)
        dt = np.asarray(args["dt"][i], np.float64)
        a = np.asarray(args["a"], np.float64)
        bh = np.repeat(np.asarray(args["b"][i], np.float64), 4, 0)
        ch = np.repeat(np.asarray(args["c"][i], np.float64), 4, 0)
        new = np.exp(dt * a)[:, None, None] * old_s.astype(np.float64) \
            + (dt[:, None] * x)[:, :, None] * bh[:, None, :]
        assert _rel(np.asarray(s2[at], np.float32), new) \
            < (F32_REL if state_dtype == jnp.float32 else 1e-2)
        y = np.einsum("hpn,hn->hp", new, ch) + x
        assert _rel(y2[i], y) < (F32_REL if state_dtype == jnp.float32
                                 else 1e-2)
        assert (np.asarray(c2[at][:-1]) == old_c[1:]).all()
        assert (np.asarray(c2[at][-1]) == np.asarray(args["xbc"][i])).all()
    assert not np.asarray(y2)[~np.asarray(live)].any()


def test_block_heads_share_one_group():
    assert ss.block_heads(32, 2) == 16 and ss.block_heads(4, 2) == 2
    assert ss.block_heads(24, 1) == 12 and ss.block_heads(6, 3) == 2


# -- the mixer's own output and its states -------------------------------


def _mixer(dtype, state_dtype=jnp.float32, slots=3):
    m = _fields()
    return fh.Mixer(
        heads=m["ssm_heads"], head_dim=m["ssm_head_dim"],
        groups=m["ssm_groups"], state=m["ssm_state"],
        conv=m["conv_kernel"], chunk=m["chunk"],
        in_multiplier=m["ssm_in_multiplier"],
        multipliers=m["ssm_multipliers"], out_multiplier=1.0,
        rms_eps=m["rms_eps"], dtype=dtype, state_dtype=state_dtype,
        decode=True, state_slots=slots)


def _mixer_run(dtype, state_dtype=jnp.float32, n=13, bucket=16, steps=20,
               seed=2, keep_previous=False, leak_pads=False):
    """One layer's mixer through a prefill of ``n`` positions padded to
    ``bucket`` into slot 1 of 3, then ``steps`` decode steps with slot 1
    the only live row; the outputs (before ``ssm_out_multiplier``) and
    the slot's states, against the reference's over the same inputs."""
    params, cast = _weights(seed=seed, dtype=dtype)
    p = cast["layer_0"]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(seed),
                          (n + steps, TINY["hidden"])).astype(dtype)
    mod = _mixer(dtype, state_dtype)
    rng = np.random.RandomState(seed)
    k1, conv = TINY["conv_kernel"] - 1, 4 * 8 + 2 * 2 * 16
    cache = {"ssm_state": jnp.asarray(rng.normal(size=(3, 4, 8, 16)),
                                      state_dtype),
             "conv_state": jnp.asarray(rng.normal(size=(3, k1, conv)),
                                       dtype),
             "state_slot": jnp.full((1,), 1, jnp.int32),
             "cache_index": jnp.zeros((1,), jnp.int32)}
    before = dict(cache)
    xs = jnp.zeros((bucket, TINY["hidden"]), dtype).at[:n].set(h[:n])
    out, upd = mod.apply({"params": p, "cache": cache}, xs[None],
                         None if leak_pads else jnp.asarray([n - 1]),
                         mutable=["cache"])
    c = upd["cache"]
    if keep_previous:   # the prefill's write of the row is lost
        c = dict(c, ssm_state=c["ssm_state"].at[1].set(
            before["ssm_state"][1]),
            conv_state=c["conv_state"].at[1].set(before["conv_state"][1]))
    outs = [out[0, :n]]
    states = [(c["ssm_state"][1], c["conv_state"][1])]
    for t in range(n, n + steps):
        x = jnp.zeros((3, 1, TINY["hidden"]), dtype).at[1, 0].set(h[t])
        c = dict(c, state_slot=jnp.arange(3, dtype=jnp.int32),
                 cache_index=jnp.asarray([0, t, 0], jnp.int32))
        out, upd = mod.apply({"params": p, "cache": c}, x, None,
                             mutable=["cache"])
        c = upd["cache"]
        outs.append(out[1])
        for row in (0, 2):     # idle rows: never read, never written
            assert (np.asarray(c["ssm_state"][row])
                    == np.asarray(before["ssm_state"][row])).all()
            assert (np.asarray(c["conv_state"][row])
                    == np.asarray(before["conv_state"][row])).all()
    states.append((c["ssm_state"][1], c["conv_state"][1]))
    got = jnp.concatenate(outs).astype(jnp.float32)

    def reference(upto):
        with jax.default_matmul_precision("highest"):
            return jax.jit(ref._mixer, static_argnames=(
                "shape", "consts", "eps", "rnd"))(
                    params["layer_0"]["mixer"],
                    h[:upto].astype(jnp.float32), ref._shape(TINY),
                    ref._consts(TINY), TINY["rms_eps"],
                    ref._rounder("float32"))

    m_all, s_all, conv_all = reference(n + steps)
    _, s_pre, conv_pre = reference(n)
    return {"out": _rel(got, m_all),
            "state_prefill": _rel(states[0][0].astype(jnp.float32), s_pre),
            "conv_prefill": _rel(states[0][1].astype(jnp.float32),
                                 conv_pre),
            "state_steps": _rel(states[1][0].astype(jnp.float32), s_all),
            "conv_steps": _rel(states[1][1].astype(jnp.float32), conv_all)}


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_REL),
                                       (jnp.bfloat16, BF16_REL)])
@pytest.mark.parametrize("n,bucket", [(13, 16), (8, 8), (2, 16), (21, 32)])
def test_mixer_output_and_states_through_prefill_and_steps(dtype, tol, n,
                                                           bucket):
    """The mixer's output before ``ssm_out_multiplier`` (the multipliers
    shrink its share of the residual, so logits alone could hide a
    broken scan) and the slot's SSM and convolution states after the
    prefill and after 20 steps, against the reference."""
    read = _mixer_run(dtype, n=n, bucket=bucket)
    assert read["out"] < tol, read
    states = F32_REL if dtype == jnp.float32 else BF16_STATE_REL
    assert max(v for k, v in read.items() if k != "out") < states, read


@pytest.mark.parametrize("seed", [2, 3, 4])
def test_the_served_precision_bfloat16_state_rows(seed):
    """The configuration's precision, bfloat16 activations AND state
    rows, through a prefill and 60 steps: the output within what the
    class's float32 state gives, the rows within ``BF16_ROW_REL``."""
    read = _mixer_run(jnp.bfloat16, state_dtype=jnp.bfloat16, steps=60,
                      seed=seed)
    assert read["out"] < BF16_REL, read
    assert max(v for k, v in read.items() if k != "out") < BF16_ROW_REL, \
        read


@pytest.mark.parametrize("rows,tol", [(jnp.float32, BF16_STATE_REL),
                                      (jnp.bfloat16, BF16_ROW_REL)])
@pytest.mark.parametrize("fault,kw", [
    ("state_one_step_under", dict()),
    ("previous_occupant_kept", dict(keep_previous=True)),
    ("pads_let_into_the_state", dict(leak_pads=True)),
])
def test_state_faults_miss_the_tolerance(rows, tol, fault, kw):
    """Negative controls, read with bfloat16 activations and the state
    rows at ``rows`` (the class's float32; the served configuration's
    bfloat16): each misses the rows' tolerance by twice and more in the
    slot's states. A state one step under is bfloat16 under float32
    (0.011-0.018 after 60 steps) and float8 under bfloat16 (0.7)."""
    state = rows
    if fault == "state_one_step_under":
        state = {jnp.float32: jnp.bfloat16,
                 jnp.bfloat16: jnp.float8_e4m3fn}[rows]
    read = _mixer_run(jnp.bfloat16, state_dtype=state, steps=60, **kw)
    assert max(read["state_prefill"], read["conv_prefill"],
               read["state_steps"]) > 2 * tol, (fault, read)


# -- through the caches and the engine -----------------------------------


def _by_hand(model, cast, prompt, steps, total=48, bucket=None, slot=1,
             slots=3):
    """Prefill ``prompt`` into ``slot`` of ``slots`` and decode ``steps``
    tokens of the reference's own choice, the host's part done by
    ``paging.CacheKinds`` as the engine does it. Returns ``(logits of
    every call, tokens fed, the slot's states after the last call)``."""
    width = total // BLOCK
    kv = paging.CacheKinds(slots, width, BLOCK, slots * width,
                           model.cache_kinds)
    dec = model.clone(decode=True, kv_block_size=BLOCK,
                      kv_blocks=slots * width + 1, state_slots=slots)
    cache = generation.init_cache(dec, 1, total)
    n = len(prompt)
    kv.full.admit(slot, n)
    for kind in kv.others():
        kind.admit(slot, n)
    bucket = bucket or -(-n // 8) * 8
    toks = np.zeros(bucket, np.int32)
    toks[:n] = prompt
    logits, upd = dec.apply(
        {"params": cast,
         "cache": generation._slot_view(dec, cache, kv.tables[slot], 0,
                                        slot)},
        jnp.asarray(toks)[None], last=jnp.asarray([n - 1]),
        mutable=["cache"])
    cache = upd["cache"]
    out, fed = [np.asarray(logits[0, 0])], []
    for i in range(steps):
        cursor = n + i
        token = int(np.argmax(out[-1]))
        kv.full.grow(slot, cursor // BLOCK)
        idx = np.zeros(slots, np.int32)
        idx[slot] = cursor
        given = np.zeros(slots, np.int32)
        given[slot] = token
        tables = np.where((np.arange(slots) == slot)[:, None], kv.tables, 0)
        stepped = generation._set_paged_leaves(
            dec, cache, jnp.asarray(idx), jnp.asarray(tables))
        logits, upd = dec.apply({"params": cast, "cache": stepped},
                                jnp.asarray(given)[:, None],
                                mutable=["cache"])
        cache = upd["cache"]
        out.append(np.asarray(logits[slot, 0]))
        fed.append(token)
    states = [(np.asarray(cache["layer_%d" % i]["mixer"]["ssm_state"][slot]),
               np.asarray(cache["layer_%d" % i]["mixer"]["conv_state"][slot]))
              for i in range(model.num_layers)]
    return np.stack(out), fed, states


@pytest.mark.parametrize("prompt_len,steps,bucket", [
    (5, 9, None), (13, 7, 16), (21, 6, 32), (8, 12, 8)],
    ids=["short", "padded", "three_chunks", "whole_bucket"])
def test_prefill_then_decode_through_the_caches_is_the_full_forward(
        prompt_len, steps, bucket):
    """Logits of the prefill's last position and of every decode step
    against the reference's full forward over the finished sequence, and
    every layer's states after the last step against the reference's."""
    params, cast = _weights(seed=prompt_len)
    prompt = np.random.RandomState(prompt_len).randint(
        0, V, size=prompt_len).tolist()
    got, fed, states = _by_hand(_model(), cast, prompt, steps, bucket=bucket)
    seq = prompt + fed
    want = np.asarray(ref.logits(params, _pad8(seq), TINY))[
        prompt_len - 1:len(seq)]
    assert _rel(got, want) < F32_REL
    # the state after the last step has seen every token fed
    _, mixers = ref.hidden(params, seq, TINY)
    for (s, conv), (_, s_ref, conv_ref) in zip(states, mixers):
        assert _rel(s, s_ref) < F32_REL and _rel(conv, conv_ref) < F32_REL


def _greedy(model, cast, prompt, n):
    whole = jax.jit(lambda p, t: model.apply({"params": p}, t))
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(jnp.argmax(whole(cast, jnp.asarray(seq)[None])[0, -1])))
    return seq


def _engine(model, cast, **kw):
    kw = dict(dict(slots=3, total_len=48, buckets=(8, 16, 32),
                   kv_block_size=BLOCK), **kw)
    return serving.DecodeEngine(model.clone(decode=True), cast, **kw)


def _counts(eng):
    return eng.counters.snapshot()["counts"]


def test_engine_serves_each_request_as_the_model_alone_would():
    """Five requests over three slots, token by token with one step in
    flight: slots are reused by new requests (whose prefill writes the
    row whole), and each answer equals greedy generation with no cache;
    every served token is the reference's best. The state counters
    count a live row a step and a write a prefill."""
    params, cast = _weights(seed=7)
    model = _model()
    rng = np.random.RandomState(7)
    reqs = [(rng.randint(0, V, size=p).tolist(), n)
            for p, n in ((5, 9), (21, 14), (30, 12), (3, 6), (12, 10))]
    with _engine(model, cast) as eng:
        handles = [eng.submit(p, n) for p, n in reqs]
        got = [h.result(300) for h in handles]
        counts = _counts(eng)
        gauges = eng.counters.snapshot()["gauges"]
        metrics = eng.metrics.render() if hasattr(eng.metrics, "render") \
            else None
        assert eng.compile_stats()["decode_programs"] == 1
    for (prompt, n), out in zip(reqs, got):
        assert out == _greedy(model, cast, prompt, n)
        want = np.asarray(ref.logits(params, _pad8(out), TINY))[
            len(prompt) - 1:len(out) - 1]
        served = np.asarray(out[len(prompt):])
        gaps = want.max(axis=-1) - want[np.arange(len(served)), served]
        assert gaps.max() < F32_REL * np.abs(want).max()
    assert counts["ssm_state_writes"] == len(reqs) == counts["prefills"]
    assert counts["ssm_state_rows"] == sum(n - 1 for _, n in reqs) \
        == counts["decode_tokens"]
    assert counts["steps_dispatched_ahead"] > 0
    # 3 slots x 2 layers x (SSM state 4 x 8 x 16 + convolution 3 x 96),
    # float32 here
    assert gauges["ssm_state_bytes"] == 3 * 2 * (4 * 8 * 16 + 3 * 96) * 4
    if metrics is not None:
        assert "tfos_serving_ssm_state_rows" in metrics


def test_admission_with_a_step_in_flight_and_many_slots_against_one():
    """A request admitted while the step before it is in flight (its
    prefill runs between two dispatches of the other row) gets its own
    tokens, and four slots answer as one slot does."""
    _, cast = _weights(seed=9)
    model = _model()
    rng = np.random.RandomState(9)
    reqs = [(rng.randint(0, V, size=p).tolist(), n)
            for p, n in ((11, 16), (6, 9), (17, 8), (4, 12))]
    seen = []
    with _engine(model, cast, slots=4) as eng:
        admit = eng._admit

        def watched(slot, handle):
            seen.append(eng._flight is not None)
            return admit(slot, handle)

        eng._admit = watched
        first = eng.submit(*reqs[0])
        stream = first.stream(timeout=300)
        next(stream), next(stream)
        rest = [eng.submit(p, n) for p, n in reqs[1:]]
        got = [first.result(300)] + [h.result(300) for h in rest]
    assert any(seen[1:]), seen
    with _engine(model, cast, slots=1) as eng:
        alone = [eng.submit(p, n).result(300) for p, n in reqs]
    assert got == alone


def test_preempted_request_re_enters_and_recomputes_its_state():
    """Two requests over a pool that holds one of them whole: the younger
    is preempted when the older grows, re-enters by a prefill of prompt
    plus what it had emitted from position 0 (which writes its state row
    anew), and both answer as if alone."""
    _, cast = _weights(seed=13)
    model = _model()
    rng = np.random.RandomState(13)
    a = rng.randint(0, V, size=14).tolist()
    b = rng.randint(0, V, size=9).tolist()
    with _engine(model, cast, slots=2, kv_blocks=11,
                 buckets=(8, 16, 32, 48)) as eng:
        with eng._cv:
            ha, hb = eng.submit(a, 26), eng.submit(b, 20)
        got_a, got_b = ha.result(300), hb.result(300)
        counts = _counts(eng)
        assert eng._kv.full.in_use() == 0
    assert counts["preemptions"] >= 1
    assert counts["ssm_state_writes"] == 2 + counts["preemptions"]
    assert got_a == _greedy(model, cast, a, 26)
    assert got_b == _greedy(model, cast, b, 20)


# -- the state kind and the engine's refusals ----------------------------


def test_the_state_kind_holds_a_row_a_slot_and_no_block():
    kv = paging.CacheKinds(4, 12, BLOCK, 48, {"state": 2})
    state = kv.kinds[-1]
    assert [k.name for k in kv] == ["full", "state"]
    assert kv.tables.shape == (4, 12)      # no columns of its own
    assert kv.others() == [state]
    assert state.need(1000) == 0 and state.lacks(1, 50) == 0
    assert state.grow(1, 50) == 0 and state.trim(1, 99) == 0
    state.admit(2, 30)
    state.release(2)
    assert state.model_fields() == {"state_slots": 4}
    assert kv.bytes_per_token()["state"] == 0
    with pytest.raises(ValueError, match="int8"):
        paging.CacheKinds(4, 12, BLOCK, 48, {"state": 2}, kv_dtype="int8")


@pytest.mark.parametrize("kw,why", [
    (dict(prefix_cache=True), "snapshot"),
    (dict(speculate_k=2), "rolled back"),
    (dict(kv_dtype="int8"), "kv_dtype"),
    (dict(tier="decode"), "tier='decode'"),
    (dict(tier="prefill"), "tier='prefill'"),
])
def test_engine_refuses_what_a_state_kind_cannot_do_yet(kw, why):
    _, cast = _weights()
    with pytest.raises(ValueError) as e:
        _engine(_model(), cast, **kw)
    assert why in str(e.value) and "recurrent state" in str(e.value)


def test_stepping_by_blocks_is_refused_for_a_state_kind():
    with pytest.raises(ValueError, match="stepping by blocks"):
        serving.DecodeEngine._check_cache_kinds(
            _model(), {"state": 2}, prefix_cache=False, speculate_k=None,
            kv_dtype=None, tier="mixed", block_len=4)


def test_an_engine_with_a_state_kind_ships_no_kv_and_shares_no_prefix():
    _, cast = _weights()
    with _engine(_model(), cast) as eng:
        assert eng.prefix_cache is False
        with pytest.raises(ValueError, match="kinds of cache"):
            eng.export_prefix([1, 2, 3, 4, 5])
        prompt = list(range(1, 13))
        assert eng.submit(prompt, 3).result(300) \
            == eng.submit(prompt, 3).result(300)
        assert _counts(eng).get("prefix_hit_blocks", 0) == 0


def test_state_leaves_cross_the_call_and_cursors_do_not():
    """What a Falcon-H1 step program takes and answers: K/V pools and
    the state rows, donated; no cursor, table or slot leaf."""
    dec = _model().clone(decode=True, kv_block_size=BLOCK, kv_blocks=13,
                         state_slots=3)
    pools = generation.init_pools(dec)
    names = {generation._leaf_name(p) for p, _
             in jax.tree_util.tree_leaves_with_path(pools)}
    assert names == {"cached_key", "cached_value", "ssm_state", "conv_state"}
    assert [generation._leaf_name(p) for p, _
            in generation.pool_leaves(pools)].count("ssm_state") == 0
    by_table = generation.pool_leaves_by_table(dec, pools)
    assert sorted(by_table) == ["block_table", "state_slot"]
    assert len(by_table["state_slot"]) == 2 * TINY["num_layers"]
