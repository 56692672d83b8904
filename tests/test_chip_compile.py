"""Compile the main path for the chip, without the chip.

Rehearsal 3 of the on-chip-measurement guide kept as tests: the TPU's
compiler is installed here and compiles for a v5e that is DESCRIBED,
not attached — so what it refuses (a block shape off the tiling, a
relayout Mosaic lacks, a program that does not fit the device) fails
tier-1 at no chip time. Interpret mode cannot see any of that: both
kernels passed every interpret-mode test while neither lowered.

Nothing runs here, so these say nothing about results or times. The
topology is described inside a module-scoped fixture (only the worker
that is handed this file loads libtpu; every other file of the suite
stays clear of it), the persistent compilation cache is off around the
compiles (an entry written for an absent chip cannot be read back),
and everything compiles in the test's own process. All of it is in
this one file on purpose.
"""

import importlib
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

fa = importlib.import_module("tensorflowonspark_tpu.ops.flash_attention")
pa = importlib.import_module("tensorflowonspark_tpu.ops.paged_attention")

#: chip_smoke.py's phase-2/3 widths (GPT-2 small: 12 heads of 64; the
#: engine's 16-token KV blocks over a 1024-token context, 8 slots)
HEADS, HEAD_DIM, SEQ, KV_BLOCK, SLOTS = 12, 64, 1024, 16, 8
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip("no v5e:2x2 topology can be described here: "
                    "{}".format(e))
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


FLASH_SHAPES = {
    # (batch, seq, heads, head_dim, dtype, causal, key_mask)
    "gpt2_bf16_causal": (8, SEQ, HEADS, HEAD_DIM, jnp.bfloat16, True, False),
    "gpt2_f32_key_mask": (8, SEQ, HEADS, HEAD_DIM, jnp.float32, False, True),
    # examples/longcontext's regime: K/V must stream, not sit whole in VMEM
    "long_bf16_causal": (1, 8192, 8, 128, jnp.bfloat16, True, False),
}


def _flash_case(name, one_chip):
    b, s, n, d, dtype, causal, masked = FLASH_SHAPES[name]
    qkv = jax.ShapeDtypeStruct((b, s, n, d), dtype, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((b, s), jnp.bool_, sharding=one_chip) \
        if masked else None

    def flash(q, k, v, key_mask=None):
        return fa.flash_attention(q, k, v, causal=causal, key_mask=key_mask,
                                  force_pallas=True, interpret=False)

    return flash, (qkv, qkv, qkv) + ((mask,) if masked else ())


@pytest.mark.parametrize("name", sorted(FLASH_SHAPES))
def test_flash_forward_compiles_for_v5e(one_chip, name):
    flash, args = _flash_case(name, one_chip)
    assert _has_kernel(_compile(flash, *args))


@pytest.mark.parametrize("name", sorted(FLASH_SHAPES))
def test_flash_backward_compiles_for_v5e(one_chip, name):
    flash, args = _flash_case(name, one_chip)

    def loss(q, k, v, *mask):
        return jnp.sum(flash(q, k, v, *mask).astype(jnp.float32) ** 2)

    compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)), *args)
    # forward (residuals), dQ, dK/dV
    assert compiled.as_text().count("tpu_custom_call") >= 3


PAGED_SHAPES = {
    # (rows, s_q, heads, head_dim, kv_block, table_width, q dtype, int8)
    "decode_f32": (SLOTS, 1, HEADS, HEAD_DIM, KV_BLOCK, 64, jnp.float32,
                   False),
    "decode_bf16": (SLOTS, 1, HEADS, HEAD_DIM, KV_BLOCK, 64, jnp.bfloat16,
                    False),
    "decode_int8_pool": (SLOTS, 1, HEADS, HEAD_DIM, KV_BLOCK, 64,
                         jnp.float32, True),
    "prefill_128_f32": (1, 128, HEADS, HEAD_DIM, KV_BLOCK, 64, jnp.float32,
                        False),
    "prefill_1024_bf16": (1, SEQ, HEADS, HEAD_DIM, KV_BLOCK, 64,
                          jnp.bfloat16, False),
    "prefill_128_int8_pool": (1, 128, HEADS, HEAD_DIM, KV_BLOCK, 64,
                              jnp.bfloat16, True),
    # tests/test_paged_attention.py's own widths (its interpret-mode
    # cases, now also put to the real lowering): 4 heads of 16, blocks
    # of 8 tokens, an odd prefill length
    "test_widths_f32": (3, 1, 4, 16, 8, 4, jnp.float32, False),
    "test_widths_int8_pool": (3, 17, 4, 16, 8, 4, jnp.float32, True),
}


@pytest.mark.parametrize("name", sorted(PAGED_SHAPES))
def test_paged_attention_compiles_for_v5e(one_chip, name):
    rows, s_q, n, d, bs, mb, dtype, quant = PAGED_SHAPES[name]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((rows * mb + 1, bs, n * d), jnp.int8 if quant else dtype)
    scale = sds((rows * mb + 1, bs, n), jnp.float32) if quant else None

    def paged(q, k, v, table, pos, ks, vs):
        return pa.paged_attention(q, k, v, table, pos, impl="pallas",
                                  interpret=False, k_scale=ks, v_scale=vs)

    lowered = jax.jit(paged).lower(
        sds((rows, s_q, n, d), dtype), pool, pool, sds((rows, mb), jnp.int32),
        sds((rows, s_q), jnp.int32), scale, scale)
    # the name the kernel's per-layer metrics pick its events by
    assert lowered.as_text().count('kernel_name = "paged_attention"') == 1
    assert _has_kernel(lowered.compile())


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _resnet50_step(mesh, batch=256, image=224):
    """(jitted train step, its abstract arguments) of chip_smoke.py's
    phase 1 on ``mesh``, from shapes alone."""
    import optax

    from tensorflowonspark_tpu import training
    from tensorflowonspark_tpu.models.resnet import ResNet50

    trainer = training.Trainer(ResNet50(), optax.sgd(0.1, momentum=0.9),
                               mesh)
    state = jax.eval_shape(lambda: trainer.init(
        jax.random.PRNGKey(0),
        np.zeros((batch, image, image, 3), np.float32)))
    trainer._build_step()
    return trainer._jit_step, (_on(trainer.replicated, state), {
        "x": jax.ShapeDtypeStruct((batch, image, image, 3), jnp.uint8,
                                  sharding=trainer.batch_sharding),
        "y": jax.ShapeDtypeStruct((batch,), jnp.int64,
                                  sharding=trainer.batch_sharding)})


@pytest.mark.slow  # ~45 s each here; the kernels above are the guard
@pytest.mark.parametrize("chips", [1, 4])
def test_resnet50_train_step_compiles_for_v5e(topo, chips):
    """Phase 1 (one chip) and ``--multichip`` (a four-chip data mesh:
    the batch split, the gradients all-reduced) fit the device. Run
    before a four-chip call: ``pytest tests/test_chip_compile.py -m
    slow``."""
    from jax.sharding import Mesh

    step, args = _resnet50_step(
        Mesh(np.array(topo.devices[:chips]), ("data",)))
    compiled = step.lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES
    assert ("all-reduce" in compiled.as_text()) == (chips > 1)


#: benchmarks/configs/gpt2-large.json's serving shapes at two layers
#: (the programs repeat per layer): 20 heads of 64, 16 slots over 512
#: tokens, a pool of 512 blocks of 16 and the scratch row; a small
#: vocabulary, which no pool-shaped value depends on
GPT2_LARGE_2L = {"vocab": 512, "hidden": 1280, "num_heads": 20,
                 "num_layers": 2, "max_len": 1024}
LARGE_SLOTS, LARGE_TOTAL = 16, 512


def _paged_engine_shapes(one_chip, widths, slots, total, blocks,
                         kv_dtype=""):
    """(paged model, abstract params, abstract cache, sds): what the
    engine's ``paged_step_fns`` programs are lowered with, ``slots``
    rows of ``total`` tokens over a pool of ``blocks`` blocks, the
    cache as the engine hands it over: its pools alone."""
    from tensorflowonspark_tpu import generation
    from tensorflowonspark_tpu.models.decoder import DecoderLM

    model = DecoderLM(decode=True, kv_block_size=KV_BLOCK,
                      kv_blocks=blocks + 1, kv_dtype=kv_dtype, **widths)
    variables = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((slots, total), jnp.int32)))

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    return (model, _on(one_chip, variables["params"]),
            _on(one_chip, generation._pools_of(variables["cache"])), sds)


def _gpt2_small_paged(one_chip):
    """Phase 2's widths: GPT-2 small, 8 slots over the whole context."""
    from chip_smoke import GPT2_SMALL

    return _paged_engine_shapes(one_chip, GPT2_SMALL, SLOTS, SEQ,
                                SLOTS * SEQ // KV_BLOCK)


def _lower_engine_program(program, model, params, cache, sds, slots, total):
    """The engine's donated ``paged_decode_step`` or ``paged_prefill``
    (bucket 128), lowered from shapes."""
    from tensorflowonspark_tpu import generation

    prefill, decode = generation.paged_step_fns(model)
    key = sds((2,), jnp.uint32)
    if program == "paged_decode_step":
        # the step before's picks, and the host's feed in one array: a
        # given token or -1, the cursor, the table row
        return decode.lower(
            params, cache, sds((slots,), jnp.int32),
            sds((slots, 2 + total // KV_BLOCK), jnp.int32), key)
    return prefill.lower(
        params, cache, sds((total // KV_BLOCK,), jnp.int32),
        sds((128,), jnp.int32), sds((), jnp.int32), sds((), jnp.int32), key)


@pytest.fixture
def kernel_on_cpu_backend(monkeypatch):
    """``impl=None`` asks ``jax.default_backend()``, which is the CPU
    here, so the TEST steers the model's attention call to the kernel."""
    paged_attention = pa.paged_attention
    monkeypatch.setattr(
        pa, "paged_attention",
        lambda *a, impl=None, **kw: paged_attention(
            *a, impl="pallas", interpret=False, **kw))


def test_gpt2_small_decode_step_compiles_for_v5e(one_chip,
                                                 kernel_on_cpu_backend):
    """The engine's one decode program (phase 2): 8 slots over the
    paged pool through the Pallas kernel."""
    from chip_smoke import GPT2_SMALL
    from tensorflowonspark_tpu import generation

    model, params, cache, sds = _gpt2_small_paged(one_chip)
    compiled = _compile(
        lambda *a: generation.paged_decode_step(model, *a), params, cache,
        sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.int32),
        sds((SLOTS, SEQ // KV_BLOCK), jnp.int32))
    # one kernel call per layer
    assert compiled.as_text().count("tpu_custom_call") \
        >= GPT2_SMALL["num_layers"]
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < V5E_HBM_BYTES


@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
def test_engine_programs_carry_their_names_for_v5e(one_chip,
                                                   kernel_on_cpu_backend,
                                                   program):
    """What a trace shows of the engine is named by the program: the
    module of each jitted engine program (the device's ``XLA Modules``
    line, the host's ``PjitFunction(...)`` span) and the Pallas call in
    it — lowered for the chip, nothing compiled."""
    text = _lower_engine_program(program, *_gpt2_small_paged(one_chip),
                                 SLOTS, SEQ).as_text()
    assert "module @jit_{} ".format(program) in text
    assert text.count('kernel_name = "paged_attention"') >= 1


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%\S+ = (.*?)\s([a-z][a-z-]*)\(")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")
_DTYPES = {"float32": "f32", "int8": "s8"}


def _pool_values(compiled, pool):
    """(opcode, layout) of every value of the entry computation that
    holds an array of the pool's type, the layout as the compiler
    prints it less the memory space: ``2,1,0:T(8,128)``."""
    entry = compiled.as_text().split("\nENTRY ", 1)[1]
    dims = ",".join(str(n) for n in pool.shape)
    found = []
    for line in entry.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        opcode = m.group(2)
        if opcode == "custom-call":
            opcode = re.search(r'custom_call_target="([^"]*)"', line).group(1)
        found += [(opcode, re.sub(r"S\(\d+\)", "", layout))
                  for dt, shape, layout in _ARRAY.findall(m.group(1))
                  if (dt, shape) == (_DTYPES[pool.dtype.name], dims)]
    return found


@pytest.mark.parametrize("kv_dtype", ["", "int8"])
@pytest.mark.parametrize("program", ["paged_decode_step", "paged_prefill"])
def test_paged_pool_keeps_one_layout_for_v5e(one_chip, kernel_on_cpu_backend,
                                             program, kv_dtype):
    """The donated programs the engine runs, at GPT-2 large's widths,
    leave the KV pools where and as they are: one row is scattered into
    the donated buffer and the kernel reads the result. A pool whose
    minor pair is ``[heads, 64]`` is kept by the runtime in another
    layout than the scatter and the kernel want, and was copied whole
    into theirs and back around every call (8 ``copy`` and 306 MB of
    temporaries in these two-layer programs; 83% of the device's busy
    time in the ledger's ``gpt2-large-chat``, PR 29).

    The int8 pair is here for what the float32 pair cannot show: an
    int8 buffer has a tiling of its own (``T(8,128)(4,1)``) and the
    write quantises first, and the 4-D int8 pool had its 8 ``copy``
    between two layouts too. What it does NOT assert is staging: the
    compiler moves a buffer of some ten megabytes through the chip's
    fast memory on its own account (``copy-start``/``ConcatBitcast``,
    same layout, no temporaries). It does for the 10.5 MB int8 pools
    here on the flat pool as it did on the 4-D one; that is its choice
    by size and room, not a relayout (PERF.md, PR 30, has the int8
    step's time on the chip)."""
    layers = GPT2_LARGE_2L["num_layers"]
    model, params, cache, sds = _paged_engine_shapes(
        one_chip, GPT2_LARGE_2L, LARGE_SLOTS, LARGE_TOTAL, 512, kv_dtype)
    compiled = _lower_engine_program(program, model, params, cache, sds,
                                     LARGE_SLOTS, LARGE_TOTAL).compile()
    assert compiled.as_text().count("tpu_custom_call") >= layers

    pool = cache["block_0"]["attn"]["cached_key"]
    values = _pool_values(compiled, pool)
    stored = {layout for op, layout in values if op == "parameter"}
    assert len(stored) == 1, values
    # the scatters' results are pool-shaped, so there is something to see
    assert sum(op == "fusion" for op, _ in values) >= 2 * layers, values
    assert not [v for v in values if v[1] not in stored], values
    assert not [v for v in values if v[0] == "copy"], values
    if not kv_dtype:
        assert not [v for v in values
                    if v[0] in ("copy-start", "ConcatBitcast")], values
    pool_bytes = math.prod(pool.shape) * pool.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < pool_bytes
    # every pool is updated in the buffer it came in
    assert mem.alias_size_in_bytes >= 2 * layers * pool_bytes


def test_work_list_is_computed_once_a_program_for_v5e(one_chip,
                                                      kernel_on_cpu_backend):
    """The kernel's grid bound and its lists (``_work_list``) follow
    the step's tables and cursors alone, which every layer shares:
    the compiled decode step computes them once, and each layer's call
    takes the very same four values ahead of its own queries."""
    model, params, cache, sds = _paged_engine_shapes(
        one_chip, dict(GPT2_LARGE_2L, num_layers=3), LARGE_SLOTS,
        LARGE_TOTAL, 512)
    entry = _lower_engine_program(
        "paged_decode_step", model, params, cache, sds, LARGE_SLOTS,
        LARGE_TOTAL).compile().as_text().split("\nENTRY ", 1)[1]
    calls = re.findall(
        r"custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"",
        entry)
    assert len(calls) == 3, entry
    # bound, table, pair, code; then q, pos and the layer's pools
    shared = {tuple(re.findall(r"%[\w.-]+", c)[:4]) for c in calls}
    own = {tuple(re.findall(r"%[\w.-]+", c)[4:]) for c in calls}
    assert len(shared) == 1 and len(own) == 3, calls


def test_token_select_adds_no_operation_over_a_pool(one_chip,
                                                    kernel_on_cpu_backend):
    """The engine's step takes its input tokens from the device (the
    step before's picks, a host token where one is given) and its
    cursors and tables out of one array. Over the KV pools that is
    nothing: the program holds the very pool-shaped values, opcode by
    opcode in one layout, of the step that is handed tokens, cursors
    and tables as three arrays, and no more temporaries than ONE
    block of a pool holds (since PR 37 both build every layer's cursor
    and table leaf inside the trace, and the compiler keeps a few
    tables' worth more for the one that cuts them out of the feed:
    32 KB of 2.8 MB here)."""
    from tensorflowonspark_tpu import generation

    model, params, cache, sds = _paged_engine_shapes(
        one_chip, GPT2_LARGE_2L, LARGE_SLOTS, LARGE_TOTAL, 512)
    fed = _lower_engine_program("paged_decode_step", model, params, cache,
                                sds, LARGE_SLOTS, LARGE_TOTAL).compile()
    tables = (LARGE_SLOTS, LARGE_TOTAL // KV_BLOCK)
    plain = jax.jit(
        lambda *a: generation.paged_decode_step(model, *a),
        donate_argnums=(1,)).lower(
            params, cache, sds((LARGE_SLOTS,), jnp.int32),
            sds((LARGE_SLOTS,), jnp.int32), sds(tables, jnp.int32)).compile()
    pool = cache["block_0"]["attn"]["cached_key"]
    assert sorted(_pool_values(fed, pool)) \
        == sorted(_pool_values(plain, pool))
    extra = fed.memory_analysis().temp_size_in_bytes \
        - plain.memory_analysis().temp_size_in_bytes
    assert extra <= pool.dtype.itemsize * math.prod(pool.shape[1:])


def test_flash_kernels_carry_their_names_for_v5e(one_chip):
    flash, args = _flash_case("gpt2_bf16_causal", one_chip)

    def loss(q, k, v):
        return jnp.sum(flash(q, k, v).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).as_text()
    for name in ("flash_attention_fwd", "flash_attention_dq",
                 "flash_attention_dkv"):
        assert 'kernel_name = "{}"'.format(name) in text


# -- SDAR-30B-A3B's kernels at its published widths ----------------------

#: hidden 2048, 32 query / 4 K/V heads of 128, 128 experts of 768 with 8
#: a position; the engine's step is 32 slots x 4 positions over a
#: 1280-token context in 16-token blocks (benchmarks/configs/sdar-30b-a3b)
SDAR = dict(hidden=2048, heads=32, kv_heads=4, head_dim=128, experts=128,
            moe_hidden=768, top_k=8, slots=32, block_len=4, total=1280)

EXPERT_GMM_SHAPES = {
    # (positions, K, N): the step's 128 positions into and out of the
    # experts, and a 1024-token prefill bucket
    "step_up": (SDAR["slots"] * SDAR["block_len"], SDAR["hidden"],
                SDAR["moe_hidden"]),
    "step_down": (SDAR["slots"] * SDAR["block_len"], SDAR["moe_hidden"],
                  SDAR["hidden"]),
    "prefill_1024_up": (1024, SDAR["hidden"], SDAR["moe_hidden"]),
}


@pytest.mark.parametrize("name", sorted(EXPERT_GMM_SHAPES))
def test_expert_gmm_compiles_for_v5e(one_chip, name):
    """The grouped product of the expert layer, bfloat16, all 128
    experts held: one weight matrix (3 MB) double-buffered in VMEM."""
    from tensorflowonspark_tpu.ops import expert_gmm

    positions, kdim, ndim = EXPERT_GMM_SHAPES[name]
    held, m = SDAR["experts"], positions * SDAR["top_k"]
    tm = expert_gmm.row_tile(m, held)
    rows = -(-(m + held * (tm - 1)) // tm) * tm

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def gmm(lhs, rhs, tile_expert, live):
        return expert_gmm.expert_gmm(
            lhs, rhs, {"tile_expert": tile_expert, "live": live}, tm,
            impl="pallas", interpret=False)

    compiled = _compile(gmm, sds((rows, kdim), jnp.bfloat16),
                        sds((held, kdim, ndim), jnp.bfloat16),
                        sds((rows // tm,), jnp.int32), sds((), jnp.int32))
    assert _has_kernel(compiled)
    assert "expert_gmm" in compiled.as_text()


@pytest.mark.parametrize("s_q", [SDAR["block_len"], 1024])
def test_paged_attention_with_grouped_heads_compiles_for_v5e(one_chip, s_q):
    """Group 8 at head size 128 over a bfloat16 pool ``[P, 16, 512]``:
    the step's 4 positions a slot, and a 1024-token prefill."""
    rows = SDAR["slots"] if s_q == SDAR["block_len"] else 1
    mb = SDAR["total"] // KV_BLOCK

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((4097, KV_BLOCK, SDAR["kv_heads"] * SDAR["head_dim"]),
               jnp.bfloat16)
    lowered = jax.jit(
        lambda *a: pa.paged_attention(*a, impl="pallas", interpret=False)
    ).lower(
        sds((rows, s_q, SDAR["heads"], SDAR["head_dim"]), jnp.bfloat16),
        pool, pool, sds((rows, mb), jnp.int32), sds((rows, s_q), jnp.int32))
    assert lowered.as_text().count('kernel_name = "paged_attention"') == 1
    compiled = lowered.compile()
    assert _has_kernel(compiled)
    assert "paged_attention" in compiled.as_text()


# -- Mellum2-12B-A2.5B's kernels at its published widths -----------------

#: hidden 2304, 32 query / 4 K/V heads of 128, 64 experts of 896 with 8
#: a position, a window of 1,024; the engine's step is 32 slots of one
#: position over 16,896-token tables in 128-token blocks
#: (benchmarks/configs/mellum2-12b-a2.5b)
MELLUM = dict(hidden=2304, heads=32, kv_heads=4, head_dim=128, experts=64,
              moe_hidden=896, top_k=8, slots=32, total=16896, block=128,
              window=1024)

MELLUM_GMM_SHAPES = {
    # (positions, K, N): the step's 32 positions into and out of the
    # experts, and the 16,384-token prefill bucket
    "step_up": (MELLUM["slots"], MELLUM["hidden"], MELLUM["moe_hidden"]),
    "step_down": (MELLUM["slots"], MELLUM["moe_hidden"], MELLUM["hidden"]),
    "prefill_16384_up": (16384, MELLUM["hidden"], MELLUM["moe_hidden"]),
}


@pytest.mark.parametrize("name", sorted(MELLUM_GMM_SHAPES))
def test_expert_gmm_at_mellum_widths_compiles_for_v5e(one_chip, name):
    """64 experts of 2304 x 896 held: one weight matrix is 4.1 MB,
    double-buffered in VMEM beside a tile of rows."""
    from tensorflowonspark_tpu.ops import expert_gmm

    positions, kdim, ndim = MELLUM_GMM_SHAPES[name]
    held, m = MELLUM["experts"], positions * MELLUM["top_k"]
    tm = expert_gmm.row_tile(m, held)
    rows = -(-(m + held * (tm - 1)) // tm) * tm

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def gmm(lhs, rhs, tile_expert, live):
        return expert_gmm.expert_gmm(
            lhs, rhs, {"tile_expert": tile_expert, "live": live}, tm,
            impl="pallas", interpret=False)

    compiled = _compile(gmm, sds((rows, kdim), jnp.bfloat16),
                        sds((held, kdim, ndim), jnp.bfloat16),
                        sds((rows // tm,), jnp.int32), sds((), jnp.int32))
    assert _has_kernel(compiled)


@pytest.mark.parametrize("window", [None, MELLUM["window"]],
                         ids=["full_layer", "window_layer"])
def test_paged_attention_of_a_mellum_step_compiles_for_v5e(one_chip, window):
    """The decode step's call of either layer kind: 32 rows of one
    position, group 8, over a bfloat16 pool of 128-token blocks and a
    table 132 wide; the window layer's pool holds 9 blocks a slot and
    its call carries the mask's lower edge."""
    mb = MELLUM["total"] // MELLUM["block"]
    blocks = MELLUM["slots"] * (mb if window is None else 9) + 1

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    pool = sds((blocks, MELLUM["block"],
                MELLUM["kv_heads"] * MELLUM["head_dim"]), jnp.bfloat16)
    lowered = jax.jit(
        lambda *a: pa.paged_attention(*a, impl="pallas", interpret=False,
                                      window=window)
    ).lower(
        sds((MELLUM["slots"], 1, MELLUM["heads"], MELLUM["head_dim"]),
            jnp.bfloat16),
        pool, pool, sds((MELLUM["slots"], mb), jnp.int32),
        sds((MELLUM["slots"], 1), jnp.int32))
    assert lowered.as_text().count('kernel_name = "paged_attention"') == 1
    assert _has_kernel(lowered.compile())
